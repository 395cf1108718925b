"""Share of its roofline that attention in the sliding-window layers reached
in the window: the least time the chip could take for the live pairs of
those layers' mask (``lib/causal_lm_flops.py``: ``q k^T`` and ``p v`` over
``w L - w (w - 1) / 2`` pairs a sequence and head, three passes in an
optimiser step and one in a validation batch; the larger of operations over
peak FLOP/s and bytes over peak bytes/s of ``lib/peaks.json``) over the
device seconds of the operations compiled under the scope
``rdp.attn.window``, forward and backward. The count is of the algorithm,
not of the kernel: a kernel that visits dead pairs, or a rematerialised
forward pass, reads lower. A program without the scope (another family's,
or this one's parent commit) reads nothing."""

from perfbench.lib import causal_lm_flops, spans


def share(ctx, scope: str, kind: str):
    """The roofline share of attention in the layers of ``kind``, whose
    kernels run under ``scope``."""
    c, model = ctx.counters, ctx.cell.config.get("model", {})
    steps = c.get("optimizer_steps")
    if ctx.peaks is None or not steps or kind not in model.get(
            "layer_types", ()):
        return None
    seconds = spans.of(ctx).device_seconds(scope)
    if seconds <= 0:
        return None
    least = causal_lm_flops.attention_least_seconds(
        model, kind, c["batch"], steps, c.get("eval_batches", 0), ctx.peaks)
    return 100.0 * least / seconds


def read(ctx):
    return share(ctx, "rdp.attn.window", causal_lm_flops.SLIDING)
