"""Share of its roofline that attention under the block-diffusion mask
reached in the window: the least time the chip could take for the live
pairs of the mask (``lib/lm_flops.py``: ``q k^T`` and ``p v`` over ``L^2 +
L B`` pairs a sequence and head, three passes in an optimiser step and one
in a validation batch; the larger of operations over peak FLOP/s and bytes
over peak bytes/s of ``lib/peaks.json``) over the device seconds of the
operations compiled under the scope ``rdp.attn.blockdiff``, forward and
backward. The count is of the algorithm, not of the kernel: a kernel that
visits dead pairs, or a rematerialised forward pass, reads lower."""

from perfbench.lib import lm_flops, spans

SCOPE = "rdp.attn.blockdiff"


def read(ctx):
    c, model = ctx.counters, ctx.cell.config.get("model", {})
    steps, evals = c.get("optimizer_steps"), c.get("eval_batches", 0)
    if ctx.peaks is None or not steps or "block_length" not in model:
        return None
    seconds = spans.of(ctx).device_seconds(SCOPE)
    if seconds <= 0:
        return None
    layers_by_sequences = model["num_layers"] * c["batch"]
    flops = layers_by_sequences * (3 * steps + evals) \
        * lm_flops.attention_flops(model)
    moved = layers_by_sequences * (
        steps * lm_flops.attention_bytes(model, True)
        + evals * lm_flops.attention_bytes(model, False))
    least = max(flops / ctx.peaks["flops_per_s"],
                moved / ctx.peaks["bytes_per_s"])
    return 100.0 * least / seconds
