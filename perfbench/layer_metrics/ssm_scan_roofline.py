"""Share of its roofline that the state-space scan of the Mamba-2 layers
reached in the window: the least time the chip could take for the scan's
operations and bytes (``lib/hybrid_lm_flops.py``: the chunked form's
products, and the scan's inputs and outputs alone, three passes of the
operations in an optimiser step and one in a validation batch; the larger of
operations over peak FLOP/s and bytes over peak bytes/s of
``lib/peaks.json``) over the device seconds of the operations compiled under
the scope ``rdp.ssm.scan`` (steps and decays, the products inside a chunk,
the pass over the chunks' states, the skip), forward and backward. The
count is of the algorithm, not of what implements it: what an
implementation writes and reads again between its parts, or a
rematerialised forward pass, reads lower. A program without the scope
(another family's, or this one's parent commit) reads nothing."""

from perfbench.lib import hybrid_lm_flops, spans

SCOPE = "rdp.ssm.scan"


def read(ctx):
    c, model = ctx.counters, ctx.cell.config.get("model", {})
    steps = c.get("optimizer_steps")
    if ctx.peaks is None or not steps or "ssm_state" not in model:
        return None
    seconds = spans.of(ctx).device_seconds(SCOPE)
    if seconds <= 0:
        return None
    least = hybrid_lm_flops.scan_least_seconds(
        model, c["batch"], steps, c.get("eval_batches", 0), ctx.peaks)
    return 100.0 * least / seconds
