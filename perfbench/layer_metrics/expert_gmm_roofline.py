"""Share of its roofline that the experts' grouped products reached in the
window: the least time the chip could take for the rows the program routed
to the experts held here (the driver's counter ``routed_rows``, from the
program's ``rdp_moe_routed_rows_total``; ``lib/lm_flops.py``: gate, up and
down products, three passes in an optimiser step, validation's rows taken
at the training steps' mean; the larger of operations over peak FLOP/s and
bytes over peak bytes/s) over the device seconds of the operations compiled
under the scope ``rdp.moe.experts``, forward and backward, the matrices'
casts to the compute type among them."""

from perfbench.lib import lm_flops, spans

SCOPE = "rdp.moe.experts"


def read(ctx):
    c, model = ctx.counters, ctx.cell.config.get("model", {})
    steps, rows = c.get("optimizer_steps"), c.get("routed_rows")
    if ctx.peaks is None or not steps or not rows \
            or "expert_width" not in model:
        return None
    seconds = spans.of(ctx).device_seconds(SCOPE)
    if seconds <= 0:
        return None
    evals = c.get("eval_batches", 0)
    row_passes = rows * (3 + evals / steps)
    layer_passes = model["num_layers"] * (3 * steps + evals)
    least = max(
        lm_flops.expert_flops(model, row_passes) / ctx.peaks["flops_per_s"],
        lm_flops.expert_bytes(model, layer_passes, row_passes)
        / ctx.peaks["bytes_per_s"])
    return 100.0 * least / seconds
