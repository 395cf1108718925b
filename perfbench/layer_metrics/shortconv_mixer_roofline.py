"""Share of its roofline that the gated short convolutions reached in the
window, the whole operator: the least time the chip could take for the
matrix products of its two projections (``lib/shortconv_lm_flops.py``:
``hidden -> 3 hidden`` and ``hidden -> hidden``, three passes in an
optimiser step and one in a validation batch, over peak FLOP/s of
``lib/peaks.json``) over the device seconds of the operations compiled
under ``shortconv_mixer_ms``'s two scopes, which hold all of the operator
(norm, both projections, gate, taps and gate; forward, the recomputed
forward and backward), wherever the compiler books a fused part. The roof
is the matrix unit's: gate, taps and gate hold no product, and their bytes
take a tenth of the products' time at peak bytes/s and need not leave the
chip between the two products (the lib file's docstring has the count), so
they set no roof of their own and every second spent on them reads as
distance from it. A program without the scopes reads nothing."""

from perfbench.layer_metrics.shortconv_mixer_ms import SCOPES
from perfbench.lib import shortconv_lm_flops, spans


def read(ctx):
    c, model = ctx.counters, ctx.cell.config.get("model", {})
    steps = c.get("optimizer_steps")
    if ctx.peaks is None or not steps or "shortconv_kernel" not in model:
        return None
    got = spans.of(ctx)
    seconds = sum(got.device_seconds(scope) for scope in SCOPES)
    if seconds <= 0:
        return None
    least = shortconv_lm_flops.shortconv_least_seconds(
        model, c["batch"], steps, c.get("eval_batches", 0), ctx.peaks)
    return 100.0 * least / seconds
