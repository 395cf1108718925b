"""Share of the chips' peak that the whole traced window reached on the
model's own operations: the operations the cell's driver states for its
window (the counter ``model_flops``, summed over all chips: forward and
backward passes of the optimiser steps and the forward passes of
evaluation, multiply-adds as 2, recomputed operations not counted) over the
time the devices were busy (the trace's mean over its device planes, times
their number) times one chip's peak rate of ``lib/peaks.json``. The driver
states the operations and this reader only divides, so it reads the same
for every family and for whatever implements the model: a Pallas kernel, an
XLA fusion or a rematerialised pass. Everything the device did in the
window (validation, optimiser, copies) is in the time."""


def read(ctx):
    flops = ctx.counters.get("model_flops")
    if ctx.trace is None or ctx.peaks is None or not flops \
            or ctx.trace.busy_s <= 0:
        return None
    chip_seconds = ctx.trace.busy_s * ctx.trace.devices
    return 100.0 * flops / (chip_seconds * ctx.peaks["flops_per_s"])
