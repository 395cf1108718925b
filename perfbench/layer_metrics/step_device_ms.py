"""Device-busy time of the traced window per optimiser step: validation,
snapshot copies and everything else the device did are in it."""


def read(ctx):
    steps = ctx.counters.get("optimizer_steps")
    if ctx.trace is None or not steps or ctx.trace.busy_s <= 0:
        return None
    return 1e3 * ctx.trace.busy_s / steps
