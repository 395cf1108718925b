"""Microseconds of the toy driver's own step clock per optimiser step."""


def read(ctx):
    steps = ctx.counters.get("optimizer_steps")
    phase = ctx.counters.get("train_phase_s")
    if not steps or not phase:
        return None
    return 1e6 * phase / steps
