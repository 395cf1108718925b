"""Share of the window that the job spent outside its train phases, by the
program's own per-epoch clock (``rdp_train_step_seconds``, one observation
of the mean step time per epoch): restore, re-trace, validation, checkpoint
stalls, the final flush and the registry write."""


def read(ctx):
    phase, window = ctx.counters.get("train_phase_s"), ctx.counters["window_s"]
    if not phase:
        return None
    return 100.0 * (1.0 - phase / window)
