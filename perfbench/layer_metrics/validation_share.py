"""Share of the window the job spent in its ``rdp.train.validation`` phase."""

from perfbench.lib import spans


def read(ctx):
    got = spans.of(ctx)
    if not got.instrumented or not got.window_s:
        return None
    return 100.0 * got.seconds("rdp.train.validation", got.main) / got.window_s
