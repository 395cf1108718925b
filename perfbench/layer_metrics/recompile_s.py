"""Seconds the window's host spent in jitted calls that traced: every
``PjitFunction(...)`` span that holds an ``rdp.jit.trace`` span (the
program's ``trace_guard`` puts one around the traced Python), from the
call's entry to its dispatch -- tracing, lowering, and the compile or the
load from the persistent cache. JAX records a call as two nested spans of
one name, so the intervals are merged, not summed."""

from perfbench.lib import spans


def read(ctx):
    got = spans.of(ctx)
    if not got.instrumented:
        return None
    return spans.merged_seconds(got.holding("PjitFunction*", "rdp.jit.trace"))
