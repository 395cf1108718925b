"""Seconds JAX reports for tracing, lowering and compiling or loading every
executable the process made before the window (``rdp_jit_seconds_total``
when the window's call began: the root's ``process_jit_s``), summed over
threads, so where a driver compiles ahead on threads of its own it may
exceed the wall time they took."""

from perfbench.lib import timelines


def read(ctx):
    found = timelines.calls(ctx, with_setup=True)
    if not found:
        return None
    return timelines.number(timelines.root(found[1]), "process_jit_s")
