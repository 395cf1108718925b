"""Programs the persistent compile cache did not hold when set-up asked for
them (``rdp_compile_cache_total{result="miss"}`` when the window's call
began: the root's ``process_cache_misses``): 0 on a machine whose cache is
warm, the programs compiled on a cold one."""

from perfbench.lib import timelines


def read(ctx):
    found = timelines.calls(ctx, with_setup=True)
    if not found:
        return None
    return timelines.number(timelines.root(found[1]), "process_cache_misses")
