"""Seconds of set-up's ``train_model`` calls that the job's own thread
spent moving its train state: ``state_io_s``'s phases (restore, the
save's snapshot and waits, the registry write), read from the calls'
timelines since no profiler session covers set-up."""

from perfbench.layer_metrics.state_io_s import PHASES
from perfbench.lib import timelines


def read(ctx):
    found = timelines.calls(ctx, with_setup=True)
    if not found:
        return None
    return sum(timelines.seconds(timelines.named(t, PHASES, own_thread=True))
               for t in found[0])
