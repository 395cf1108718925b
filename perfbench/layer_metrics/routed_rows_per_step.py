"""Rows the held experts took per optimiser step of the window's call: the
``routed_rows`` of its ``rdp.train.epoch`` spans over their ``steps``. It
follows the seed and how far the job has trained, and the expert layers'
time follows it: two runs with the same number did the same work."""

from perfbench.lib import timelines

SPAN = "rdp.train.epoch"


def read(ctx):
    found = timelines.calls(ctx)
    if not found:
        return None
    epochs = [s for s in timelines.named(found[1], SPAN)
              if timelines.number(s, "routed_rows") is not None
              and timelines.number(s, "steps")]
    if not epochs:
        return None
    return (sum(timelines.number(s, "routed_rows") for s in epochs)
            / sum(timelines.number(s, "steps") for s in epochs))
