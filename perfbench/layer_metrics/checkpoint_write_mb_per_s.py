"""What the checkpoint thread's writes of the window's call sustained: the
``bytes`` of its ``rdp.train.checkpoint.write`` spans (the tree handed to
the writer, ``checkpoint.tree_bytes``) over their seconds, in MB/s. The
span is the window's largest idle gap's name in three cells; this says
whether the disk or the amount written moved it."""

from perfbench.lib import timelines

SPAN = "rdp.train.checkpoint.write"


def read(ctx):
    found = timelines.calls(ctx)
    if not found:
        return None
    writes = [s for s in timelines.named(found[1], SPAN)
              if timelines.number(s, "bytes") is not None]
    took = timelines.seconds(writes)
    if took <= 0:
        return None
    return sum(timelines.number(s, "bytes") for s in writes) / 1e6 / took
