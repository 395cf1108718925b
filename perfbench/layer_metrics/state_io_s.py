"""Seconds of the window that the job's main thread spent moving its train
state: the restore at the start (``rdp.train.restore``: files to device,
leaf by leaf), the save (``rdp.train.checkpoint.snapshot``: device to host;
``.wait`` and ``rdp.train.flush``: the wait for the writer thread) and the
registry write (``rdp.train.register``)."""

from perfbench.lib import spans

PHASES = ("rdp.train.restore", "rdp.train.checkpoint.snapshot",
          "rdp.train.checkpoint.wait", "rdp.train.register",
          "rdp.train.flush")


def read(ctx):
    got = spans.of(ctx)
    if not got.instrumented:
        return None
    return got.seconds(PHASES, got.main)
