"""Seconds the job's main thread stood in checkpointing: waiting for the
previous save (``rdp.train.checkpoint.wait``), copying the snapshot and
handing it over (``.snapshot``), and the final drain (``rdp.train.flush``).
The worker thread's fetch and write are not in it: they overlap the next
epoch and cost the job only what these three show."""

from perfbench.lib import spans

STALLS = ("rdp.train.checkpoint.wait", "rdp.train.checkpoint.snapshot",
          "rdp.train.flush")


def read(ctx):
    got = spans.of(ctx)
    if not got.instrumented:
        return None
    return got.seconds(STALLS, got.main)
