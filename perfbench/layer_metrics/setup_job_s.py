"""Seconds of set-up inside ``train_model``: the ``rdp.train.job`` spans of
set-up's calls (the probe and the first epoch), summed. What is left of
``setup_s`` beside this and ``setup_before_job_s`` is the harness's own
work between the calls."""

from perfbench.lib import timelines


def read(ctx):
    found = timelines.calls(ctx, with_setup=True)
    if not found:
        return None
    return timelines.seconds(timelines.root(t) for t in found[0])
