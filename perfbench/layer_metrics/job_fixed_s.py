"""Seconds of the window's ``train_model`` call outside its epochs: the
``rdp.train.job`` span less the sum of its ``rdp.train.epoch`` spans, i.e.
init, restore, data staging, run set-up, registry write and final flush."""

from perfbench.lib import spans


def read(ctx):
    got = spans.of(ctx)
    job = got.seconds("rdp.train.job", got.main)
    if not job:
        return None
    return job - got.seconds("rdp.train.epoch", got.main)
