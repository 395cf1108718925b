"""Seconds from the start of the process to the start of set-up's first
``train_model`` call (the root's ``process_age_s``): the interpreter and
its imports, the chip, the data set from the seed, the seeded weights and
the step-0 checkpoint."""

from perfbench.lib import timelines


def read(ctx):
    found = timelines.calls(ctx, with_setup=True)
    if not found:
        return None
    return timelines.number(timelines.root(found[0][0]), "process_age_s")
