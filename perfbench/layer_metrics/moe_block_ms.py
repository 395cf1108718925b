"""Device time of a whole expert layer's branch, per optimiser step: the
operations compiled under ``rdp.moe.route`` (router product, scores, top-k,
the sort by expert, the rows' gather and the weighted scatter back),
``rdp.moe.experts`` (the held experts' grouped products) and
``rdp.moe.shared`` (the shared expert's dense products), forward and
backward; validation's are in the time. A program without the scopes reads
nothing."""

from perfbench.lib import spans

SCOPES = ("rdp.moe.route", "rdp.moe.experts", "rdp.moe.shared")


def read(ctx):
    steps = ctx.counters.get("optimizer_steps")
    if not steps:
        return None
    got = spans.of(ctx)
    seconds = sum(got.device_seconds(scope) for scope in SCOPES)
    if seconds <= 0:
        return None
    return 1e3 * seconds / steps
