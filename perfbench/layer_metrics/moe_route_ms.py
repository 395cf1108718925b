"""Device time of the operations compiled under the scope ``rdp.moe.route``
(router product, softmax, top-k, the sort by expert, the rows' gather and
the weighted scatter back to their tokens, forward and backward), per
optimiser step; validation's routing is in the time."""

from perfbench.lib import spans

SCOPE = "rdp.moe.route"


def read(ctx):
    steps = ctx.counters.get("optimizer_steps")
    seconds = spans.of(ctx).device_seconds(SCOPE)
    if not steps or seconds <= 0:
        return None
    return 1e3 * seconds / steps
