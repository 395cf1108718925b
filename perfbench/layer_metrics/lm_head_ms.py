"""Device time of the operations compiled under the scopes ``rdp.lm.head``
(the final norm and the product with the vocabulary slice, forward, the
backward pass's recomputed forward and its two products) and ``rdp.loss``
(the float32 cross-entropy over the slice), per optimiser step; validation's
head is in the time. A causal language model's head runs at every position.
A program without the scopes reads nothing."""

from perfbench.lib import spans

SCOPES = ("rdp.lm.head", "rdp.loss")


def read(ctx):
    steps = ctx.counters.get("optimizer_steps")
    if not steps:
        return None
    got = spans.of(ctx)
    seconds = sum(got.device_seconds(scope) for scope in SCOPES)
    if seconds <= 0:
        return None
    return 1e3 * seconds / steps
