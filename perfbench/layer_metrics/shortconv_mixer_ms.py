"""Device time of the operations compiled under the scopes of a gated short
convolution (``rdp.shortconv.proj``, the operator's two projections and the
norm before them; ``rdp.shortconv.mix``, gate, convolution and gate),
forward, the backward pass's recomputed forward and the backward pass
itself, per optimiser step; validation's operators are in the time. A
program without the scopes reads nothing."""

from perfbench.lib import spans

SCOPES = ("rdp.shortconv.proj", "rdp.shortconv.mix")


def scopes_ms(ctx, scopes):
    """Device ms an optimiser step under ``scopes``; nothing where there
    is no step or no operation under them."""
    steps = ctx.counters.get("optimizer_steps")
    if not steps:
        return None
    got = spans.of(ctx)
    seconds = sum(got.device_seconds(scope) for scope in scopes)
    if seconds <= 0:
        return None
    return 1e3 * seconds / steps


def read(ctx):
    return scopes_ms(ctx, SCOPES)
