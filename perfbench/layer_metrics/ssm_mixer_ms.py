"""Device time of the operations compiled under the scopes of a Mamba-2
mixer (``rdp.ssm.proj``, the two projections; ``rdp.ssm.conv``, the causal
convolution and its activation; ``rdp.ssm.scan``, the state-space scan;
``rdp.ssm.gate``, the gate and its grouped norm), forward, the backward
pass's recomputed forward and the backward pass itself, per optimiser step;
validation's mixers are in the time. A program without the scopes reads
nothing."""

from perfbench.lib import spans

SCOPES = ("rdp.ssm.proj", "rdp.ssm.conv", "rdp.ssm.scan", "rdp.ssm.gate")


def read(ctx):
    steps = ctx.counters.get("optimizer_steps")
    if not steps:
        return None
    got = spans.of(ctx)
    seconds = sum(got.device_seconds(scope) for scope in SCOPES)
    if seconds <= 0:
        return None
    return 1e3 * seconds / steps
