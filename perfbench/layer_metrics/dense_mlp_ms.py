"""Device time of the operations compiled under the scope ``rdp.mlp`` (a
dense gated MLP's norm, its three products and its activation), forward,
the backward pass's recomputed forward and the backward pass itself, per
optimiser step; validation's are in the time. A program without the scope
reads nothing."""

from perfbench.layer_metrics.shortconv_mixer_ms import scopes_ms

SCOPE = "rdp.mlp"


def read(ctx):
    return scopes_ms(ctx, (SCOPE,))
