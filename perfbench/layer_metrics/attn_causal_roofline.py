"""Share of its roofline that attention in the full (causal) layers reached
in the window: as ``attn_window_roofline``, over the ``L (L + 1) / 2`` live
pairs a sequence and head of the causal mask and the device seconds under
the scope ``rdp.attn.causal``."""

from perfbench.layer_metrics.attn_window_roofline import share
from perfbench.lib import causal_lm_flops


def read(ctx):
    return share(ctx, "rdp.attn.causal", causal_lm_flops.FULL)
