"""Plain reference of ``seg``: the U-Net with the bilinear decoder (ladder
64-128-256-512-512). The shared arithmetic is in ``_unet``; the decoder is
chosen by the configuration's ``bilinear`` key."""

from perfbench.reference._unet import (  # noqa: F401
    adam_init, bce_with_logits, forward, init, param_shapes, stat_shapes,
    train_step)
