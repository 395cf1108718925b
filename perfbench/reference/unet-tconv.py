"""Plain reference of ``unet-tconv``: the U-Net with 2x2 stride-2
transposed-convolution up-sampling and the 1024-wide bottleneck. The shared
arithmetic is in ``_unet``; the decoder is chosen by the configuration's
``bilinear`` key."""

from perfbench.reference._unet import (  # noqa: F401
    adam_init, bce_with_logits, forward, init, param_shapes, stat_shapes,
    train_step)
