"""Flax U-Net for binary actuator segmentation, TPU-first.

Same architecture family as the reference PyTorch model
(reference: pkg/segmentation_model.py:24-120): DoubleConv blocks of
(3x3 conv, no bias -> norm -> ReLU) x 2, a 4-level encoder with 2x2
max-pooling, a decoder with bilinear upsampling (default) or transposed
convolution, pad-free skip fusion, and a 1x1 output head. Channel ladder
64 -> 128 -> 256 -> 512 -> 1024//factor with factor = 2 when bilinear
(the deployed configuration -- the reference instantiates ``UNet(3, 1)``
everywhere, e.g. scripts/train_segmenter.py:143).

TPU-first design departures (deliberate, not omissions):
- **NHWC layout** -- the native layout for XLA TPU convolutions (the
  reference is NCHW because cuDNN prefers it).
- **bfloat16 compute, float32 params** via ``dtype``/``param_dtype`` so
  convs hit the MXU at full rate; the output head is cast back to f32.
- **Resize-to-skip upsampling**: instead of the reference's pad-then-concat
  (segmentation_model.py:67-76) the decoder resizes the upsampled feature
  map directly to the skip's spatial shape -- identical result for even
  sizes, and shape-safe for odd sizes without dynamic padding.
- Optional **GroupNorm** (``norm="group"``) as a batch-size-independent
  alternative to BatchNorm for small per-device batches under data
  parallelism; ``norm="batch"`` matches the reference semantics and is the
  default.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from robotic_discovery_platform_tpu.analysis.contracts import shape_contract
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.utils.config import ModelConfig

DType = Any


#: The batch from which :func:`upsample_align_corners` writes its two
#: products over reshaped operands. Settled on the chip (PERF.md section 5,
#: PR 36): at batch 8, 16 and 32 that form makes a whole forward pass or
#: train step 20 to 47% faster, at batch 1, 2 and 4 the einsum form is the
#: faster by 1 to 5%. Eight is where XLA's TPU convolutions start to keep
#: the batch in the sublanes of a channel-minor tile.
_BATCHED_FORM_MIN_BATCH = 8


@shape_contract(x="b ih iw c")
def upsample_align_corners(x, h: int, w: int):
    """Bilinear 2D resize with ``align_corners=True`` sampling -- the exact
    semantics of the reference decoder's ``nn.Upsample(scale_factor=2,
    mode="bilinear", align_corners=True)`` (pkg/segmentation_model.py:58-60).

    ``jax.image.resize`` samples half-pixel centers (align_corners=False),
    a subtly different grid; matching torch's grid exactly is what lets
    trained reference checkpoints import with bit-comparable outputs
    (tools/import_torch_weights.py, tests/test_torch_parity.py).

    Two dense interpolation products over the static spatial dims, the H
    pass then the W pass: operands in ``x.dtype``, float32 products, a
    float32 intermediate. How the two are WRITTEN settles more than their
    own time, because XLA's TPU layout assignment starts from them. As
    ``"Hh,bhwc->bHwc"`` then ``"Ww,bhwc->bhWc"`` they follow whatever
    layout a small batch's convolutions want (a 64-channel activation of
    batch 4 has a spatial dimension minor). From a batch of 8 the
    convolutions keep channels minor, and that form gave every elementwise
    operation of the two full-resolution blocks one layout and their
    convolutions another: a batch-32 256x256 training step copied each
    64-channel activation of ``inc`` and ``up4`` between the two, 29 of its
    116 ms. There both passes keep ``(w c)`` resp. ``c`` contiguous as the
    one free dimension of a plain product, over ``[b, h, (w c)]`` and
    ``[(b H), w, c]``. On the chip the two forms agree bit for bit, forward
    and backward. ``rdp_unet_upsample_form_total`` counts the choice;
    tests/test_unet_layout.py guards the compiled layout.
    """
    b, ih, iw, c = x.shape

    def interp_matrix(out: int, inp: int):
        if out == 1 or inp == 1:
            pos = np.zeros((out,))
        else:
            pos = np.arange(out) * (inp - 1) / (out - 1)
        i0 = np.clip(np.floor(pos).astype(int), 0, inp - 1)
        i1 = np.minimum(i0 + 1, inp - 1)
        frac = (pos - i0).astype(np.float32)
        m = np.zeros((out, inp), np.float32)
        np.add.at(m, (np.arange(out), i0), 1.0 - frac)
        np.add.at(m, (np.arange(out), i1), frac)
        return jnp.asarray(m, x.dtype)

    mh, mw = interp_matrix(h, ih), interp_matrix(w, iw)
    batched = b >= _BATCHED_FORM_MIN_BATCH
    obs.UNET_UPSAMPLE_FORM.labels(
        form="batched" if batched else "einsum").inc()
    f32 = jnp.float32
    if batched:
        y = jnp.einsum("Hh,bhk->bHk", mh, x.reshape(b, ih, iw * c),
                       preferred_element_type=f32)
        y = jnp.einsum("Ww,nwc->nWc", mw, y.reshape(b * h, iw, c),
                       preferred_element_type=f32).reshape(b, h, w, c)
    else:
        y = jnp.einsum("Hh,bhwc->bHwc", mh, x, preferred_element_type=f32)
        y = jnp.einsum("Ww,bhwc->bhWc", mw, y, preferred_element_type=f32)
    return y.astype(x.dtype)


def _kernel_init(init: str):
    """Conv kernel initializer family.

    ``"torch"`` reproduces torch ``Conv2d``'s default
    ``kaiming_uniform_(a=sqrt(5))`` (reference models are built with it:
    pkg/segmentation_model.py:30-33 uses plain ``nn.Conv2d``): gain
    ``sqrt(2/(1+5)) = sqrt(1/3)`` over fan_in with a uniform distribution,
    i.e. ``U(+-sqrt(1/fan_in))`` -- exactly
    ``variance_scaling(1/3, "fan_in", "uniform")``. Matching the init
    family makes seed-for-seed training comparisons against the torch
    anchor fair (round-3 verdict item 1). ``"lecun"`` is the Flax default.
    """
    if init == "torch":
        return nn.initializers.variance_scaling(
            1.0 / 3.0, "fan_in", "uniform"
        )
    if init == "lecun":
        return nn.initializers.lecun_normal()
    raise ValueError(f"unknown init {init!r}")


def _bias_init(init: str, fan_in: int):
    """torch ``Conv2d`` bias default is ``U(+-1/sqrt(fan_in))``; Flax's is
    zeros. fan_in is known statically at call time (in_features * kh * kw)."""
    if init != "torch":
        return nn.initializers.zeros_init()
    bound = 1.0 / float(np.sqrt(fan_in))

    def initializer(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return initializer


def _norm(norm: str, dtype: DType, train: bool, features: int):
    if norm == "batch":
        # momentum 0.9 matches the reference's torch BatchNorm2d default
        # (momentum=0.1 on the *new* batch, i.e. 0.9 decay on the running
        # value; pkg/segmentation_model.py:35). Flax's own default of 0.99
        # leaves running stats ~30% initialization after a 120-step run,
        # which wrecks eval-mode predictions on short trainings.
        return nn.BatchNorm(use_running_average=not train, momentum=0.9,
                            dtype=dtype)
    if norm == "group":
        import math

        return nn.GroupNorm(num_groups=math.gcd(32, features), dtype=dtype)
    raise ValueError(f"unknown norm {norm!r}")


class TrainConv3x3(nn.Module):
    """3x3 SAME no-bias conv of the TRAINING step through
    ops/pallas/conv.conv3x3, which picks the form by the layer's shape:
    the custom-VJP Pallas kernels (forward, dx, dw) where its one
    predicate says they win, else the plain XLA convolution with JAX's own
    derivative -- what ``nn.Conv`` issues, so ``conv_impl="auto"`` and
    ``"flax"`` compile one program wherever no layer goes to Pallas. Same
    parameter name/shape as ``nn.Conv`` ("kernel", [3, 3, Cin, Cout]), so
    checkpoints, torch-weight import, and the PallasUNet variable walk are
    layout-identical.

    ``conv3x3`` engages only under ``train=True``: inference
    consumers of ``model.apply`` keep the plain XLA conv (per-layer
    Pallas/XLA mixing measures ~24% slower end-to-end, and the Pallas
    serving path is the uniformly-fused ``PallasUNet``, not this module).
    """

    features: int
    dtype: DType = jnp.bfloat16
    kernel_init: Any = nn.initializers.lecun_normal()
    impl: str = "auto"  # custom-VJP dispatch: auto | pallas | xla | interpret

    @nn.compact
    def __call__(self, x, train: bool = False):
        from robotic_discovery_platform_tpu.ops.pallas import conv as pconv

        kernel = self.param(
            "kernel", self.kernel_init,
            (3, 3, x.shape[-1], self.features), jnp.float32,
        )
        x = x.astype(self.dtype)
        kernel = kernel.astype(self.dtype)
        if train:
            return pconv.conv3x3(x, kernel, self.impl)
        y = jax.lax.conv_general_dilated(
            x, kernel, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32,
        )
        return y.astype(x.dtype)


class DoubleConv(nn.Module):
    """(3x3 conv no-bias -> norm -> ReLU) x 2
    (reference: pkg/segmentation_model.py:24-40).

    ``conv_impl="flax"`` uses ``nn.Conv`` (XLA convs end to end);
    anything else routes the convs through :class:`TrainConv3x3` with
    that dispatch mode ("auto": by the layer's shape; the rest pin the
    custom VJP for the tests).
    """

    features: int
    mid_features: int | None = None
    norm: str = "batch"
    dtype: DType = jnp.bfloat16
    weight_init: str = "torch"
    conv_impl: str = "flax"

    @nn.compact
    def __call__(self, x, train: bool = False):
        mid = self.mid_features or self.features
        kinit = _kernel_init(self.weight_init)

        def conv(features, name, y):
            if self.conv_impl == "flax":
                return nn.Conv(features, (3, 3), padding="SAME",
                               use_bias=False, dtype=self.dtype,
                               kernel_init=kinit, name=name)(y)
            return TrainConv3x3(features, dtype=self.dtype,
                                kernel_init=kinit, impl=self.conv_impl,
                                name=name)(y, train)

        x = conv(mid, "Conv_0", x)
        x = _norm(self.norm, self.dtype, train, mid)(x)
        x = nn.relu(x)
        x = conv(self.features, "Conv_1", x)
        x = _norm(self.norm, self.dtype, train, self.features)(x)
        return nn.relu(x)


class Down(nn.Module):
    """2x2 max-pool then DoubleConv (reference: pkg/segmentation_model.py:42-52)."""

    features: int
    norm: str = "batch"
    dtype: DType = jnp.bfloat16
    weight_init: str = "torch"
    conv_impl: str = "flax"

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        return DoubleConv(self.features, norm=self.norm, dtype=self.dtype,
                          weight_init=self.weight_init,
                          conv_impl=self.conv_impl)(x, train)


class Up(nn.Module):
    """Upsample, fuse with the skip, DoubleConv
    (reference: pkg/segmentation_model.py:54-76).

    ``bilinear=True`` (deployed default) resizes by interpolation and gives
    the DoubleConv a halved mid-channel width; otherwise a 2x2 stride-2
    transposed conv halves the channel count before fusion.
    """

    features: int
    bilinear: bool = True
    norm: str = "batch"
    dtype: DType = jnp.bfloat16
    weight_init: str = "torch"
    conv_impl: str = "flax"

    @nn.compact
    def __call__(self, x, skip, train: bool = False):
        b, h, w, c = skip.shape
        if self.bilinear:
            # align_corners=True grid, matching the reference decoder exactly
            x = upsample_align_corners(x, h, w)
            mid = (x.shape[3] + c) // 2
            x = jnp.concatenate([skip, x.astype(skip.dtype)], axis=-1)
            return DoubleConv(self.features, mid_features=mid,
                              norm=self.norm, dtype=self.dtype,
                              weight_init=self.weight_init,
                              conv_impl=self.conv_impl)(x, train)
        in_ch = x.shape[3]
        # torch ConvTranspose2d computes init fan_in over weight dim 1
        # (out_channels) * kh * kw = (in_ch // 2) * 4 -- for BOTH kernel
        # and bias. variance_scaling's "fan_in" would use in_ch * kh * kw
        # (Flax ConvTranspose kernels are (kh, kw, in, out)), a bound
        # sqrt(2) too small here, so the kernel uses the same explicit
        # U(+-1/sqrt(fan)) closure as the bias.
        tfan = (in_ch // 2) * 4
        x = nn.ConvTranspose(
            in_ch // 2, (2, 2), strides=(2, 2), dtype=self.dtype,
            kernel_init=(_bias_init("torch", tfan)
                         if self.weight_init == "torch"
                         else _kernel_init(self.weight_init)),
            bias_init=_bias_init(self.weight_init, tfan),
        )(x)
        x = jax.image.resize(x, (x.shape[0], h, w, x.shape[3]), method="nearest")
        x = jnp.concatenate([skip, x.astype(skip.dtype)], axis=-1)
        return DoubleConv(self.features, norm=self.norm, dtype=self.dtype,
                          weight_init=self.weight_init,
                          conv_impl=self.conv_impl)(x, train)


class UNet(nn.Module):
    """Encoder/decoder U-Net (reference: pkg/segmentation_model.py:86-120).

    Call with NHWC input; returns NHWC logits in float32.
    """

    num_classes: int = 1
    base_features: int = 64
    bilinear: bool = True
    norm: str = "batch"
    dtype: DType = jnp.bfloat16
    in_features: int = 3  # used by init helpers; convs infer from input
    weight_init: str = "torch"
    conv_impl: str = "flax"

    @nn.compact
    def __call__(self, x, train: bool = False):
        f = self.base_features
        factor = 2 if self.bilinear else 1
        x = x.astype(self.dtype)
        kw = dict(norm=self.norm, dtype=self.dtype,
                  weight_init=self.weight_init, conv_impl=self.conv_impl)
        # one stable scope per block (the reference's block names,
        # pkg/segmentation_model.py:98-107): part of each operation's
        # op_name, whatever the compiler names its fusions (PERF.md
        # section 3 says where a profile keeps it)
        scope = jax.named_scope
        with scope("rdp.unet.inc"):
            x1 = DoubleConv(f, **kw)(x, train)
        with scope("rdp.unet.down1"):
            x2 = Down(f * 2, **kw)(x1, train)
        with scope("rdp.unet.down2"):
            x3 = Down(f * 4, **kw)(x2, train)
        with scope("rdp.unet.down3"):
            x4 = Down(f * 8, **kw)(x3, train)
        with scope("rdp.unet.down4"):
            x5 = Down(f * 16 // factor, **kw)(x4, train)
        with scope("rdp.unet.up1"):
            y = Up(f * 8 // factor, self.bilinear, **kw)(x5, x4, train)
        with scope("rdp.unet.up2"):
            y = Up(f * 4 // factor, self.bilinear, **kw)(y, x3, train)
        with scope("rdp.unet.up3"):
            y = Up(f * 2 // factor, self.bilinear, **kw)(y, x2, train)
        with scope("rdp.unet.up4"):
            y = Up(f, self.bilinear, **kw)(y, x1, train)
        # 1x1 head: the only conv with a bias (reference OutConv,
        # pkg/segmentation_model.py:78-84); fan_in = in_features * 1 * 1
        with scope("rdp.unet.head"):
            logits = nn.Conv(
                self.num_classes, (1, 1), dtype=self.dtype,
                kernel_init=_kernel_init(self.weight_init),
                bias_init=_bias_init(self.weight_init, y.shape[-1]),
            )(y)
            return logits.astype(jnp.float32)


def with_compute_dtype(model: UNet, dtype: DType) -> UNet:
    """A clone of ``model`` whose activations compute in ``dtype`` (params
    stay float32 -- ``param_dtype`` is untouched). The serving precision
    tiers (ops/pallas/quant.apply_precision) use this to force bf16
    activations regardless of how the checkpoint was trained; the variable
    tree is layout-identical so trained variables bind unchanged."""
    return model.clone(dtype=jnp.dtype(dtype))


def build_unet(cfg: ModelConfig = ModelConfig()) -> UNet:
    return UNet(
        num_classes=cfg.num_classes,
        base_features=cfg.base_features,
        bilinear=cfg.bilinear,
        norm=cfg.norm,
        dtype=jnp.dtype(cfg.compute_dtype),
        in_features=cfg.in_channels,
        weight_init=cfg.init,
        conv_impl=cfg.conv_impl,
    )


def init_unet(model: UNet, rng, img_size: int = 256):
    """Initialize variables with a dummy batch; returns the variable dict
    (``params`` + ``batch_stats`` when BatchNorm is used)."""
    dummy = jnp.zeros((1, img_size, img_size, model.in_features), jnp.float32)
    return model.init(rng, dummy, train=False)


def param_count(variables) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(variables.get("params", variables)))
