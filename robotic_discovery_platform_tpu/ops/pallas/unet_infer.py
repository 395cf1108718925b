"""Pallas-fused U-Net inference forward built from Flax variables.

Consumes the exact variable tree that ``models/unet.py`` trains (params +
batch_stats) and re-expresses the whole forward pass with the fused kernels
in :mod:`ops.pallas.conv`: every (conv -> BatchNorm -> ReLU) half-block of
the reference DoubleConv (reference: pkg/segmentation_model.py:24-40) is one
kernel launch with BatchNorm pre-folded, the decoder's 2x2 stride-2
transposed conv (reference: :62-63) is one kernel, and the 1x1 head
(reference: :78-84) is one kernel. Max-pooling and bilinear resizing stay in
XLA (bandwidth-bound data movement XLA already emits optimally).

Dispatch between the Pallas and XLA form of each conv is per-layer and
empirical: measured on v5e, the Pallas kernels win below ~2^23 activation
elements per launch (batch * H * W * max(Cin, Cout)) and lose to XLA's conv
above it, so :func:`auto` picks per shape. Inference-only: training uses the
Flax module (BatchNorm statistics must update).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from robotic_discovery_platform_tpu.analysis import recompile
from robotic_discovery_platform_tpu.models.unet import upsample_align_corners
from robotic_discovery_platform_tpu.ops.pallas import conv as pconv

# Measured v5e crossover for the UNIFORM whole-net choice: Pallas when the
# widest layer's activation volume (b * h * w * 128 concat channels at
# full resolution) stays within the budget, folded-XLA above it. At batch
# 1 the deployed 256^2 forward sits exactly at the budget and runs
# Pallas-uniform (r03: 544 vs 347 FPS over the unfolded Flax path; r04
# full-pipeline: 413 vs 379); batched forwards run XLA-uniform (r04 b4:
# 321 XLA vs 266 Pallas, and batched wide-map Pallas launches overflow
# VMEM outright at b=8).
#
# Why not per-LAYER dispatch: PALLASBENCH.json's isolated-launch timings
# show 3 of 16 conv shapes losing to XLA (0.48-0.64x), but rerouting just
# those to XLA was measured 24% SLOWER end-to-end in the fused serving
# graph (interleaved A/B: 472 vs 584 FPS), and the r04 remeasure agrees
# (mixed auto at b4: 457 FPS forward-only vs 814 XLA-uniform) -- every
# pallas<->XLA boundary pays a layout transition that outweighs the
# per-launch loss. The dispatcher therefore picks ONE backend for the
# whole forward, per input shape.
PALLAS_MAX_ELEMS = 2 ** 23


def _dispatch_3x3(x, w, scale, bias, *, relu, interpret, force):
    if force == "xla" or (
        force is None and not (interpret or pconv.use_pallas())
    ):
        return pconv.conv3x3_bn_relu_xla(x, w, scale, bias, relu=relu)
    from robotic_discovery_platform_tpu.ops.pallas import tuning

    b, h, width, cin = x.shape
    tiling = tuning.lookup(h, width, cin, w.shape[-1], batch=b,
                           dtype=jnp.dtype(x.dtype).name)
    return pconv.conv3x3_bn_relu(
        x, w, scale, bias, relu=relu, interpret=interpret, tiling=tiling
    )


class PallasUNet:
    """Callable inference forward over a fixed variable tree.

    Args:
        model: the Flax ``UNet`` the variables belong to (architecture
            hyperparameters are read off it).
        variables: ``{"params": ..., "batch_stats": ...}`` as produced by
            training.
        interpret: run kernels in the Pallas interpreter (CPU tests).
        force: None (auto per-shape dispatch), "pallas", or "xla".
    """

    def __init__(self, model, variables, *, interpret: bool = False,
                 force: str | None = None):
        if model.norm != "batch":
            raise ValueError(
                "PallasUNet folds BatchNorm; got norm="
                f"{model.norm!r} (use the Flax module instead)"
            )
        self.model = model
        self.interpret = interpret
        self.force = force
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        self._layers = self._fold(params, stats)
        # Per-instance trace budget (analysis/recompile): the serving
        # engine traces this forward once per camera geometry / batch
        # bucket through the fused analyzer's jit. traced_only means
        # eager interpret-mode test calls never consume budget.
        self._guarded_forward = recompile.trace_guard(
            "pallas.unet_forward", budget=8
        )(self._forward)

    # -- variable-tree walking ------------------------------------------

    def _fold(self, params, stats):
        """Pre-fold every BatchNorm into (scale, bias) next to its conv."""

        def double_conv(p, s):
            out = []
            for conv, bn in (("Conv_0", "BatchNorm_0"), ("Conv_1", "BatchNorm_1")):
                scale, bias = pconv.fold_batchnorm(p[bn], s[bn])
                out.append((p[conv]["kernel"], scale, bias))
            return out

        layers = {"inc": double_conv(params["DoubleConv_0"],
                                     stats["DoubleConv_0"])}
        for i in range(4):
            layers[f"down{i}"] = double_conv(
                params[f"Down_{i}"]["DoubleConv_0"],
                stats[f"Down_{i}"]["DoubleConv_0"],
            )
        for i in range(4):
            up = {"dc": double_conv(
                params[f"Up_{i}"]["DoubleConv_0"],
                stats[f"Up_{i}"]["DoubleConv_0"],
            )}
            if not self.model.bilinear:
                ct = params[f"Up_{i}"]["ConvTranspose_0"]
                up["convt"] = (ct["kernel"], ct["bias"])
            layers[f"up{i}"] = up
        head = params["Conv_0"]
        layers["head"] = (
            head["kernel"][0, 0],  # 1x1 conv kernel -> [Cin, Cout]
            jnp.ones((head["kernel"].shape[-1],), jnp.float32),
            jnp.asarray(head["bias"], jnp.float32),
        )
        return layers

    # -- forward --------------------------------------------------------

    def uniform_backend(self, batch: int, h: int, w: int) -> str:
        """ONE backend for the whole forward, per input shape (see the
        PALLAS_MAX_ELEMS comment): "pallas" or "xla"."""
        if self.force is not None:
            return self.force
        if self.interpret:
            # interpret-mode tests exist to validate the Pallas kernels;
            # the volume gate must never silently reroute them to XLA
            return "pallas"
        if not pconv.use_pallas():
            return "xla"
        widest = batch * h * w * 2 * self.model.base_features
        return "pallas" if widest <= PALLAS_MAX_ELEMS else "xla"

    def _double_conv(self, x, taps, force):
        for w, scale, bias in taps:
            x = _dispatch_3x3(
                x, w, scale, bias, relu=True,
                interpret=self.interpret, force=force,
            )
        return x

    def _up(self, x, skip, layer, force):
        b, h, w, c = skip.shape
        if self.model.bilinear:
            x = upsample_align_corners(x, h, w)
        else:
            wk, bias = layer["convt"]
            x = pconv.conv_transpose2x2(
                x, wk, bias, interpret=self.interpret
            ) if (force != "xla" and (
                self.interpret or pconv.use_pallas()
            )) else pconv.conv_transpose2x2_xla(x, wk, bias)
            x = jax.image.resize(
                x, (x.shape[0], h, w, x.shape[3]), method="nearest"
            )
        x = jnp.concatenate([skip, x.astype(skip.dtype)], axis=-1)
        return self._double_conv(x, layer["dc"], force)

    def __call__(self, x):
        """NHWC input -> NHWC f32 logits, same contract as
        ``model.apply(variables, x, train=False)``."""
        return self._guarded_forward(x)

    def _forward(self, x):
        L = self._layers
        force = self.uniform_backend(*x.shape[:3])
        x = x.astype(self.model.dtype)
        x1 = self._double_conv(x, L["inc"], force)
        xs = [x1]
        for i in range(4):
            x = nn.max_pool(xs[-1], (2, 2), strides=(2, 2))
            xs.append(self._double_conv(x, L[f"down{i}"], force))
        y = xs[4]
        for i in range(4):
            y = self._up(y, xs[3 - i], L[f"up{i}"], force)
        w, scale, bias = L["head"]
        logits = pconv.conv1x1(
            y, w, scale, bias, relu=False, out_dtype=jnp.float32,
            interpret=self.interpret,
        ) if (force != "xla" and (
            self.interpret or pconv.use_pallas()
        )) else pconv.conv1x1_xla(
            y, w, scale, bias, relu=False, out_dtype=jnp.float32
        )
        return logits


def make_pallas_unet(model, variables, *, interpret: bool = False,
                     force: str | None = None) -> PallasUNet:
    return PallasUNet(model, variables, interpret=interpret, force=force)
