"""Fused TPU Pallas convolution kernels for the U-Net inference path.

The reference's hot blocks are DoubleConv = (3x3 conv no-bias -> BatchNorm ->
ReLU) x 2 (reference: pkg/segmentation_model.py:24-40) and the 2x2 stride-2
transposed conv of the non-bilinear decoder (reference: :54-65). On GPU the
reference leans on cuDNN; here each conv + folded-BatchNorm + ReLU is ONE
Pallas kernel:

- the 3x3 SAME conv is expressed as nine shifted ``(tile_h * W, Cin) @
  (Cin, Cout)`` matmuls accumulated in float32 -- the MXU-native decomposition
  (no im2col materialization, no gather);
- the input rides in as an overlapping row slab (halo = 1 row) via
  ``pl.Element`` block indexing, so the Pallas pipeline DMAs each row of HBM
  exactly once per tile;
- inference BatchNorm is folded to a per-channel scale/bias applied in the
  matmul epilogue together with ReLU, so normalized activations never touch
  HBM.

Everything accumulates in f32 and stores in the requested compute dtype
(bf16 by default, matching models/unet.py). The plain-XLA equivalents of
every kernel live alongside (``*_xla``) as the fallback path and the
numerics oracle; ``use_pallas()`` picks per-backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from robotic_discovery_platform_tpu.analysis.contracts import shape_contract
from robotic_discovery_platform_tpu.observability import instruments as obs


def _element_block_spec(shape, index_map) -> pl.BlockSpec:
    """A BlockSpec whose index_map returns ELEMENT offsets
    (``pl.Element`` per dimension). The halo-slab input of the 3x3 kernel
    needs element indexing: overlapping row tiles cannot be expressed as
    block indices."""
    return pl.BlockSpec(tuple(pl.Element(d) for d in shape), index_map)


def use_pallas() -> bool:
    """Default policy: compiled Pallas on TPU, XLA fallback elsewhere.

    (Kernels also run under ``interpret=True`` on CPU -- that is the test
    path, not the serving default.)
    """
    return jax.default_backend() == "tpu"


def fold_batchnorm(bn_params, bn_stats, eps: float = 1e-5):
    """Fold inference BatchNorm into per-channel (scale, bias), f32.

    y = (x - mean) / sqrt(var + eps) * gamma + beta
      = x * scale + bias.
    """
    gamma = jnp.asarray(bn_params["scale"], jnp.float32)
    beta = jnp.asarray(bn_params["bias"], jnp.float32)
    mean = jnp.asarray(bn_stats["mean"], jnp.float32)
    var = jnp.asarray(bn_stats["var"], jnp.float32)
    scale = gamma * jax.lax.rsqrt(var + eps)
    return scale, beta - mean * scale


def _pick_tile(size: int, target: int) -> int:
    """Largest divisor of ``size`` that is <= target."""
    t = min(size, target)
    while size % t:
        t -= 1
    return t


def _lane(n: int) -> int:
    """VMEM lane padding: a buffer's final dim is tiled to 128 lanes, so a
    narrow channel count occupies ceil(n/128)*128 lanes of space -- 16x
    the naive size at n=8. Every VMEM budget below must count this."""
    return -(-n // 128) * 128


_VMEM_BUDGET = 10 * 1024 * 1024  # against the 16 MB scoped-vmem limit


def vmem_bytes_3x3(tile_h: int, tile_co: int, w: int, cin: int,
                   in_itemsize: int, out_itemsize: int) -> int:
    """Estimated VMEM for one 3x3-conv grid step: halo slab, weight block,
    f32 accumulator, output block -- lane padding on every final dim and
    the Pallas pipeline's double buffering (x2 on every streamed block)
    counted. Shared by the analytic heuristic and the autotuner's
    candidate filter (ops/pallas/tuning.py)."""
    w_bytes = 2 * 9 * cin * _lane(tile_co) * in_itemsize
    slab = 2 * (tile_h + 2) * (w + 2) * _lane(cin) * in_itemsize
    acc = tile_h * w * _lane(tile_co) * 4
    out = 2 * tile_h * w * _lane(tile_co) * out_itemsize
    return w_bytes + slab + acc + out


def _tiles_3x3(h: int, w: int, cin: int, cout: int,
               in_itemsize: int, out_itemsize: int):
    """(tile_h, tile_co) under the VMEM budget (vmem_bytes_3x3). 10 MB
    against the 16 MB scoped-vmem limit: with the lane padding counted for
    real, this reproduces the serving tiles that have been stable since
    round 2 while keeping narrow-channel (test-sized) models under the
    hard limit."""
    budget = _VMEM_BUDGET
    tile_co = _pick_tile(cout, 256)
    while (tile_co > 128
           and 2 * 9 * cin * _lane(tile_co) * in_itemsize > budget // 3):
        tile_co = _pick_tile(cout, tile_co // 2)
    tile_h = _pick_tile(h, 64)
    while tile_h > 1:
        if vmem_bytes_3x3(tile_h, tile_co, w, cin, in_itemsize,
                          out_itemsize) <= budget:
            break
        tile_h = _pick_tile(h, tile_h // 2)
    return tile_h, tile_co


def _conv3x3_kernel(x_ref, w_ref, sb_ref, o_ref, *, tile_h, width, relu,
                    dx_major):
    """One (batch, row-tile, cout-tile) grid step.

    x_ref: [tile_h + 2, W + 2, Cin] halo slab (pl.Element rows) cut from the
        batch-flattened [B * (H + 2), W + 2, Cin] padded input.
    w_ref: [3, 3, Cin, tile_co].
    sb_ref: [2, tile_co] folded scale/bias rows.
    o_ref: [tile_h, W, tile_co] tile of the [B * H, W, Cout] output.

    Two loop orders, chosen statically (measured on v5e, see
    tests/test_pallas.py and BENCH notes):
    - ``dx_major``: one sublane shift per dx (3 total); after flattening rows
      into the sublane dim the dy offsets are W-aligned slices (an address
      offset, not a relayout). Wins for narrow feature maps (W <= ~128).
    - dy-major: nine small shifted patches. Wins for wide maps (W >= ~256)
      where whole-slab relayouts are the dominant cost.
    """
    cin = x_ref.shape[-1]
    tile_co = o_ref.shape[-1]
    slab = x_ref[:]
    acc = jnp.zeros((tile_h * width, tile_co), jnp.float32)
    if dx_major:
        for dx in range(3):
            flat = slab[:, dx:dx + width, :].reshape(
                (tile_h + 2) * width, cin
            )
            for dy in range(3):
                patch = flat[dy * width:dy * width + tile_h * width]
                acc = acc + jnp.dot(
                    patch, w_ref[dy, dx], preferred_element_type=jnp.float32
                )
    else:
        for dy in range(3):
            for dx in range(3):
                patch = slab[dy:dy + tile_h, dx:dx + width, :].reshape(
                    tile_h * width, cin
                )
                acc = acc + jnp.dot(
                    patch, w_ref[dy, dx], preferred_element_type=jnp.float32
                )
    y = acc * sb_ref[0:1, :] + sb_ref[1:2, :]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[:] = y.reshape(tile_h, width, tile_co).astype(o_ref.dtype)


@shape_contract(x="b h w ci", w="3 3 ci co", scale="co", bias="co",
                out="b h w co")
@functools.partial(
    jax.jit, static_argnames=("relu", "out_dtype", "interpret", "tiling")
)
def conv3x3_bn_relu(
    x, w, scale, bias, *, relu: bool = True, out_dtype=None,
    interpret: bool = False, tiling=None,
):
    """Fused NHWC 3x3 SAME conv + per-channel scale/bias (+ ReLU).

    The Pallas form of the reference DoubleConv half-block
    (pkg/segmentation_model.py:33-39: Conv2d(bias=False) -> BatchNorm ->
    ReLU), with BatchNorm pre-folded via :func:`fold_batchnorm`.

    Args:
        x: [B, H, W, Cin].
        w: [3, 3, Cin, Cout] (HWIO, the Flax kernel layout).
        scale, bias: [Cout] f32 epilogue coefficients.
        relu: apply max(y, 0) in the epilogue.
        out_dtype: output dtype (default: x.dtype).
        interpret: run the Pallas interpreter (CPU tests).
        tiling: optional (tile_h, tile_co, dx_major) override of the
            analytic VMEM-budget heuristic -- the autotuner
            (bench_pallas.py autotune / ops/pallas/tuning.py) sweeps these
            per shape; tile_h must divide H and tile_co divide Cout.
    """
    b, h, width, cin = x.shape
    cout = w.shape[-1]
    out_dtype = x.dtype if out_dtype is None else out_dtype
    if tiling is not None:
        tile_h, tile_co, dx_major = tiling
        if h % tile_h or cout % tile_co:
            raise ValueError(
                f"tiling {tiling} does not divide (H={h}, Cout={cout})"
            )
        need = vmem_bytes_3x3(tile_h, tile_co, width, cin, x.dtype.itemsize,
                              jnp.dtype(out_dtype).itemsize)
        if need > _VMEM_BUDGET:
            raise ValueError(
                f"tiling {tiling} needs an estimated {need} bytes of VMEM "
                f"at W={width}, Cin={cin}; the kernel's budget is "
                f"{_VMEM_BUDGET}"
            )
    else:
        tile_h, tile_co = _tiles_3x3(
            h, width, cin, cout, x.dtype.itemsize,
            jnp.dtype(out_dtype).itemsize
        )
        dx_major = width <= 192

    # Flatten batch into rows: each image is padded separately, so a halo
    # slab never crosses an image boundary (row tiles divide H exactly).
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0))).reshape(
        b * (h + 2), width + 2, cin
    )
    w = w.astype(x.dtype)  # MXU-native operand dtype, same as the XLA path
    sb = jnp.stack([scale, bias]).astype(jnp.float32)  # [2, Cout]

    kern = functools.partial(
        _conv3x3_kernel, tile_h=tile_h, width=width, relu=relu,
        dx_major=dx_major,
    )
    tiles = h // tile_h
    out = pl.pallas_call(
        kern,
        grid=(b * tiles, cout // tile_co),
        in_specs=[
            _element_block_spec(
                (tile_h + 2, width + 2, cin),
                lambda t, co: (
                    (t // tiles) * (h + 2) + (t % tiles) * tile_h, 0, 0
                ),
            ),
            pl.BlockSpec((3, 3, cin, tile_co), lambda t, co: (0, 0, 0, co)),
            pl.BlockSpec((2, tile_co), lambda t, co: (0, co)),
        ],
        out_specs=pl.BlockSpec(
            (tile_h, width, tile_co), lambda t, co: (t, 0, co)
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, width, cout), out_dtype),
        interpret=interpret,
        name="conv3x3_bn_relu",
    )(xp, w, sb)
    return out.reshape(b, h, width, cout)


def conv3x3_bn_relu_xla(x, w, scale, bias, *, relu: bool = True,
                        out_dtype=None):
    """XLA fallback / numerics oracle for :func:`conv3x3_bn_relu`."""
    out_dtype = x.dtype if out_dtype is None else out_dtype
    y = jax.lax.conv_general_dilated(
        x.astype(x.dtype), w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )
    y = y * scale + bias
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(out_dtype)


def _conv1x1_kernel(x_ref, w_ref, sb_ref, o_ref, *, relu):
    """x_ref: [1, tile_h, W, Cin]; w_ref: [Cin, tile_co]."""
    th, width, cin = x_ref.shape[1:]
    tile_co = o_ref.shape[-1]
    y = jnp.dot(
        x_ref[0].reshape(th * width, cin), w_ref[:],
        preferred_element_type=jnp.float32,
    )
    y = y * sb_ref[0:1, :] + sb_ref[1:2, :]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[0] = y.reshape(th, width, tile_co).astype(o_ref.dtype)


def _conv1x1_squeeze_kernel(x_ref, w_ref, sb_ref, o_ref, *, relu):
    """cout == 1 head: the output block is [1, tile_h, W] so the *width*
    rides on the VMEM lane dimension. Writing a [..., 1] block instead would
    pad that final dim 1 -> 128 lanes and blow the scoped-VMEM budget 128x
    (observed as a 24 MB stack allocation at batch 8, 256x256)."""
    th, width, cin = x_ref.shape[1:]
    y = jnp.dot(
        x_ref[0].reshape(th * width, cin), w_ref[:],
        preferred_element_type=jnp.float32,
    )
    y = y * sb_ref[0, 0] + sb_ref[1, 0]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[0] = y.reshape(th, width).astype(o_ref.dtype)


@shape_contract(x="b h w ci", w="ci co", scale="co", bias="co",
                out="b h w co")
@functools.partial(
    jax.jit, static_argnames=("relu", "out_dtype", "interpret")
)
def conv1x1(x, w, scale, bias, *, relu: bool = False, out_dtype=None,
            interpret: bool = False):
    """Fused NHWC 1x1 conv + scale/bias (+ ReLU): the OutConv head
    (reference: pkg/segmentation_model.py:78-84) with an identity scale and
    the conv bias riding in ``bias``."""
    b, h, width, cin = x.shape
    cout = w.shape[-1]
    out_dtype = x.dtype if out_dtype is None else out_dtype
    tile_co = _pick_tile(cout, 256)
    squeeze = cout == 1
    # VMEM budget per block, counting the lane padding the (8,128) tiled
    # layout applies to each buffer's final dimension.
    budget = 5 * 1024 * 1024

    def _padded(n: int) -> int:
        return -(-n // 128) * 128

    out_lanes = width if squeeze else _padded(tile_co)
    out_lane_rows = 1 if squeeze else width
    tile_h = _pick_tile(h, 128)
    while tile_h > 1 and 2 * tile_h * (
        width * _padded(cin) * x.dtype.itemsize
        + out_lane_rows * out_lanes * jnp.dtype(out_dtype).itemsize
    ) + tile_h * width * tile_co * 4 > budget:
        tile_h = _pick_tile(h, tile_h // 2)
    w = w.astype(x.dtype)
    sb = jnp.stack([scale, bias]).astype(jnp.float32)

    x_spec = pl.BlockSpec(
        (1, tile_h, width, cin), lambda bi, t, co: (bi, t, 0, 0)
    )
    if squeeze:
        out = pl.pallas_call(
            functools.partial(_conv1x1_squeeze_kernel, relu=relu),
            grid=(b, h // tile_h),
            in_specs=[
                pl.BlockSpec(
                    (1, tile_h, width, cin), lambda bi, t: (bi, t, 0, 0)
                ),
                pl.BlockSpec((cin, 1), lambda bi, t: (0, 0)),
                pl.BlockSpec((2, 1), lambda bi, t: (0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, tile_h, width), lambda bi, t: (bi, t, 0)
            ),
            out_shape=jax.ShapeDtypeStruct((b, h, width), out_dtype),
            interpret=interpret,
            name="conv1x1",
        )(x, w, sb)
        return out[..., None]

    kern = functools.partial(_conv1x1_kernel, relu=relu)
    return pl.pallas_call(
        kern,
        grid=(b, h // tile_h, cout // tile_co),
        in_specs=[
            x_spec,
            pl.BlockSpec((cin, tile_co), lambda bi, t, co: (0, co)),
            pl.BlockSpec((2, tile_co), lambda bi, t, co: (0, co)),
        ],
        out_specs=pl.BlockSpec(
            (1, tile_h, width, tile_co), lambda bi, t, co: (bi, t, 0, co)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, width, cout), out_dtype),
        interpret=interpret,
        name="conv1x1",
    )(x, w, sb)


def conv1x1_xla(x, w, scale, bias, *, relu: bool = False, out_dtype=None):
    out_dtype = x.dtype if out_dtype is None else out_dtype
    y = jnp.einsum(
        "bhwi,io->bhwo", x, w.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    y = y * scale + bias
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(out_dtype)


def _convt2x2_kernel(x_ref, w_ref, b_ref, o_ref, *, tile_h, width):
    """2x2 stride-2 transposed conv: each input pixel spawns a 2x2 output
    patch, so the kernel is four independent matmuls whose results
    interleave. x_ref: [1, tile_h, W, Cin]; w_ref: [2, 2, Cin, tile_co]."""
    cin = x_ref.shape[-1]
    tile_co = o_ref.shape[-1]
    x2d = x_ref[0].reshape(tile_h * width, cin)

    def tap(dy, dx):
        # out[2h+dy, 2w+dx] = x[h, w] @ w[1-dy, 1-dx] -- the spatially
        # flipped tap, matching lax.conv_transpose/Flax semantics
        # (verified exact against an f64 oracle).
        y = jnp.dot(
            x2d, w_ref[1 - dy, 1 - dx], preferred_element_type=jnp.float32
        )
        return y.reshape(tile_h, width, tile_co)

    # interleave columns then rows
    row0 = jnp.stack([tap(0, 0), tap(0, 1)], axis=2).reshape(
        tile_h, 2 * width, tile_co
    )
    row1 = jnp.stack([tap(1, 0), tap(1, 1)], axis=2).reshape(
        tile_h, 2 * width, tile_co
    )
    out = jnp.stack([row0, row1], axis=1).reshape(
        2 * tile_h, 2 * width, tile_co
    )
    o_ref[0] = (out + b_ref[0:1, :]).astype(o_ref.dtype)


@shape_contract(x="b h w ci", w="2 2 ci co", bias="co")
@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def conv_transpose2x2(x, w, bias, *, out_dtype=None, interpret: bool = False):
    """NHWC 2x2 stride-2 transposed conv + bias: the reference's
    non-bilinear ``Up`` upsampler (pkg/segmentation_model.py:62-63).

    Args:
        x: [B, H, W, Cin]; w: [2, 2, Cin, Cout]; bias: [Cout].
    Returns [B, 2H, 2W, Cout].
    """
    b, h, width, cin = x.shape
    cout = w.shape[-1]
    out_dtype = x.dtype if out_dtype is None else out_dtype
    tile_co = _pick_tile(cout, 256)
    budget = 5 * 1024 * 1024
    tile_h = _pick_tile(h, 32)
    while tile_h > 1 and 2 * tile_h * width * (
        _lane(cin) * x.dtype.itemsize
        + 4 * _lane(tile_co) * jnp.dtype(out_dtype).itemsize
    ) + 4 * tile_h * width * _lane(tile_co) * 4 > budget:
        tile_h = _pick_tile(h, tile_h // 2)
    w = w.astype(x.dtype)
    bias2d = jnp.asarray(bias, jnp.float32).reshape(1, cout)

    kern = functools.partial(_convt2x2_kernel, tile_h=tile_h, width=width)
    return pl.pallas_call(
        kern,
        grid=(b, h // tile_h, cout // tile_co),
        in_specs=[
            pl.BlockSpec(
                (1, tile_h, width, cin), lambda bi, t, co: (bi, t, 0, 0)
            ),
            pl.BlockSpec((2, 2, cin, tile_co), lambda bi, t, co: (0, 0, 0, co)),
            pl.BlockSpec((1, tile_co), lambda bi, t, co: (0, co)),
        ],
        out_specs=pl.BlockSpec(
            (1, 2 * tile_h, 2 * width, tile_co),
            lambda bi, t, co: (bi, t, 0, co),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 2 * h, 2 * width, cout), out_dtype),
        interpret=interpret,
        name="conv_transpose2x2",
    )(x, w, bias2d)


def conv_transpose2x2_xla(x, w, bias, *, out_dtype=None):
    out_dtype = x.dtype if out_dtype is None else out_dtype
    y = jax.lax.conv_transpose(
        x, w.astype(x.dtype), (2, 2), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )
    return (y + jnp.asarray(bias, jnp.float32)).astype(out_dtype)


# ---------------------------------------------------------------------------
# Training-path custom-VJP conv (forward AND backward as Pallas kernels).
#
# The inference kernels above fold BatchNorm, which training cannot (batch
# statistics must be computed from the live conv output), so the training
# unit is the RAW 3x3 no-bias conv of the reference DoubleConv
# (pkg/segmentation_model.py:30-33); BatchNorm/ReLU stay in XLA where their
# train-mode statistics autodiff for free. All three derivatives of a
# stride-1 SAME 3x3 conv are themselves MXU-shaped programs:
#
#   y  = conv(x, w)                   -- the forward kernel (unit epilogue)
#   dx = conv(dy, flipT(w))           -- SAME conv with the spatially
#                                        flipped, in/out-transposed kernel:
#                                        the SAME forward kernel reused
#   dw[ky,kx] = sum_bhw xpad[...+ky, ...+kx]^T @ dy   -- nine reduction
#                                        matmuls: a dedicated accumulating
#                                        kernel below
# ---------------------------------------------------------------------------


def _conv3x3_dw_kernel(x_ref, g_ref, o_ref, *, tile_h, width):
    """One (cout-tile, slab) grid step of the weight-gradient reduction.

    x_ref: [1, tile_h + 2, W + 2, Cin] pre-materialized halo slab (standard
        block indexing -- see conv3x3_grad_weights for why not pl.Element).
    g_ref: [1, tile_h, W, tile_co] tile of the upstream gradient.
    o_ref: [9, Cin, tile_co] all nine taps' gradient block, revisited (and
        accumulated into) across every slab grid step -- the slab axis is
        the minor grid dimension, so TPU grid sequencing makes the
        accumulation well-defined. The nine tap windows are SLICED inside
        the kernel (static offsets), the same scheme as the forward kernel.
    """
    cin = x_ref.shape[-1]
    tile_co = o_ref.shape[-1]
    s = pl.program_id(1)
    slab = x_ref[0]
    g2d = g_ref[0].reshape(tile_h * width, tile_co)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    for ky in range(3):
        for kx in range(3):
            patch = slab[ky:ky + tile_h, kx:kx + width, :].reshape(
                tile_h * width, cin
            )
            part = jax.lax.dot_general(
                patch, g2d, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            o_ref[ky * 3 + kx] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv3x3_grad_weights(x, g, *, interpret: bool = False):
    """dL/dw for a stride-1 SAME 3x3 no-bias conv: [3, 3, Cin, Cout] f32.

    Unlike the forward kernel, the overlapping halo slabs are materialized
    at the XLA level (one extra HBM copy of x, ~2/tile_h overhead) and the
    kernel uses standard block indexing. The pl.Element halo scheme the
    forward kernel uses is not used here: under an earlier JAX the TPU
    compiler crashed whenever an Element-indexed dw kernel shared one XLA
    module with the forward kernel -- as every backward pass does (not
    re-tested under jax 0.9.0).

    Args:
        x: [B, H, W, Cin] forward input.
        g: [B, H, W, Cout] upstream gradient.
    """
    b, h, width, cin = x.shape
    cout = g.shape[-1]
    if cin < 64:
        # narrow lane dims (the RGB input layer) crashed the TPU compiler
        # at serving scale under an earlier JAX; zero-padded channels
        # contribute exactly zero to the gradient, so pad up to a full lane
        # tile and slice the result back (a negligible FLOP fraction)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 64 - cin)))
        return conv3x3_grad_weights(x, g, interpret=interpret)[:, :, :cin]
    # VMEM accounting against the 16 MB scoped limit (observed error
    # text): the f32 9-tap accumulator block, the double-buffered slab and
    # gradient tiles, AND the nine unrolled in-kernel patch reshapes --
    # the compiler stack-allocates all nine live (measured: 9 x patch
    # dominates the 16.69M OOM at tile_h=32, W=256, C=64).
    tile_co = cout
    while 9 * cin * _lane(tile_co) * 4 > 6 * 1024 * 1024 and tile_co % 256 == 0:
        tile_co //= 2
    acc = 9 * cin * _lane(tile_co) * 4
    budget = 10 * 1024 * 1024
    tile_h = _pick_tile(h, 32)
    while tile_h > 1 and (
        2 * ((tile_h + 2) * (width + 2) * _lane(cin) * x.dtype.itemsize
             + tile_h * width * _lane(tile_co) * g.dtype.itemsize)
        + 9 * tile_h * width * _lane(cin) * x.dtype.itemsize
        + acc
    ) > budget:
        tile_h = _pick_tile(h, tile_h // 2)
    tiles = h // tile_h

    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    # overlapping slabs: [B, tiles, tile_h + 2, W + 2, Cin] -> flat slabs
    slabs = jnp.stack(
        [xp[:, i * tile_h:i * tile_h + tile_h + 2] for i in range(tiles)],
        axis=1,
    ).reshape(b * tiles, tile_h + 2, width + 2, cin)
    gf = g.reshape(b * tiles, tile_h, width, cout)

    out = pl.pallas_call(
        functools.partial(_conv3x3_dw_kernel, tile_h=tile_h, width=width),
        grid=(cout // tile_co, b * tiles),
        in_specs=[
            pl.BlockSpec(
                (1, tile_h + 2, width + 2, cin),
                lambda co, s: (s, 0, 0, 0),
            ),
            pl.BlockSpec((1, tile_h, width, tile_co),
                         lambda co, s: (s, 0, 0, co)),
        ],
        out_specs=pl.BlockSpec((9, cin, tile_co), lambda co, s: (0, 0, co)),
        out_shape=jax.ShapeDtypeStruct((9, cin, cout), jnp.float32),
        interpret=interpret,
        name="conv3x3_grad_weights",
    )(slabs, gf)
    return out.reshape(3, 3, cin, cout)


def conv3x3_grad_weights_xla(x, g):
    """XLA oracle for :func:`conv3x3_grad_weights` (the standard
    activations*grads correlation, expressed as a conv over the batch dim)."""
    dw = jax.lax.conv_general_dilated(
        jnp.transpose(x, (3, 1, 2, 0)),  # [Cin, H, W, B]
        jnp.transpose(g, (1, 2, 0, 3)),  # [H, W, B, Cout] as an HxW kernel
        window_strides=(1, 1), padding=((1, 1), (1, 1)),  # -> 3x3 output
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )  # [Cin, 3, 3, Cout]
    return jnp.transpose(dw, (1, 2, 0, 3))


#: Where "auto" sends a 3x3 training convolution to the Pallas kernels: a
#: batch of at most 4 and at most 2^18 output pixels (batch x H x W), the
#: reference configuration's regime (batch 4 at 256^2,
#: scripts/train_segmenter.py:46) and the only one measured in which they
#: win (:func:`_vjp_pallas` has the numbers).
_PALLAS_TRAIN_MAX_BATCH = 4
_PALLAS_TRAIN_MAX_PIXELS = 2 ** 18


def _vjp_pallas(x, cin: int, cout: int, impl: str, interpret: bool) -> bool:
    """ONE dispatch predicate for a 3x3 training convolution, shared by
    :func:`conv3x3` (custom VJP or JAX's own derivative) and by the custom
    VJP's forward, dx and dw (so the rules cannot drift apart between
    them). True -> Pallas kernels.

    - interpret always exercises the interpreted Pallas kernels (they are
      what the CPU tests exist to validate);
    - sub-sublane channel counts (the RGB input layer, its cout=3 dx conv,
      and any dw whose lane dim would be < 8) crashed the TPU compiler at
      large batch under an earlier JAX; those layers are a negligible FLOP fraction
      and already sit at XLA boundaries, so they run the XLA forms under
      every COMPILED dispatch mode, forced "pallas" included;
    - "auto" reads the layer's shape. Measured on one TPU v5e ("TPU v5
      lite", jax 0.9.0, PR 34; the train step of the 64-128-256-512-512
      U-Net at 256^2 inside the whole-epoch scan, device ms a step, every
      layer Pallas against every layer the plain XLA convolution with
      JAX's own derivative): batch 4: 22.43 vs 23.56; batch 8: 48.96 vs
      28.96; batch 16: 100.56 vs 56.47; batch 32: 207.62 vs 115.72 (and
      215.75 vs 89.19 on the 1024-wide up-convolution U-Net). The Pallas
      step costs 5.6-6.5 ms an image at every batch; XLA's costs 5.9 at
      batch 4 and 3.5-3.6 from batch 8 on. The rule this replaced, batch
      x H x W <= 2^18 alone, sent the blocks at 64^2 and below of a
      batch-32 step to Pallas, and each was slower there (that mixed step:
      125.31 ms). So "auto" keeps Pallas for a batch of at most 4 with at
      most 2^18 pixels, the one regime measured where it wins; batches
      5-7, and 1-2 at other sizes, are unmeasured. The table by block and
      by layer is in PERF.md section 5.
    """
    if interpret or impl == "interpret":
        return True
    if min(cin, cout) < 8:
        return False
    if impl == "pallas":
        return True
    if impl == "xla":
        return False
    batch, h, width = x.shape[:3]
    small = (batch <= _PALLAS_TRAIN_MAX_BATCH
             and batch * h * width <= _PALLAS_TRAIN_MAX_PIXELS)
    return use_pallas() and small


@jax.named_scope("rdp.conv3x3")
def _conv3x3_raw(x, w, impl: str, interpret: bool):
    cin, cout = w.shape[2], w.shape[3]
    unit = jnp.ones((cout,), jnp.float32)
    zero = jnp.zeros((cout,), jnp.float32)
    interpret = interpret or impl == "interpret"
    if _vjp_pallas(x, cin, cout, impl, interpret):
        return conv3x3_bn_relu(
            x, w, unit, zero, relu=False, interpret=interpret
        )
    return conv3x3_bn_relu_xla(x, w, unit, zero, relu=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv3x3_vjp(x, w, impl: str, interpret: bool):
    return _conv3x3_raw(x, w, impl, interpret)


def _conv3x3_fwd(x, w, impl, interpret):
    return _conv3x3_raw(x, w, impl, interpret), (x, w)


@jax.named_scope("rdp.conv3x3")
def _conv3x3_bwd(impl, interpret, res, g):
    x, w = res
    g = g.astype(x.dtype)
    # dx: SAME conv of the upstream gradient with the flipped, transposed
    # kernel -- the same forward kernel on transformed weights.
    wt = jnp.transpose(w[::-1, ::-1], (0, 1, 3, 2)).astype(x.dtype)
    dx = _conv3x3_raw(g, wt, impl, interpret)
    interpret = interpret or impl == "interpret"
    # the shared predicate, on the conv's own (cin, cout): the dw kernel's
    # lane dims are cout (accumulator) and cin (slab)
    if _vjp_pallas(x, w.shape[2], w.shape[3], impl, interpret):
        dw = conv3x3_grad_weights(x, g, interpret=interpret)
    else:
        dw = conv3x3_grad_weights_xla(x, g)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv3x3_vjp.defvjp(_conv3x3_fwd, _conv3x3_bwd)


def conv3x3(x, w, impl: str = "auto", interpret: bool = False):
    """Differentiable stride-1 SAME 3x3 no-bias conv of the training step
    -- the DoubleConv half-block's conv (reference:
    pkg/segmentation_model.py:30-33).

    ``impl="auto"`` asks :func:`_vjp_pallas` about the layer's shape. Where
    it says yes, and under every pinned ``impl`` ("pallas", "xla",
    "interpret": the tests'), the conv is a ``jax.custom_vjp`` whose
    forward, dx and dw are the Pallas kernels above (or their hand-written
    XLA oracles where the predicate says no to a pinned mode). Where
    "auto" says no there is no custom VJP: the conv is the plain
    ``lax.conv_general_dilated`` that ``nn.Conv`` issues (operands in the
    compute dtype, float32 accumulation inside the product, the result in
    the compute dtype) and JAX derives dx and dw, so XLA chooses every
    layout and fuses the neighbouring norm and ReLU as it sees fit.

    Counts one sample of ``rdp_train_conv_dispatch_total`` per call, that
    is per trace of the step that holds it. Forward, dx and dw run under
    the named scope ``rdp.conv3x3``, whichever form is picked.
    """
    pallas = _vjp_pallas(x, w.shape[2], w.shape[3], impl, interpret)
    obs.TRAIN_CONV_DISPATCH.labels(impl="pallas" if pallas else "xla").inc()
    if impl == "auto" and not pallas:
        with jax.named_scope("rdp.conv3x3"):
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
    return _conv3x3_vjp(x, w, impl, interpret)
