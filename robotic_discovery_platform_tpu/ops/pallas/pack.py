"""Device-side mask bitpacking for the egress wire.

The egress twin of :mod:`decode`: where the split decode keeps decoded
PIXELS off the host on the way in, this kernel keeps the full-resolution
mask off the host on the way out. The analyzer's ``[B, H, W]`` uint8
binary mask packs to ``[B, H, ceil(W/8)]`` on the device (8 pixels per
byte, MSB first -- ``np.packbits`` order, so ``np.unpackbits`` is the
exact host-side inverse), an 8x reduction of the dominant D2H payload
before the completer's single blocking fetch. The op is one HBM pass --
mask in, bytes out -- so it is bandwidth-bound by construction
(utils/flops.py ``mask_bitpack_roofline_ms``; bench_pallas.py asserts
it).

Dispatch rides the same machinery as the geometry and decode kernels:
``GeometryConfig.kernel_impl`` through :func:`geometry.resolve_impl`
with the op key ``"mask_pack"``, so PALLAS_TUNE.json can pin either
backend per (batch, height, width) shape. The XLA path shifts and adds
integers (:func:`_pack_math`); the Pallas kernel compacts lanes with one
matmul against a static bit-weight matrix (:func:`_pack_matrix`) whose
products and sums are exact, so xla / pallas / interpret results are
bitwise identical -- the tests/test_egress.py co-traced gate.

This module also owns the PACKED PAYLOAD ROW layout the pipeline's
``pack_analysis`` emits and ``serving/egress.py`` parses: one
self-describing uint8 row per frame,

    [0:16)   header: ``<4sIII`` = (b"RDPP", height, width, n_pts)
    [16:..)  f32 sidecar, bitcast little-endian: coverage, mean
             curvature, max curvature, validity (1.0/0.0), confidence
             margin, then the [n_pts, 3] spline block row-major
    [..:..)  the bitpacked mask rows, H * ceil(W/8) bytes
    [..:P)   zero pad up to :func:`frame_payload_bytes` (a multiple of
             64, so every row of a 64-byte-aligned [B, P] staging
             buffer is itself 64-byte aligned)

The header makes each row self-describing: the completer hands rows out
without threading any (geometry, spline-count) metadata through the
dispatcher.
"""

from __future__ import annotations

import functools
import struct

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from robotic_discovery_platform_tpu.ops.pallas.conv import _pick_tile
from robotic_discovery_platform_tpu.ops.pallas.geometry import resolve_impl

#: f32 scalars ahead of the spline block in the sidecar: coverage,
#: mean curvature, max curvature, validity, confidence margin.
N_SCALARS = 5

#: bytes of the self-describing row header, ``<4sIII``.
HEADER_BYTES = 16

#: header magic of a packed payload ROW (staging layout). Wire payloads
#: carry their own magics (serving/egress.py: b"RDPB" / b"RDPR").
ROW_MAGIC = b"RDPP"

#: staging rows pad to a multiple of this, so rows of a 64-byte-aligned
#: pooled buffer (serving/batching._aligned_empty) stay 64-byte aligned.
ROW_ALIGN = 64


def sidecar_floats(n_pts: int) -> int:
    """f32 slots in the per-frame sidecar: the scalars + the spline."""
    return N_SCALARS + 3 * n_pts


def packed_row_bytes(w: int) -> int:
    """Bytes of one bitpacked mask row: ceil(w / 8)."""
    return (w + 7) // 8


def frame_payload_bytes(h: int, w: int, n_pts: int) -> int:
    """Total bytes of one frame's packed payload row, 64-byte padded."""
    raw = HEADER_BYTES + 4 * sidecar_floats(n_pts) + h * packed_row_bytes(w)
    return -(-raw // ROW_ALIGN) * ROW_ALIGN


@functools.lru_cache(maxsize=None)
def payload_header(h: int, w: int, n_pts: int) -> np.ndarray:
    """The [16] uint8 header constant for one frame geometry."""
    return np.frombuffer(
        struct.pack("<4sIII", ROW_MAGIC, h, w, n_pts), np.uint8
    )


def _pack_math(m):
    """The XLA path's bitpack arithmetic, ``[..., wb, 8]`` -> ``[..., wb]``
    (pure integer ops). Nonzero input is a set bit, MSB first --
    ``np.packbits``' default bit order, which makes
    ``np.unpackbits(packed, axis=-1)[..., :w]`` the exact inverse."""
    bits = (m != 0).astype(jnp.int32)
    packed = bits[..., 0]
    for k in range(1, 8):
        packed = packed * 2 + bits[..., k]
    return packed.astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _pack_matrix(w: int) -> np.ndarray:
    """The [W, ceil(W/8)] bit-weight matrix ``P`` with ``P[8j + k, j] =
    2**(7 - k)``: ``bits @ P`` packs eight adjacent lanes into one byte,
    MSB first. Every entry and every row sum (<= 255) is exact in bf16
    products with f32 accumulation."""
    r = np.arange(w)
    p = np.zeros((w, packed_row_bytes(w)), np.float32)
    p[r, r // 8] = 2.0 ** (7 - (r % 8))
    return p


def _pack_kernel(m_ref, p_ref, o_ref):
    """One (frame, row-tile) grid step: [1, tile_h, W] mask bytes to
    [1, tile_h, ceil(W/8)] packed bytes. The lane compaction is ONE matmul
    against the static bit-weight matrix (:func:`_pack_matrix`) -- the
    block keeps its minor dimension whole, where a [.., W/8, 8] view would
    pad each 8-lane group to a full 128-lane tile and overflow VMEM
    (Mosaic: 40.6 MB scoped at 480x640 against the 16 MB limit)."""
    bits = (m_ref[0] != 0).astype(jnp.bfloat16)
    packed = jnp.dot(bits, p_ref[:], preferred_element_type=jnp.float32)
    o_ref[0] = packed.astype(jnp.int32).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("impl",))
def bitpack_mask(mask, *, impl: str = "auto"):
    """Bitpack a ``[B, H, W]`` uint8 binary mask to ``[B, H, ceil(W/8)]``.

    Args:
        mask: [B, H, W] uint8 (any nonzero pixel packs as a set bit --
            the analyzer emits exact 0/1).
        impl: ``GeometryConfig.kernel_impl`` semantics via
            :func:`resolve_impl` ("auto" consults PALLAS_TUNE.json, then
            Pallas-on-TPU/XLA-elsewhere).

    Returns [B, H, ceil(W/8)] uint8, MSB-first per byte --
    ``np.unpackbits(out, axis=-1)[..., :W]`` recovers the exact mask.
    """
    b, h, w = mask.shape
    wb = packed_row_bytes(w)
    which = resolve_impl(impl, "mask_pack", b=b, h=h, w=w)
    if which == "xla":
        if w % 8:
            mask = jnp.pad(mask, ((0, 0), (0, 0), (0, wb * 8 - w)))
        return _pack_math(mask.reshape(b, h, wb, 8))
    # row tiles must be whole 32-row uint8 sublane tiles: pad H up
    # (padded rows pack to zero bytes and are sliced away)
    hp = -(-h // 32) * 32
    if hp != h:
        mask = jnp.pad(mask, ((0, 0), (0, hp - h), (0, 0)))
    tile_h = 32 * _pick_tile(hp // 32, 8)
    out = pl.pallas_call(
        _pack_kernel,
        grid=(b, hp // tile_h),
        in_specs=[
            pl.BlockSpec((1, tile_h, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((w, wb), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_h, wb), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hp, wb), jnp.uint8),
        interpret=which == "interpret",
        name="bitpack_mask",
    )(mask, jnp.asarray(_pack_matrix(w), jnp.bfloat16))
    return out[:, :h] if hp != h else out
