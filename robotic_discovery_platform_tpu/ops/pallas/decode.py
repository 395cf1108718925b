"""Fused dequant + 8x8 IDCT for the split JPEG decode, on the block axis.

The device half of the ROADMAP "device-side ingest" split (host half:
serving/entropy.py). The host ships QUANTIZED coefficient blocks
``[B, N, 64] int16`` plus per-frame quant tables ``[B, 64]``; this kernel
fuses the dequantize multiply with the 2-D 8x8 inverse DCT and the final
level shift/clamp, so the only HBM traffic is coefficients in, spatial
samples out -- bandwidth-bound by construction (utils/flops.py
``jpeg_idct_roofline_ms``; bench_pallas.py asserts it).

**Why integer matmuls.** libjpeg's ``jpeg_idct_islow`` -- what
``cv2.imdecode`` runs -- is a fixed-point Loeffler factorization that is
LINEAR between its two DESCALE roundings: pass 1 (columns) is an exact
integer linear map of the dequantized inputs, DESCALE(.., 11), and pass 2
(rows) is the SAME map followed by DESCALE(.., 18) + 128. Feeding unit
vectors through the butterflies with exact integer arithmetic yields the
8x8 integer basis matrix A (:func:`islow_basis`); on the flattened block
axis the two passes become two ``[N, 64] @ [64, 64]`` matmuls --
``kron(A, I8)`` then ``kron(I8, A)`` -- i.e. batched DCT-basis matmuls in
exactly the MXU shape the ISSUE/ROADMAP call for, while staying BITWISE
equal to libjpeg (int32 two's-complement wrap and arithmetic shifts match
C semantics in both numpy and XLA). That bit-exactness is what lets the
golden tests pin the whole split decode against ``cv2.imdecode`` and the
XLA path against the Pallas path (co-traced in one jit, the
tests/test_pallas_geometry.py idiom).

Dispatch rides the same machinery as the geometry kernels:
``GeometryConfig.kernel_impl`` through :func:`geometry.resolve_impl` with
the op key ``"jpeg_idct"``. On the chip "auto" runs the XLA path: Mosaic
refuses the kernel's int32 x int32 matmul (the v5e MXU has no int32
accumulate; ``geometry.MOSAIC_REFUSES``), while XLA's own int32 dot is exact
there -- the decode stays bitwise equal to libjpeg (chip_smoke.py checks it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from robotic_discovery_platform_tpu.ops.pallas.conv import _pick_tile
from robotic_discovery_platform_tpu.ops.pallas.geometry import resolve_impl

# islow fixed-point constants: FIX(x) at CONST_BITS = 13.
_CONST_BITS = 13
_PASS1_SHIFT = _CONST_BITS - 2           # 11: pass 1 DESCALE
_PASS2_SHIFT = _CONST_BITS + 2 + 3       # 18: pass 2 DESCALE
_FIX = {
    "c0298": 2446, "c0390": 3196, "c0541": 4433, "c0765": 6270,
    "c0899": 7373, "c1175": 9633, "c1501": 12299, "c1847": 15137,
    "c1961": 16069, "c2053": 16819, "c2562": 20995, "c3072": 25172,
}


@functools.lru_cache(maxsize=None)
def islow_basis() -> np.ndarray:
    """The exact [8, 8] int32 basis matrix of one ``jpeg_idct_islow`` pass.

    Runs the islow butterfly on unit vectors with Python ints (the pass is
    linear up to its DESCALE, so columns of the result ARE the matrix).
    ``pass_out = DESCALE(A @ x, shift)`` reproduces libjpeg bit for bit.
    """
    f = _FIX
    a = np.zeros((8, 8), np.int64)
    for j in range(8):
        x = [0] * 8
        x[j] = 1
        z2, z3 = x[2], x[6]
        z1 = (z2 + z3) * f["c0541"]
        t2 = z1 - z3 * f["c1847"]
        t3 = z1 + z2 * f["c0765"]
        t0 = (x[0] + x[4]) << _CONST_BITS
        t1 = (x[0] - x[4]) << _CONST_BITS
        t10, t13 = t0 + t3, t0 - t3
        t11, t12 = t1 + t2, t1 - t2
        o0, o1, o2, o3 = x[7], x[5], x[3], x[1]
        z1, z2 = o0 + o3, o1 + o2
        z3, z4 = o0 + o2, o1 + o3
        z5 = (z3 + z4) * f["c1175"]
        o0 *= f["c0298"]
        o1 *= f["c2053"]
        o2 *= f["c3072"]
        o3 *= f["c1501"]
        z1 *= -f["c0899"]
        z2 *= -f["c2562"]
        z3 = z3 * -f["c1961"] + z5
        z4 = z4 * -f["c0390"] + z5
        o0 += z1 + z3
        o1 += z2 + z4
        o2 += z2 + z3
        o3 += z1 + z4
        col = (t10 + o3, t11 + o2, t12 + o1, t13 + o0,
               t13 - o0, t12 - o1, t11 - o2, t10 - o3)
        for i in range(8):
            a[i, j] = col[i]
    return a.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _pass_matrices() -> tuple:
    """([64, 64], [64, 64]) int32 right-multiply forms of the two passes.

    With blocks flattened row-major (index = 8*row + col):
    pass 1 contracts block COLUMNS -> ``x @ kron(A, I8).T``;
    pass 2 contracts block ROWS    -> ``ws @ kron(I8, A).T``.
    """
    a = islow_basis().astype(np.int64)
    eye = np.eye(8, dtype=np.int64)
    m1 = np.kron(a, eye).T.astype(np.int32)
    m2 = np.kron(eye, a).T.astype(np.int32)
    return m1, m2


def _descale(x, shift: int):
    """libjpeg DESCALE: round-half-up then arithmetic shift right."""
    return (x + (1 << (shift - 1))) >> shift


def _idct_math(deq, m1, m2):
    """The shared two-pass islow arithmetic, [M, 64] int32 in/out.

    Used verbatim by BOTH the XLA reference path and the Pallas kernel
    body, so interpret-mode results match the XLA path bitwise (integer
    ops have no contraction-order freedom).
    """
    ws = _descale(
        jax.lax.dot(deq, m1, preferred_element_type=jnp.int32),
        _PASS1_SHIFT,
    )
    s = _descale(
        jax.lax.dot(ws, m2, preferred_element_type=jnp.int32),
        _PASS2_SHIFT,
    ) + 128
    return jnp.clip(s, 0, 255)


def _idct_kernel(c_ref, q_ref, m1_ref, m2_ref, o_ref):
    """One (frame, block-tile) grid step: [1, tile_n, 64] coefficients
    dequantized against that frame's [1, 64] quant row, then the two
    matmul passes. The basis matrices ride in as inputs (a kernel cannot
    close over array constants)."""
    o_ref[0] = _idct_math(
        c_ref[0] * q_ref[:], m1_ref[:], m2_ref[:]
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def dequant_idct(coefs, q, *, impl: str = "auto"):
    """Fused dequantize + 8x8 islow IDCT over the block axis.

    Args:
        coefs: [B, N, 64] integer QUANTIZED coefficients, natural
            (row-major) order -- ``serving.entropy.CoefficientFrame``
            planes, batched.
        q: [B, 64] integer quant tables (per frame: tables may differ
            across cameras/qualities within one batch).
        impl: ``GeometryConfig.kernel_impl`` semantics via
            :func:`resolve_impl` ("auto" consults PALLAS_TUNE.json, then
            Pallas-on-TPU/XLA-elsewhere).

    Returns [B, N, 64] int32 spatial samples in 0..255 (level-shifted,
    range-limited), bitwise equal to libjpeg's islow output.
    """
    b, n, _ = coefs.shape
    cc = jnp.asarray(coefs).astype(jnp.int32)
    qq = jnp.asarray(q).astype(jnp.int32)
    m1, m2 = _pass_matrices()
    which = resolve_impl(impl, "jpeg_idct", b=b, n=n)
    if which == "xla":
        deq = (cc * qq[:, None, :]).reshape(b * n, 64)
        return _idct_math(
            deq, jnp.asarray(m1), jnp.asarray(m2)
        ).reshape(b, n, 64)
    tile_n = _pick_tile(n, 512)
    return pl.pallas_call(
        _idct_kernel,
        grid=(b, n // tile_n),
        in_specs=[
            pl.BlockSpec((1, tile_n, 64), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 64), lambda i, j: (i, 0)),
            pl.BlockSpec((64, 64), lambda i, j: (0, 0)),
            pl.BlockSpec((64, 64), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_n, 64), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, 64), jnp.int32),
        interpret=which == "interpret",
        name="dequant_idct",
    )(cc, qq, jnp.asarray(m1), jnp.asarray(m2))
