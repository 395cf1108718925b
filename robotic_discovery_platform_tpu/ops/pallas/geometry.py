"""Fused Pallas kernels for the non-conv analyzer stages.

The analyzer's remaining XLA-op chains (ROADMAP "Roofline-driven Pallas
expansion") are bandwidth-bound elementwise/reduction pipelines that XLA
emits as several HBM round trips:

- **deproject + masked edge-stats** (ops/geometry.py): the pinhole
  deprojection writes four dense [H, W] maps, then `_edge_points` re-reads
  them five times for the masked min/max/count reductions that seed the
  binning. :func:`deproject_edge_stats` computes the maps AND the five
  reductions in ONE pass over the input tiles -- each pixel is read once,
  the per-tile partials (one aligned [8, 128] tile per grid step) are
  folded outside the kernel with order-independent min/max/integer-sum, so
  the result is bitwise identical to the XLA reference path.
- **B-spline design matmuls** (ops/bspline.py): the Cox-de Boor basis
  matrix B [N, C] is materialized to HBM only to be immediately contracted
  into the [C, C] Gram matrix and [C, D] right-hand side.
  :func:`bspline_design` computes the basis in VMEM and performs both
  contractions in the same kernel -- B never touches HBM.
- **curvature evaluation** (ops/bspline.py): three derivative design
  matrices and the cross/norm curvature formula fuse into
  :func:`bspline_curvature`.

Every kernel mirrors the XLA reference path op for op (the basis recursion
and curvature formula are the SAME shared helpers from ops/bspline.py), so
tests/test_pallas_geometry.py compares them BITWISE on CPU in interpret
mode. Compiled by Mosaic the two spline kernels agree with XLA to f32
rounding only (the f32 matmul passes are ordered differently;
chip_smoke.py measures it). Dispatch is per-shape via :func:`resolve_impl`:
``GeometryConfig.kernel_impl`` ("auto" = Pallas on TPU, XLA elsewhere) with
the PALLAS_TUNE.json autotable able to veto or force a backend per
(op, shape) -- the same measured-overlay convention as the conv tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from robotic_discovery_platform_tpu.ops.pallas.conv import (
    _pick_tile,
    use_pallas,
)

KERNEL_IMPLS = ("auto", "pallas", "xla", "interpret")

#: Ops whose Pallas kernel Mosaic refuses to compile on the one supported
#: installation (jax 0.9.0 / libtpu 0.0.34, TPU v5e), with the compiler's
#: reason. "auto" runs their XLA twin on every backend -- a stated, static
#: choice (chip_smoke.py prints it), never a run-time fallback. The
#: kernels and their interpret-mode tests stay for a later repair;
#: ``kernel_impl="pallas"`` still pins them and surfaces Mosaic's error.
MOSAIC_REFUSES = {
    "jpeg_idct": (
        "int32 x int32 matmul with int32 accumulation, which the v5e MXU "
        "does not have: \"Mosaic failed to compile TPU kernel: Bad lhs/rhs "
        "type: 'vector<480x128xi32>' 'vector<128x128xi32>'\" (tpu.matmul)"
    ),
}


def resolve_impl(configured: str, op: str, **dims) -> str:
    """The backend one fused-geometry launch runs: "pallas", "interpret",
    or "xla".

    ``configured`` is ``GeometryConfig.kernel_impl``: "xla" / "pallas" /
    "interpret" pin a path; "auto" runs Pallas on TPU and XLA elsewhere --
    except the ops in :data:`MOSAIC_REFUSES`, which run XLA everywhere --
    with a per-(op, shape) entry in the PALLAS_TUNE.json table able to
    override the default either way (the escape hatch for shapes where the
    measured kernel loses to XLA, exactly like the conv tile overrides).
    """
    if configured not in KERNEL_IMPLS:
        raise ValueError(
            f"unknown kernel_impl {configured!r} (choose from "
            f"{KERNEL_IMPLS})"
        )
    if configured != "auto":
        return configured
    if op in MOSAIC_REFUSES:
        return "xla"
    from robotic_discovery_platform_tpu.ops.pallas import tuning

    table = tuning.lookup_impl(op, **dims)
    if table in ("pallas", "xla"):
        return table
    return "pallas" if use_pallas() else "xla"


# -- deproject + masked edge-stats ------------------------------------------


#: per-tile stats ride one aligned f32 vreg tile; lanes 0..4 of every row
#: hold x_min, x_max, y_min, y_max, n_valid
_STATS_TILE = (8, 128)


def _deproject_kernel(p_ref, m_ref, d_ref, x_ref, y_ref, z_ref, v_ref,
                      s_ref, *, tile_h, width, stride):
    """One row-tile grid step: maps + per-tile masked stats.

    p_ref: [8] f32 parameters in SMEM (fx, fy, cx, cy, depth_scale, 0...).
    m_ref/d_ref: [tile_h, W] f32 mask/depth tiles (pre-cast by the
        wrapper: uint8/uint16 -> f32 is exact).
    x/y/z/v_ref: [tile_h, W] f32 output map tiles (v is 0/1).
    s_ref: one [8, 128] stats tile per grid step (Mosaic stores no scalars
        to VMEM, and a block's last two dims must tile by 8 x 128): lane k
        of every row carries stat k -- x_min, x_max, y_min, y_max, n_valid
        (masked with the same +-1e30 sentinels as the XLA path, so folding
        the tiles with min/max/sum outside reproduces its values).
    """
    i = pl.program_id(0)
    fx, fy = p_ref[0], p_ref[1]
    cx, cy = p_ref[2], p_ref[3]
    ds = p_ref[4]
    off = (stride - 1) / 2.0
    # integer iota (the TPU has no float iota); small ints convert exactly
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile_h, width), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile_h, width), 1)
    vv = (rows + i * tile_h).astype(jnp.float32) * stride + off
    uu = cols.astype(jnp.float32) * stride + off
    z = d_ref[:] * ds
    valid = (m_ref[:] > 0) & (z > 0)
    x = (uu - cx) * z / fx
    y = (vv - cy) * z / fy
    x_ref[:] = x
    y_ref[:] = y
    z_ref[:] = z
    v_ref[:] = valid.astype(jnp.float32)
    big = jnp.float32(1e30)
    stats = (
        jnp.min(jnp.where(valid, x, big)),
        jnp.max(jnp.where(valid, x, -big)),
        jnp.min(jnp.where(valid, y, big)),
        jnp.max(jnp.where(valid, y, -big)),
        jnp.sum(valid.astype(jnp.float32)),
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, _STATS_TILE, 1)
    tile = jnp.zeros(_STATS_TILE, jnp.float32)
    for k, stat in enumerate(stats):
        tile = jnp.where(lane == k, stat, tile)
    s_ref[:] = tile


@functools.partial(jax.jit, static_argnames=("stride", "interpret"))
def deproject_edge_stats(mask, depth, fx, fy, cx, cy, depth_scale, *,
                         stride: int = 1, interpret: bool = False):
    """Fused pinhole deprojection + masked edge-stat reductions.

    Args:
        mask, depth: [H, W] (any dtype; cast to f32 -- exact for the
            uint8/uint16 camera formats).
        fx, fy, cx, cy, depth_scale: scalars (traced OK).
        stride: the pooled-view stride (iota coordinates scale, center
            offset), same semantics as ops/geometry.deproject.

    Returns ``(x, y, z, valid_bool, (x_min, x_max, y_min, y_max,
    n_valid_i32))`` -- the same elementwise f32 ops as the XLA reference
    path (``deproject`` + the inline reductions of ``_edge_points``) with
    order-independent min/max/integer-count folds: bitwise identical to it
    in interpret mode and, compiled by Mosaic on a v5e, on every frame
    chip_smoke.py has compared.
    """
    h, width = depth.shape
    mf = jnp.asarray(mask).astype(jnp.float32)
    df = jnp.asarray(depth).astype(jnp.float32)
    # row tiles must be whole 8-row f32 sublane tiles: pad H up (padded
    # rows carry mask 0, so they are invalid and leave the stats alone)
    hp = -(-h // 8) * 8
    if hp != h:
        mf = jnp.pad(mf, ((0, hp - h), (0, 0)))
        df = jnp.pad(df, ((0, hp - h), (0, 0)))
    params = jnp.stack([
        jnp.asarray(fx, jnp.float32), jnp.asarray(fy, jnp.float32),
        jnp.asarray(cx, jnp.float32), jnp.asarray(cy, jnp.float32),
        jnp.asarray(depth_scale, jnp.float32),
        *(jnp.zeros((), jnp.float32),) * 3,
    ])
    tile_h = 8 * _pick_tile(hp // 8, 8)
    tiles = hp // tile_h
    map_shape = jax.ShapeDtypeStruct((hp, width), jnp.float32)
    map_spec = pl.BlockSpec((tile_h, width), lambda i: (i, 0))
    sr, sl = _STATS_TILE
    x, y, z, v, part = pl.pallas_call(
        functools.partial(_deproject_kernel, tile_h=tile_h, width=width,
                          stride=stride),
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            map_spec,
            map_spec,
        ],
        out_specs=[
            map_spec, map_spec, map_spec, map_spec,
            pl.BlockSpec(_STATS_TILE, lambda i: (i, 0)),
        ],
        out_shape=[map_shape, map_shape, map_shape, map_shape,
                   jax.ShapeDtypeStruct((tiles * sr, sl), jnp.float32)],
        interpret=interpret,
        name="deproject_edge_stats",
    )(params, mf, df)
    part = part.reshape(tiles, sr, sl)[:, 0]
    stats = (
        jnp.min(part[:, 0]),
        jnp.max(part[:, 1]),
        jnp.min(part[:, 2]),
        jnp.max(part[:, 3]),
        jnp.sum(part[:, 4]).astype(jnp.int32),
    )
    if hp != h:
        x, y, z, v = (a[:h] for a in (x, y, z, v))
    return x, y, z, v > 0, stats


# -- fused B-spline design matrices -----------------------------------------


def _design_kernel(u_ref, w_ref, p_ref, k_ref, g_ref, r_ref, *, degree):
    """Single-step kernel: Cox-de Boor basis in VMEM, then the weighted
    Gram/RHS contractions -- the basis matrix never reaches HBM. The basis
    recursion and the matmul spelling are the SAME code the XLA path runs
    (ops/bspline._basis_columns / _mm), so interpret-mode results match it
    bitwise. The knot vector rides in as a [1, K] input (a kernel cannot
    close over array constants)."""
    from robotic_discovery_platform_tpu.ops import bspline

    uu = u_ref[:]  # [N, 1]
    b = bspline._basis_columns(uu, k_ref[:], degree)  # [N, C]
    bw = b * w_ref[:]  # weights ride in as [N, 1]
    g_ref[:] = bspline._mm(bw.T, b)
    r_ref[:] = bspline._mm(bw.T, p_ref[:])


@functools.partial(
    jax.jit, static_argnames=("knots", "degree", "interpret")
)
def bspline_design(points, weights, u, knots, degree: int = 3,
                   interpret: bool = False):
    """Fused ``(B^T W B, B^T W X)`` for the penalized least-squares fit.

    Args:
        points: [N, D]; weights: [N]; u: [N] parameters.
        knots: STATIC knot vector as a tuple of floats (hashable; the
            callers' knot vectors are compile-time numpy constants).

    Returns ``(gram [C, C], rhs [C, D])`` in f32.
    """
    n = u.shape[0]
    n_knots = len(knots)
    num_ctrl = n_knots - degree - 1
    d = points.shape[1]
    pts = jnp.asarray(points, jnp.float32)
    return pl.pallas_call(
        functools.partial(_design_kernel, degree=degree),
        in_specs=[
            pl.BlockSpec((n, 1), lambda: (0, 0)),
            pl.BlockSpec((n, 1), lambda: (0, 0)),
            pl.BlockSpec((n, d), lambda: (0, 0)),
            pl.BlockSpec((1, n_knots), lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((num_ctrl, num_ctrl), lambda: (0, 0)),
            pl.BlockSpec((num_ctrl, d), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_ctrl, num_ctrl), jnp.float32),
            jax.ShapeDtypeStruct((num_ctrl, d), jnp.float32),
        ],
        interpret=interpret,
        name="bspline_design",
        # every [N, k] operand and basis temporary pads its minor dim to
        # 128 lanes, ~N * 512 bytes each: Mosaic asked for 18.4 MB at
        # N = 6400 against the 16 MB default scoped-VMEM limit
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(96, max(16, n * 4096 // 2**20)) * 2**20
        ),
    )(
        jnp.asarray(u, jnp.float32)[:, None],
        jnp.asarray(weights, jnp.float32)[:, None],
        pts,
        jnp.asarray(knots, jnp.float32)[None, :],
    )


# -- fused curvature evaluation ---------------------------------------------


def _curvature_kernel(c_ref, u_ref, k_ref, m1_ref, m2_ref, kap_ref, v_ref,
                      r_ref, *, degree):
    """r, r', r'' via the shared basis recursion and the (input-fed)
    static derivative-matrix products, then the curvature formula -- one
    launch instead of three design matmuls plus an elementwise chain."""
    from robotic_discovery_platform_tpu.ops import bspline

    uu = u_ref[:]  # [N, 1]
    ctrl = c_ref[:]
    knots_j = k_ref[:]  # [1, K]
    r = bspline._mm(bspline._basis_columns(uu, knots_j, degree), ctrl)
    b1 = bspline._basis_columns(uu, knots_j, degree - 1)
    r1 = bspline._mm(bspline._mm(b1, m1_ref[:]), ctrl)
    b2 = bspline._basis_columns(uu, knots_j, degree - 2)
    r2 = bspline._mm(bspline._mm(b2, m2_ref[:]), ctrl)
    kappa, valid = bspline._curvature_formula(r1, r2)
    r_ref[:] = r
    kap_ref[:] = kappa[:, None]
    v_ref[:] = valid.astype(jnp.float32)[:, None]


@functools.partial(
    jax.jit, static_argnames=("knots", "degree", "interpret")
)
def bspline_curvature(ctrl, u, knots, degree: int = 3,
                      interpret: bool = False):
    """Fused curvature profile: ``(kappa [N], valid [N] bool, r [N, D])``,
    bitwise-matching ops/bspline.curvature_profile's XLA path."""
    from robotic_discovery_platform_tpu.ops import bspline

    n = u.shape[0]
    c, d = ctrl.shape
    # knots is a STATIC tuple (static_argnames), not a traced value: the
    # numpy conversion runs at trace time to build the static derivative
    # matrices, exactly like the XLA path does.
    knots_np = np.asarray(knots)  # jaxlint: disable=JL001
    n_knots = knots_np.shape[0]
    m1 = bspline._deriv_matrix_product(knots_np, degree, 1)  # [C+1, C]
    m2 = bspline._deriv_matrix_product(knots_np, degree, 2)  # [C+2, C]
    kappa, valid, r = pl.pallas_call(
        functools.partial(_curvature_kernel, degree=degree),
        in_specs=[
            pl.BlockSpec((c, d), lambda: (0, 0)),
            pl.BlockSpec((n, 1), lambda: (0, 0)),
            pl.BlockSpec((1, n_knots), lambda: (0, 0)),
            pl.BlockSpec(m1.shape, lambda: (0, 0)),
            pl.BlockSpec(m2.shape, lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n, 1), lambda: (0, 0)),
            pl.BlockSpec((n, 1), lambda: (0, 0)),
            pl.BlockSpec((n, d), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
        ],
        interpret=interpret,
        name="bspline_curvature",
    )(
        jnp.asarray(ctrl, jnp.float32),
        jnp.asarray(u, jnp.float32)[:, None],
        jnp.asarray(knots_np, jnp.float32)[None, :],
        jnp.asarray(m1, jnp.float32),
        jnp.asarray(m2, jnp.float32),
    )
    return kappa[:, 0], valid[:, 0] > 0, r


def static_knots(knots) -> tuple:
    """A hashable (static-arg) form of a numpy knot vector."""
    return tuple(float(k) for k in np.asarray(knots))
