"""Fixed-knot cubic B-spline fitting in pure jax.numpy.

TPU-native replacement for the reference's FITPACK usage
(reference: pkg/geometry_utils.py:78 ``splprep(..., s=0.1, k=3)`` and
:148-149 ``splev(..., der=1|2)``). FITPACK is Fortran with data-dependent
knot placement -- unusable inside an XLA graph. Here the knot vector is
*static* (clamped, uniform interior knots), so fitting is a small dense
weighted least-squares solve with a difference penalty on control points
(P-spline smoothing, Eilers & Marx 1996) -- a few MXU-friendly matmuls and
one [C,C] solve, fully jittable and differentiable.

All functions take/return fixed-shape arrays and support a per-point
``weights`` vector so padded/invalid points (weight 0) are ignored without
dynamic shapes.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from robotic_discovery_platform_tpu.analysis.contracts import shape_contract

# All spline matmuls are tiny ([N, C] with C ~ 16); force full f32 precision
# so the TPU MXU's default-bf16 f32 matmul does not degrade curvature (second
# derivatives amplify rounding ~1e-3 relative under bf16 accumulation).
_mm = functools.partial(jnp.matmul, precision="highest")


def clamped_uniform_knots(num_ctrl: int, degree: int = 3) -> np.ndarray:
    """Clamped knot vector on [0, 1] with uniform interior knots.

    Length is ``num_ctrl + degree + 1``; the first/last ``degree + 1`` knots
    are pinned to 0/1 so the spline interpolates the parameter range ends.
    Static (numpy) because knots are compile-time constants.
    """
    if num_ctrl <= degree:
        raise ValueError(f"num_ctrl ({num_ctrl}) must exceed degree ({degree})")
    interior = np.linspace(0.0, 1.0, num_ctrl - degree + 1)[1:-1]
    return np.concatenate(
        [np.zeros(degree + 1), interior, np.ones(degree + 1)]
    ).astype(np.float64)


def _basis_columns(uu, knots, degree: int):
    """Cox-de Boor recursion on a COLUMN of parameters: ``uu`` is [N, 1],
    ``knots`` an already-cast [1, K] jnp ROW (2-D throughout, static
    non-negative slices only: what a TPU kernel can lower). Shared verbatim
    by the XLA path (:func:`bspline_basis`) and the fused Pallas kernels
    (ops/pallas/geometry.py), so the two paths are the same ops and their
    results compare bitwise."""
    n_knots = knots.shape[1]
    num_ctrl = n_knots - degree - 1

    # Degree-0: indicator of the half-open knot span, closed at the top so
    # u == 1 lands in the last nonempty span (FITPACK convention).
    t_lo = knots[:, :-1]  # [1, n_knots-1]
    t_hi = knots[:, 1:]
    last_span = t_hi >= knots[:, n_knots - 1:]
    b = jnp.where(
        (uu >= t_lo) & ((uu < t_hi) | (last_span & (uu <= t_hi))),
        1.0,
        0.0,
    ).astype(uu.dtype)
    # Zero-width spans (clamped ends) must not fire.
    b = jnp.where((t_hi - t_lo) > 0, b, 0.0)

    for d in range(1, degree + 1):
        n_b = n_knots - 1 - d  # number of degree-d functions
        t_i = knots[:, :n_b]
        t_id = knots[:, d : d + n_b]
        t_i1 = knots[:, 1 : 1 + n_b]
        t_id1 = knots[:, d + 1 : d + 1 + n_b]
        denom_l = t_id - t_i
        denom_r = t_id1 - t_i1
        left = jnp.where(denom_l > 0, (uu - t_i) / jnp.where(denom_l > 0, denom_l, 1.0), 0.0)
        right = jnp.where(denom_r > 0, (t_id1 - uu) / jnp.where(denom_r > 0, denom_r, 1.0), 0.0)
        b = left * b[:, :n_b] + right * b[:, 1 : 1 + n_b]
    assert b.shape[-1] == num_ctrl
    return b


def bspline_basis(u, knots, degree: int = 3):
    """Cox-de Boor basis matrix, vectorized over parameters.

    Args:
        u: [N] parameters in [0, 1].
        knots: [num_ctrl + degree + 1] knot vector (static).
        degree: spline degree (static).

    Returns:
        [N, num_ctrl] basis matrix B with ``spline(u) = B @ ctrl``.
    """
    u = jnp.asarray(u)
    knots = jnp.asarray(knots, dtype=u.dtype)
    return _basis_columns(u[:, None], knots[None, :], degree)


def _deriv_matrix_product(knots_np: np.ndarray, degree: int,
                          order: int) -> np.ndarray:
    """Static numpy product ``M_{p-order+1} @ ... @ M_p`` mapping the
    degree-(p-order) basis to the order-th derivative of the degree-p
    basis. Shared by :func:`bspline_basis_derivative` and the fused
    curvature kernel (ops/pallas/geometry.py)."""
    n_knots = knots_np.shape[0]

    # D maps degree-(d-1) basis coefficients to the derivative contribution of
    # degree-d basis: a static sparse-ish [n_{d-1}, n_d] matrix per level.
    def deriv_matrix(d: int) -> np.ndarray:
        n_hi = n_knots - 1 - d  # degree-d functions
        n_lo = n_hi + 1  # degree-(d-1) functions
        m = np.zeros((n_lo, n_hi))
        for i in range(n_hi):
            dl = knots_np[i + d] - knots_np[i]
            dr = knots_np[i + d + 1] - knots_np[i + 1]
            if dl > 0:
                m[i, i] += d / dl
            if dr > 0:
                m[i + 1, i] -= d / dr
        return m

    low = degree - order
    return functools.reduce(
        np.matmul, [deriv_matrix(d) for d in range(low + 1, degree + 1)]
    )


def bspline_basis_derivative(u, knots, degree: int = 3, order: int = 1):
    """Basis matrix of the ``order``-th derivative of the degree-``degree``
    basis: ``spline^(k)(u) = D @ ctrl``.

    Uses the standard recursion B'_{i,d} = d * (B_{i,d-1}/(t_{i+d}-t_i)
    - B_{i+1,d-1}/(t_{i+d+1}-t_{i+1})) applied ``order`` times.
    """
    if order == 0:
        return bspline_basis(u, knots, degree)
    knots_np = np.asarray(knots)
    num_ctrl = knots_np.shape[0] - degree - 1

    # order-th derivative of degree-p basis = B_{p-order} @ M_{p-order+1} ... @ M_p
    low = degree - order
    if low < 0:
        return jnp.zeros((jnp.asarray(u).shape[0], num_ctrl))
    b = bspline_basis(u, knots, low)
    m = _deriv_matrix_product(knots_np, degree, order)
    return _mm(b, jnp.asarray(m, dtype=b.dtype))


@shape_contract(points="n d", weights="n", out="n")
def chord_length_params(points, weights):
    """Normalized cumulative chord-length parametrization (the ``splprep``
    default, reference: pkg/geometry_utils.py:78) for a *weighted* fixed-shape
    point set. Points must be pre-sorted; zero-weight (padded) points inherit
    the running parameter and contribute nothing downstream.

    Args:
        points: [N, D].
        weights: [N] in {0, 1} (or soft).

    Returns:
        [N] parameters in [0, 1].
    """
    w = weights.astype(points.dtype)
    deltas = jnp.linalg.norm(jnp.diff(points, axis=0), axis=1)
    # A segment counts only when both endpoints are valid.
    seg_w = w[1:] * w[:-1]
    cum = jnp.concatenate([jnp.zeros((1,), points.dtype), jnp.cumsum(deltas * seg_w)])
    total = cum[-1]
    return jnp.where(total > 1e-12, cum / jnp.maximum(total, 1e-12), jnp.zeros_like(cum))


def second_difference_penalty(num_ctrl: int) -> np.ndarray:
    """P-spline penalty ``P = D2.T @ D2`` on control points (static)."""
    d2 = np.diff(np.eye(num_ctrl), n=2, axis=0)
    return d2.T @ d2


@shape_contract(points="n d", weights="n", knots="k")
def fit_bspline(points, weights, knots, degree: int = 3,
                smoothing: float = 1e-3, impl: str = "xla"):
    """Weighted penalized least-squares B-spline fit (all shapes static).

    Solves ``(B^T W B + lam * P + eps I) C = B^T W X`` per coordinate, where
    ``lam = smoothing * sum(w)`` scales the P-spline penalty with the active
    point count so smoothness is resolution-independent.

    Args:
        points: [N, D] pre-sorted points (padding allowed).
        weights: [N] validity weights.
        knots: static knot vector.
        degree: static degree.
        smoothing: penalty strength (plays the role of FITPACK ``s``).
        impl: "xla" (default -- the reference path), or
            "pallas"/"interpret" to run the basis + design contractions as
            ONE fused Pallas kernel (ops/pallas/geometry.bspline_design;
            the basis matrix stays in VMEM). Requires a static (numpy)
            knot vector; the two paths are bitwise-compared in
            tests/test_pallas_geometry.py. The [C, C] solve stays in XLA
            either way (LU has no MXU win at C ~ 16).

    Returns:
        (ctrl [num_ctrl, D], u [N]) control points and per-point parameters.
    """
    u = chord_length_params(points, weights)
    w = weights.astype(points.dtype)
    num_ctrl = np.asarray(knots).shape[0] - degree - 1
    if impl in ("pallas", "interpret") and not isinstance(
        knots, jnp.ndarray
    ):
        from robotic_discovery_platform_tpu.ops.pallas import (
            geometry as pallas_geometry,
        )

        gram, rhs = pallas_geometry.bspline_design(
            points, w, u, pallas_geometry.static_knots(knots), degree,
            interpret=impl == "interpret",
        )
    else:
        b = bspline_basis(u, knots, degree)  # [N, C]
        bw = b * w[:, None]
        gram = _mm(bw.T, b)  # [C, C]
        rhs = _mm(bw.T, points)  # [C, D]
    lam = smoothing * jnp.maximum(jnp.sum(w), 1.0)
    pen = jnp.asarray(second_difference_penalty(num_ctrl), dtype=points.dtype)
    reg = gram + lam * pen + 1e-8 * jnp.eye(num_ctrl, dtype=points.dtype)
    ctrl = jnp.linalg.solve(reg, rhs)
    return ctrl, u


@shape_contract(ctrl="c d", knots="k", u="n", out="n d")
def evaluate_bspline(ctrl, knots, u, degree: int = 3, order: int = 0):
    """Evaluate the spline (or its ``order``-th derivative) at parameters
    ``u``: returns [N, D]."""
    d = bspline_basis_derivative(u, knots, degree, order)
    return _mm(d, ctrl)


def _curvature_formula(r1, r2):
    """kappa = ||r' x r''|| / ||r'||^3 with the reference's degenerate-
    tangent guard (:155). Shared by the XLA path and the fused curvature
    kernel so the two stay op-identical."""
    cross = jnp.cross(r1, r2)
    num = jnp.linalg.norm(cross, axis=-1)
    den = jnp.linalg.norm(r1, axis=-1)
    valid = den > 1e-6
    kappa = jnp.where(valid, num / jnp.maximum(den, 1e-6) ** 3, 0.0)
    return kappa, valid


@shape_contract(ctrl="c d", knots="k", u="n")
def curvature_profile(ctrl, knots, u, degree: int = 3, impl: str = "xla"):
    """kappa(u) = ||r' x r''|| / ||r'||^3 along the fitted curve
    (reference: pkg/geometry_utils.py:144-162), plus the sample points.

    ``impl`` follows :func:`fit_bspline`: "pallas"/"interpret" fuses the
    three derivative design matmuls and the curvature formula into one
    Pallas launch (ops/pallas/geometry.bspline_curvature).

    Returns:
        (kappa [N], valid [N] bool, r [N, D]).
    """
    if impl in ("pallas", "interpret") and not isinstance(
        knots, jnp.ndarray
    ):
        from robotic_discovery_platform_tpu.ops.pallas import (
            geometry as pallas_geometry,
        )

        return pallas_geometry.bspline_curvature(
            ctrl, u, pallas_geometry.static_knots(knots), degree,
            interpret=impl == "interpret",
        )
    r = evaluate_bspline(ctrl, knots, u, degree, order=0)
    r1 = evaluate_bspline(ctrl, knots, u, degree, order=1)
    r2 = evaluate_bspline(ctrl, knots, u, degree, order=2)
    kappa, valid = _curvature_formula(r1, r2)
    return kappa, valid, r
