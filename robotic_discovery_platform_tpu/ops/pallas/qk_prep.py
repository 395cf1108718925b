"""What happens between a q or k projection's product and the attention
kernel, in one pass: the head split, the per-head RMSNorm, the rotary
embedding and the ``1 / sqrt(head_dim)`` scale.

:func:`prepare_heads` takes the projection's output ``y`` ``[batch,
positions, heads x head_dim]`` and returns ``[batch, heads, positions,
head_dim]`` as ``masked_attention`` wants it::

    x = y split into heads                           (float32 from here on)
    x <- norm_weight * x / sqrt(mean_d(x^2) + eps)   where a weight is given
    x <- x cos + rotate_half(x) sin                  where a table is given
    x <- scale x                                     rounded once, to y's dtype

``rotate_half(x) = [-x[d/2:], x[:d/2]]`` and ``table = (cos, sin)``, each
``[positions, head_dim]`` float32 (``models/causal_lm.rope_table``).

**Two forms, chosen from what the call shows.** The *dense* form
(:func:`dense_heads`) is the definition, written with the models' own
``rms_norm`` and :func:`apply_rotary`: a reshape, a transpose and a float32
pass each for the norm, the rotation and the scale, every one rounded to
``y``'s dtype, left to XLA. The *fused* form is one Pallas kernel a
direction: a tile ``[positions tile, heads block x head_dim]`` of ``y`` comes
in, each head's 128-lane slice is normed, rotated as ``x cos + roll(x, d/2)
sin_signed`` (the sign and the scale are folded into the table's tile once a
grid step, so no slice, negation or concatenate runs a head) and stored to
``[heads block, positions tile, head_dim]``: one read, one write, one
rounding, which makes it more exact than the dense form and never less. Its
``jax.custom_vjp`` keeps ``y`` alone and runs the same pass transposed: from
the cotangent in ``[batch, heads, positions, head_dim]`` the rotation by
``-sin``, the norm's derivative from row statistics it recomputes, the
cotangent of ``y`` stored in ``y``'s layout, and the norm weight's cotangent
as a partial sum a grid step, added outside. The table takes no cotangent
there (it is made from positions and constants; the dense form
differentiates it like anything else).

The fused form runs where ``impl`` asks for the kernel (``"pallas"``, or
``"auto"`` on a TPU; ``"interpret"`` for CPU tests), ``head_dim`` fills whole
128-lane tiles and there is a norm weight or a table to fuse; a bare scale
or split, and heads of 64, run the dense form. Each call adds one sample of
``rdp_attn_qk_prep_total{form}`` (``fused`` / ``xla``) while a program is
traced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from robotic_discovery_platform_tpu.models.moe import rms_norm
from robotic_discovery_platform_tpu.observability import instruments as obs

_F32 = jnp.float32
#: positions a grid step takes, and the bytes of ``y`` it may take: the
#: heads block is the largest divisor of the heads that fits
_TILE = 512
_BLOCK_BYTES = 2 << 20
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=48 << 20)


def split_heads(y, heads: int, head_dim: int):
    """``[batch, positions, heads x head_dim]`` -> ``[batch, heads,
    positions, head_dim]``."""
    b, s, _ = y.shape
    return y.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)


def apply_rotary(x, table, scale: float = 1.0):
    """``[..., s, d]`` rotated by ``table`` (rotate-half form) and
    multiplied by ``scale``, in float32, in one pass."""
    cos, sin = table
    d = x.shape[-1]
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    out = x32 * cos + rotated * sin
    return (out * scale if scale != 1.0 else out).astype(x.dtype)


def dense_heads(y, heads: int, head_dim: int, *, norm_weight=None,
                eps: float = 0.0, table=None, scale: float = 1.0):
    """The definition, and the form XLA runs: every step its own pass."""
    x = split_heads(y, heads, head_dim)
    if norm_weight is not None:
        x = rms_norm(x, norm_weight, eps)
    if table is not None:
        return apply_rotary(x, table, scale)
    if scale != 1.0:
        x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    return x


def _blocks(s: int, heads: int, head_dim: int, itemsize: int) -> tuple:
    """(positions a tile, heads a block)."""
    tile = s if s <= _TILE else _TILE
    fit = max(_BLOCK_BYTES // (tile * head_dim * itemsize), 1)
    return tile, max(h for h in range(1, heads + 1)
                     if heads % h == 0 and h <= fit)


def _table_tile(refs, d: int, scale: float):
    """(cos, sin_signed) of a grid step, scaled: ``sin_signed`` carries
    rotate-half's sign, so the rotation is ``x cos + roll(x, d/2)
    sin_signed``. Made once for all the heads of a block."""
    if not refs:
        return None
    cos, sin = (r[...] * scale if scale != 1.0 else r[...] for r in refs)
    lane = jax.lax.broadcasted_iota(jnp.int32, sin.shape, 1)
    return cos, jnp.where(lane < d // 2, -sin, sin)


def _forward_kernel(*refs, hb, d, eps, scale, norm, rope):
    y_ref, *rest, o_ref = refs
    w = rest.pop(0)[...] if norm else None          # [1, d]
    table = _table_tile(rest, d, scale)
    for h in range(hb):
        x = y_ref[:, h * d:(h + 1) * d].astype(_F32)
        if norm:
            x = x * jax.lax.rsqrt(
                jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
        if rope:
            x = x * table[0] + pltpu.roll(x, d // 2, 1) * table[1]
        elif scale != 1.0:
            x = x * scale
        o_ref[h] = x.astype(o_ref.dtype)


def _backward_kernel(*refs, hb, d, eps, scale, norm, rope, ragged):
    g_ref, y_ref, *rest = refs
    if norm:
        *rest, dy_ref, dw_ref = rest
        w = rest.pop(0)[...]
        dw = jnp.zeros((1, d), _F32)
    else:
        *rest, dy_ref = rest
    table = _table_tile(rest, d, scale)
    tile = y_ref.shape[0]
    if norm and ragged:
        # the last tile's rows past the sequence hold what was in memory
        live = jax.lax.broadcasted_iota(jnp.int32, (tile, d), 0) < (
            ragged - pl.program_id(0) * tile)
    for h in range(hb):
        g = g_ref[h].astype(_F32)
        if rope:
            g = g * table[0] + pltpu.roll(g * table[1], d // 2, 1)
        elif scale != 1.0:
            g = g * scale
        if norm:
            x = y_ref[:, h * d:(h + 1) * d].astype(_F32)
            r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            xr = x * r
            part = jnp.where(live, g * xr, 0.0) if ragged else g * xr
            dw = dw + jnp.sum(part, axis=0, keepdims=True)
            t = g * w
            g = r * (t - xr * jnp.mean(t * xr, axis=-1, keepdims=True))
        dy_ref[:, h * d:(h + 1) * d] = g.astype(dy_ref.dtype)
    if norm:
        dw_ref[...] = dw


def _specs(y, heads: int, weight, table):
    """(grid, tile, heads block, head_dim, [y's spec, the heads' spec, the
    small inputs' specs]). Tiles of positions lead the grid: a table's tile
    stays put while batch and heads blocks pass under it."""
    b, s, width = y.shape
    d = width // heads
    tile, hb = _blocks(s, heads, d, y.dtype.itemsize)
    grid = (pl.cdiv(s, tile), b, heads // hb)
    small = []
    if weight is not None:
        small.append(pl.BlockSpec((1, d), lambda i, z, j: (0, 0)))
    if table is not None:
        small += [pl.BlockSpec((tile, d), lambda i, z, j: (i, 0))] * 2
    return grid, tile, hb, d, [
        pl.BlockSpec((None, tile, hb * d), lambda i, z, j: (z, i, j)),
        pl.BlockSpec((None, hb, tile, d), lambda i, z, j: (z, j, i, 0)),
        *small]


def _small(weight, table):
    return ([] if weight is None else [weight.astype(_F32).reshape(1, -1)]) + (
        [] if table is None else [t.astype(_F32) for t in table])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused(y, weight, table, heads, eps, scale, interpret):
    grid, _, hb, d, (y_spec, heads_spec, *small) = _specs(
        y, heads, weight, table)
    return pl.pallas_call(
        functools.partial(_forward_kernel, hb=hb, d=d, eps=eps, scale=scale,
                          norm=weight is not None, rope=table is not None),
        grid=grid, in_specs=[y_spec, *small], out_specs=heads_spec,
        out_shape=jax.ShapeDtypeStruct(
            (y.shape[0], heads, y.shape[1], d), y.dtype),
        compiler_params=_PARAMS, interpret=interpret, name="qk_prep_forward",
    )(y, *_small(weight, table))


def _fused_fwd(y, weight, table, heads, eps, scale, interpret):
    return _fused(y, weight, table, heads, eps, scale, interpret), (
        y, weight, table)


def _fused_bwd(heads, eps, scale, interpret, kept, g):
    y, weight, table = kept
    s = y.shape[1]
    grid, tile, hb, d, (y_spec, heads_spec, *small) = _specs(
        y, heads, weight, table)
    norm = weight is not None
    out_specs = [y_spec]
    out_shape = [jax.ShapeDtypeStruct(y.shape, y.dtype)]
    if norm:
        out_specs.append(pl.BlockSpec(
            (None, None, None, 1, d), lambda i, z, j: (i, z, j, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((*grid, 1, d), _F32))
    dy, *dw = pl.pallas_call(
        functools.partial(_backward_kernel, hb=hb, d=d, eps=eps, scale=scale,
                          norm=norm, rope=table is not None,
                          ragged=s if s % tile else 0),
        grid=grid, in_specs=[heads_spec, y_spec, *small],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=_PARAMS, interpret=interpret, name="qk_prep_backward",
    )(g, y, *_small(weight, table))
    return (dy,
            jnp.sum(dw[0], axis=(0, 1, 2, 3)).astype(weight.dtype)
            if norm else None,
            None)


_fused.defvjp(_fused_fwd, _fused_bwd)


def prepare_heads(y, heads: int, head_dim: int, *, norm_weight=None,
                  eps: float = 0.0, table=None, scale: float = 1.0,
                  impl: str = "auto"):
    """``y`` ``[batch, positions, heads x head_dim]`` -> ``[batch, heads,
    positions, head_dim]``, normed by ``norm_weight`` ``[head_dim]`` (and
    ``eps``), rotated by ``table`` and multiplied by ``scale``, each where
    given. ``impl`` as ``masked_attention`` takes it; the module's
    docstring says which form it leads to."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    fused = (impl != "xla" and head_dim % 128 == 0
             and (norm_weight is not None or table is not None))
    obs.ATTN_QK_PREP.labels(form="fused" if fused else "xla").inc()
    if not fused:
        return dense_heads(y, heads, head_dim, norm_weight=norm_weight,
                           eps=eps, table=table, scale=scale)
    return _fused(y, norm_weight, table, heads, float(eps), float(scale),
                  impl == "interpret")
