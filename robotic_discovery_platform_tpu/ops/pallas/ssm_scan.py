"""The chunked state-space scan's part over the chunks (``ops/ssm_scan`` has
the contract and what stays outside) as two Pallas kernels: a chunk's
``C B^T``, its decays and weights live in VMEM and are never written, and the
state ``[N, heads a group x P]`` float32 is carried in scratch along a
sequential chunk axis.

The grid is (batch, group, chunk), the chunk axis last and ``"arbitrary"``; a
group's heads are a loop inside the kernel, so ``C B^T`` is one product a
group and chunk and the gradients of ``B`` and ``C`` (which sum over a
group's heads) are reduced where they are made.

*Forward*, a chunk of ``Q`` positions, head ``r`` of the group (``cum`` the
running sum of ``dt A`` inside the chunk, ``H`` the state the chunk starts
from)::

    W_r[l, s] = (C B^T)[l, s] exp(cum_l - cum_s) dt_s      l >= s, else 0
    y_r       = W_r x_r + grow_l (C H_r) + D_r x_r         grow = exp(cum)
    H_r      <- exp(cum_last) H_r + (to_end_s x_r)^T B     to_end =
                                                exp(cum_last - cum) dt

and, when a backward pass will follow, the state each chunk starts from is
written out (float32, the one residual that is no input). ``grow``,
``to_end`` and ``exp(cum_last)`` are made outside, where JAX differentiates
them; the kernels take them beside ``cum`` and ``dt``.

*Backward* walks the chunks in reverse with the state's cotangent in
scratch, builds every ``Q x Q`` matrix transposed (``[s, l]``: ``B C^T``
and the decays), so that no head's matrix is transposed in the kernel, and
returns cotangents for ``x``, ``B``, ``C``, the skip's ``D`` (summed over
the chunks in an output block that stays put along the chunk axis) and the
five small inputs.

**What a head and position scales by, on the lanes.** The four small
quantities come as rows (``[4 x heads a group, Q]``, positions in the
lanes), which is the orientation a ``Q x Q`` tile reads along its lanes for
nothing. Along its sublanes a tile needs a column spread over 128 lanes,
and ``x``'s rows need ``grow`` and ``to_end`` spread over a head's ``P``
channels; lane broadcasts, a head at a time, kept the unit that permutes
lanes busy for most of a first version's time. So the rows are turned once
a chunk (one 128 x 128 transpose) and spread by the matrix unit, which idles
otherwise: a product with a 0/1 matrix (:func:`spreaders`). It is exact: a
float32 is the sum of three bfloat16 parts (:func:`in_parts`, made outside
from the rows), the parts lie side by side in the turned tile's lanes, each
column of the 0/1 matrix picks the three of one number, and the unit adds
them in float32. Heads of 64 channels share a 128-lane tile in pairs (a
product for each over the whole tile, the other's lanes masked), so no
operand is half a tile.

The decays' differences, every exponential and the state are float32; the
products run on ``x``'s dtype and accumulate in float32; the difference is
masked before its exponential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # contract both operands' last axis


def takes(x_shape, b_shape, chunk: int) -> bool:
    """Whether the kernels run a scan of ``x`` ``[batch, L, heads, P]`` and
    ``b`` ``[batch, L, groups, N]`` in chunks of ``chunk``: a chunk of 128
    (the steps and sums of a chunk are turned and spread as one 128 x 128
    tile), a state that fills whole 128-lane tiles, heads that tile 128
    lanes (64 wide, two to a tile, or a multiple of 128), and at most 10
    heads a group: four quantities a head in three bfloat16 parts share the
    turned tile's 128 lanes."""
    (heads, head_dim), (groups, state) = x_shape[2:], b_shape[2:]
    per = heads // max(groups, 1)
    return (groups > 0 and heads % groups == 0 and chunk == 128
            and state % 128 == 0 and 0 < 12 * per <= 128
            and (head_dim == 64 or head_dim % 128 == 0)
            and (per * head_dim) % 128 == 0)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _iotas(q):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0),
            jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


# The four quantities a head and position that the kernels read, in the
# order of their rows: the running sum, the steps, exp(cum), and
# exp(cum_last - cum) dt.
_CUM, _DT, _GROW, _TO_END = range(4)


def spreaders(per: int, p: int) -> tuple:
    """The two 0/1 matrices that spread a turned tile (:func:`_columns`)
    over the lanes. ``[128, 2 per 128]``: column ``(t, r, j)`` reads
    quantity ``t`` (the running sum, the steps) of head ``r``, over a whole
    128-lane tile. ``[128, 2 per p]``: ``exp(cum)``, then ``exp(cum_last -
    cum) dt``, over the ``p`` channels of each head. Each column picks the
    three bfloat16 parts of its float32."""
    wide = per * p
    tiles = np.zeros((128, 2 * per * 128), np.float32)
    channels = np.zeros((128, 2 * wide), np.float32)
    for part in range(3):
        for r in range(per):
            for t in (_CUM, _DT):
                at = (t * per + r) * 128
                tiles[(4 * part + t) * per + r, at:at + 128] = 1
            for t in (_GROW, _TO_END):
                at = (t - _GROW) * wide + r * p
                channels[(4 * part + t) * per + r, at:at + p] = 1
    return tiles, channels


def in_parts(rows):
    """``rows`` ``[..., 4 per, L]`` float32 as its three bfloat16 parts
    below one another, ``[..., 12 per, L]`` float32: their sum is ``rows``
    exactly, and a bfloat16 product with a 0/1 matrix moves each exactly.
    Rounded by ``reduce_precision``: a cast to bfloat16 and back is no
    rounding on the chip (XLA's simplifier removes the pair)."""
    parts, rest = [], rows
    for _ in range(3):
        part = jax.lax.reduce_precision(rest, exponent_bits=8,
                                        mantissa_bits=7)
        parts.append(part)
        rest = rest - part
    return jnp.concatenate(parts, axis=-2)


def _columns(parts_ref, turn_ref):
    """(``rows`` ``[4 per, Q]`` float32; ``[Q, 128]`` bfloat16 whose lane
    ``(4 part + t) per + r`` is part ``part`` of quantity ``t`` of head
    ``r`` down the chunk, the lanes behind them zero: the spreading product
    sums over all of them; ``[Q, 4 per]`` float32, the quantities as
    columns, for a lane broadcast)."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        turn_ref[...] = jnp.zeros_like(turn_ref)

    parts = parts_ref[...]
    n = parts.shape[0] // 3
    turn_ref[0:3 * n, :] = parts
    cols = turn_ref[...].T
    return (parts[0:n] + parts[n:2 * n] + parts[2 * n:],
            cols.astype(jnp.bfloat16),
            cols[:, 0:n] + cols[:, n:2 * n] + cols[:, 2 * n:3 * n])


def _lane_tiles(per, p):
    """(lanes a tile, [(tile, [(head, its mask over the tile's lanes or
    None)])]): heads of 64 share a 128-lane tile, wider ones fill tiles."""
    k = max(1, 128 // p)
    width = max(p, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return width, [
        (t, [(t * k + i, None if k == 1 else (lane >= i * p) & (
            lane < (i + 1) * p)) for i in range(k)])
        for t in range(per // k)]


def _forward_kernel(x_ref, b_ref, c_ref, parts_ref, whole_ref, skip_ref,
                    channels_ref, y_ref, *rest, per, p, keep):
    entering_ref = rest[0] if keep else None
    state_ref, turn_ref = rest[-2:]
    dtype = x_ref.dtype
    q, wide = x_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    b, c = b_ref[...], c_ref[...]
    rows, cols, exact = _columns(parts_ref, turn_ref)
    to_end = _dot(cols, channels_ref[:, wide:])
    x32 = x_ref[...].astype(_F32)
    own = _dot(b.T, (x32 * to_end).astype(dtype))       # [N, heads x P]
    # what the products inside the chunk are added to: the entering state's
    # part and the skip                                   [l, heads x P]
    base = _dot(c, state_ref[...].astype(dtype)) * _dot(
        cols, channels_ref[:, :wide]) + x32 * skip_ref[...]
    cb = _dot(c, b, _NT)                                # [l, s]
    l_ids, s_ids = _iotas(q)
    live = l_ids >= s_ids
    width, tiles = _lane_tiles(per, p)
    for t, heads in tiles:
        at = slice(t * width, (t + 1) * width)
        x = x_ref[:, at]
        y = None
        for r, mask in heads:
            # a lane broadcast: the unit that permutes lanes is idle in this
            # kernel, and the matrix unit the busiest
            to_l = exact[:, r:r + 1]
            decay = jnp.exp(jnp.where(live, to_l - rows[r:r + 1, :],
                                      -jnp.inf))
            weights = (cb * decay * rows[per + r:per + r + 1, :]).astype(
                dtype)
            mine = _dot(weights, x)
            y = mine if y is None else jnp.where(mask, mine, y)
        y_ref[:, at] = (y + base[:, at]).astype(dtype)
    if keep:
        entering_ref[...] = state_ref[...]
    state_ref[...] = state_ref[...] * whole_ref[...] + own


def _backward_kernel(x_ref, b_ref, c_ref, parts_ref, whole_ref, skip_ref,
                     tiles_ref, channels_ref, entering_ref, dy_ref, dx_ref,
                     db_ref, dc_ref, drows_ref, dwhole_ref, dskip_ref,
                     dstate_ref, turn_ref, *, per, p):
    dtype = x_ref.dtype
    q, wide = x_ref.shape

    @pl.when(pl.program_id(2) == 0)         # the last chunk: nothing follows
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    b, c = b_ref[...], c_ref[...]
    rows, cols, _ = _columns(parts_ref, turn_ref)
    grow = _dot(cols, channels_ref[:, :wide])
    to_end = _dot(cols, channels_ref[:, wide:])
    x32, dy32 = x_ref[...].astype(_F32), dy_ref[...].astype(_F32)
    state_lo = entering_ref[...].astype(dtype)
    dstate_lo = dstate_ref[...].astype(dtype)
    dye = (dy32 * grow).astype(dtype)
    dc_state = _dot(dye, state_lo, _NT)                 # [l, N], more below
    db_state = _dot((x32 * to_end).astype(dtype), dstate_lo, _NT)
    dscaled = _dot(b, dstate_lo)                        # [s, heads x P]
    dwhole_ref[...] = jnp.sum(dstate_ref[...] * entering_ref[...], axis=0,
                              keepdims=True)
    dstate_ref[...] = dstate_ref[...] * whole_ref[...] + _dot(c.T, dye)
    dskip_ref[...] += jnp.sum(dy32 * x32, axis=0, keepdims=True)
    dgrow = dy32 * _dot(c, state_lo)    # summed over a head's channels below
    dto_end = dscaled * x32
    dx_base = dscaled * to_end + dy32 * skip_ref[...]
    cbt = _dot(b, c, _NT)                               # [s, l]
    s_ids, l_ids = _iotas(q)
    live = l_ids >= s_ids
    lane = l_ids[:1]
    dcbt = jnp.zeros((q, q), _F32)
    dcols = jnp.zeros((q, 128), _F32)       # lane t per + r: quantity, head
    width, tiles = _lane_tiles(per, p)
    for t, heads in tiles:
        at = slice(t * width, (t + 1) * width)
        x, dy = x_ref[:, at], dy_ref[:, at]
        dx = None
        for r, mask in heads:
            to_s = _dot(cols, tiles_ref[:, r * 128:(r + 1) * 128])
            dt_s = _dot(cols, tiles_ref[:, (per + r) * 128:
                                        (per + r + 1) * 128])
            decay = jnp.exp(jnp.where(live, rows[r:r + 1, :] - to_s,
                                      -jnp.inf))
            cbd = cbt * decay
            mine = x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))
            dweights = _dot(mine, dy, _NT)
            dx_r = _dot((cbd * dt_s).astype(dtype), dy)
            dx = dx_r if dx is None else jnp.where(mask, dx_r, dx)
            dcbt = dcbt + dweights * (decay * dt_s)
            moved = dweights * cbd              # d / d(cum_l - cum_s) / dt_s
            over_l = jnp.sum(moved, axis=1, keepdims=True)          # [Q, 1]
            drows_ref[r:r + 1, :] = jnp.sum(moved * dt_s, axis=0,
                                            keepdims=True)
            # the running sum of the columns got -dt_s times what the steps
            # got
            dcols = jnp.where(lane == r, -over_l * dt_s, dcols)
            dcols = jnp.where(lane == per + r, over_l, dcols)
            for k, part in ((_GROW, dgrow), (_TO_END, dto_end)):
                part = part[:, at]
                if mask is not None:
                    part = jnp.where(mask, part, 0.0)
                dcols = jnp.where(lane == k * per + r, jnp.sum(
                    part, axis=1, keepdims=True), dcols)
        dx_ref[:, at] = (dx + dx_base[:, at]).astype(dtype)
    dc_ref[...] = (dc_state + _dot(dcbt.T.astype(dtype), b)).astype(dtype)
    db_ref[...] = (db_state + _dot(dcbt.astype(dtype), c)).astype(dtype)
    back = dcols.T                                      # [128, Q]
    drows_ref[0:per, :] = drows_ref[0:per, :] + back[0:per]
    drows_ref[per:4 * per, :] = back[per:4 * per]


def _specs(q, n, wide, per, chunks, reverse) -> dict:
    """Block specs by what they carry, on the grid (batch, group, chunk),
    the chunks walked backwards under ``reverse``."""
    at = (lambda k: chunks - 1 - k) if reverse else (lambda k: k)

    def by_chunk(*block):       # [batch, groups, rows, L], L in chunks
        return pl.BlockSpec((None, None, *block),
                            lambda z, g, k: (z, g, 0, at(k)))

    def a_chunk(*block):        # [batch, chunks, groups, ...], whole
        return pl.BlockSpec((None, None, None, *block),
                            lambda z, g, k: (z, at(k), g, 0, 0))

    return {
        "x": pl.BlockSpec((None, q, wide), lambda z, g, k: (z, at(k), g)),
        "b": pl.BlockSpec((None, q, n), lambda z, g, k: (z, at(k), g)),
        "rows": by_chunk(4 * per, q), "parts": by_chunk(12 * per, q),
        "whole": a_chunk(1, wide), "state": a_chunk(n, wide),
        "skip": pl.BlockSpec((None, 1, wide), lambda z, g, k: (g, 0, 0)),
        # its cotangent, summed over the chunks where it stays put
        "dskip": pl.BlockSpec((None, None, 1, wide),
                              lambda z, g, k: (z, g, 0, 0))}


def _constant(array):
    return pl.BlockSpec(array.shape, lambda z, g, k: (0,) * array.ndim)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _sizes(x, b, rows, whole):
    """(batch, groups, chunks, Q, N, heads a group x P, heads a group)."""
    groups, chunks = rows.shape[1], whole.shape[1]
    return (x.shape[0], groups, chunks, x.shape[1] // chunks,
            b.shape[2] // groups, x.shape[2] // groups, rows.shape[2] // 4)


def _forward(x, b, c, rows, whole, skip, *, interpret, keep):
    batch, groups, chunks, q, n, wide, per = _sizes(x, b, rows, whole)
    spec = _specs(q, n, wide, per, chunks, False)
    channels = jnp.asarray(spreaders(per, wide // per)[1], jnp.bfloat16)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [spec["x"]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, chunks, groups, n, wide), _F32))
        out_specs.append(spec["state"])
    out = pl.pallas_call(
        functools.partial(_forward_kernel, per=per, p=wide // per, keep=keep),
        grid=(batch, groups, chunks),
        in_specs=[spec[k] for k in ("x", "b", "b", "parts", "whole", "skip")]
        + [_constant(channels)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, wide), _F32),
                        pltpu.VMEM((128, q), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="ssm_scan_forward",
    )(x, b, c, in_parts(rows), whole, skip, channels)
    return out if keep else (out[0], None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, b, c, rows, whole, skip, interpret):
    return _forward(x, b, c, rows, whole, skip, interpret=interpret,
                    keep=False)[0]


def _scan_fwd(x, b, c, rows, whole, skip, interpret):
    y, entering = _forward(x, b, c, rows, whole, skip, interpret=interpret,
                           keep=True)
    return y, (x, b, c, rows, whole, skip, entering)


def _scan_bwd(interpret, kept, dy):
    x, b, c, rows, whole, skip, entering = kept
    batch, groups, chunks, q, n, wide, per = _sizes(x, b, rows, whole)
    spec = _specs(q, n, wide, per, chunks, True)
    tiles, channels = (jnp.asarray(m, jnp.bfloat16)
                       for m in spreaders(per, wide // per))
    *grads, dskip = pl.pallas_call(
        functools.partial(_backward_kernel, per=per, p=wide // per),
        grid=(batch, groups, chunks),
        in_specs=[spec[k] for k in ("x", "b", "b", "parts", "whole", "skip")]
        + [_constant(tiles), _constant(channels), spec["state"], spec["x"]],
        out_specs=[spec[k] for k in ("x", "b", "b", "rows", "whole",
                                     "dskip")],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (x, b, c, rows, whole)] + [
            jax.ShapeDtypeStruct((batch,) + skip.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((n, wide), _F32),
                        pltpu.VMEM((128, q), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="ssm_scan_backward",
    )(x, b, c, in_parts(rows), whole, skip, tiles, channels, entering, dy)
    return (*grads, jnp.sum(dskip, axis=0))


_scan.defvjp(_scan_fwd, _scan_bwd)


def chunk_scan(x, dt, cum, b, c, d, *, chunk: int, interpret: bool = False):
    """``y`` ``[batch, L, heads x P]`` in ``x``'s dtype, the skip ``d x``
    included, of a scan that :func:`takes` accepts. ``L`` is whole chunks;
    ``x`` ``[batch, L, heads, P]``; ``dt`` and ``cum``, the steps and the
    running sum of ``dt A`` inside each chunk, ``[batch, L, heads]``
    float32; ``b``, ``c`` ``[batch, L, groups, N]`` in ``x``'s dtype; ``d``
    ``[heads]``."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per, chunks = heads // groups, length // chunk
    by_chunk = cum.reshape(batch, chunks, chunk, heads)
    ends = by_chunk[:, :, -1:]
    # what a position's row scales by, outside the kernels where JAX
    # differentiates it: the entering state's part of y by exp(cum), x on
    # its way into the chunk's own state by exp(cum_last - cum) dt
    to_end = jnp.exp(ends - by_chunk).reshape(cum.shape) * dt

    def by_group(v):    # [batch, groups, heads a group, L]
        return jnp.moveaxis(v.reshape(batch, length, groups, per), 1, 3)

    # exp(cum_last), a lane a channel: [batch, chunks, groups, 1, per x P]
    whole = jnp.repeat(jnp.exp(ends).reshape(batch, chunks, groups, 1, per),
                       p, axis=-1)
    return _scan(x.reshape(batch, length, heads * p),
                 b.reshape(batch, length, groups * n),
                 c.reshape(batch, length, groups * n),
                 jnp.concatenate([by_group(v) for v in (
                     cum, dt, jnp.exp(cum), to_end)], axis=2),
                 whole, jnp.repeat(d.astype(_F32), p).reshape(
                     groups, 1, per * p), interpret)
