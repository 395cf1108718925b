"""The selective state-space scan of a Mamba-2 mixer, in chunks: for each
head (state ``[P, N]``, ``h_{-1} = 0``)

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
    y_t = h_t C_t + D x_t

over the positions of a sequence, without a pass position by position and
without an ``L x L`` matrix. The positions are cut into chunks of ``chunk``
(the state-space duality form): with ``cum_t`` the running sum of
``dt_t A`` inside a chunk,

- *within a chunk* ``y_l += sum_{s <= l} (C_l . B_s) exp(cum_l - cum_s)
  dt_s x_s``: a ``chunk x chunk`` product a chunk, masked below the
  diagonal (the difference is masked before the exponential: above the
  diagonal it is positive and may overflow);
- *the chunk's own state* ``S_c = sum_s exp(cum_last - cum_s) dt_s x_s (x)
  B_s``, and *between chunks* a ``lax.scan`` over the chunks that carries
  ``H_c = exp(cum_last) H_{c-1} + S_c`` (float32, ``[heads, P, N]`` a
  sequence) and hands each chunk the state it starts from;
- *from the carried state* ``y_l += exp(cum_l) C_l . H_{c-1}``.

``B`` and ``C`` come by group: head ``h`` reads group ``h // (heads /
groups)``. The decays ``dt A``, their running sums, every exponential of
them and the carried state are float32 whatever the inputs are; the
products run on the inputs' dtype and accumulate in float32. A length that
is no multiple of the chunk is padded behind with ``dt = 0``, which decays
nothing and adds nothing. The backward pass is JAX's own derivative of
these products (the caller's ``jax.checkpoint`` decides what of them is
kept).

Each time the scan is traced, one sample of ``rdp_ssm_scan_chunks_total
{kind}`` counts the chunks of one sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from robotic_discovery_platform_tpu.observability import instruments as obs

#: the implementation's label on ``rdp_ssm_scan_chunks_total``
KIND = "xla"


def ssm_scan(x, dt, a, b, c, d, *, chunk: int):
    """``y`` ``[batch, L, heads, P]`` in ``x``'s dtype.

    ``x`` ``[batch, L, heads, P]``; ``dt`` ``[batch, L, heads]`` float32,
    the steps after their softplus; ``a`` ``[heads]`` float32, negative;
    ``b``, ``c`` ``[batch, L, groups, N]``; ``d`` ``[heads]``."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups
    dtype, f32 = x.dtype, jnp.float32
    pad = -length % chunk
    chunks = (length + pad) // chunk
    obs.SSM_SCAN_CHUNKS.labels(kind=KIND).inc(chunks)
    with jax.named_scope("rdp.ssm.scan"):
        skip = x.astype(f32) * d.astype(f32)[:, None]
        dt = dt.astype(f32)
        if pad:
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
                v.ndim - 2)) for v in (x, dt, b, c))
        # [batch, chunks, chunk, groups, heads a group, ...]
        x = x.reshape(batch, chunks, chunk, groups, per, p)
        dt = dt.reshape(batch, chunks, chunk, groups, per)
        b = b.reshape(batch, chunks, chunk, groups, n)
        c = c.reshape(batch, chunks, chunk, groups, n)
        cum = jnp.cumsum(dt * a.astype(f32).reshape(groups, per), axis=2)

        # within a chunk
        cb = jnp.einsum("zclgn,zcsgn->zcgls", c, b,
                        preferred_element_type=f32)
        to_l = jnp.moveaxis(cum, 2, -1)             # [z, c, g, r, chunk]
        live = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            live, to_l[..., :, None] - to_l[..., None, :], -jnp.inf))
        weights = (cb[:, :, :, None] * decay
                   * jnp.moveaxis(dt, 2, -1)[..., None, :]).astype(dtype)
        y = jnp.einsum("zcgrls,zcsgrp->zclgrp", weights, x,
                       preferred_element_type=f32)

        # each chunk's own state, and the pass over the chunks
        to_end = jnp.exp(cum[:, :, -1:] - cum) * dt
        own = jnp.einsum("zcsgrp,zcsgn->zcgrpn",
                         (x.astype(f32) * to_end[..., None]).astype(dtype),
                         b, preferred_element_type=f32)
        whole = jnp.exp(cum[:, :, -1])              # [z, c, g, r]

        def carry_on(state, chunk_):
            own_c, whole_c = chunk_
            return state * whole_c[..., None, None] + own_c, state

        _, entering = jax.lax.scan(
            carry_on, jnp.zeros((batch, groups, per, p, n), f32),
            (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)     # [z, c, g, r, p, n]

        # from the state a chunk starts from
        y = y + jnp.einsum("zclgn,zcgrpn->zclgrp", c, entering.astype(dtype),
                           preferred_element_type=f32) \
            * jnp.exp(cum)[..., None]
        y = y.reshape(batch, chunks * chunk, heads, p)[:, :length]
        return (y + skip).astype(dtype)
