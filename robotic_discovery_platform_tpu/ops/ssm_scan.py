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
nothing and adds nothing.

**Two forms of the three parts, one contract.** The running sum ``cum``
(with ``dt A`` before it) is XLA's in both, and JAX differentiates it. What
follows it runs

- on a TPU, for the shapes ``kernel_takes`` accepts (a chunk of 128, a
  state that is a multiple of 128, heads of 64 channels or a multiple of
  128, at most 10 of them a group), as the two Pallas kernels of
  ``ops/pallas/ssm_scan``: a grid over (batch, group, chunk) that keeps a
  chunk's ``C B^T``, decays and weights in VMEM and carries the state in
  scratch, and, under a ``jax.custom_vjp``, a second kernel that walks the
  chunks in reverse with the state's cotangent in scratch and returns the
  cotangents of ``x``, ``B``, ``C``, ``D``, ``dt``, ``cum`` and three
  exponentials of ``cum`` that are made outside. The skip is added where
  ``y`` is made (as a pass of XLA's it read ``y`` in float32 and wrote it
  again, and its backward pass two more copies of ``dy``). The one residual
  that is no input is the state each chunk starts from (float32, written by
  the forward pass that a backward pass follows; under the caller's
  ``jax.checkpoint`` that is the recomputed one);
- everywhere else (another backend, the tests' ``chunk=8``, a toy state of
  16) as XLA einsums and a ``lax.scan`` over the chunks' states, which
  write the decays and weights of every chunk to memory, and the skip
  after them; the backward pass there is JAX's own derivative of these
  products (the caller's ``jax.checkpoint`` decides what of them is kept).

``impl`` picks as ``masked_attention``'s and ``grouped_matmul``'s does;
the shape decides before it, so one ``kernel_impl`` serves a model
whatever its sizes.

Each time the scan is traced, one sample of ``rdp_ssm_scan_chunks_total
{kind}`` counts the chunks of one sequence, ``kind`` the form that was
traced (``pallas`` or ``xla``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.ops.pallas.ssm_scan import (
    chunk_scan, takes as kernel_takes)


def ssm_scan(x, dt, a, b, c, d, *, chunk: int, impl: str = "auto"):
    """``y`` ``[batch, L, heads, P]`` in ``x``'s dtype.

    ``x`` ``[batch, L, heads, P]``; ``dt`` ``[batch, L, heads]`` float32,
    the steps after their softplus; ``a`` ``[heads]`` float32, negative;
    ``b``, ``c`` ``[batch, L, groups, N]``; ``d`` ``[heads]``. ``impl``:
    ``"pallas"`` (the kernels), ``"interpret"`` (the kernels in the Pallas
    interpreter, for CPU tests), ``"xla"`` (the einsums), ``"auto"`` (the
    kernels on a TPU, the einsums elsewhere); a shape ``kernel_takes``
    (``ops/pallas/ssm_scan.takes``, the dispatch's one predicate) refuses
    runs the einsums whatever ``impl`` says."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if not kernel_takes(x.shape, b.shape, chunk):
        impl = "xla"
    batch, length, heads, p = x.shape
    dtype, f32 = x.dtype, jnp.float32
    pad = -length % chunk
    chunks = (length + pad) // chunk
    obs.SSM_SCAN_CHUNKS.labels(
        kind="xla" if impl == "xla" else "pallas").inc(chunks)
    with jax.named_scope("rdp.ssm.scan"):
        dt = dt.astype(f32)
        if pad:
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
                v.ndim - 2)) for v in (x, dt, b, c))
        cum = jnp.cumsum((dt * a.astype(f32)).reshape(
            batch, chunks, chunk, heads), axis=2)
        if impl == "xla":
            y = (_einsums(x, dt, cum, b, c, chunk) + x.astype(f32)
                 * d.astype(f32)[:, None]).astype(dtype)
        else:
            y = chunk_scan(
                x, dt, cum.reshape(dt.shape), b, c, d, chunk=chunk,
                interpret=impl == "interpret").reshape(x.shape)
        return y[:, :length]


def _einsums(x, dt, cum, b, c, chunk: int):
    """The scan without its skip as XLA products, float32 ``[batch, L,
    heads, P]``: ``L`` whole chunks, ``cum`` ``[batch, chunks, chunk,
    heads]``."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per, chunks = heads // groups, length // chunk
    dtype, f32 = x.dtype, jnp.float32
    # [batch, chunks, chunk, groups, heads a group, ...]
    x = x.reshape(batch, chunks, chunk, groups, per, p)
    dt = dt.reshape(batch, chunks, chunk, groups, per)
    b = b.reshape(batch, chunks, chunk, groups, n)
    c = c.reshape(batch, chunks, chunk, groups, n)
    cum = cum.reshape(dt.shape)

    # within a chunk
    cb = jnp.einsum("zclgn,zcsgn->zcgls", c, b, preferred_element_type=f32)
    to_l = jnp.moveaxis(cum, 2, -1)                 # [z, c, g, r, chunk]
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
    whole = jnp.exp(cum[:, :, -1])                  # [z, c, g, r]

    def carry_on(state, chunk_):
        own_c, whole_c = chunk_
        return state * whole_c[..., None, None] + own_c, state

    _, entering = jax.lax.scan(
        carry_on, jnp.zeros((batch, groups, per, p, n), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)         # [z, c, g, r, p, n]

    # from the state a chunk starts from
    y = y + jnp.einsum("zclgn,zcgrpn->zclgrp", c, entering.astype(dtype),
                       preferred_element_type=f32) \
        * jnp.exp(cum)[..., None]
    return y.reshape(batch, length, heads, p)
