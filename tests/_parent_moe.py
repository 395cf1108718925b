"""``models/moe.py`` as the parent commit of PR 37 had it (06f5636), verbatim
below this docstring but for this docstring: what ``tests/test_hybrid_lm.py``
holds the expert layer of the block-diffusion and the window-attention
families bit-equal to, now that the layer takes its form from the
configuration."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from robotic_discovery_platform_tpu.ops.pallas.grouped_matmul import (
    grouped_matmul)


def seeded_params(shapes: dict, rng, init_std: float,
                  embed_init_std: float) -> dict:
    """Normal(0, ``init_std``) matrices (the leaf ``embed`` at
    ``embed_init_std``), norms at one, by a rule a reference can re-derive
    from ``jax.random`` alone: leaf ``i`` of ``shapes`` (name -> shape, "/"
    between the levels), in its order, is ``std * normal(split(rng,
    n_leaves)[i], shape, float32)`` (a norm takes its key and draws
    nothing)."""
    from flax.traverse_util import unflatten_dict

    keys = jax.random.split(rng, len(shapes))
    flat = {}
    for key, (name, shape) in zip(keys, shapes.items()):
        if name.endswith("norm"):
            flat[name] = jnp.ones(shape, jnp.float32)
        else:
            std = embed_init_std if name == "embed" else init_std
            flat[name] = std * jax.random.normal(key, shape, jnp.float32)
    return unflatten_dict(flat, sep="/")


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * weight).astype(x.dtype)


def route(cfg, probs):
    """From ``probs`` [tokens, num_experts] (float32): the dropless plan for
    the held experts. Of the ``experts_per_token`` rows a token has, those
    for held experts come first, sorted by expert: ``token`` (each row's
    token), ``weight`` (its renormalised probability), ``group_sizes``
    [experts_held] and ``rows``, their sum. Rows from ``rows`` on belong to
    experts held elsewhere."""
    k, held = cfg.experts_per_token, cfg.experts_held
    top, ids = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    local = ids - cfg.first_expert
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    # a count by comparison: bincount is a scatter-add of every row
    sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    return {"token": (order // k).astype(jnp.int32),
            "weight": top.reshape(-1)[order], "group_sizes": sizes,
            "rows": jnp.sum(sizes)}


def _chunk_plan(lo, chunk_rows: int, token, weight, starts, ends, total):
    """The sorted rows ``lo .. lo + chunk_rows``: which hold a routed row,
    their tokens and weights, and how many of them each held expert has."""
    valid = lo + jnp.arange(chunk_rows) < total
    here = (jnp.clip(ends, lo, lo + chunk_rows)
            - jnp.clip(starts, lo, lo + chunk_rows))
    return (valid, jax.lax.dynamic_slice(token, (lo,), (chunk_rows,)),
            jax.lax.dynamic_slice(weight, (lo,), (chunk_rows,)), here)


def _chunk_experts(rows, w_gate, w_up, w_down, here, impl: str):
    """Wdown_e(silu(x Wgate_e) * (x Wup_e)) for a chunk's sorted rows."""
    with jax.named_scope("rdp.moe.experts"):
        gate = grouped_matmul(rows, w_gate, here, impl=impl)
        up = grouped_matmul(rows, w_up, here, impl=impl)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(rows.dtype)
        return grouped_matmul(act, w_down, here, impl=impl,
                              out_dtype=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def routed_experts(chunk_rows: int, impl: str, h, w_gate, w_up, w_down,
                   token, weight, starts, ends, total):
    """``mixed[t] = sum over t's rows r of weight[r] * expert(r)(h[t])``
    for the sorted rows ``0 .. total`` (``route``'s plan), float32
    [tokens, hidden].

    Shapes are static at the worst case (every row of every token for a
    held expert) and the rows are taken ``chunk_rows`` at a time; the loop
    runs as many chunks as hold a routed row, so a step pays for the rows
    its routing gives and no routing overflows. Each chunk gathers its
    rows, multiplies them group by group and adds the weighted results to
    their tokens. The backward pass is the same loop: it recomputes a
    chunk, takes its gradients and adds them up in place, so nothing a
    chunk makes outlives it and the chunks that hold no row cost nothing
    there either (a ``lax.scan`` of ``lax.cond``s, differentiated by JAX,
    zero-fills and adds the matrices' 0.45 GB for every skipped chunk)."""
    return _routed_experts_fwd(chunk_rows, impl, h, w_gate, w_up, w_down,
                               token, weight, starts, ends, total)[0]


def _routed_experts_fwd(chunk_rows, impl, h, w_gate, w_up, w_down, token,
                        weight, starts, ends, total):
    with jax.named_scope("rdp.moe.experts"):
        mats = tuple(w.astype(h.dtype) for w in (w_gate, w_up, w_down))

    def body(i, mixed):
        lo = i * chunk_rows
        with jax.named_scope("rdp.moe.route"):
            valid, tok, w, here = _chunk_plan(
                lo, chunk_rows, token, weight, starts, ends, total)
            rows = jnp.where(valid[:, None], h[tok], 0)
        out = _chunk_experts(rows, *mats, here, impl)
        with jax.named_scope("rdp.moe.route"):
            out = jnp.where(valid[:, None], out, 0) * w[:, None]
            return mixed.at[tok].add(out)

    mixed = jax.lax.fori_loop(
        0, (total + chunk_rows - 1) // chunk_rows, body,
        jnp.zeros(h.shape, jnp.float32))
    return mixed, (h, w_gate, w_up, w_down, token, weight, starts, ends,
                   total)


def _routed_experts_bwd(chunk_rows, impl, residuals, g):
    h, w_gate, w_up, w_down, token, weight, starts, ends, total = residuals
    with jax.named_scope("rdp.moe.experts"):
        mats = tuple(w.astype(h.dtype) for w in (w_gate, w_up, w_down))

    def body(i, carry):
        dh, d_mats, d_weight = carry
        lo = i * chunk_rows
        with jax.named_scope("rdp.moe.route"):
            valid, tok, w, here = _chunk_plan(
                lo, chunk_rows, token, weight, starts, ends, total)
            rows = jnp.where(valid[:, None], h[tok], 0)
            g_rows = jnp.where(valid[:, None], g[tok], 0)
        out, vjp = jax.vjp(
            lambda rows, *mats: _chunk_experts(rows, *mats, here, impl),
            rows, *mats)
        d_rows, *d_chunk = vjp(g_rows * w[:, None])
        with jax.named_scope("rdp.moe.route"):
            d_w = jnp.sum(jnp.where(valid[:, None], out, 0) * g_rows, axis=1)
            dh = dh.at[tok].add(jnp.where(
                valid[:, None], d_rows.astype(jnp.float32), 0))
            d_weight = jax.lax.dynamic_update_slice(d_weight, d_w, (lo,))
        with jax.named_scope("rdp.moe.experts"):
            d_mats = tuple(acc + d.astype(jnp.float32)
                           for acc, d in zip(d_mats, d_chunk))
        return dh, d_mats, d_weight

    dh, d_mats, d_weight = jax.lax.fori_loop(
        0, (total + chunk_rows - 1) // chunk_rows, body,
        (jnp.zeros(h.shape, jnp.float32),
         tuple(jnp.zeros(w.shape, jnp.float32) for w in mats),
         jnp.zeros(weight.shape, jnp.float32)))
    return (dh.astype(h.dtype), *d_mats, None, d_weight, None, None, None)


routed_experts.defvjp(_routed_experts_fwd, _routed_experts_bwd)


def expert_layer(cfg, layer: dict, h, impl: str):
    """What the held experts add for ``h`` [tokens, hidden], and the rows
    each took."""
    n_rows = h.shape[0] * cfg.experts_per_token
    chunk_rows = min(cfg.moe_chunk_rows, n_rows)
    if n_rows % chunk_rows:
        raise ValueError(f"{n_rows} rows are no multiple of {chunk_rows}")
    with jax.named_scope("rdp.moe.route"):
        logits = jnp.dot(h.astype(jnp.float32), layer["router"],
                         precision=jax.lax.Precision.HIGHEST)
        plan = route(cfg, jax.nn.softmax(logits, axis=-1))
        ends = jnp.cumsum(plan["group_sizes"])
    mixed = routed_experts(
        chunk_rows, impl, h, layer["w_gate"], layer["w_up"],
        layer["w_down"], plan["token"], plan["weight"],
        ends - plan["group_sizes"], ends, plan["rows"])
    return mixed.astype(h.dtype), plan["group_sizes"]
