"""What the sparse-expert decoders share (``models/blockdiff_lm``,
``models/causal_lm``, ``models/hybrid_lm``): RMSNorm, the seeded start, and
the expert layer of one chip's share of an expert-parallel job.

**The chip's share.** ``experts_held`` experts of a layer live here (ids
``first_expert .. first_expert + experts_held - 1``). The layer routes over
all ``num_experts``, computes what its own experts add for the rows routed
to them, and passes that partial sum on; nothing stands in for the other
chips or their exchange. Routing drops nothing: the rows for the held
experts are sorted by expert and multiplied group by group
(``ops/pallas/grouped_matmul``) at a static size of ``experts_per_token``
rows a position, whatever the counts, in chunks of which only those that
hold routed rows run (:func:`routed_experts`).

**The layer's form** is the configuration's (four fields, the same on
every family's configuration; the defaults are what the block-diffusion and
the window-attention families compute):

``router_scoring``
    ``"softmax"``: a softmax over all experts, the largest picked and
    (``norm_topk_prob``) renormalised. ``"sigmoid"``: ``s = sigmoid(logits)``;
    the largest of ``s + router_bias`` are picked (the bias is a leaf of the
    layer that takes no gradient), weighed by ``s`` alone, renormalised over
    ``sum + router_norm_eps`` (1e-20 by default) and multiplied by
    ``routed_scaling_factor``.
``expert_act``
    ``"swiglu"``: ``Wdown(silu(x Wgate) * (x Wup))``, three matrices an
    expert. ``"relu2"``: ``Wdown relu(x Wup)^2``, two.
``shared_expert_width``
    above 0, an expert of that width and the same form that every token
    passes through at weight 1. Every chip of the deployment computes it
    alike (it is held whole, as attention is), so the shares of a layer add
    up to the uncut layer with the shared expert counted once.

``cfg`` is any family's configuration: the functions read those and
``hidden_size``, ``num_experts``, ``experts_per_token``, ``experts_held``,
``first_expert``, ``expert_width``, ``norm_topk_prob``,
``router_norm_eps`` and ``moe_chunk_rows`` of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from robotic_discovery_platform_tpu.ops.pallas.grouped_matmul import (
    grouped_matmul)


def seeded_params(shapes: dict, rng, init_std: float,
                  embed_init_std: float, draws: dict | None = None) -> dict:
    """Normal(0, ``init_std``) matrices (the leaf ``embed`` at
    ``embed_init_std``), norms at one, a router's selection bias at zero, by
    a rule a reference can re-derive from ``jax.random`` alone: leaf ``i``
    of ``shapes`` (name -> shape, "/" between the levels), in its order, is
    ``std * normal(split(rng, n_leaves)[i], shape, float32)`` (a norm takes
    its key and draws nothing). ``draws`` maps a leaf's own name (after the
    last "/") to ``(key, shape) -> array`` for the leaves a family starts
    otherwise."""
    from flax.traverse_util import unflatten_dict

    keys = jax.random.split(rng, len(shapes))
    flat = {}
    for key, (name, shape) in zip(keys, shapes.items()):
        leaf = name.rsplit("/", 1)[-1]
        if draws and leaf in draws:
            flat[name] = draws[leaf](key, shape)
        elif leaf.endswith("norm"):
            flat[name] = jnp.ones(shape, jnp.float32)
        elif leaf == "router_bias":
            flat[name] = jnp.zeros(shape, jnp.float32)
        else:
            std = embed_init_std if name == "embed" else init_std
            flat[name] = std * jax.random.normal(key, shape, jnp.float32)
    return unflatten_dict(flat, sep="/")


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * weight).astype(x.dtype)


def expert_shapes(cfg) -> dict:
    """name -> shape of an expert layer's leaves, in their order (a
    family's ``param_shapes`` puts its own axes in front): the router, its
    selection bias where it scores by sigmoid, the held experts' matrices
    and the shared expert's."""
    h, e, f = cfg.hidden_size, cfg.experts_held, cfg.expert_width
    gated, s = cfg.expert_act == "swiglu", cfg.shared_expert_width
    return {
        "router": (h, cfg.num_experts),
        **({"router_bias": (cfg.num_experts,)}
           if cfg.router_scoring == "sigmoid" else {}),
        **({"w_gate": (e, h, f)} if gated else {}),
        "w_up": (e, h, f), "w_down": (e, f, h),
        **({"shared_gate": (h, s)} if gated and s else {}),
        **({"shared_up": (h, s), "shared_down": (s, h)} if s else {})}


def expert_matrices(layer: dict, prefix: str = "w_") -> tuple:
    """The held (``"w_"``) or the shared (``"shared_"``) expert's matrices
    in the order of their use: (gate,) up, down."""
    return tuple(layer[prefix + name] for name in ("gate", "up", "down")
                 if prefix + name in layer)


def router_scores(cfg, layer: dict, logits):
    """(what the picked experts are weighed by, what they are picked by
    where that differs), each ``[tokens, num_experts]`` float32."""
    if cfg.router_scoring == "softmax":
        return jax.nn.softmax(logits, axis=-1), None
    scores = jax.nn.sigmoid(logits)
    return scores, scores + jax.lax.stop_gradient(layer["router_bias"])


def route(cfg, probs, picked_by=None):
    """From ``probs`` [tokens, num_experts] (float32): the dropless plan for
    the held experts. Of the ``experts_per_token`` rows a token has, those
    for held experts come first, sorted by expert: ``token`` (each row's
    token), ``weight`` (its renormalised probability), ``group_sizes``
    [experts_held] and ``rows``, their sum. Rows from ``rows`` on belong to
    experts held elsewhere. Where ``picked_by`` is given (a sigmoid
    router's biased scores) the experts are its largest and the weights
    ``probs`` at those."""
    k, held = cfg.experts_per_token, cfg.experts_held
    if picked_by is None:
        top, ids = jax.lax.top_k(probs, k)
    else:
        _, ids = jax.lax.top_k(picked_by, k)
        top = jnp.take_along_axis(probs, ids, axis=-1)
    if cfg.norm_topk_prob:
        total = jnp.sum(top, axis=-1, keepdims=True)
        # a softmax's picks sum to more than nothing; sigmoids may not
        top = top / (total if picked_by is None
                     else total + cfg.router_norm_eps)
    if cfg.routed_scaling_factor != 1.0:
        top = top * cfg.routed_scaling_factor
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


def _activation(projected: tuple, dtype):
    """silu(gate) * up of a gated expert's two projections, relu(up)^2 of
    an ungated one's, in float32."""
    if len(projected) == 2:
        gate, up = (p.astype(jnp.float32) for p in projected)
        return (jax.nn.silu(gate) * up).astype(dtype)
    return jnp.square(jax.nn.relu(projected[0].astype(jnp.float32))).astype(
        dtype)


def _chunk_experts(rows, mats: tuple, here, impl: str):
    """Wdown_e(silu(x Wgate_e) * (x Wup_e)), or Wdown_e relu(x Wup_e)^2 of
    two matrices, for a chunk's sorted rows."""
    with jax.named_scope("rdp.moe.experts"):
        act = _activation(tuple(grouped_matmul(rows, w, here, impl=impl)
                                for w in mats[:-1]), rows.dtype)
        return grouped_matmul(act, mats[-1], here, impl=impl,
                              out_dtype=jnp.float32)


def dense_expert(mats: tuple, h):
    """An expert's form on every row of ``h`` [..., hidden] at weight 1, as
    dense products: ``mats`` is (gate,) up, down."""
    *ins, w_down = (w.astype(h.dtype) for w in mats)
    act = _activation(tuple(jnp.dot(h, w) for w in ins), h.dtype)
    return jnp.dot(act, w_down)


def shared_expert(mats: tuple, h):
    """The shared expert's branch for ``h`` [tokens, hidden]."""
    with jax.named_scope("rdp.moe.shared"):
        return dense_expert(mats, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def routed_experts(chunk_rows: int, impl: str, h, mats: tuple, token,
                   weight, starts, ends, total):
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
    return _routed_experts_fwd(chunk_rows, impl, h, mats, token, weight,
                               starts, ends, total)[0]


def _routed_experts_fwd(chunk_rows, impl, h, mats, token, weight, starts,
                        ends, total):
    residuals = (h, mats, token, weight, starts, ends, total)
    with jax.named_scope("rdp.moe.experts"):
        mats = tuple(w.astype(h.dtype) for w in mats)

    def body(i, mixed):
        lo = i * chunk_rows
        with jax.named_scope("rdp.moe.route"):
            valid, tok, w, here = _chunk_plan(
                lo, chunk_rows, token, weight, starts, ends, total)
            rows = jnp.where(valid[:, None], h[tok], 0)
        out = _chunk_experts(rows, mats, here, impl)
        with jax.named_scope("rdp.moe.route"):
            out = jnp.where(valid[:, None], out, 0) * w[:, None]
            return mixed.at[tok].add(out)

    mixed = jax.lax.fori_loop(
        0, (total + chunk_rows - 1) // chunk_rows, body,
        jnp.zeros(h.shape, jnp.float32))
    return mixed, residuals


def _routed_experts_bwd(chunk_rows, impl, residuals, g):
    h, mats, token, weight, starts, ends, total = residuals
    with jax.named_scope("rdp.moe.experts"):
        mats = tuple(w.astype(h.dtype) for w in mats)

    def body(i, carry):
        dh, d_mats, d_weight = carry
        lo = i * chunk_rows
        with jax.named_scope("rdp.moe.route"):
            valid, tok, w, here = _chunk_plan(
                lo, chunk_rows, token, weight, starts, ends, total)
            rows = jnp.where(valid[:, None], h[tok], 0)
            g_rows = jnp.where(valid[:, None], g[tok], 0)
        out, vjp = jax.vjp(
            lambda rows, mats: _chunk_experts(rows, mats, here, impl),
            rows, mats)
        d_rows, d_chunk = vjp(g_rows * w[:, None])
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
    return (dh.astype(h.dtype), d_mats, None, d_weight, None, None, None)


routed_experts.defvjp(_routed_experts_fwd, _routed_experts_bwd)


def expert_layer(cfg, layer: dict, h, impl: str):
    """What the held experts (and the shared one, where the configuration
    has one) add for ``h`` [tokens, hidden], and the rows each held expert
    took."""
    n_rows = h.shape[0] * cfg.experts_per_token
    chunk_rows = min(cfg.moe_chunk_rows, n_rows)
    if n_rows % chunk_rows:
        raise ValueError(f"{n_rows} rows are no multiple of {chunk_rows}")
    with jax.named_scope("rdp.moe.route"):
        logits = jnp.dot(h.astype(jnp.float32), layer["router"],
                         precision=jax.lax.Precision.HIGHEST)
        plan = route(cfg, *router_scores(cfg, layer, logits))
        ends = jnp.cumsum(plan["group_sizes"])
    mixed = routed_experts(
        chunk_rows, impl, h, expert_matrices(layer), plan["token"],
        plan["weight"], ends - plan["group_sizes"], ends, plan["rows"])
    mixed = mixed.astype(h.dtype)
    if cfg.shared_expert_width:
        mixed = mixed + shared_expert(expert_matrices(layer, "shared_"), h)
    return mixed, plan["group_sizes"]
