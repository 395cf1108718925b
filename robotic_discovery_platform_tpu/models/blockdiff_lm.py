"""A sparse-expert decoder trained by block diffusion, as one chip's share of
an expert-parallel job.

The layer equations (``BlockDiffLMConfig``; RMSNorm ``eps``, no biases)::

    h = RMSNorm(x);  q = h Wq, k = h Wk, v = h Wv      (heads x head_dim,
    kv_heads x head_dim; each key/value head shared by heads / kv_heads)
    q, k <- per-head RMSNorm over head_dim, then rotary embedding (theta,
            over the full head, rotate-half form)
    x += softmax(q k^T / sqrt(head_dim) + M) v Wo
    h = RMSNorm(x);  p = softmax_f32(h Wr) over all num_experts
    the experts_per_token largest, renormalised to sum 1 (norm_topk_prob)
    x += sum_e p_e Wdown_e( silu(h Wgate_e) * (h Wup_e) )

then a final RMSNorm and an untied head. Parameters are float32; matrix
products and activations run in ``compute_dtype``; router logits, softmax,
RMSNorm statistics, attention's softmax and the loss in float32.

**The chip's share.** ``experts_held`` experts of every layer live here
(ids ``first_expert .. first_expert + experts_held - 1``), and
``vocab_size`` rows of the embedding and the head. The layer routes over
all ``num_experts``, computes what its own experts add for the rows routed
to them, and passes that partial sum on; nothing stands in for the other
chips or their exchange. Routing drops nothing: the rows for the held
experts are sorted by expert and multiplied group by group
(``ops/pallas/grouped_matmul``) at a static size of ``experts_per_token``
rows a position, whatever the counts, in chunks of which only those that
hold routed rows run (:func:`routed_experts`).

**Training by block diffusion.** The model sees the noisy copy of a
sequence followed by the clean copy (``2 L`` positions, both copies at
rotary positions ``0..L-1``) under the mask of
``ops/pallas/blockdiff_attention``; the loss is the cross-entropy at the
masked positions of the noisy copy against the clean token (no shift),
weighted ``1 / t`` of the position's block, summed and divided by the number
of tokens. Noise comes from ``training/data.block_diffusion_noise``.

Layers are stacked and run under ``lax.scan`` with one ``jax.checkpoint`` a
layer: the layer's input and attention's output (with its row sums) are
saved, the rest is recomputed.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from robotic_discovery_platform_tpu.ops.pallas.blockdiff_attention import (
    ATTN_RESIDUALS, blockdiff_attention)
from robotic_discovery_platform_tpu.ops.pallas.grouped_matmul import (
    grouped_matmul)
from robotic_discovery_platform_tpu.utils.config import BlockDiffLMConfig

def param_shapes(cfg: BlockDiffLMConfig) -> dict:
    """name -> shape; the layers' leaves carry the depth in front."""
    n, h, d = cfg.num_layers, cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_heads * d, cfg.num_kv_heads * d
    e, f = cfg.experts_held, cfg.expert_width
    return {
        "embed": (cfg.vocab_size, h),
        "layers/attn_norm": (n, h), "layers/wq": (n, h, q),
        "layers/wk": (n, h, kv), "layers/wv": (n, h, kv),
        "layers/q_norm": (n, d), "layers/k_norm": (n, d),
        "layers/wo": (n, q, h), "layers/moe_norm": (n, h),
        "layers/router": (n, h, cfg.num_experts),
        "layers/w_gate": (n, e, h, f), "layers/w_up": (n, e, h, f),
        "layers/w_down": (n, e, f, h),
        "final_norm": (h,), "head": (h, cfg.vocab_size),
    }


def init_params(cfg: BlockDiffLMConfig, rng) -> dict:
    """Normal(0, ``init_std``) matrices (the embedding at
    ``embed_init_std``), norms at one, by a rule a reference can re-derive
    from ``jax.random`` alone: leaf ``i`` of :func:`param_shapes`, in its
    order, is ``std * normal(split(rng, n_leaves)[i], shape, float32)``
    (a norm takes its key and draws nothing). The training task says which
    ``rng`` a job's seed gives (``tasks.BlockDiffLMTask.init_variables``)."""
    from flax.traverse_util import unflatten_dict

    shapes = param_shapes(cfg)
    keys = jax.random.split(rng, len(shapes))
    flat = {}
    for key, (name, shape) in zip(keys, shapes.items()):
        if name.endswith("norm"):
            flat[name] = jnp.ones(shape, jnp.float32)
        else:
            std = cfg.embed_init_std if name == "embed" else cfg.init_std
            flat[name] = std * jax.random.normal(key, shape, jnp.float32)
    return unflatten_dict(flat, sep="/")


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * weight).astype(x.dtype)


def rotary(x, positions, theta: float):
    """[..., s, d] at integer ``positions`` [s]; rotate-half form."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def route(cfg: BlockDiffLMConfig, probs):
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


def expert_layer(cfg: BlockDiffLMConfig, layer: dict, h, impl: str):
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


def decoder_layer(cfg: BlockDiffLMConfig, layer: dict, x, positions,
                  impl: str):
    """One layer on ``x`` [batch, 2L, hidden] -> (x, rows per held expert)."""
    b, s, hid = x.shape
    heads, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = x.dtype
    with jax.named_scope("rdp.attn.proj"):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)

        def heads_of(w, n):
            y = jnp.dot(h, w.astype(dtype))
            return y.reshape(b, s, n, d).transpose(0, 2, 1, 3)

        q = heads_of(layer["wq"], heads)
        k = heads_of(layer["wk"], kvh)
        v = heads_of(layer["wv"], kvh)
        q = rotary(rms_norm(q, layer["q_norm"], cfg.rms_norm_eps),
                   positions, cfg.rope_theta)
        k = rotary(rms_norm(k, layer["k_norm"], cfg.rms_norm_eps),
                   positions, cfg.rope_theta)
        q = (q.astype(jnp.float32) * d ** -0.5).astype(dtype)
    a = blockdiff_attention(q, k, v, seq_len=s // 2, block=cfg.block_length,
                            impl=impl)
    with jax.named_scope("rdp.attn.proj"):
        a = a.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
        x = x + jnp.dot(a, layer["wo"].astype(dtype))
    h = rms_norm(x, layer["moe_norm"], cfg.rms_norm_eps)
    mixed, sizes = expert_layer(cfg, layer, h.reshape(b * s, hid), impl)
    return x + mixed.reshape(b, s, hid), sizes


def forward(cfg: BlockDiffLMConfig, params: dict, tokens, masked,
            impl: str | None = None):
    """Logits [batch, L, vocab] (float32) at the noisy copy's positions, and
    the rows each held expert took, [layers, experts_held]. ``tokens``
    [batch, L] int32 are the clean sequence, ``masked`` [batch, L] bool the
    positions the noisy copy shows as ``mask_token_id``."""
    impl = cfg.kernel_impl if impl is None else impl
    dtype = jnp.dtype(cfg.compute_dtype)
    length = tokens.shape[1]
    with jax.named_scope("rdp.lm.embed"):
        noisy = jnp.where(masked, cfg.mask_token_id, tokens)
        ids = jnp.concatenate([noisy, tokens], axis=1)
        x = params["embed"].astype(dtype)[ids]
        positions = jnp.concatenate([jnp.arange(length)] * 2)

    # of a layer, its input and attention's output and row sums are kept;
    # the rest is recomputed in the backward pass
    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS))
    def layer_fn(x, layer):
        with jax.named_scope("rdp.lm.layer"):
            return decoder_layer(cfg, layer, x, positions, impl)

    x, sizes = jax.lax.scan(layer_fn, x, params["layers"])
    with jax.named_scope("rdp.lm.head"):
        x = rms_norm(x[:, :length], params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(x, params["head"].astype(dtype),
                         preferred_element_type=jnp.float32)
    return logits, sizes


def diffusion_loss(logits, tokens, masked, t):
    """Cross-entropy at the masked positions against the clean token,
    weighted ``1 / t`` of the position's block, over the number of tokens."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(masked, nll / t, 0.0)) / tokens.size


@dataclasses.dataclass(frozen=True)
class BlockDiffLM:
    """The model object a training task holds: pure functions of ``cfg``."""

    cfg: BlockDiffLMConfig

    def init(self, rng) -> dict:
        return init_params(self.cfg, rng)

    def apply(self, params, tokens, masked, **kw):
        return forward(self.cfg, params, tokens, masked, **kw)


def build_blockdiff_lm(cfg: BlockDiffLMConfig) -> BlockDiffLM:
    return BlockDiffLM(cfg)
