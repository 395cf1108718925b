"""A causal sparse-expert decoder whose attention layers differ in kind, as
one chip's share of an expert-parallel job: the second of the platform's
three language-model families (the first, ``models/blockdiff_lm``, stacks
identical layers and is trained by block diffusion; the third,
``models/hybrid_lm``, mixes layers of one residual branch each (state-space
mixers, gated short convolutions, attention, dense MLPs, experts) and takes
this module's head, loss and rotary table; the three share the expert layer
in the form their configuration gives it, RMSNorm and the seeded start,
``models/moe``).

One layer (``CausalLMConfig``; RMSNorm ``eps``, no biases, no q/k norm)::

    h = RMSNorm(x);  q = h Wq, k = h Wk, v = h Wv      (heads x head_dim,
    kv_heads x head_dim; each key/value head shared by heads / kv_heads)
    q, k <- rotary by the layer kind's table (rotate-half, whole head)
    x += softmax(q k^T / sqrt(head_dim) + M_kind) v Wo
       M_full:    key j live for query i iff j <= i
       M_sliding: iff j <= i and i - j < sliding_window
    h = RMSNorm(x);  p = softmax_f32(h Wr) over all num_experts
    the experts_per_token largest, renormalised to sum 1 (norm_topk_prob)
    x += sum_e p_e Wdown_e( silu(h Wgate_e) * (h Wup_e) )

(the expert layer in its default form: a softmax router and gated SiLU
experts; ``models/moe`` has the others, and a configuration that asks for
one gets its leaves from ``moe.expert_shapes``) then a final RMSNorm and an
untied head (``head_logits`` and ``next_token_loss`` also serve a model
whose parameters hold no ``head``: its embedding is the head, tied); the
loss is the mean cross-entropy
of position ``i``'s logits against token ``i + 1`` over positions
``0 .. L - 2``. Parameters are float32; matrix products and activations run
in ``compute_dtype``; the rotary tables and their application, router
logits, softmaxes, RMSNorm statistics and the loss in float32.

**Layers of two kinds.** ``layer_types`` names each layer's attention. The
pattern drives the program: the model finds the pattern's shortest period,
runs the period's layers one after another, each under its own mask
(``ops/pallas/masked_attention``: :class:`Causal`, :class:`Window`) and its
own rotary table, and repeats the period (``lax.scan`` over the periods; a
model of one period, as the benchmark's cell holds, runs it unrolled with no
loop around it). The parameters are laid out to match: leaf
``layers/<j>/<name>`` holds layer ``j`` of every period, the periods in
front. **One rotary table a kind** (:func:`rope_table`), built once a
forward pass from its ``RotaryConfig`` and handed to the layers of that
kind; with ``factor`` above 1 it is YaRN's. A layer hands each projection's
product, the table and the scale to ``ops/pallas/qk_prep.prepare_heads``,
where q and k are made ready for attention: head split, rotation and scale
in one Pallas pass a direction on a TPU (heads of 128), the dense chain
(``apply_rotary``, kept there as the definition) elsewhere.

**The chip's share** is ``models/moe``'s: ``experts_held`` experts of every
layer and ``vocab_size`` rows of the embedding and the head.

Each layer runs under one ``jax.checkpoint``: its input and attention's
output (with its row sums) are saved, the rest is recomputed. Head and loss
run ``HEAD_CHUNK`` positions at a time, each chunk under a
``jax.checkpoint`` of its own: at the cell's size the float32 logits of all
positions are 1.61 GB and their gradient as much again.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from robotic_discovery_platform_tpu.models.moe import (
    expert_layer, expert_shapes, rms_norm, seeded_params)
from robotic_discovery_platform_tpu.ops.pallas.masked_attention import (
    ATTN_RESIDUALS, Causal, Window, masked_attention)
from robotic_discovery_platform_tpu.ops.pallas.qk_prep import (  # noqa: F401
    apply_rotary, prepare_heads, split_heads)
from robotic_discovery_platform_tpu.utils.config import (
    CausalLMConfig, RotaryConfig)

#: positions of a sequence whose logits are alive at a time
HEAD_CHUNK = 2048


def period(layer_types: tuple) -> int:
    """The shortest ``p`` dividing the depth with ``layer_types[i] ==
    layer_types[i % p]``."""
    n = len(layer_types)
    return next(p for p in range(1, n + 1) if n % p == 0 and all(
        kind == layer_types[i % p] for i, kind in enumerate(layer_types)))


def param_shapes(cfg: CausalLMConfig) -> dict:
    """name -> shape; ``layers/<j>/<name>`` is layer ``j`` of every period,
    the periods in front."""
    p = period(cfg.layer_types)
    r, h, d = cfg.num_layers // p, cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_heads * d, cfg.num_kv_heads * d
    layer = {"attn_norm": (r, h), "wq": (r, h, q), "wk": (r, h, kv),
             "wv": (r, h, kv), "wo": (r, q, h), "moe_norm": (r, h),
             **{name: (r, *shape)
                for name, shape in expert_shapes(cfg).items()}}
    return {"embed": (cfg.vocab_size, h),
            **{f"layers/{j}/{name}": shape for j in range(p)
               for name, shape in layer.items()},
            "final_norm": (h,), "head": (h, cfg.vocab_size)}


def init_params(cfg: CausalLMConfig, rng) -> dict:
    """The seeded start (``moe.seeded_params``) of :func:`param_shapes`."""
    return seeded_params(param_shapes(cfg), rng, cfg.init_std,
                         cfg.embed_init_std)


def yarn_range(rope: RotaryConfig, head_dim: int) -> tuple:
    """(low, high): the frequency indices between which YaRN's ramp runs.
    ``corr(n) = d ln(original / (2 pi n)) / (2 ln theta)`` is the index whose
    wavelength makes ``n`` turns over the original length; ``low =
    floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``, clamped to
    ``[0, d - 1]``."""
    def corr(turns):
        return head_dim * math.log(rope.original_max_position / (
            2 * math.pi * turns)) / (2 * math.log(rope.theta))

    return (max(math.floor(corr(rope.beta_fast)), 0),
            min(math.ceil(corr(rope.beta_slow)), head_dim - 1))


def rope_table(rope: RotaryConfig, head_dim: int, positions):
    """(cos, sin), each ``[positions, head_dim]`` float32, rotate-half
    layout (the half's frequencies twice). ``inv_freq_i = theta^(-2i/d)``;
    YaRN (``factor`` > 1) multiplies it by ``(1 - ramp_i) + ramp_i /
    factor``, ``ramp_i = clip((i - low) / (high - low), 0, 1)`` over
    ``i = 0 .. d/2 - 1`` (:func:`yarn_range`), and cos and sin by
    ``attention_factor``."""
    with jax.named_scope("rdp.attn.rope"):
        half = jnp.arange(0, head_dim, 2, dtype=jnp.float32)
        inv_freq = 1.0 / rope.theta ** (half / head_dim)
        if rope.factor > 1:
            low, high = yarn_range(rope, head_dim)
            high = high + 0.001 if high == low else high
            ramp = jnp.clip((half / 2 - low) / (high - low), 0.0, 1.0)
            inv_freq = inv_freq * ((1 - ramp) + ramp / rope.factor)
        angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
        cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
        sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
        return (cos * rope.attention_factor, sin * rope.attention_factor)


def attention_rule(cfg: CausalLMConfig, kind: str):
    """The mask of a layer kind, as a rule of ``masked_attention``."""
    return (Window(cfg.sliding_window) if kind == "sliding_attention"
            else Causal())


def rope_of(cfg: CausalLMConfig, kind: str) -> RotaryConfig:
    return cfg.sliding_rope if kind == "sliding_attention" else cfg.full_rope


def decoder_layer(cfg: CausalLMConfig, kind: str, layer: dict, x, table,
                  impl: str):
    """One layer of ``kind`` on ``x`` [batch, L, hidden] -> (x, rows per
    held expert). ``table`` is the kind's :func:`rope_table`."""
    b, s, hid = x.shape
    heads, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = x.dtype
    with jax.named_scope("rdp.attn.proj"):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)

        def product(name):
            return jnp.dot(h, layer[name].astype(dtype))

        q = prepare_heads(product("wq"), heads, d, table=table,
                          scale=d ** -0.5, impl=impl)
        k = prepare_heads(product("wk"), kvh, d, table=table, impl=impl)
        v = split_heads(product("wv"), kvh, d)
    a = masked_attention(q, k, v, attention_rule(cfg, kind), impl=impl)
    with jax.named_scope("rdp.attn.proj"):
        a = a.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
        x = x + jnp.dot(a, layer["wo"].astype(dtype))
    h = rms_norm(x, layer["moe_norm"], cfg.rms_norm_eps)
    mixed, sizes = expert_layer(cfg, layer, h.reshape(b * s, hid), impl)
    return x + mixed.reshape(b, s, hid), sizes


def hidden_states(cfg: CausalLMConfig, params: dict, tokens,
                  impl: str | None = None):
    """The stream after the last layer, ``[batch, L, hidden]``, and the
    rows each held expert took, ``[layers, experts_held]``."""
    impl = cfg.kernel_impl if impl is None else impl
    dtype = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("rdp.lm.embed"):
        x = params["embed"].astype(dtype)[tokens]
    positions = jnp.arange(tokens.shape[1])
    kinds = cfg.layer_types[:period(cfg.layer_types)]
    tables = {kind: rope_table(rope_of(cfg, kind), cfg.head_dim, positions)
              for kind in sorted(set(kinds))}

    # of a layer, its input and attention's output and row sums are kept;
    # the rest is recomputed in the backward pass
    @functools.partial(
        jax.checkpoint, static_argnums=(0,),
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS))
    def layer_fn(kind, x, layer, table):
        with jax.named_scope("rdp.lm.layer"):
            return decoder_layer(cfg, kind, layer, x, table, impl)

    def one_period(x, layers):
        sizes = []
        for j, kind in enumerate(kinds):
            x, taken = layer_fn(kind, x, layers[str(j)], tables[kind])
            sizes.append(taken)
        return x, jnp.stack(sizes)

    if cfg.num_layers == len(kinds):
        return one_period(x, jax.tree.map(lambda a: a[0], params["layers"]))
    x, sizes = jax.lax.scan(one_period, x, params["layers"])
    return x, sizes.reshape(cfg.num_layers, cfg.experts_held)


def head_logits(cfg, params: dict, x):
    """The final norm and the head on a stream ``x``: float32 logits over
    the vocabulary slice (``cfg`` gives ``rms_norm_eps``). ``params`` without
    a ``head`` are a tied model's: the embedding's rows are the head's
    columns."""
    with jax.named_scope("rdp.lm.head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        if "head" not in params:
            return jnp.einsum("...h,vh->...v", x,
                              params["embed"].astype(x.dtype),
                              preferred_element_type=jnp.float32)
        return jnp.dot(x, params["head"].astype(x.dtype),
                       preferred_element_type=jnp.float32)


def forward(cfg: CausalLMConfig, params: dict, tokens,
            impl: str | None = None):
    """Logits ``[batch, L, vocab]`` (float32) of every position, whole, and
    the rows each held expert took: for sizes at which they fit."""
    x, sizes = hidden_states(cfg, params, tokens, impl)
    return head_logits(cfg, params, x), sizes


def next_token_loss(cfg: CausalLMConfig, params: dict, x, tokens,
                    with_hits: bool = False):
    """From the last layer's stream: the mean over positions ``0 .. L - 2``
    of the cross-entropy of position ``i``'s logits against token ``i + 1``
    and, where asked, the share of those positions whose largest logit is
    that token. ``HEAD_CHUNK`` positions at a time."""
    b, length, _ = x.shape
    chunk = HEAD_CHUNK if length % HEAD_CHUNK == 0 else length
    # position L - 1 has no next token: it runs with the rest at weight 0
    targets = jnp.roll(tokens, -1, axis=1)
    weight = (jnp.arange(length) < length - 1).astype(jnp.float32)

    def chunks(a):      # [b, L, ...] -> [L / chunk, b, chunk, ...]
        return jnp.moveaxis(
            a.reshape(b, length // chunk, chunk, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one(head_params, x, targets, weight):
        logits = head_logits(cfg, head_params, x)
        with jax.named_scope("rdp.loss"):
            picked = jnp.take_along_axis(logits, targets[..., None], -1)
            nll = jax.nn.logsumexp(logits, axis=-1) - picked[..., 0]
            hits = ((jnp.argmax(logits, -1) == targets) * weight
                    if with_hits else jnp.zeros(()))
            return jnp.sum(nll * weight), jnp.sum(hits)

    # a tied model's head is its embedding, whose gradient is then the sum
    # of the gather's and the chunks'
    head_params = {k: params[k] for k in (
        "final_norm", "head" if "head" in params else "embed")}

    def body(total, args):
        nll, hits = one(head_params, *args)
        return (total[0] + nll, total[1] + hits), None

    (nll, hits), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())),
        (chunks(x), chunks(targets),
         weight.reshape(length // chunk, 1, chunk)))
    n = b * (length - 1)
    return nll / n, hits / n


@dataclasses.dataclass(frozen=True)
class CausalLM:
    """The model object a training task holds: pure functions of ``cfg``."""

    cfg: CausalLMConfig

    def init(self, rng) -> dict:
        return init_params(self.cfg, rng)

    def apply(self, params, tokens, **kw):
        return forward(self.cfg, params, tokens, **kw)

    def loss(self, params, tokens, with_hits: bool = False):
        """(loss, next-token accuracy, rows per layer and held expert)."""
        x, sizes = hidden_states(self.cfg, params, tokens)
        loss, hits = next_token_loss(self.cfg, params, x, tokens, with_hits)
        return loss, hits, sizes


def build_causal_lm(cfg: CausalLMConfig) -> CausalLM:
    return CausalLM(cfg)
