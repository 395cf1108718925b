"""A causal decoder whose layers are one residual branch each, a Mamba-2
mixer, a gated short convolution, grouped-query attention, a dense gated
MLP or a sparse-expert layer, as one chip's share of an expert-parallel
job: the third of the platform's language-model families
(``models/blockdiff_lm`` and ``models/causal_lm`` are stacks of
attention-then-experts layers; the three share the expert layer, RMSNorm
and the seeded start, ``models/moe``, and this one takes ``causal_lm``'s
head, next-token loss and rotary table). A published model whose layer is
an operator and a feed-forward branch (LFM2: a short convolution or
attention, then a dense MLP or experts) is two entries of the pattern a
layer.

Every layer is ``x += branch(RMSNorm(x))`` (``HybridLMConfig``; RMSNorm
``eps``, no biases but the Mamba convolution's), its kind named by
``layer_pattern``:

``mamba`` (``inner`` = ``mamba_heads x mamba_head_dim`` channels, ``G`` =
``ssm_groups``, ``N`` = ``ssm_state``)::

    [z | xBC | dt] = u W_in              widths inner | inner + 2 G N | heads
    xBC = silu(conv(xBC))                causal, depthwise, conv_kernel wide:
                                         position t sees t - K + 1 .. t
    [xs | B | C] = xBC                   widths inner | G N | G N; head h
                                         reads group h // (heads / G)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)       (a scalar a head)
    h_t = exp(dt_t A) h_{t-1} + dt_t xs_t (x) B_t       (h_{-1} = 0; no reset
    y_t = h_t C_t + D xs_t                              inside a sequence)
    y = gate_norm * RMSNorm_groups(y * silu(z))         statistics over each
                                                        of the G groups apart
    branch = y W_out

``shortconv`` (LFM2's operator; ``K`` = ``shortconv_kernel`` taps)::

    [B | C | x'] = u W_in                hidden -> 3 x hidden, in that order
    y = C * conv(B * x')                 causal, depthwise, no bias, no
                                         activation: position t sees
                                         t - K + 1 .. t
    branch = y W_out

``attention``: ``q = u Wq`` (heads x head_dim), ``k = u Wk``, ``v = u Wv``
(kv_heads x head_dim); under ``qk_norm`` an RMSNorm over each head of ``q``
and ``k`` (``q_norm``, ``k_norm``); with ``rope_theta`` above 0 the rotary
embedding of ``causal_lm.rope_table`` (rotate-half, whole head, no
scaling), else **no rotary embedding**; ``softmax(q k^T / sqrt(head_dim) +
causal) v Wo``. The head split, norm, rotation and scale of q and k are
``ops/pallas/qk_prep.prepare_heads``'s: one Pallas pass a direction on a
TPU where heads fill 128-lane tiles and there is a norm or a table to fuse,
the dense chain for heads of 64 (LFM2) and for a bare scale (no norm, no
positions).

``mlp``: ``Wdown(silu(u Wgate) * (u Wup))`` of width ``mlp_width``.

``experts``: ``models/moe.expert_layer`` in the form the configuration
gives it (sigmoid scores picked with a bias that takes no gradient,
renormalised over their sum plus ``router_norm_eps`` and scaled;
squared-ReLU experts of two matrices or gated SiLU ones of three; a shared
expert or none).

Then a final RMSNorm and the head, untied or, under ``tie_embeddings``,
the embedding itself (one leaf, whose gradient is the sum of both uses);
the loss is ``causal_lm.next_token_loss``, every position. Parameters are
float32; matrix products and activations run in ``compute_dtype``; the
mixer's steps, decays, their running sums and the carried state
(``ops/ssm_scan``), both convolutions' sums and the short convolution's two
gates, the gate norm's statistics, router scores, RMSNorm statistics, the
rotary table and its application, attention's softmax and the loss in
float32.

**The pattern drives the program** as ``causal_lm``'s does: the model
finds the pattern's shortest period, runs the period's layers one after
another and repeats it (``lax.scan`` over the periods; a model of one
period, as the benchmark's cells hold, runs unrolled). Parameters are laid
out to match: ``layers/<j>/<name>`` holds layer ``j`` of every period, the
periods in front, with the leaves of that layer's kind
(:func:`layer_shapes`). Each layer runs under one ``jax.checkpoint``: its
input is saved and, of an attention layer, the kernel's output and row
sums; the rest is recomputed.

**The chip's share** is ``models/moe``'s: ``experts_held`` experts of every
expert layer, ``vocab_size`` rows of the embedding and the head; mixers,
convolutions, attention, dense MLPs, routers and shared experts are held
whole.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from robotic_discovery_platform_tpu.models.causal_lm import (
    head_logits, next_token_loss, period, rope_table)
from robotic_discovery_platform_tpu.models.moe import (
    dense_expert, expert_layer, expert_shapes, rms_norm, seeded_params)
from robotic_discovery_platform_tpu.ops.pallas.masked_attention import (
    ATTN_RESIDUALS, Causal, masked_attention)
from robotic_discovery_platform_tpu.ops.pallas.qk_prep import (
    prepare_heads, split_heads)
from robotic_discovery_platform_tpu.ops.ssm_scan import ssm_scan
from robotic_discovery_platform_tpu.utils.config import (
    HybridLMConfig, RotaryConfig)


def layer_shapes(cfg: HybridLMConfig, kind: str) -> dict:
    """name -> shape of one layer of ``kind``, in the leaves' order."""
    h = cfg.hidden_size
    if kind == "mamba":
        inner, heads = cfg.mamba_inner, cfg.mamba_heads
        return {"norm": (h,), "w_in": (h, inner + cfg.conv_dim + heads),
                "conv_w": (cfg.conv_dim, cfg.conv_kernel),
                "conv_b": (cfg.conv_dim,), "dt_bias": (heads,),
                "A_log": (heads,), "D": (heads,), "gate_norm": (inner,),
                "w_out": (inner, h)}
    if kind == "shortconv":
        return {"norm": (h,), "w_in": (h, 3 * h),
                "conv_taps": (h, cfg.shortconv_kernel), "w_out": (h, h)}
    if kind == "attention":
        q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        return {"norm": (h,), "wq": (h, q), "wk": (h, kv), "wv": (h, kv),
                **({"q_norm": (cfg.head_dim,), "k_norm": (cfg.head_dim,)}
                   if cfg.qk_norm else {}),
                "wo": (q, h)}
    if kind == "mlp":
        return {"norm": (h,), "w_gate": (h, cfg.mlp_width),
                "w_up": (h, cfg.mlp_width), "w_down": (cfg.mlp_width, h)}
    return {"norm": (h,), **expert_shapes(cfg)}


def param_shapes(cfg: HybridLMConfig) -> dict:
    """name -> shape; ``layers/<j>/<name>`` is layer ``j`` of every period,
    the periods in front."""
    p = period(cfg.layer_pattern)
    r = cfg.num_layers // p
    return {"embed": (cfg.vocab_size, cfg.hidden_size),
            **{f"layers/{j}/{name}": (r, *shape)
               for j, kind in enumerate(cfg.layer_pattern[:p])
               for name, shape in layer_shapes(cfg, kind).items()},
            "final_norm": (cfg.hidden_size,),
            **({} if cfg.tie_embeddings
               else {"head": (cfg.hidden_size, cfg.vocab_size)})}


def mixer_draws(cfg: HybridLMConfig) -> dict:
    """How the seeded start draws the mixer's leaves that are no matrices,
    as Mamba-2 initialises them: ``D`` at one; ``A_log = log(a)``, ``a``
    uniform on ``a_range``; ``dt_bias`` the inverse softplus of a step
    drawn log-uniform on ``[time_step_min, time_step_max]`` and floored at
    ``time_step_floor``; a convolution's weight and bias uniform on
    ``+- 1 / sqrt(taps)`` (torch's ``Conv1d`` default, which Mamba-2 leaves
    in place; the short convolution's ``conv_taps`` by the same rule on its
    own width). Each from ``jax.random.uniform(key, shape)``."""
    lo, hi = cfg.a_range

    def uniform(key, shape):
        return jax.random.uniform(key, shape, jnp.float32)

    def dt_bias(key, shape):
        step = jnp.exp(uniform(key, shape) * (
            math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
            + math.log(cfg.time_step_min))
        step = jnp.maximum(step, cfg.time_step_floor)
        return step + jnp.log(-jnp.expm1(-step))

    def conv(key, shape, taps=cfg.conv_kernel):
        return (2 * uniform(key, shape) - 1) * taps ** -0.5

    return {"D": lambda key, shape: jnp.ones(shape, jnp.float32),
            "A_log": lambda key, shape: jnp.log(
                lo + (hi - lo) * uniform(key, shape)),
            "dt_bias": dt_bias, "conv_w": conv, "conv_b": conv,
            "conv_taps": functools.partial(conv, taps=cfg.shortconv_kernel)}


def init_params(cfg: HybridLMConfig, rng) -> dict:
    """The seeded start (``moe.seeded_params``) of :func:`param_shapes`,
    the mixer's own leaves by :func:`mixer_draws`."""
    return seeded_params(param_shapes(cfg), rng, cfg.init_std,
                         cfg.embed_init_std, mixer_draws(cfg))


def causal_conv(x, weight, bias=None):
    """Depthwise over positions: ``y_t = bias + sum_k weight[:, k]
    x_{t - K + 1 + k}`` for ``x`` ``[batch, L, channels]``, positions before
    the sequence reading zero; the sum in float32, with or without a bias
    (the Mamba-2 mixer's inner convolution has one, the short
    convolution's none)."""
    taps = weight.shape[1]
    length = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(jnp.float32)
    for k in range(taps):
        tap = padded[:, k:k + length].astype(jnp.float32) * weight[:, k]
        out = tap if out is None else out + tap
    return out


def grouped_rms_norm(x, weight, groups: int, eps: float):
    """RMSNorm of the last axis with the statistics over each of its
    ``groups`` equal parts apart."""
    x32 = x.astype(jnp.float32).reshape(*x.shape[:-1], groups, -1)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * scale).reshape(x.shape) * weight).astype(x.dtype)


def mamba_layer(cfg: HybridLMConfig, layer: dict, x, impl: str):
    """``x + mixer(RMSNorm(x))`` for ``x`` [batch, L, hidden]."""
    b, s, _ = x.shape
    dtype = x.dtype
    inner, heads, groups, n = (cfg.mamba_inner, cfg.mamba_heads,
                               cfg.ssm_groups, cfg.ssm_state)
    with jax.named_scope("rdp.ssm.proj"):
        u = rms_norm(x, layer["norm"], cfg.rms_norm_eps)
        z, xbc, dt = jnp.split(jnp.dot(u, layer["w_in"].astype(dtype)),
                               (inner, inner + cfg.conv_dim), axis=-1)
    with jax.named_scope("rdp.ssm.conv"):
        xbc = jax.nn.silu(causal_conv(
            xbc, layer["conv_w"], layer["conv_b"])).astype(dtype)
        xs, b_, c_ = jnp.split(xbc, (inner, inner + groups * n), axis=-1)
    with jax.named_scope("rdp.ssm.scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
        a = -jnp.exp(layer["A_log"])
    y = ssm_scan(xs.reshape(b, s, heads, cfg.mamba_head_dim), dt, a,
                 b_.reshape(b, s, groups, n), c_.reshape(b, s, groups, n),
                 layer["D"], chunk=cfg.ssm_chunk, impl=impl)
    with jax.named_scope("rdp.ssm.gate"):
        y = y.reshape(b, s, inner).astype(jnp.float32) * jax.nn.silu(
            z.astype(jnp.float32))
        y = grouped_rms_norm(y, layer["gate_norm"], groups,
                             cfg.rms_norm_eps).astype(dtype)
    with jax.named_scope("rdp.ssm.proj"):
        return x + jnp.dot(y, layer["w_out"].astype(dtype)), None


def shortconv_layer(cfg: HybridLMConfig, layer: dict, x, impl: str):
    """``x + shortconv(RMSNorm(x))``: LFM2's gated short convolution."""
    dtype = x.dtype
    with jax.named_scope("rdp.shortconv.proj"):
        u = rms_norm(x, layer["norm"], cfg.rms_norm_eps)
        b_, c_, xs = jnp.split(jnp.dot(u, layer["w_in"].astype(dtype)), 3,
                               axis=-1)
    with jax.named_scope("rdp.shortconv.mix"):
        y = shortconv_mix(b_, c_, xs, layer["conv_taps"])
    with jax.named_scope("rdp.shortconv.proj"):
        return x + jnp.dot(y, layer["w_out"].astype(dtype)), None


def shortconv_mix(b_, c_, xs, taps):
    """``C * conv(B * x')``: both gates and the convolution's sum in
    float32, the result in the inputs' type."""
    gated = b_.astype(jnp.float32) * xs.astype(jnp.float32)
    return (c_.astype(jnp.float32) * causal_conv(gated, taps)).astype(
        xs.dtype)


def attention_layer(cfg: HybridLMConfig, layer: dict, x, impl: str,
                    table=None):
    """``x + attention(RMSNorm(x))``: grouped-query, causal; a norm over
    each head of q and k where the configuration has one, positions by
    ``table`` (a ``causal_lm.rope_table``) where it has a rotary base."""
    b, s, _ = x.shape
    heads, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = x.dtype
    with jax.named_scope("rdp.attn.proj"):
        u = rms_norm(x, layer["norm"], cfg.rms_norm_eps)

        def product(name):
            return jnp.dot(u, layer[name].astype(dtype))

        def prepared(name, n, scale=1.0):
            return prepare_heads(
                product("w" + name), n, d,
                norm_weight=layer[name + "_norm"] if cfg.qk_norm else None,
                eps=cfg.rms_norm_eps, table=table, scale=scale, impl=impl)

        q, k = prepared("q", heads, d ** -0.5), prepared("k", kvh)
        v = split_heads(product("wv"), kvh, d)
    a = masked_attention(q, k, v, Causal(), impl=impl)
    with jax.named_scope("rdp.attn.proj"):
        a = a.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
        return x + jnp.dot(a, layer["wo"].astype(dtype)), None


def mlp_layer(cfg: HybridLMConfig, layer: dict, x, impl: str):
    """``x + Wdown(silu(u Wgate) * (u Wup))``, ``u = RMSNorm(x)``."""
    with jax.named_scope("rdp.mlp"):
        u = rms_norm(x, layer["norm"], cfg.rms_norm_eps)
        return x + dense_expert(
            tuple(layer[k] for k in ("w_gate", "w_up", "w_down")), u), None


def experts_layer(cfg: HybridLMConfig, layer: dict, x, impl: str):
    """``x + experts(RMSNorm(x))`` -> (x, rows per held expert)."""
    b, s, hid = x.shape
    u = rms_norm(x, layer["norm"], cfg.rms_norm_eps)
    mixed, sizes = expert_layer(cfg, layer, u.reshape(b * s, hid), impl)
    return x + mixed.reshape(b, s, hid), sizes


LAYERS = {"mamba": mamba_layer, "shortconv": shortconv_layer,
          "attention": attention_layer, "mlp": mlp_layer,
          "experts": experts_layer}


def hidden_states(cfg: HybridLMConfig, params: dict, tokens,
                  impl: str | None = None):
    """The stream after the last layer, ``[batch, L, hidden]``, and the
    rows each held expert took, ``[expert layers, experts_held]``."""
    impl = cfg.kernel_impl if impl is None else impl
    dtype = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("rdp.lm.embed"):
        x = params["embed"].astype(dtype)[tokens]
    kinds = cfg.layer_pattern[:period(cfg.layer_pattern)]
    # one rotary table a forward pass, for the attention layers
    table = rope_table(RotaryConfig(theta=cfg.rope_theta), cfg.head_dim,
                       jnp.arange(tokens.shape[1])) if cfg.rope_theta else None

    # of a layer its input is kept and, of an attention layer, the
    # kernel's output and row sums; the rest is recomputed
    @functools.partial(
        jax.checkpoint, static_argnums=(0,),
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS))
    def layer_fn(kind, x, layer, *extra):
        with jax.named_scope("rdp.lm.layer"):
            return LAYERS[kind](cfg, layer, x, impl, *extra)

    def one_period(x, layers):
        sizes = []
        for j, kind in enumerate(kinds):
            x, taken = layer_fn(kind, x, layers[str(j)],
                                *((table,) if kind == "attention" else ()))
            if taken is not None:
                sizes.append(taken)
        return x, (jnp.stack(sizes) if sizes else jnp.zeros(
            (0, cfg.experts_held), jnp.int32))

    if cfg.num_layers == len(kinds):
        return one_period(x, jax.tree.map(lambda a: a[0], params["layers"]))
    x, sizes = jax.lax.scan(one_period, x, params["layers"])
    return x, sizes.reshape(-1, cfg.experts_held)


def forward(cfg: HybridLMConfig, params: dict, tokens,
            impl: str | None = None):
    """Logits ``[batch, L, vocab]`` (float32) of every position, whole, and
    the rows each held expert took: for sizes at which they fit."""
    x, sizes = hidden_states(cfg, params, tokens, impl)
    return head_logits(cfg, params, x), sizes


@dataclasses.dataclass(frozen=True)
class HybridLM:
    """The model object a training task holds: pure functions of ``cfg``."""

    cfg: HybridLMConfig

    def init(self, rng) -> dict:
        return init_params(self.cfg, rng)

    def apply(self, params, tokens, **kw):
        return forward(self.cfg, params, tokens, **kw)

    def loss(self, params, tokens, with_hits: bool = False):
        """(loss, next-token accuracy, rows per expert layer and held
        expert)."""
        x, sizes = hidden_states(self.cfg, params, tokens)
        loss, hits = next_token_loss(self.cfg, params, x, tokens, with_hits)
        return loss, hits, sizes


def build_hybrid_lm(cfg: HybridLMConfig) -> HybridLM:
    return HybridLM(cfg)
