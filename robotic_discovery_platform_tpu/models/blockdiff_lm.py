"""A sparse-expert decoder trained by block diffusion, as one chip's share of
an expert-parallel job: one of the platform's three language-model families
(``models/causal_lm`` is trained by next-token prediction and mixes two
kinds of attention layer; ``models/hybrid_lm`` mixes layers of one branch
each: state-space mixers, gated short convolutions, attention, dense MLPs
and experts; the three share the expert layer, RMSNorm and the seeded start,
``models/moe``).

The layer equations (``BlockDiffLMConfig``; RMSNorm ``eps``, no biases)::

    h = RMSNorm(x);  q = h Wq, k = h Wk, v = h Wv      (heads x head_dim,
    kv_heads x head_dim; each key/value head shared by heads / kv_heads)
    q, k <- per-head RMSNorm over head_dim, then rotary embedding (theta,
            over the full head, rotate-half form)
    x += softmax(q k^T / sqrt(head_dim) + M) v Wo
    h = RMSNorm(x);  p = softmax_f32(h Wr) over all num_experts
    the experts_per_token largest, renormalised to sum 1 (norm_topk_prob)
    x += sum_e p_e Wdown_e( silu(h Wgate_e) * (h Wup_e) )

(the expert layer in its default form; ``models/moe`` has the others) then
a final RMSNorm and an untied head. Parameters are float32; matrix
products and activations run in ``compute_dtype``; router logits, softmax,
RMSNorm statistics, attention's softmax and the loss in float32.

**The chip's share.** ``experts_held`` experts of every layer live here and
``vocab_size`` rows of the embedding and the head; the expert layer
(``moe.expert_layer``) routes over all ``num_experts``, computes its own
experts' part and passes that partial sum on.

**Training by block diffusion.** The model sees the noisy copy of a
sequence followed by the clean copy (``2 L`` positions, both copies at
rotary positions ``0..L-1``) under the mask of
``ops/pallas/blockdiff_attention``; the loss is the cross-entropy at the
masked positions of the noisy copy against the clean token (no shift),
weighted ``1 / t`` of the position's block, summed and divided by the number
of tokens. Noise comes from ``training/data.block_diffusion_noise``.

Layers are identical, stacked and run under ``lax.scan`` with one
``jax.checkpoint`` a layer: the layer's input and attention's output (with
its row sums) are saved, the rest is recomputed.

**Where q and k are made ready.** A layer hands each projection's product
to ``ops/pallas/qk_prep.prepare_heads`` with its norm weight, the forward
pass's one rotary table (:func:`rotary_table`, built before the layer scan)
and the scale: the head split, norm, rotation and scale are one Pallas pass
a direction on a TPU (heads of 128) and the dense chain elsewhere;
:func:`rotary` stays as the definition of what a layer does with its
positions.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from robotic_discovery_platform_tpu.models.causal_lm import rope_table
from robotic_discovery_platform_tpu.models.moe import (  # noqa: F401
    expert_layer, expert_shapes, rms_norm, route, routed_experts,
    seeded_params)
from robotic_discovery_platform_tpu.ops.pallas.blockdiff_attention import (
    ATTN_RESIDUALS, blockdiff_attention)
from robotic_discovery_platform_tpu.ops.pallas.qk_prep import (
    prepare_heads, split_heads)
from robotic_discovery_platform_tpu.utils.config import (
    BlockDiffLMConfig, RotaryConfig)


def param_shapes(cfg: BlockDiffLMConfig) -> dict:
    """name -> shape; the layers' leaves carry the depth in front."""
    n, h, d = cfg.num_layers, cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_heads * d, cfg.num_kv_heads * d
    return {
        "embed": (cfg.vocab_size, h),
        "layers/attn_norm": (n, h), "layers/wq": (n, h, q),
        "layers/wk": (n, h, kv), "layers/wv": (n, h, kv),
        "layers/q_norm": (n, d), "layers/k_norm": (n, d),
        "layers/wo": (n, q, h), "layers/moe_norm": (n, h),
        **{f"layers/{name}": (n, *shape)
           for name, shape in expert_shapes(cfg).items()},
        "final_norm": (h,), "head": (h, cfg.vocab_size),
    }


def init_params(cfg: BlockDiffLMConfig, rng) -> dict:
    """The seeded start (``moe.seeded_params``) of :func:`param_shapes`.
    The training task says which ``rng`` a job's seed gives
    (``tasks.BlockDiffLMTask.init_variables``)."""
    return seeded_params(param_shapes(cfg), rng, cfg.init_std,
                         cfg.embed_init_std)


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


def rotary_table(cfg: BlockDiffLMConfig, length: int):
    """The (cos, sin) of a forward pass (``causal_lm.rope_table``, no
    scaling): both copies of a sequence of ``length`` at positions
    ``0..length-1``. Built once and handed to every layer; :func:`rotary`
    says what a layer does with it."""
    return rope_table(RotaryConfig(theta=cfg.rope_theta), cfg.head_dim,
                      jnp.concatenate([jnp.arange(length)] * 2))


def decoder_layer(cfg: BlockDiffLMConfig, layer: dict, x, table,
                  impl: str):
    """One layer on ``x`` [batch, 2L, hidden] -> (x, rows per held expert).
    ``table`` is the forward pass's :func:`rotary_table`."""
    b, s, hid = x.shape
    heads, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = x.dtype
    with jax.named_scope("rdp.attn.proj"):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)

        def product(name):
            return jnp.dot(h, layer[name].astype(dtype))

        def prepared(name, n, scale=1.0):
            return prepare_heads(
                product("w" + name), n, d, norm_weight=layer[name + "_norm"],
                eps=cfg.rms_norm_eps, table=table, scale=scale, impl=impl)

        q, k = prepared("q", heads, d ** -0.5), prepared("k", kvh)
        v = split_heads(product("wv"), kvh, d)
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
    table = rotary_table(cfg, length)

    # of a layer, its input and attention's output and row sums are kept;
    # the rest is recomputed in the backward pass
    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS))
    def layer_fn(x, layer):
        with jax.named_scope("rdp.lm.layer"):
            return decoder_layer(cfg, layer, x, table, impl)

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
