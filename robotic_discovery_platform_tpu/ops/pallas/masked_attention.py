"""Attention under a structured mask: softmax(q k^T + M) v where ``M`` is a
function of the two indices, computed on the tile's ``iota``s and never read
from memory, and the tiles it empties are skipped in the forward pass and in
both backward passes.

The kernel is the splash-attention kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``: blockwise, scores
never materialised, grouped query heads, a custom VJP with a dq and a dkv
kernel, block-sparse grids built from the mask), given ``M`` as one of its
computable masks. What is added here: the rules, padding to the tile, the
batch, a dense ``jax.numpy`` form for hosts without a TPU, and the count of
the tiles a rule visits (``rdp_attn_mask_tiles_total``).

**A rule** is a frozen (hashable) object with

``kind``
    its label: the scope ``rdp.attn.<kind>`` and the counter's ``kind``.
``tile``
    the tile edge on the chip (splash's q and kv blocks, forward and
    backward), settled there for the rule's own shape: a larger tile feeds
    the matrix unit better, a smaller one leaves fewer dead pairs inside
    the visited tiles.
``definition(q_ids, kv_ids)``
    ``M`` as its definition reads, on numpy index arrays: the dense form's
    mask and what the tests hold the kernel's form to.
``rows(padded)`` and ``live(rows, kv_ids)``
    the form the kernel evaluates, element by element on the vector unit on
    every tile it visits, where it costs as much as the softmax beside it:
    ``rows`` is worked out once on the host and handed to the kernel in the
    row index's place (int32, one value a padded query row), and ``live``
    makes ``M`` from it and the key index, on numpy arrays and inside the
    kernel alike. A padding row must see some key and no row a padding key.
``live_pairs(positions)``
    pairs ``M`` leaves live in one sequence and head.

The rules here are the causal language model's (``models/causal_lm``):
:class:`Causal` and :class:`Window`. The block-diffusion rule lives with its
packed row encoding in ``ops/pallas/blockdiff_attention``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from robotic_discovery_platform_tpu.observability import instruments as obs

#: ``jax.ad_checkpoint`` name of the kernel's output and row sums, for a
#: rematerialisation policy that keeps them (``save_only_these_names``):
#: the backward pass then runs no second forward kernel
ATTN_RESIDUALS = "blockdiff_attn_residuals"


@dataclasses.dataclass(frozen=True)
class Causal:
    """Key ``j`` is live for query ``i`` iff ``j <= i``."""

    tile: int = 1024
    kind = "causal"

    def definition(self, q_ids, kv_ids):
        return kv_ids <= q_ids

    def rows(self, padded: int) -> np.ndarray:
        return np.arange(padded, dtype=np.int32)

    def live(self, rows, kv_ids):
        return kv_ids <= rows

    def live_pairs(self, positions: int) -> int:
        return positions * (positions + 1) // 2


@dataclasses.dataclass(frozen=True)
class Window:
    """Causal inside a window: key ``j`` is live for query ``i`` iff
    ``j <= i`` and ``i - j < window`` (a query sees itself and the
    ``window - 1`` keys before it)."""

    window: int
    tile: int = 512
    kind = "window"

    def definition(self, q_ids, kv_ids):
        return (kv_ids <= q_ids) & (q_ids - kv_ids < self.window)

    def rows(self, padded: int) -> np.ndarray:
        return np.arange(padded, dtype=np.int32)

    def live(self, rows, kv_ids):
        # one subtraction and one unsigned compare: a key after the query
        # wraps to a distance of 2^32 less its lead
        return (rows - kv_ids).astype("uint32") < self.window

    def live_pairs(self, positions: int) -> int:
        w = min(self.window, positions)
        return w * positions - w * (w - 1) // 2


def dense_attention(q, k, v, mask):
    """[b, h, s, d] x [b, g, s, d]: scores materialised, float32 softmax."""
    b, h, s, d = q.shape
    g = k.shape[1]
    qg = q.reshape(b, g, h // g, s, d)
    scores = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, s, d).astype(q.dtype)


def splash_mask(padded: int, rule):
    """``rule`` as one of splash's computable masks over ``padded``
    positions, with the rule's ``rows`` in the row index's place.

    This leans on two internals of the splash kernels of JAX 0.9.0 (the
    version this is written against; ``tests/test_blockdiff_lm.py`` and
    ``tests/test_causal_lm.py`` pin both): ``_ComputableMask`` builds the
    block map by calling ``mask_function`` on ``q_sequence`` values, and the
    kernel hands ``q_sequence`` on to ``mask_function`` unchanged. After an
    upgrade of JAX those tests say whether tiles are still skipped and rows
    still carry what the rule packed."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)

    class _Mask(sm._ComputableMask):
        def __init__(self):
            super().__init__(shape=(padded, padded), mask_function=rule.live)
            self.q_sequence = rule.rows(padded)

        def __eq__(self, other):
            return isinstance(other, _Mask)

        def __hash__(self):
            return hash((_Mask, padded, rule))

    return _Mask()


@functools.lru_cache(maxsize=16)
def _splash_kernel(heads: int, padded: int, rule, tile: int,
                   interpret: bool):
    """(kernel, tiles the forward grid visits for one head, its tiles)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    sizes = sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=tile,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=tile,
        block_q_dq=tile, block_kv_dq=tile)
    with jax.ensure_compile_time_eval():
        kernel = sk.make_splash_mha(
            sm.MultiHeadMask([splash_mask(padded, rule)] * heads),
            block_sizes=sizes, head_shards=1, q_seq_shards=1,
            interpret=interpret, residual_checkpoint_name=ATTN_RESIDUALS)
        visited = int(
            (np.asarray(kernel.fwd_mask_info.block_mask[0]) > 0).sum())
    return kernel, visited, (padded // tile) ** 2


def masked_attention(q, k, v, rule, *, impl: str = "auto"):
    """softmax(q k^T + M) v under ``rule``'s mask.

    ``q`` is ``[batch, heads, positions, head_dim]`` and already scaled by
    ``1 / sqrt(head_dim)``; ``k`` and ``v`` are ``[batch, kv_heads, ...]``,
    each shared by ``heads // kv_heads`` query heads. ``impl``: ``"pallas"``
    (the kernel), ``"interpret"`` (the kernel in the Pallas interpreter, for
    CPU tests), ``"xla"`` (dense scores: small sizes only), ``"auto"`` (the
    kernel on a TPU, dense elsewhere). Where the kernel's grid is built, one
    sample of ``rdp_attn_mask_tiles_total{kind, state}`` counts the tiles of
    one head's forward grid that it visits and skips: at trace time, so a
    change of tile or rule shows without a trace."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    h, s = q.shape[1], q.shape[2]
    with jax.named_scope(f"rdp.attn.{rule.kind}"):
        if impl == "xla":
            ids = np.arange(s)
            mask = rule.definition(ids[:, None], ids[None, :])
            return checkpoint_name(
                dense_attention(q, k, v, jnp.asarray(mask)), ATTN_RESIDUALS)
        tile = min(rule.tile, -(-s // 128) * 128)
        padded = -(-s // tile) * tile
        if padded != s:
            pad = ((0, 0), (0, 0), (0, padded - s), (0, 0))
            q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        kernel, visited, tiles = _splash_kernel(h, padded, rule, tile,
                                                impl == "interpret")
        obs.ATTN_MASK_TILES.labels(kind=rule.kind, state="visited").inc(
            visited)
        obs.ATTN_MASK_TILES.labels(kind=rule.kind, state="skipped").inc(
            tiles - visited)
        out = jax.vmap(kernel)(q, k, v)
        return out[:, :, :s] if padded != s else out
