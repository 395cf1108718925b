"""Attention under the block-diffusion training mask ``M``.

A block-diffusion language model is trained on ``2L`` positions: the noisy
copy of a sequence (positions ``0..L-1``) followed by the clean copy
(``L..2L-1``), both cut into blocks of ``block`` tokens. With ``k(i)`` the
block of position ``i`` inside its copy:

- a noisy query of block ``k`` sees the noisy keys of block ``k`` (both
  directions) and the clean keys of blocks ``< k``;
- a clean query of block ``k`` sees the clean keys of blocks ``<= k``;
- no clean query sees a noisy key.

Of the ``4 L^2`` pairs ``L^2 + L * block`` are live. ``M`` is a function of
the two indices (:func:`live`) and is never read from memory: the kernel
evaluates it on the tile's ``iota``s, and tiles that it empties are skipped
in the forward pass and in both backward passes.

The kernel is the splash-attention kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``: blockwise, scores
never materialised, grouped query heads, a custom VJP with a dq and a dkv
kernel, block-sparse grids built from the mask), given ``M`` as one of its
computable masks. What is added here: ``M`` itself (in a form that costs
the kernel seven vector operations an element), padding to the tile, the
batch, and a dense ``jax.numpy`` form for hosts without a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

#: tile edges on the chip: splash's q and kv blocks, forward and backward
TILE = 1024
#: ``jax.ad_checkpoint`` name of the kernel's output and row sums, for a
#: rematerialisation policy that keeps them (``save_only_these_names``):
#: the backward pass then runs no second forward kernel
ATTN_RESIDUALS = "blockdiff_attn_residuals"


def live(q_ids, kv_ids, seq_len: int, block: int):
    """``M`` for positions ``q_ids`` x ``kv_ids`` of the ``2 * seq_len``
    (noisy copy first), as the definition reads; on numpy arrays."""
    n = seq_len
    q_clean, kv_clean = q_ids >= n, kv_ids >= n
    q_blk = (q_ids - q_clean * n) // block
    kv_blk = (kv_ids - kv_clean * n) // block
    return (((~q_clean) & (~kv_clean) & (q_blk == kv_blk))
            | ((~q_clean) & kv_clean & (kv_blk < q_blk))
            | (q_clean & kv_clean & (kv_blk <= q_blk)))


# The kernel evaluates its mask on every tile it visits, element by element
# on the vector unit, where it costs as much as the softmax beside it: the
# definition above (two divisions, a dozen compares and selects) made a
# visited tile 2.7 times as dear as an unmasked one on a v5e. So the row's
# part of ``M`` is worked out once on the host and handed to the kernel in
# the place of the row index. For a query row the live keys are two
# intervals, [lo, lo + block) (its own block: of the noisy copy for a noisy
# row, of the clean copy for a clean one) and [seq_len, seq_len + width)
# (the clean blocks before it); the row carries ``lo | width << 16`` and
# the kernel is left with two subtractions, two unsigned compares and an or.
_PACK = 16


def packed_rows(padded: int, seq_len: int, block: int) -> np.ndarray:
    """``lo | width << 16`` for each of ``padded`` query rows. A padding
    row (``>= 2 * seq_len``) is given the first block's keys, so that no
    row is empty; its output is cut off and its gradient is zero. No row
    reaches a padding key."""
    if padded >= 1 << _PACK:
        raise ValueError(f"{padded} positions do not pack into 16 bits")
    i = np.arange(padded)
    noisy, pad = i < seq_len, i >= 2 * seq_len
    start = np.where(noisy, i - i % block, i - (i - seq_len) % block)
    lo = np.where(pad, 0, start)
    width = np.where(pad, 0, np.where(noisy, start, start - seq_len))
    return (lo | (width << _PACK)).astype(np.int32)


def live_packed(rows, kv_ids, seq_len: int, block: int):
    """``M`` from :func:`packed_rows` values and key positions; on numpy
    arrays and inside the kernel alike."""
    lo, width = rows & ((1 << _PACK) - 1), rows >> _PACK
    own = (kv_ids - lo).astype("uint32") < block
    before = (kv_ids - seq_len).astype("uint32") < width.astype("uint32")
    return own | before


def dense(rows, kv_ids, seq_len: int, block: int):
    """Every pair live: the same kernel with no tile to skip, for the one
    measurement of what skipping saves (``PERF.md``)."""
    del rows, seq_len, block
    return kv_ids >= 0


MASKS = {"blockdiff": live_packed, "dense": dense}


def live_pairs(seq_len: int, block: int) -> int:
    """Pairs that ``M`` leaves live in one sequence and head."""
    return seq_len * seq_len + seq_len * block


def _dense_attention(q, k, v, mask):
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


def _splash_mask(padded: int, seq_len: int, block: int, mask: str):
    """``MASKS[mask]`` as one of splash's computable masks over ``padded``
    positions, with the row's part of the mask in the row index's place.

    This leans on two internals of the splash kernels of JAX 0.9.0 (the
    version this is written against; ``tests/test_blockdiff_lm.py`` pins
    both): ``_ComputableMask`` builds the block map by calling
    ``mask_function`` on ``q_sequence`` values, and the kernel hands
    ``q_sequence`` on to ``mask_function`` unchanged. After an upgrade of
    JAX those tests say whether tiles are still skipped and rows still
    carry their intervals."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)

    rule = MASKS[mask]

    class _Mask(sm._ComputableMask):
        def __init__(self):
            super().__init__(
                shape=(padded, padded),
                mask_function=lambda q, kv: rule(q, kv, seq_len, block))
            # the row's part of the mask in the row index's place
            self.q_sequence = packed_rows(padded, seq_len, block)

        def __eq__(self, other):
            return isinstance(other, _Mask)

        def __hash__(self):
            return hash((_Mask, padded, seq_len, block, mask))

    return _Mask()


@functools.lru_cache(maxsize=8)
def _splash_kernel(heads: int, padded: int, seq_len: int, block: int,
                   tile: int, mask: str, interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    sizes = sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=tile,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=tile,
        block_q_dq=tile, block_kv_dq=tile)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(
            sm.MultiHeadMask([_splash_mask(padded, seq_len, block, mask)]
                             * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret,
            residual_checkpoint_name=ATTN_RESIDUALS)


def blockdiff_attention(q, k, v, *, seq_len: int, block: int,
                        impl: str = "auto", mask: str = "blockdiff",
                        tile: int = TILE):
    """softmax(q k^T + M) v over ``2 * seq_len`` positions.

    ``q`` is ``[batch, heads, 2 * seq_len, head_dim]`` and already scaled by
    ``1 / sqrt(head_dim)``; ``k`` and ``v`` are ``[batch, kv_heads, ...]``,
    each shared by ``heads // kv_heads`` query heads. ``impl``: ``"pallas"``
    (the kernel), ``"interpret"`` (the kernel in the Pallas interpreter, for
    CPU tests), ``"xla"`` (dense scores: small sizes only), ``"auto"`` (the
    kernel on a TPU, dense elsewhere). ``mask`` names a rule of
    :data:`MASKS`; ``"dense"`` is for the measurement alone.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    b, h, s, d = q.shape
    if s != 2 * seq_len or seq_len % block:
        raise ValueError(f"{s} positions are not two copies of {seq_len} "
                         f"tokens in blocks of {block}")
    with jax.named_scope("rdp.attn.blockdiff"):
        if impl == "xla":
            ids = np.arange(s)
            m = (live(ids[:, None], ids[None, :], seq_len, block)
                 if mask == "blockdiff" else np.ones((s, s), bool))
            return checkpoint_name(
                _dense_attention(q, k, v, jnp.asarray(m)), ATTN_RESIDUALS)
        tile = min(tile, -(-s // 128) * 128)
        padded = -(-s // tile) * tile
        if padded != s:
            pad = ((0, 0), (0, 0), (0, padded - s), (0, 0))
            q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        kernel = _splash_kernel(h, padded, seq_len, block, tile, mask,
                                impl == "interpret")
        out = jax.vmap(kernel)(q, k, v)
        return out[:, :, :s] if padded != s else out
