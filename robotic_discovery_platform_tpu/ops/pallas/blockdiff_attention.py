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

The kernel, its padding, batch and dense form are
``ops/pallas/masked_attention``'s, which this module hands ``M`` as one of
its rules (:class:`BlockDiff`; the causal language model's two rules live
there). What is here: ``M`` itself, in a form that costs the kernel seven
vector operations an element.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from robotic_discovery_platform_tpu.ops.pallas.masked_attention import (
    ATTN_RESIDUALS, masked_attention, splash_mask)

__all__ = ["ATTN_RESIDUALS", "TILE", "BlockDiff", "blockdiff_attention",
           "live", "live_packed", "live_pairs", "packed_rows"]

#: tile edges on the chip: splash's q and kv blocks, forward and backward
TILE = 1024


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


def live_pairs(seq_len: int, block: int) -> int:
    """Pairs that ``M`` leaves live in one sequence and head."""
    return seq_len * seq_len + seq_len * block


@dataclasses.dataclass(frozen=True)
class BlockDiff:
    """``M`` as a rule of ``masked_attention`` over ``2 * seq_len``
    positions. ``every_pair`` leaves every pair live: the same kernel with
    no tile to skip, for the one measurement of what skipping saves
    (``PERF.md``)."""

    seq_len: int
    block: int
    tile: int = TILE
    every_pair: bool = False
    kind = "blockdiff"

    def definition(self, q_ids, kv_ids):
        if self.every_pair:
            return np.ones(np.broadcast_shapes(q_ids.shape, kv_ids.shape),
                           bool)
        return live(q_ids, kv_ids, self.seq_len, self.block)

    def rows(self, padded: int) -> np.ndarray:
        return packed_rows(padded, self.seq_len, self.block)

    def live(self, rows, kv_ids):
        if self.every_pair:
            return kv_ids >= 0
        return live_packed(rows, kv_ids, self.seq_len, self.block)

    def live_pairs(self, positions: int) -> int:
        return positions * positions if self.every_pair \
            else live_pairs(self.seq_len, self.block)


def _rule(seq_len: int, block: int, mask: str, tile: int = TILE):
    if mask not in ("blockdiff", "dense"):
        raise KeyError(mask)
    return BlockDiff(seq_len, block, tile, every_pair=mask == "dense")


def _splash_mask(padded: int, seq_len: int, block: int, mask: str):
    """The rule as splash's computable mask (``masked_attention.splash_mask``
    says which of splash's internals that leans on)."""
    return splash_mask(padded, _rule(seq_len, block, mask))


def blockdiff_attention(q, k, v, *, seq_len: int, block: int,
                        impl: str = "auto", mask: str = "blockdiff",
                        tile: int = TILE):
    """softmax(q k^T + M) v over ``2 * seq_len`` positions.

    ``q`` is ``[batch, heads, 2 * seq_len, head_dim]`` and already scaled by
    ``1 / sqrt(head_dim)``; ``k`` and ``v`` are ``[batch, kv_heads, ...]``,
    each shared by ``heads // kv_heads`` query heads. ``impl`` as
    ``masked_attention``'s. ``mask``: ``"blockdiff"``, or ``"dense"`` for
    the measurement alone.
    """
    s = q.shape[2]
    if s != 2 * seq_len or seq_len % block:
        raise ValueError(f"{s} positions are not two copies of {seq_len} "
                         f"tokens in blocks of {block}")
    return masked_attention(q, k, v, _rule(seq_len, block, mask, tile),
                            impl=impl)
