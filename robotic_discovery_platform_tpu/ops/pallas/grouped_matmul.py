"""The grouped product of an expert layer: rows sorted by expert, each group
multiplied by its own expert's matrix.

``lhs`` is ``[rows, k]`` with the rows of group 0 first, then group 1's, and
so on; ``group_sizes`` (``[groups]`` int32, any counts a step's routing
gives, zeros among them) says where each group ends; ``rhs`` is
``[groups, k, n]``. Rows after the last group belong to no expert held here:
their result is unspecified (the kernel never visits them) and callers mask
them. Shapes are static whatever the counts are.

On a TPU the kernel is megablox's ``gmm`` that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox``: tiles of rows visited group
by group through scalar-prefetched metadata, with a custom VJP whose
backward pass is a ``gmm`` against the transposed matrices and a ``tgmm`` for
the matrices' gradients); elsewhere ``jax.lax.ragged_dot``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: rows x contraction x columns of one tile on the chip
TILE_ROWS, TILE_K, TILE_N = 512, 1024, 1024


def grouped_matmul(lhs, rhs, group_sizes, *, impl: str = "auto",
                   out_dtype=None):
    """``out[r] = lhs[r] @ rhs[group of r]`` for the rows inside a group."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    out_dtype = lhs.dtype if out_dtype is None else out_dtype
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "xla":
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=jnp.float32).astype(out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    rows, k = lhs.shape
    n = rhs.shape[2]
    tile_rows = min(TILE_ROWS, rows)
    if rows % tile_rows:
        raise ValueError(f"{rows} rows are no multiple of {tile_rows}")
    tile_k, tile_n = min(TILE_K, k), min(TILE_N, n)
    if tile_k * tile_n == TILE_K * TILE_N:
        # both full: the backward pass's transposed product (the same
        # tiles) then holds a float32 tile of the cotangent and its
        # float32 sum beside the operands, 16.1 MB of the 16 a kernel has
        tile_n //= 2
    return megablox.gmm(
        lhs, rhs, group_sizes, out_dtype, (tile_rows, tile_k, tile_n),
        None, None, False, impl == "interpret")
