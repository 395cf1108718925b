"""Operations and bytes of a block-diffusion sparse-expert decoder, from its
shapes: what the algorithm needs, whatever implements it. Multiply-adds
count as 2; a training step is three passes of every matrix product (the
forward pass and the two products of its backward pass); recomputed passes,
norms, activations, softmax, loss, the embedding's gather and the optimiser
are not counted. ``model`` is the configuration file's ``model`` group.

The model sees ``2 L`` positions a sequence (the noisy copy and the clean
one); the head runs on the noisy half; of the ``4 L^2`` pairs of positions
the block-diffusion mask leaves ``L^2 + L B`` live, and attention is
counted on those alone; the experts' products are counted by the rows that
the program's routing sent to the experts held here.
"""

from __future__ import annotations


def live_pairs(seq_len: int, block: int) -> int:
    """Pairs of positions the mask leaves live, one sequence and head: a
    noisy query sees its own block's noisy keys and the clean keys of
    earlier blocks, a clean query the clean keys up to its own block."""
    n = seq_len // block
    noisy = n * block * block + block * block * n * (n - 1) // 2
    clean = block * block * n * (n + 1) // 2
    return noisy + clean


def projection_flops(model: dict) -> int:
    """q, k, v, o and the router of one layer, one sequence, forward."""
    positions, h = 2 * model["seq_len"], model["hidden_size"]
    q = model["num_heads"] * model["head_dim"]
    kv = model["num_kv_heads"] * model["head_dim"]
    return 2 * positions * h * (2 * q + 2 * kv + model["num_experts"])


def attention_flops(model: dict) -> int:
    """q k^T and p v over the live pairs of one layer, one sequence,
    forward."""
    return (4 * model["head_dim"] * model["num_heads"]
            * live_pairs(model["seq_len"], model["block_length"]))


def attention_bytes(model: dict, train: bool) -> int:
    """The least an attention pass moves for one layer and sequence at two
    bytes an element: forward reads q, k, v and writes o; backward reads
    those and do and writes dq, dk, dv."""
    positions, d = 2 * model["seq_len"], model["head_dim"]
    q = positions * model["num_heads"] * d * 2
    kv = positions * model["num_kv_heads"] * d * 2
    forward = 2 * q + 2 * kv
    return forward + (4 * q + 4 * kv if train else 0)


def expert_flops(model: dict, rows: float) -> float:
    """gate, up and down products of ``rows`` routed rows, forward."""
    return rows * 6 * model["hidden_size"] * model["expert_width"]


def expert_bytes(model: dict, layer_passes: float, rows: float) -> float:
    """The held experts' three matrices read once a layer and pass at two
    bytes, and each row's input, two intermediates and output."""
    h, f = model["hidden_size"], model["expert_width"]
    return (layer_passes * model["experts_held"] * 3 * h * f * 2
            + rows * (2 * h + 3 * f) * 2)


def head_flops(model: dict) -> int:
    """The output head on the noisy half of one sequence, forward."""
    return 2 * model["seq_len"] * model["hidden_size"] * model["vocab_size"]


def dense_forward_flops(model: dict) -> int:
    """One sequence's forward pass without its experts' products."""
    return (model["num_layers"] * (projection_flops(model)
                                   + attention_flops(model))
            + head_flops(model))


def window_flops(model: dict, batch: int, steps: int, eval_batches: int,
                 train_rows: float) -> float:
    """Everything a window asks of the model: ``steps`` optimiser steps and
    ``eval_batches`` forward passes of ``batch`` sequences; ``train_rows``
    are the rows the held experts took in the optimiser steps, over all
    layers (the program's counter). Validation's rows are not counted by
    the program; they are taken at the training steps' mean."""
    sequences = batch * (3 * steps + eval_batches)
    rows = train_rows * (3 + (eval_batches / steps if steps else 0))
    return sequences * dense_forward_flops(model) + expert_flops(model, rows)
