"""Operations and bytes of a causal sparse-expert decoder whose layers differ
in kind, from its shapes: what the algorithm needs, whatever implements it.
Multiply-adds count as 2; a training step is three passes of every matrix
product (the forward pass and the two products of its backward pass);
recomputed passes, norms, rotary embedding, activations, softmax, loss, the
embedding's gather and the optimiser are not counted. ``model`` is the
configuration file's ``model`` group.

The model sees ``L`` positions a sequence and the head runs on every one.
Attention is counted on the pairs its layer's mask leaves live: ``L (L + 1) /
2`` in a full layer, ``w L - w (w - 1) / 2`` in a sliding layer of window
``w`` (a query sees itself and the ``w - 1`` keys before it). The experts'
products are counted by the rows that the program's routing sent to the
experts held here.
"""

from __future__ import annotations

from perfbench.lib.lm_flops import expert_flops  # the same expert layer

SLIDING, FULL = "sliding_attention", "full_attention"


def live_pairs(kind: str, seq_len: int, window: int) -> int:
    """Pairs of positions a layer kind's mask leaves live, one sequence and
    head."""
    if kind == FULL:
        return seq_len * (seq_len + 1) // 2
    w = min(window, seq_len)
    return w * seq_len - w * (w - 1) // 2


def layers_of(model: dict, kind: str) -> int:
    return sum(k == kind for k in model["layer_types"])


def projection_flops(model: dict) -> int:
    """q, k, v, o and the router of one layer, one sequence, forward."""
    q = model["num_heads"] * model["head_dim"]
    kv = model["num_kv_heads"] * model["head_dim"]
    return 2 * model["seq_len"] * model["hidden_size"] * (
        2 * q + 2 * kv + model["num_experts"])


def attention_flops(model: dict, kind: str) -> int:
    """q k^T and p v over the live pairs of one layer of ``kind``, one
    sequence, forward."""
    return 4 * model["head_dim"] * model["num_heads"] * live_pairs(
        kind, model["seq_len"], model["sliding_window"])


def attention_bytes(model: dict, train: bool) -> int:
    """The least an attention pass moves for one layer and sequence at two
    bytes an element, whatever its mask: forward reads q, k, v and writes o;
    backward reads those and do and writes dq, dk, dv."""
    q = model["seq_len"] * model["num_heads"] * model["head_dim"] * 2
    kv = model["seq_len"] * model["num_kv_heads"] * model["head_dim"] * 2
    forward = 2 * q + 2 * kv
    return forward + (4 * q + 4 * kv if train else 0)


def head_flops(model: dict) -> int:
    """The output head on every position of one sequence, forward."""
    return 2 * model["seq_len"] * model["hidden_size"] * model["vocab_size"]


def dense_forward_flops(model: dict) -> int:
    """One sequence's forward pass without its experts' products."""
    return (model["num_layers"] * projection_flops(model)
            + sum(layers_of(model, kind) * attention_flops(model, kind)
                  for kind in (SLIDING, FULL))
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


def attention_least_seconds(model: dict, kind: str, batch: int, steps: int,
                            eval_batches: int, peaks: dict) -> float:
    """The least time a chip of ``peaks`` could take for the window's
    attention in the layers of ``kind``: the larger of operations over peak
    FLOP/s and bytes over peak bytes/s."""
    layers_by_sequences = layers_of(model, kind) * batch
    flops = layers_by_sequences * (3 * steps + eval_batches) \
        * attention_flops(model, kind)
    moved = layers_by_sequences * (
        steps * attention_bytes(model, True)
        + eval_batches * attention_bytes(model, False))
    return max(flops / peaks["flops_per_s"], moved / peaks["bytes_per_s"])
