"""Operations and bytes of a hybrid decoder whose layers are one branch
each (a Mamba-2 mixer, causal attention, or a sparse-expert layer with a
shared expert), from its shapes: what the algorithm needs, whatever
implements it. Multiply-adds count as 2; a training step is three passes of
every matrix product (the forward pass and the two products of its backward
pass); recomputed passes, norms, the convolution, activations, softmax,
gates, loss, the embedding's gather and the optimiser are not counted.
``model`` is the configuration file's ``model`` group.

The state-space scan is counted in its chunked form (chunks of ``Q =
ssm_chunk`` positions, ``H`` heads of ``P`` channels, ``G`` groups, state
``N``), the form with the fewest operations that runs on a matrix unit: a
position by position recurrence costs ``6 L H P N``, the chunked one
``2 L (Q G N + Q H P + 2 H P N)``. Its bytes are its inputs and outputs
alone at two bytes an element (``dt`` at four): a forward pass reads ``xs``,
``B``, ``C``, ``dt`` and writes ``y``; a backward pass reads those and
``dy`` and writes their gradients. A recomputed forward pass is not counted,
and nothing an implementation keeps between its parts, so that no
implementation, fused or not, can read over 100% of the roofline.

Attention is counted on the pairs the causal mask leaves live, ``L (L + 1)
/ 2``; the routed experts' products by the rows that the program's routing
sent to the experts held here; the shared expert on every position.
"""

from __future__ import annotations

MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
LETTERS = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}


def layers_of(model: dict, kind: str) -> int:
    return sum(LETTERS.get(k, k) == kind for k in model["layer_pattern"])


def mamba_inner(model: dict) -> int:
    return model["mamba_heads"] * model["mamba_head_dim"]


def mamba_projection_flops(model: dict) -> int:
    """The mixer's two projections, one layer and sequence, forward:
    ``hidden -> 2 inner + 2 G N + heads`` and ``inner -> hidden``."""
    inner = mamba_inner(model)
    wide = (2 * inner + 2 * model["ssm_groups"] * model["ssm_state"]
            + model["mamba_heads"])
    return 2 * model["seq_len"] * model["hidden_size"] * (wide + inner)


def scan_flops(model: dict) -> int:
    """The chunked scan's products of one layer and sequence, forward: ``C
    B^T`` inside a chunk (a group), its product with ``xs`` (a head), each
    chunk's state and the carried state's part of the output."""
    q, n = model["ssm_chunk"], model["ssm_state"]
    hp = mamba_inner(model)
    return 2 * model["seq_len"] * (
        q * model["ssm_groups"] * n + q * hp + 2 * hp * n)


def scan_bytes(model: dict, train: bool) -> int:
    """The least a scan moves for one layer and sequence: forward reads
    ``xs``, ``B``, ``C`` (two bytes) and ``dt`` (four) and writes ``y``;
    backward reads those and ``dy`` and writes the four gradients."""
    length, heads = model["seq_len"], model["mamba_heads"]
    xs = length * mamba_inner(model) * 2
    bc = 2 * length * model["ssm_groups"] * model["ssm_state"] * 2
    dt = length * heads * 4
    forward = 2 * xs + bc + dt
    return forward + (3 * xs + 2 * bc + 2 * dt if train else 0)


def attention_projection_flops(model: dict) -> int:
    """q, k, v and o of one attention layer, one sequence, forward."""
    q = model["num_heads"] * model["head_dim"]
    kv = model["num_kv_heads"] * model["head_dim"]
    return 2 * model["seq_len"] * model["hidden_size"] * (2 * q + 2 * kv)


def attention_flops(model: dict) -> int:
    """q k^T and p v over the causal mask's live pairs, one layer and
    sequence, forward."""
    length = model["seq_len"]
    return (4 * model["head_dim"] * model["num_heads"]
            * (length * (length + 1) // 2))


def dense_expert_flops(model: dict) -> int:
    """The router and the shared expert's two products of one expert layer,
    one sequence, forward."""
    return 2 * model["seq_len"] * model["hidden_size"] * (
        model["num_experts"] + 2 * model["shared_expert_width"])


def routed_flops(model: dict, rows: float) -> float:
    """The up and down products of ``rows`` routed rows, forward."""
    return rows * 4 * model["hidden_size"] * model["expert_width"]


def head_flops(model: dict) -> int:
    """The output head on every position of one sequence, forward."""
    return 2 * model["seq_len"] * model["hidden_size"] * model["vocab_size"]


def dense_forward_flops(model: dict) -> int:
    """One sequence's forward pass without its routed experts' products."""
    return (layers_of(model, MAMBA) * (mamba_projection_flops(model)
                                       + scan_flops(model))
            + layers_of(model, ATTENTION) * (
                attention_projection_flops(model) + attention_flops(model))
            + layers_of(model, EXPERTS) * dense_expert_flops(model)
            + head_flops(model))


def window_flops(model: dict, batch: int, steps: int, eval_batches: int,
                 train_rows: float) -> float:
    """Everything a window asks of the model: ``steps`` optimiser steps and
    ``eval_batches`` forward passes of ``batch`` sequences; ``train_rows``
    are the rows the held experts took in the optimiser steps, over all
    expert layers (the program's counter). Validation's rows are not
    counted by the program; they are taken at the training steps' mean."""
    sequences = batch * (3 * steps + eval_batches)
    rows = train_rows * (3 + (eval_batches / steps if steps else 0))
    return sequences * dense_forward_flops(model) + routed_flops(model, rows)


def scan_least_seconds(model: dict, batch: int, steps: int,
                       eval_batches: int, peaks: dict) -> float:
    """The least time a chip of ``peaks`` could take for the window's
    scans: the larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    layers_by_sequences = layers_of(model, MAMBA) * batch
    flops = layers_by_sequences * (3 * steps + eval_batches) \
        * scan_flops(model)
    moved = layers_by_sequences * (
        steps * scan_bytes(model, True)
        + eval_batches * scan_bytes(model, False))
    return max(flops / peaks["flops_per_s"], moved / peaks["bytes_per_s"])
