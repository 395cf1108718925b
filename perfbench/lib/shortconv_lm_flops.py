"""Operations and bytes of a decoder whose layers are an operator branch (a
gated short convolution, or causal grouped-query attention) and a
feed-forward branch (a dense gated MLP, or sparse experts), from its shapes:
what the algorithm needs, whatever implements it. Multiply-adds count as 2;
a training step is three passes of every matrix product (the forward pass
and the two products of its backward pass); recomputed passes, norms, the
convolution's taps, gates, rotary embedding, activations, softmax, loss, the
embedding's gather and the optimiser are not counted. ``model`` is the
configuration file's ``model`` group, whose ``layer_pattern`` names the
branches one by one (``c`` short convolution, ``m`` dense MLP, ``*``
attention, ``E`` experts).

The short convolution's gate, taps and gate hold no matrix product. As a
pass of their own over HBM they would move, at two bytes an element, ``B``,
``C``, ``x'`` and ``y`` forward and those, ``dy`` and three gradients
backward: 11 x hidden x 2 bytes a token and step against the projections'
24 x hidden^2 operations, 1.09 x hidden operations a byte where the chip's
balance is 240, so the products bind from a hidden size of 220 on (at 2048
the bytes take a tenth of the products' time), and an implementation may
keep them on the chip between the two products. The operator's roofline
(:func:`shortconv_least_seconds`) is therefore that of its two projections:
their operations at peak FLOP/s, against device time that holds the whole
operator. A roof that added the mix's bytes could be passed; the products'
alone cannot.

Attention is counted on the pairs the causal mask leaves live, ``L (L + 1)
/ 2`` a sequence and head, at the heads' own size (64 here: a kernel that
pads them to the 128 lanes of a row pays for twice that); the experts'
three products by the rows that the program's routing sent to the experts
held here.
"""

from __future__ import annotations

# the same counts of the same keys: attention and head as the hybrid
# family's, an expert of three matrices
from perfbench.lib.hybrid_lm_flops import (  # noqa: F401
    attention_flops, attention_projection_flops, head_flops)
from perfbench.lib.lm_flops import expert_flops as routed_flops  # noqa: F401

SHORTCONV, MLP, ATTENTION, EXPERTS = "shortconv", "mlp", "attention", \
    "experts"
LETTERS = {"c": SHORTCONV, "m": MLP, "*": ATTENTION, "E": EXPERTS}


def layers_of(model: dict, kind: str) -> int:
    return sum(LETTERS.get(k, k) == kind for k in model["layer_pattern"])


def shortconv_projection_flops(model: dict) -> int:
    """The operator's two projections, one layer and sequence, forward:
    ``hidden -> 3 hidden`` and ``hidden -> hidden``."""
    h = model["hidden_size"]
    return 2 * model["seq_len"] * h * (3 * h + h)


def mlp_flops(model: dict) -> int:
    """gate, up and down of one dense MLP, one sequence, forward."""
    return 6 * model["seq_len"] * model["hidden_size"] * model["mlp_width"]


def router_flops(model: dict) -> int:
    """The router's product of one expert layer, one sequence, forward."""
    return 2 * model["seq_len"] * model["hidden_size"] * model["num_experts"]


def dense_forward_flops(model: dict) -> int:
    """One sequence's forward pass without its routed experts' products."""
    return (layers_of(model, SHORTCONV) * shortconv_projection_flops(model)
            + layers_of(model, MLP) * mlp_flops(model)
            + layers_of(model, ATTENTION) * (
                attention_projection_flops(model) + attention_flops(model))
            + layers_of(model, EXPERTS) * router_flops(model)
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


def shortconv_least_seconds(model: dict, batch: int, steps: int,
                            eval_batches: int, peaks: dict) -> float:
    """The least time a chip of ``peaks`` could take for the window's
    short-convolution operators: their projections' operations over peak
    FLOP/s."""
    passes = layers_of(model, SHORTCONV) * batch * (3 * steps + eval_batches)
    return passes * shortconv_projection_flops(model) / peaks["flops_per_s"]
