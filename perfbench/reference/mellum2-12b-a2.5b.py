"""Plain reference of ``mellum2-12b-a2.5b``: the Mellum2-12B-A2.5B-Instruct
decoder (grouped-query attention without biases or q/k norm, layers of two
kinds in a period of four, sliding-window layers under a plain rotary table
and full layers under a YaRN one, a 64-expert top-8 layer in every block,
untied embedding and head) trained by next-token prediction, as one chip's
share of a 4-way expert-parallel job: the experts ``first_expert ..
first_expert + experts_held - 1`` of every layer and a slice of the
vocabulary. ``jax.numpy``, float32, every matrix product at
``Precision.HIGHEST`` (and traced under
``jax.default_matmul_precision("highest")``), both masks as dense matrices
from their definitions, YaRN from its formula in float64 numpy, a loop over
the held experts with every token multiplied by every one of them; no
kernel, nothing imported from the program.

So that a step at the cell's size fits beside the 7.14 GB of parameters and
Adam moments, it is computed in pieces: one sequence and one layer at a time
(the layer's backward pass recomputes its forward pass from the saved layer
input), attention one query head at a time (8,192 x 8,192 float32 scores
are 268 MB), head and loss ``HEAD_ROWS`` positions at a time.

Departures from the published model are listed under ``assumed`` in the
configuration (no multi-token-prediction head, no balance loss, loss over
every position), and the cut itself.

``precision``: ``"f32"``, or ``"int8"``: both operands of every matrix
product rounded to symmetric per-tensor int8, the nearest precision below
the configuration's bfloat16 (``control.py``'s control). ``fault``:
``None``, ``"capacity"`` (each held expert takes at most as many rows of a
sequence as even routing would give it, the rest dropped: a capacity factor
of 1), ``"no_window"`` (the sliding layers under the full causal mask) or
``"plain_rope"`` (the full layers under the sliding layers' table): planted
faults that the cell's limits have to catch.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
SLIDING, FULL = "sliding_attention", "full_attention"
HEAD_ROWS = 4096    # positions whose logits are alive at a time
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "moe_norm", "router",
                "w_gate", "w_up", "w_down")


def period(model: dict) -> int:
    """The shortest period of ``layer_types``."""
    kinds = list(model["layer_types"])
    return next(p for p in range(1, len(kinds) + 1)
                if len(kinds) % p == 0 and kinds == kinds[:p] * (
                    len(kinds) // p))


def param_shapes(model: dict) -> dict:
    """name -> shape, in the program's documented layout and order: leaf
    ``layers/<j>/<name>`` holds layer ``j`` of every period, the periods in
    front."""
    p = period(model)
    r, h, d = model["num_layers"] // p, model["hidden_size"], model["head_dim"]
    q, kv = model["num_heads"] * d, model["num_kv_heads"] * d
    e, f, v = model["experts_held"], model["expert_width"], model["vocab_size"]
    layer = dict(zip(LAYER_LEAVES, (
        (r, h), (r, h, q), (r, h, kv), (r, h, kv), (r, q, h), (r, h),
        (r, h, model["num_experts"]), (r, e, h, f), (r, e, h, f),
        (r, e, f, h))))
    shapes = {"embed": (v, h)}
    for j in range(p):
        shapes.update({f"layers/{j}/{k}": s for k, s in layer.items()})
    return {**shapes, "final_norm": (h,), "head": (h, v)}


def init(model: dict, seed: int) -> dict:
    """The weights a job of this seed starts from, by the program's
    documented rule (``training/tasks`` and ``models/moe.seeded_params``),
    re-derived: under one ``jit``, on ``rng = jax.random.key(seed,
    impl="rbg")``, leaf ``i`` of :func:`param_shapes`, in its order, is
    ``std * normal(split(rng, n_leaves)[i], shape, float32)``, ``std`` the
    configuration's ``init_std`` (``embed_init_std`` for the embedding);
    norms at one. On the default device."""
    shapes = param_shapes(model)

    @jax.jit
    def draw(rng):
        out = {}
        for key, (name, shape) in zip(jax.random.split(rng, len(shapes)),
                                      shapes.items()):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                std = model["embed_init_std" if name == "embed"
                            else "init_std"]
                out[name] = std * jax.random.normal(key, shape, jnp.float32)
        return out

    return draw(jax.random.key(seed, impl="rbg"))


def tokens(model: dict, seed: int, n: int) -> np.ndarray:
    """``n`` full sequences: ids uniform over the vocabulary slice."""
    rng = np.random.default_rng([seed, 1])
    return rng.integers(0, model["vocab_size"],
                        (n, model["seq_len"])).astype(np.int32)


def mask_matrix(kind: str, length: int, window: int) -> np.ndarray:
    """A layer kind's mask as a dense ``[L, L]`` bool matrix: key ``j`` is
    live for query ``i`` iff ``j <= i``, and in a sliding layer also
    ``i - j < window``."""
    i, j = np.arange(length)[:, None], np.arange(length)[None, :]
    live = j <= i
    return live & (i - j < window) if kind == SLIDING else live


def yarn_range(rope: dict, d: int) -> tuple:
    """(low, high) of YaRN's ramp: ``corr(n) = d ln(original / (2 pi n)) /
    (2 ln theta)``, ``low = floor(corr(beta_fast))``, ``high =
    ceil(corr(beta_slow))``, clamped to ``[0, d - 1]``."""
    def corr(n):
        return d * math.log(rope["original_max_position"] / (
            2 * math.pi * n)) / (2 * math.log(rope["theta"]))

    return (max(math.floor(corr(rope["beta_fast"])), 0),
            min(math.ceil(corr(rope["beta_slow"])), d - 1))


def rope_table(rope: dict, d: int, length: int) -> np.ndarray:
    """``[2, L, d]`` float32: cos and sin of ``position * inv_freq`` in the
    rotate-half layout, worked in float64. ``inv_freq_i = theta^(-2i/d)``;
    with ``factor`` above 1 (YaRN) times ``(1 - ramp_i) + ramp_i / factor``,
    ``ramp_i = clip((i - low) / (high - low), 0, 1)`` over ``i = 0 .. d/2 -
    1``, and cos and sin times ``attention_factor``."""
    i = np.arange(d // 2, dtype=np.float64)
    inv_freq = rope["theta"] ** (-2 * i / d)
    if rope.get("factor", 1.0) > 1:
        low, high = yarn_range(rope, d)
        high = high + 0.001 if high == low else high
        ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
        inv_freq = inv_freq * ((1 - ramp) + ramp / rope["factor"])
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None]
    table = np.stack([np.concatenate([np.cos(angles)] * 2, -1),
                      np.concatenate([np.sin(angles)] * 2, -1)])
    return (table * rope.get("attention_factor", 1.0)).astype(np.float32)


def layer_inputs(model: dict, fault: str | None = None) -> dict:
    """kind -> (mask ``[L, L]`` bool, rotary table ``[2, L, d]``), as numpy
    arrays, with the planted fault where one is asked for."""
    length, d = model["seq_len"], model["head_dim"]
    ropes = {SLIDING: model["sliding_rope"], FULL: model["full_rope"]}
    if fault == "plain_rope":
        ropes[FULL] = ropes[SLIDING]
    return {kind: (mask_matrix(FULL if fault == "no_window" else kind,
                               length, model["sliding_window"]),
                   rope_table(ropes[kind], d, length))
            for kind in (SLIDING, FULL)}


def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


def _mm(a, b, precision: str):
    if precision == "int8":
        a, b = _int8(a), _int8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, table):
    """[heads, L, d] by the table's cos and sin; rotate-half."""
    d = x.shape[-1]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * table[0] + rot * table[1]


def routing(model: dict, probs, fault: str | None = None):
    """[tokens, experts_held] weights: the renormalised probability where a
    held expert is among the token's top-k, else 0; and the rows each held
    expert takes."""
    k, first, held = (model["experts_per_token"], model["first_expert"],
                      model["experts_held"])
    top, ids = jax.lax.top_k(probs, k)
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    hit = ids[:, :, None] == (first + jnp.arange(held))[None, None, :]
    weights = jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)
    taken = jnp.any(hit, axis=1)
    if fault == "capacity":
        cap = probs.shape[0] * k // model["num_experts"]
        taken = taken & (jnp.cumsum(taken, axis=0) <= cap)
        weights = jnp.where(taken, weights, 0.0)
    return weights, jnp.sum(taken, axis=0)


def layer(model: dict, p: dict, x, mask, table, precision: str = "f32",
          fault: str | None = None):
    """One decoder layer on one sequence ``x`` [L, hidden] under its kind's
    ``mask`` and rotary ``table`` -> (x, rows per held expert). ``p`` holds
    the layer's leaves without the period axis."""
    heads, kvh, d = (model["num_heads"], model["num_kv_heads"],
                     model["head_dim"])
    eps, s = model["rms_norm_eps"], x.shape[0]
    mm = functools.partial(_mm, precision=precision)
    h = _rms(x, p["attn_norm"], eps)
    split = lambda y, n: y.reshape(s, n, d).transpose(1, 0, 2)
    q = _rotary(split(mm(h, p["wq"]), heads), table)
    k = _rotary(split(mm(h, p["wk"]), kvh), table)
    v = split(mm(h, p["wv"]), kvh)

    @jax.checkpoint
    def one_head(args):
        qh, g = args
        scores = mm(qh, k[g].T) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return mm(probs, v[g])

    a = jax.lax.map(one_head, (q, jnp.arange(heads) // (heads // kvh)))
    x = x + mm(a.transpose(1, 0, 2).reshape(s, heads * d), p["wo"])

    h = _rms(x, p["moe_norm"], eps)
    probs = jax.nn.softmax(jnp.matmul(h, p["router"], precision=HIGHEST), -1)
    weights, rows = routing(model, probs, fault)

    @jax.checkpoint
    def add_expert(y, expert):
        # one trip of the loop over the held experts; its backward pass
        # computes the trip's products again (kept for all 16 trips they
        # are gigabytes the chip does not have beside parameters, moments
        # and gradients)
        w_gate, w_up, w_down, weight = expert
        act = jax.nn.silu(mm(h, w_gate)) * mm(h, w_up)
        return y + weight[:, None] * mm(act, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], weights.T))
    return x + y, rows


def _head_loss(model, final_norm, head, x, targets, weight, precision):
    """A run of positions' part of the loss (the sum of their weighted
    cross-entropies, divided by the count later) and their logits."""
    logits = _mm(_rms(x, final_norm, model["rms_norm_eps"]), head, precision)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
    return jnp.sum(nll * weight), logits


def _frozen(model: dict) -> str:
    return json.dumps(model, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted(model_key: str, precision: str, fault):
    """The three jitted pieces: a layer forward, a layer backward, head and
    loss with their gradients. Mask and table are arguments of the two that
    use them: one program serves both layer kinds."""
    model = json.loads(model_key)

    @jax.jit
    def fwd(p, x, mask, table):
        with jax.default_matmul_precision("highest"):
            return layer(model, p, x, mask, table, precision, fault)

    @jax.jit
    def bwd(p, x, dy, mask, table):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(
                lambda p, x: layer(model, p, x, mask, table, precision,
                                   fault)[0], p, x)
            return vjp(dy)

    @jax.jit
    def head(final_norm, head_w, x, targets, weight):
        with jax.default_matmul_precision("highest"):
            (loss, logits), grads = jax.value_and_grad(
                lambda f, w, x: _head_loss(model, f, w, x, targets, weight,
                                           precision),
                argnums=(0, 1, 2), has_aux=True)(final_norm, head_w, x)
            return loss, logits, grads

    return fwd, bwd, head


@functools.lru_cache(maxsize=None)
def _programs(model_key: str, precision: str, fault):
    """:func:`_jitted` with each kind's mask and table on the device:
    (fwd(kind, p, x), bwd(kind, p, x, dy), head)."""
    fwd, bwd, head = _jitted(model_key, precision, fault)
    inputs = {kind: tuple(jnp.asarray(a) for a in pair) for kind, pair in
              layer_inputs(json.loads(model_key), fault).items()}
    return (lambda kind, p, x: fwd(p, x, *inputs[kind]),
            lambda kind, p, x, dy: bwd(p, x, dy, *inputs[kind]), head)


def _head_rows(length: int) -> int:
    return HEAD_ROWS if length % HEAD_ROWS == 0 else length


def warm(model: dict, precision: str = "f32", fault: str | None = None):
    """Compiles a step's programs (the three of :func:`_jitted` and the
    per-leaf ones) for sequences of the model's ``seq_len``, from shapes
    alone: nothing is placed on a device and nothing runs. At the cell's
    size the TPU's compiler takes over a minute for them (a float32 product
    at ``HIGHEST`` is six passes), on four or five of the host's cores. The
    first call of each then finds it compiled (JAX keeps an executable with
    the lowering it was made from and, where its persistent compilation
    cache is on, on disk); a driver calls this in a thread of its own while
    its job's calls keep the chip and the main thread."""
    length, hidden, d = model["seq_len"], model["hidden_size"], \
        model["head_dim"]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    shapes = param_shapes(model)
    p = {k: f32(*shapes[f"layers/0/{k}"][1:]) for k in LAYER_LEAVES}
    x = f32(length, hidden)
    mask = jax.ShapeDtypeStruct((length, length), jnp.bool_)
    table = f32(2, length, d)
    fwd, bwd, head = _jitted(_frozen(model), precision, fault)
    fwd.lower(p, x, mask, table).compile()
    bwd.lower(p, x, x, mask, table).compile()
    rows = _head_rows(length)
    head.lower(f32(hidden), f32(hidden, model["vocab_size"]),
               f32(rows, hidden), jax.ShapeDtypeStruct((rows,), jnp.int32),
               f32(rows)).compile()
    # the small ones of a step, a second or so apiece: a leaf's part of the
    # gradient added in, Adam on a leaf, once a shape
    for shape in set(shapes.values()):
        leaf = f32(*shape)
        _adam_leaf.lower(leaf, leaf, leaf, leaf, f32(), f32()).compile()
    index = jax.ShapeDtypeStruct((), jnp.int32, weak_type=True)
    for shape in {shape for name, shape in shapes.items()
                  if name.startswith("layers/")}:
        _add_at.lower(f32(*shape), index, f32(*shape[1:])).compile()
    _add_at.lower(f32(*shapes["embed"]),
                  jax.ShapeDtypeStruct((length,), jnp.int32), x).compile()


def _layers(model: dict, params: dict):
    """Per layer, in depth order: (kind, leaf-name prefix, index along the
    period axis, the layer's leaves)."""
    p = period(model)
    for i, kind in enumerate(model["layer_types"]):
        prefix, r = f"layers/{i % p}/", i // p
        yield kind, prefix, r, {k: params[prefix + k][r]
                                for k in LAYER_LEAVES}


def _head_pieces(length: int, ids):
    """(slice, targets, weights) of each run of ``HEAD_ROWS`` positions:
    position ``i``'s target is token ``i + 1``; the last position has none
    and weighs 0."""
    targets = jnp.roll(ids, -1)
    weight = (jnp.arange(length) < length - 1).astype(jnp.float32)
    rows = _head_rows(length)
    for lo in range(0, length, rows):
        piece = slice(lo, lo + rows)
        yield piece, targets[piece], weight[piece]


def loss_and_grads(model: dict, params: dict, batch,
                   precision: str = "f32", fault: str | None = None):
    """Loss of a batch [b, L], its gradient in every leaf, and the rows the
    held experts took, [layers, experts_held] summed over the batch."""
    fwd, bwd, head = _programs(_frozen(model), precision, fault)
    n, length = model["num_layers"], batch.shape[1]
    grads = {k: jnp.zeros_like(v) for k, v in params.items()}
    total, rows = 0.0, np.zeros((n, model["experts_held"]), np.int64)
    scale = 1.0 / (batch.shape[0] * (length - 1))
    for ids in jnp.asarray(batch):
        x = params["embed"][ids]
        inputs = []
        for i, (kind, _, _, p) in enumerate(_layers(model, params)):
            inputs.append(x)
            x, r = fwd(kind, p, x)
            rows[i] += np.asarray(r)
        dx = []
        for piece, targets, weight in _head_pieces(length, ids):
            loss, _, (g_norm, g_head, g_x) = head(
                params["final_norm"], params["head"], x[piece], targets,
                weight)
            total += float(loss) * scale
            grads["final_norm"] += g_norm * scale
            grads["head"] += g_head * scale
            dx.append(g_x * scale)
        dx = jnp.concatenate(dx)
        for i, (kind, prefix, r, p) in reversed(list(enumerate(
                _layers(model, params)))):
            g, dx = bwd(kind, p, inputs[i], dx)
            for k, v in g.items():
                grads[prefix + k] = _add_at(grads[prefix + k], r, v)
        grads["embed"] = _add_at(grads["embed"], ids, dx)
    return total, grads, rows


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_at(total, index, part):
    """``total[index] += part`` in place: the sum's buffer is donated, so a
    leaf of 0.5 GB is never held twice."""
    return total.at[index].add(part)


def _heads(model, params, batch, precision, fault):
    """Per sequence of the batch, the forward pass alone: (its part of the
    loss, its logits [L, vocab])."""
    fwd, _, head = _programs(_frozen(model), precision, fault)
    for ids in jnp.asarray(batch):
        x = params["embed"][ids]
        for kind, _, _, p in _layers(model, params):
            x, _ = fwd(kind, p, x)
        parts = [head(params["final_norm"], params["head"], x[piece],
                      targets, weight)[:2]
                 for piece, targets, weight in _head_pieces(len(ids), ids)]
        yield (sum(float(part) for part, _ in parts),
               jnp.concatenate([logits for _, logits in parts]))


def forward(model: dict, params: dict, batch, precision: str = "f32",
            fault: str | None = None):
    """Logits [b, L, vocab] of every position."""
    return jnp.stack([logits for _, logits in _heads(
        model, params, batch, precision, fault)])


def loss(model: dict, params: dict, batch, precision: str = "f32",
         fault: str | None = None) -> float:
    """The batch's loss alone (validation): the mean over positions
    ``0 .. L - 2`` of the cross-entropy against the next token."""
    return sum(part for part, _ in _heads(
        model, params, batch, precision, fault)) / (
            batch.shape[0] * (batch.shape[1] - 1))


def adam_init(params: dict) -> dict:
    return {"mu": {k: jnp.zeros_like(v) for k, v in params.items()},
            "nu": {k: jnp.zeros_like(v) for k, v in params.items()},
            "count": 0}


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adam_leaf(p, g, mu, nu, count, lr):
    mu = ADAM_B1 * mu + (1 - ADAM_B1) * g
    nu = ADAM_B2 * nu + (1 - ADAM_B2) * g * g
    mu_hat = mu / (1 - ADAM_B1 ** count)
    nu_hat = nu / (1 - ADAM_B2 ** count)
    return p - lr * mu_hat / (jnp.sqrt(nu_hat) + ADAM_EPS), mu, nu


def adam_update(params: dict, opt: dict, grads: dict, lr: float):
    """Adam as optax has it (b1 0.9, b2 0.999, eps 1e-8, bias-corrected),
    leaf by leaf and in place: the buffers of ``params``, ``opt`` and
    ``grads`` are donated, and none of the three may be used afterwards."""
    count = opt["count"] + 1
    new, mu, nu = {}, {}, {}
    for k in list(params):
        new[k], mu[k], nu[k] = _adam_leaf(
            params.pop(k), grads.pop(k), opt["mu"].pop(k), opt["nu"].pop(k),
            jnp.float32(count), jnp.float32(lr))
    return new, {"mu": mu, "nu": nu, "count": count}


def train_step(model: dict, lr: float, seed: int, params: dict, opt: dict,
               batch, precision: str = "f32", fault: str | None = None,
               keep_grads: bool = False):
    """One optimiser step on ``batch`` [b, L] -> (params, opt, loss, grads,
    rows); ``params`` and ``opt`` as passed in are consumed. The gradient
    comes back on the host (numpy) where ``keep_grads``, else as ``None``.
    ``seed`` is not read (a step draws nothing); it is the place the
    driver's ``follow`` gives it."""
    del seed
    value, grads, rows = loss_and_grads(model, params, batch, precision,
                                        fault)
    kept = {k: np.asarray(v) for k, v in grads.items()} if keep_grads \
        else None
    params, opt = adam_update(dict(params), opt, grads, lr)
    return params, opt, value, kept, rows


def eval_loss(model: dict, seed: int, params: dict, batch,
              precision: str = "f32", fault: str | None = None) -> float:
    del seed
    return loss(model, params, batch, precision, fault)
