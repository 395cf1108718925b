"""Plain reference of ``lfm2-8b-a1b``: LFM2-8B-A1B (``model_type:
lfm2_moe``), a causal stack whose layers are two residual branches each,
``x += operator(RMSNorm(x))`` then ``x += ffn(RMSNorm(x))``: the operator a
gated short convolution (``[B | C | x'] = u W_in``, ``y = C * conv(B *
x')`` over three causal depthwise taps, no bias, no activation, ``y W_out``)
or grouped-query attention (an RMSNorm over each head of q and k, rotary
embedding by rotate-half over the whole head, causal); the feed-forward a
dense SwiGLU MLP in the leading layers and a 32-expert top-4 layer after
them (sigmoid scores, a selection bias, the picked scores over their sum +
1e-6, SwiGLU experts, no shared expert); a final RMSNorm and a head that is
the embedding. Trained by next-token prediction as one chip's share of a
4-way expert-parallel job: the experts ``first_expert .. first_expert +
experts_held - 1`` of every expert layer and a slice of the vocabulary.
``jax.numpy``, float32, every matrix product at ``Precision.HIGHEST`` (and
traced under ``jax.default_matmul_precision("highest")``); the convolution
as three shifted sums; the causal mask as a dense matrix; a loop over the
held experts with every token multiplied by every one of them under a dense
mask of weights; no kernel, nothing imported from the program.

The configuration's ``model`` group names the branches one by one, as the
program's pattern does (``layer_pattern``, letters ``c`` short convolution,
``m`` dense MLP, ``*`` attention, ``E`` experts: two letters a published
layer), and a step is computed in pieces so that it fits beside the 8 GB of
parameters and Adam moments: one sequence and one branch at a time (a
branch's backward pass recomputes its forward pass from the saved input),
attention one query head at a time, the experts one at a time, head and
loss ``HEAD_ROWS`` positions at a time.

Departures from the published description are listed under ``assumed`` in
the configuration: the tie of head and embedding (the family's default; the
config has no key), the expert block's form and its 1e-6 (from memory of
``lfm2_moe``, which the installed transformers lacks), the selection bias
fixed at 0, no auxiliary loss, every position in the loss, the seeded
start, and the cut itself.

``precision``: ``"f32"``, or ``"int8"``: both operands of every matrix
product rounded to symmetric per-tensor int8, the nearest precision below
the configuration's bfloat16 (``control.py``'s control). ``fault``: ``None``
or one planted fault that the cell's limits have to catch: ``"conv_bf16"``
(the short convolution's gate product, its taps' products and their running
sum each rounded to bfloat16), ``"tap_ahead"`` (the taps one position late:
position ``t`` sees ``t - 1 .. t + 1``), ``"no_c_gate"`` (the ``C`` gate
left out), ``"no_qk_norm"`` (q and k not normed), ``"untied_grad"`` (the
head's gradient kept from the embedding: the leaf takes the gather's part
alone), ``"norm_eps_tiny"`` (1e-20 where the router's 1e-6 belongs: read,
and caught by no limit, since it moves a weight by under 5e-7 of itself).

**The operator alone** (:func:`shortconv_check_inputs`,
:func:`shortconv_taps_grad`): gate, convolution and gate on what the
stack's first branch is handed at the job's start (``B``, ``C`` and ``x'``
of the embedded, normed and projected token rows a driver gives, whole
sequences of the model's length, every value rounded to one that bfloat16
holds exactly), and the gradient that branch's taps take under a drawn
linear readout. A driver hands the same inputs to the program's operator
and compares the two gradients as vectors: the program's sums are float32
and agree to their order, sums kept in bfloat16 are a thousand times
further off, and through the whole model both drown in the bfloat16 of the
matrix products.
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
SHORTCONV, MLP, ATTENTION, EXPERTS = "shortconv", "mlp", "attention", \
    "experts"
LETTERS = {"c": SHORTCONV, "m": MLP, "*": ATTENTION, "E": EXPERTS}
HEAD_ROWS = 4096    # positions whose logits are alive at a time


def kinds(model: dict) -> list:
    """Each branch's kind, from ``layer_pattern`` as letters or names."""
    return [LETTERS.get(k, k) for k in model["layer_pattern"]]


def period(model: dict) -> int:
    """The shortest period of the pattern."""
    pattern = kinds(model)
    return next(p for p in range(1, len(pattern) + 1)
                if len(pattern) % p == 0 and pattern == pattern[:p] * (
                    len(pattern) // p))


def layer_shapes(model: dict, kind: str) -> dict:
    """name -> shape of one branch of ``kind``, in the program's order."""
    h = model["hidden_size"]
    if kind == SHORTCONV:
        return {"norm": (h,), "w_in": (h, 3 * h),
                "conv_taps": (h, model["shortconv_kernel"]),
                "w_out": (h, h)}
    if kind == ATTENTION:
        d = model["head_dim"]
        q, kv = model["num_heads"] * d, model["num_kv_heads"] * d
        return {"norm": (h,), "wq": (h, q), "wk": (h, kv), "wv": (h, kv),
                "q_norm": (d,), "k_norm": (d,), "wo": (q, h)}
    if kind == MLP:
        f = model["mlp_width"]
        return {"norm": (h,), "w_gate": (h, f), "w_up": (h, f),
                "w_down": (f, h)}
    e, f = model["experts_held"], model["expert_width"]
    return {"norm": (h,), "router": (h, model["num_experts"]),
            "router_bias": (model["num_experts"],), "w_gate": (e, h, f),
            "w_up": (e, h, f), "w_down": (e, f, h)}


def param_shapes(model: dict) -> dict:
    """name -> shape, in the program's documented layout and order: leaf
    ``layers/<j>/<name>`` holds branch ``j`` of every period, the periods
    in front, with the leaves of that branch's kind; the embedding is the
    head, so there is no ``head``."""
    p = period(model)
    r = model["num_layers"] // p
    shapes = {"embed": (model["vocab_size"], model["hidden_size"])}
    for j, kind in enumerate(kinds(model)[:p]):
        shapes.update({f"layers/{j}/{k}": (r, *s)
                       for k, s in layer_shapes(model, kind).items()})
    return {**shapes, "final_norm": (model["hidden_size"],)}


def init(model: dict, seed: int) -> dict:
    """The weights a job of this seed starts from, by the program's
    documented rule (``training/tasks``, ``models/moe.seeded_params`` and
    ``models/hybrid_lm.mixer_draws``), re-derived: under one ``jit``, on
    ``rng = jax.random.key(seed, impl="rbg")``, leaf ``i`` of
    :func:`param_shapes`, in its order, takes ``key_i = split(rng,
    n_leaves)[i]``: a matrix is ``std * normal(key_i, shape, float32)``,
    ``std`` the configuration's ``init_std`` (``embed_init_std`` for the
    embedding); norms are one and ``router_bias`` zero; the convolution's
    taps ``(2 u - 1) / sqrt(taps)`` with ``u = uniform(key_i, shape,
    float32)``. On the default device."""
    shapes = param_shapes(model)

    def leaf(key, name, shape):
        own = name.rsplit("/", 1)[-1]
        if own.endswith("norm"):
            return jnp.ones(shape, jnp.float32)
        if own == "router_bias":
            return jnp.zeros(shape, jnp.float32)
        if own == "conv_taps":
            u = jax.random.uniform(key, shape, jnp.float32)
            return (2 * u - 1) * model["shortconv_kernel"] ** -0.5
        std = model["embed_init_std" if name == "embed" else "init_std"]
        return std * jax.random.normal(key, shape, jnp.float32)

    @jax.jit
    def draw(rng):
        return {name: leaf(key, name, shape) for key, (name, shape) in zip(
            jax.random.split(rng, len(shapes)), shapes.items())}

    return draw(jax.random.key(seed, impl="rbg"))


def tokens(model: dict, seed: int, n: int) -> np.ndarray:
    """``n`` full sequences: ids uniform over the vocabulary slice."""
    rng = np.random.default_rng([seed, 1])
    return rng.integers(0, model["vocab_size"],
                        (n, model["seq_len"])).astype(np.int32)


def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


def _mm(a, b, precision: str):
    if precision == "int8":
        a, b = _int8(a), _int8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _bf16(x):
    """Rounded to bfloat16's 8 bits of exponent and 7 of mantissa, by the
    operation a compiler may not simplify away (a cast there and back it
    may)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def shortconv_mix(b, c, xs, taps, fault: str | None = None):
    """``C * conv(B * x')`` on one sequence, ``[L, hidden]`` each and
    ``taps`` ``[hidden, K]``: ``conv(g)_t = sum_k taps[:, k] g_{t - K + 1 +
    k}``, positions before the sequence reading zero, as ``K`` shifted
    sums."""
    length, k_taps = xs.shape[0], taps.shape[1]
    rounded = _bf16 if fault == "conv_bf16" else (lambda v: v)
    gated = rounded(b * xs)
    # "tap_ahead": every tap one position late, so that t sees t + 1
    ahead = 1 if fault == "tap_ahead" else 0
    padded = jnp.pad(gated, ((k_taps - 1 - ahead, ahead), (0, 0)))
    conv = None
    for k in range(k_taps):
        tap = rounded(padded[k:k + length] * taps[:, k])
        conv = tap if conv is None else rounded(conv + tap)
    return conv if fault == "no_c_gate" else rounded(c * conv)


def shortconv_check_inputs(model: dict, seed: int, params: dict,
                           rows) -> dict:
    """What the operator is compared on alone: what the stack's first
    branch, a short convolution, is handed on the token rows ``rows`` ``[n,
    L]`` under ``params`` (a driver gives the weights the job starts from
    and rows the job trains on). ``b``, ``c`` and ``xs`` ``[n, L, hidden]``:
    the three parts of ``RMSNorm(embed[rows]) W_in`` by this file's float32
    products, each value rounded to the nearest that bfloat16 holds, so
    that an operator in either type is handed the same numbers; ``taps``
    ``[hidden, K]``, that branch's own (float32); and the readout ``[n, L,
    hidden]`` that weighs the output into a loss, normal at 0.01 from
    ``numpy.random.default_rng([seed, 2])`` and rounded likewise. On the
    host."""
    import ml_dtypes

    kind, _, _, p = next(_layers(model, params))
    if kind != SHORTCONV:
        raise ValueError("the stack's first branch is no short convolution")
    rows = np.asarray(rows)

    @jax.jit
    def parts(embed, norm, w_in, ids):
        u = _rms(embed[ids], norm, model["rms_norm_eps"])
        return jnp.split(_bf16(_mm(u, w_in, "f32")), 3, axis=-1)

    b, c, xs = (np.stack(part) for part in zip(*(
        [np.asarray(a) for a in parts(params["embed"], p["norm"], p["w_in"],
                                      ids)] for ids in rows)))
    rng = np.random.default_rng([seed, 2])
    readout = (0.01 * rng.standard_normal(xs.shape, np.float32)).astype(
        ml_dtypes.bfloat16).astype(np.float32)
    return {"b": b, "c": c, "xs": xs, "taps": np.asarray(p["conv_taps"]),
            "readout": readout}


@functools.lru_cache(maxsize=None)
def _shortconv_check_program(fault):
    """(taps, one sequence of the other inputs) -> the gradient of
    ``sum(readout * shortconv_mix)`` to the taps, jitted."""
    def loss(taps, v):
        return jnp.sum(v["readout"] * shortconv_mix(
            v["b"], v["c"], v["xs"], taps, fault))

    return jax.jit(jax.grad(loss))


def shortconv_taps_grad(inputs: dict, fault: str | None = None) -> np.ndarray:
    """What the operator alone gives its taps on
    :func:`shortconv_check_inputs`, ``[hidden, K]``, on the host: the sum
    over the sequences, one at a time."""
    program, taps = _shortconv_check_program(fault), jnp.asarray(
        inputs["taps"])
    total = 0.0
    for i in range(len(inputs["xs"])):
        total = total + program(taps, {k: jnp.asarray(inputs[k][i]) for k in (
            "b", "c", "xs", "readout")})
    return np.asarray(total)


def shortconv_layer(model: dict, p: dict, x, precision: str = "f32",
                    fault: str | None = None):
    """``x + shortconv(RMSNorm(x))`` on one sequence ``x`` [L, hidden]."""
    mm = functools.partial(_mm, precision=precision)
    u = _rms(x, p["norm"], model["rms_norm_eps"])
    b, c, xs = jnp.split(mm(u, p["w_in"]), 3, axis=-1)
    return x + mm(shortconv_mix(b, c, xs, p["conv_taps"], fault), p["w_out"])


def rope_table(model: dict, length: int):
    """(cos, sin), each ``[L, head_dim]``: ``inv_freq_i = theta^(-2i/d)``,
    the half's frequencies twice (rotate-half layout), no scaling."""
    d = model["head_dim"]
    inv_freq = 1.0 / model["rope_theta"] ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    return (jnp.concatenate([jnp.cos(angles)] * 2, -1),
            jnp.concatenate([jnp.sin(angles)] * 2, -1))


def _rotary(x, table):
    cos, sin = table
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     -1) * sin


def attention_layer(model: dict, p: dict, x, precision: str = "f32",
                    fault: str | None = None):
    """``x + attention(RMSNorm(x))``: causal, grouped-query, q and k normed
    head by head, then rotated."""
    heads, kvh, d = (model["num_heads"], model["num_kv_heads"],
                     model["head_dim"])
    s, eps = x.shape[0], model["rms_norm_eps"]
    mm = functools.partial(_mm, precision=precision)
    u = _rms(x, p["norm"], eps)
    split = lambda y, n: y.reshape(s, n, d).transpose(1, 0, 2)
    q, k, v = (split(mm(u, p["wq"]), heads), split(mm(u, p["wk"]), kvh),
               split(mm(u, p["wv"]), kvh))
    if fault != "no_qk_norm":
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    table = rope_table(model, s)
    q, k = _rotary(q, table), _rotary(k, table)
    live = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    @jax.checkpoint
    def one_head(args):
        qh, g = args
        scores = mm(qh, k[g].T) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
        return mm(probs, v[g])

    a = jax.lax.map(one_head, (q, jnp.arange(heads) // (heads // kvh)))
    return x + mm(a.transpose(1, 0, 2).reshape(s, heads * d), p["wo"])


def mlp_layer(model: dict, p: dict, x, precision: str = "f32",
              fault: str | None = None):
    """``x + Wdown(silu(u Wgate) * (u Wup))``, ``u = RMSNorm(x)``."""
    del fault
    mm = functools.partial(_mm, precision=precision)
    u = _rms(x, p["norm"], model["rms_norm_eps"])
    return x + mm(jax.nn.silu(mm(u, p["w_gate"])) * mm(u, p["w_up"]),
                  p["w_down"])


def routing(model: dict, logits, bias, fault: str | None = None):
    """[tokens, experts_held] weights and the rows each held expert takes:
    ``s = sigmoid(logits)``; the ``experts_per_token`` largest of ``s +
    bias`` are picked; a picked expert's weight is its ``s`` over the
    picked ones' sum plus ``router_norm_eps``, times
    ``routed_scaling_factor``."""
    k, first, held = (model["experts_per_token"], model["first_expert"],
                      model["experts_held"])
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if model["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + (
            1e-20 if fault == "norm_eps_tiny" else model["router_norm_eps"]))
    top = top * model["routed_scaling_factor"]
    hit = ids[:, :, None] == (first + jnp.arange(held))[None, None, :]
    weights = jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)
    return weights, jnp.sum(jnp.any(hit, axis=1), axis=0)


def experts_layer(model: dict, p: dict, x, precision: str = "f32",
                  fault: str | None = None):
    """``x + experts(RMSNorm(x))`` on one sequence -> (x, rows per held
    expert): every token through every held expert, weighed by a dense mask
    that is zero where the token did not pick it."""
    mm = functools.partial(_mm, precision=precision)
    u = _rms(x, p["norm"], model["rms_norm_eps"])
    weights, rows = routing(
        model, jnp.matmul(u, p["router"], precision=HIGHEST),
        p["router_bias"], fault)

    @jax.checkpoint
    def add_expert(y, expert):
        # one trip of the loop over the held experts; its backward pass
        # computes the trip's products again
        w_gate, w_up, w_down, weight = expert
        act = jax.nn.silu(mm(u, w_gate)) * mm(u, w_up)
        return y + weight[:, None] * mm(act, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], weights.T))
    return x + y, rows


BRANCHES = {SHORTCONV: shortconv_layer, MLP: mlp_layer,
            ATTENTION: attention_layer}


def layer(model: dict, kind: str, p: dict, x, precision: str = "f32",
          fault: str | None = None):
    """One branch of ``kind`` on one sequence -> (x, rows per held expert;
    zeros for a branch without experts)."""
    if kind == EXPERTS:
        return experts_layer(model, p, x, precision, fault)
    return (BRANCHES[kind](model, p, x, precision, fault),
            jnp.zeros(model["experts_held"], jnp.int32))


def _head_loss(model, final_norm, embed, x, targets, weight, precision):
    """A run of positions' part of the loss (the sum of their weighted
    cross-entropies, divided by the count later) and their logits: the
    head is the embedding."""
    logits = _mm(_rms(x, final_norm, model["rms_norm_eps"]), embed.T,
                 precision)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
    return jnp.sum(nll * weight), logits


def _frozen(model: dict) -> str:
    return json.dumps(model, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _programs(model_key: str, precision: str, fault):
    """The jitted pieces: (fwd(kind, p, x), bwd(kind, p, x, dy), head and
    loss with their gradients); one pair of programs a branch kind."""
    model = json.loads(model_key)

    @functools.partial(jax.jit, static_argnums=0)
    def fwd(kind, p, x):
        with jax.default_matmul_precision("highest"):
            return layer(model, kind, p, x, precision, fault)

    @functools.partial(jax.jit, static_argnums=0)
    def bwd(kind, p, x, dy):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda p, x: layer(
                model, kind, p, x, precision, fault)[0], p, x)
            return vjp(dy)

    @jax.jit
    def head(final_norm, embed, x, targets, weight):
        with jax.default_matmul_precision("highest"):
            (loss, logits), grads = jax.value_and_grad(
                lambda f, w, x: _head_loss(model, f, w, x, targets, weight,
                                           precision),
                argnums=(0, 1, 2), has_aux=True)(final_norm, embed, x)
            return loss, logits, grads

    return fwd, bwd, head


def _head_rows(length: int) -> int:
    return HEAD_ROWS if length % HEAD_ROWS == 0 else length


def warm(model: dict, precision: str = "f32", fault: str | None = None):
    """Compiles a step's programs (a forward and a backward one a branch
    kind, the head's, the per-leaf ones and the operator alone) for
    sequences of the model's ``seq_len``, from shapes alone: nothing is
    placed on a device and nothing runs. A driver calls this in a thread of
    its own while its job's calls keep the chip and the main thread."""
    length, hidden = model["seq_len"], model["hidden_size"]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    shapes = param_shapes(model)
    x = f32(length, hidden)
    fwd, bwd, head = _programs(_frozen(model), precision, fault)
    for kind in sorted(set(kinds(model))):
        p = {k: f32(*s) for k, s in layer_shapes(model, kind).items()}
        fwd.lower(kind, p, x).compile()
        bwd.lower(kind, p, x, x).compile()
    rows = _head_rows(length)
    head.lower(f32(hidden), f32(*shapes["embed"]), f32(rows, hidden),
               jax.ShapeDtypeStruct((rows,), jnp.int32), f32(rows)).compile()
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
    _add.lower(f32(*shapes["embed"]), f32(*shapes["embed"])).compile()
    _shortconv_check_program(fault).lower(
        f32(hidden, model["shortconv_kernel"]),
        {k: x for k in ("b", "c", "xs", "readout")}).compile()


def _layers(model: dict, params: dict):
    """Per branch, in depth order: (kind, leaf-name prefix, index along the
    period axis, the branch's leaves)."""
    p = period(model)
    for i, kind in enumerate(kinds(model)):
        prefix, r = f"layers/{i % p}/", i // p
        yield kind, prefix, r, {k: params[prefix + k][r]
                                for k in layer_shapes(model, kind)}


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


def expert_layers(model: dict) -> list:
    """The depth indices of the expert branches."""
    return [i for i, kind in enumerate(kinds(model)) if kind == EXPERTS]


def loss_and_grads(model: dict, params: dict, batch,
                   precision: str = "f32", fault: str | None = None):
    """Loss of a batch [b, L], its gradient in every leaf, and the rows the
    held experts took, [expert branches, experts_held] summed over the
    batch. The embedding's gradient is the sum of its two uses, the gather
    at the bottom and the head at the top (under ``"untied_grad"`` the
    gather's alone)."""
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
            loss, _, (g_norm, g_embed, g_x) = head(
                params["final_norm"], params["embed"], x[piece], targets,
                weight)
            total += float(loss) * scale
            grads["final_norm"] += g_norm * scale
            if fault != "untied_grad":
                grads["embed"] = _add(grads["embed"], g_embed * scale)
            dx.append(g_x * scale)
        dx = jnp.concatenate(dx)
        for i, (kind, prefix, r, p) in reversed(list(enumerate(
                _layers(model, params)))):
            g, dx = bwd(kind, p, inputs[i], dx)
            for k, v in g.items():
                grads[prefix + k] = _add_at(grads[prefix + k], r, v)
        grads["embed"] = _add_at(grads["embed"], ids, dx)
    return total, grads, rows[expert_layers(model)]


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(total, part):
    return total + part


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
        parts = [head(params["final_norm"], params["embed"], x[piece],
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
