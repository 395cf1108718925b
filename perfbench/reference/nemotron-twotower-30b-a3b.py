"""Plain reference of ``nemotron-twotower-30b-a3b``: the causal tower of
Nemotron-Labs-TwoTower-30B-A3B (``model_type: nemotron_h``), a stack whose
layers are one residual branch each, ``x += branch(RMSNorm(x))``: a Mamba-2
mixer, grouped-query attention without positions, or a 128-expert top-6
layer with a sigmoid router, squared-ReLU experts and a shared expert;
untied embedding and head; trained by next-token prediction, as one chip's
share of a 16-way expert-parallel job: the experts ``first_expert ..
first_expert + experts_held - 1`` of every expert layer and a slice of the
vocabulary. ``jax.numpy``, float32, every matrix product at
``Precision.HIGHEST`` (and traced under
``jax.default_matmul_precision("highest")``); **the state-space recurrence
as the recurrence**: a ``lax.scan`` over the positions, one state update a
position, no chunks and no cumulative sums; the causal mask as a dense
matrix; a loop over the held experts with every token multiplied by every
one of them; no kernel, nothing imported from the program.

So that a step at the cell's size fits beside the 8 GB of parameters and
Adam moments, it is computed in pieces: one sequence and one layer at a time
(the layer's backward pass recomputes its forward pass from the saved layer
input), the recurrence in stretches of ``STRETCH`` positions under a
``jax.checkpoint`` each (the 8,192 states of a sequence, 2 MB apiece, are
16 GB; kept are the 128 that start a stretch and, while a stretch is
differentiated, its own 64), the mixer's three parts under a checkpoint
each, attention one query head at a time, head and loss ``HEAD_ROWS``
positions at a time.

Departures from the published description are listed under ``assumed`` in
the configuration: the denoiser tower (adaLN, conditioning between towers,
bidirectional attention inside a block) is left out because the config has
no key that defines it; the selection bias stays at 0; no auxiliary loss;
the loss counts every position; the seeded start; and the cut itself.

``precision``: ``"f32"``, or ``"int8"``: both operands of every matrix
product rounded to symmetric per-tensor int8, the nearest precision below
the configuration's bfloat16 (``control.py``'s control). ``fault``: ``None``
or one planted fault that the cell's limits have to catch:
``"decay_bf16"`` (the decays' running sum inside each chunk of
``ssm_chunk`` positions rounded to bfloat16, as a chunked scan that kept
its cumulative sums in bfloat16 would have them: the step from ``t - 1`` to
``t`` then decays by ``exp(c_t - c_{t-1})`` of the rounded sums),
``"no_shared"`` (the shared expert left out), ``"no_conv_bias"`` (the
convolution's bias left out), ``"softmax_router"`` (a softmax over the
experts where the sigmoid belongs).

**The recurrence alone** (:func:`scan_check_inputs`,
:func:`scan_decay_grads`): one mixer's state-space part on inputs drawn
from the seed, ``SCAN_CHECK_LENGTH`` positions at the model's widths, and
the gradients its two decay leaves (``dt_bias``, ``A_log``) take under a
drawn linear readout. A driver hands the same inputs to the program's scan
and compares the two gradients as vectors: through the whole model a decay
kept in too low a precision moves those 64-number leaves by about what the
program's own bfloat16 moves them, and alone it moves them twenty times
that.
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
MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
LETTERS = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}
HEAD_ROWS = 4096    # positions whose logits are alive at a time
STRETCH = 64        # positions of the recurrence under one checkpoint
SCAN_CHECK_LENGTH = 1024    # positions of the recurrence compared alone


def kinds(model: dict) -> list:
    """Each layer's kind, from ``layer_pattern`` as letters (a published
    ``hybrid_override_pattern``) or as names."""
    return [LETTERS.get(k, k) for k in model["layer_pattern"]]


def period(model: dict) -> int:
    """The shortest period of the pattern."""
    pattern = kinds(model)
    return next(p for p in range(1, len(pattern) + 1)
                if len(pattern) % p == 0 and pattern == pattern[:p] * (
                    len(pattern) // p))


def mamba_sizes(model: dict) -> tuple:
    """(inner channels, the convolution's channels)."""
    inner = model["mamba_heads"] * model["mamba_head_dim"]
    return inner, inner + 2 * model["ssm_groups"] * model["ssm_state"]


def layer_shapes(model: dict, kind: str) -> dict:
    """name -> shape of one layer of ``kind``, in the program's order."""
    h = model["hidden_size"]
    if kind == MAMBA:
        inner, conv_dim = mamba_sizes(model)
        heads = model["mamba_heads"]
        return {"norm": (h,), "w_in": (h, inner + conv_dim + heads),
                "conv_w": (conv_dim, model["conv_kernel"]),
                "conv_b": (conv_dim,), "dt_bias": (heads,),
                "A_log": (heads,), "D": (heads,), "gate_norm": (inner,),
                "w_out": (inner, h)}
    if kind == ATTENTION:
        q = model["num_heads"] * model["head_dim"]
        kv = model["num_kv_heads"] * model["head_dim"]
        return {"norm": (h,), "wq": (h, q), "wk": (h, kv), "wv": (h, kv),
                "wo": (q, h)}
    e, f, s = (model["experts_held"], model["expert_width"],
               model["shared_expert_width"])
    return {"norm": (h,), "router": (h, model["num_experts"]),
            "router_bias": (model["num_experts"],), "w_up": (e, h, f),
            "w_down": (e, f, h), "shared_up": (h, s), "shared_down": (s, h)}


def param_shapes(model: dict) -> dict:
    """name -> shape, in the program's documented layout and order: leaf
    ``layers/<j>/<name>`` holds layer ``j`` of every period, the periods in
    front, with the leaves of that layer's kind."""
    p = period(model)
    r = model["num_layers"] // p
    shapes = {"embed": (model["vocab_size"], model["hidden_size"])}
    for j, kind in enumerate(kinds(model)[:p]):
        shapes.update({f"layers/{j}/{k}": (r, *s)
                       for k, s in layer_shapes(model, kind).items()})
    return {**shapes, "final_norm": (model["hidden_size"],),
            "head": (model["hidden_size"], model["vocab_size"])}


def init(model: dict, seed: int) -> dict:
    """The weights a job of this seed starts from, by the program's
    documented rule (``training/tasks``, ``models/moe.seeded_params`` and
    ``models/hybrid_lm.mixer_draws``), re-derived: under one ``jit``, on
    ``rng = jax.random.key(seed, impl="rbg")``, leaf ``i`` of
    :func:`param_shapes`, in its order, takes ``key_i = split(rng,
    n_leaves)[i]``: a matrix is ``std * normal(key_i, shape, float32)``,
    ``std`` the configuration's ``init_std`` (``embed_init_std`` for the
    embedding); norms and ``D`` are one and ``router_bias`` zero; with ``u =
    uniform(key_i, shape, float32)``, ``A_log = log(lo + (hi - lo) u)`` over
    ``a_range``, ``dt_bias`` the inverse softplus ``s + log(-expm1(-s))``
    of ``s = max(exp(u (ln max - ln min) + ln min), floor)`` over the
    configuration's time steps, and the convolution's weight and bias ``(2 u
    - 1) / sqrt(conv_kernel)``. On the default device."""
    shapes = param_shapes(model)
    lo, hi = model["a_range"]
    t_min, t_max = model["time_step_min"], model["time_step_max"]

    def leaf(key, name, shape):
        own = name.rsplit("/", 1)[-1]
        if own.endswith("norm") or own == "D":
            return jnp.ones(shape, jnp.float32)
        if own == "router_bias":
            return jnp.zeros(shape, jnp.float32)
        if own in ("A_log", "dt_bias", "conv_w", "conv_b"):
            u = jax.random.uniform(key, shape, jnp.float32)
            if own == "A_log":
                return jnp.log(lo + (hi - lo) * u)
            if own == "dt_bias":
                s = jnp.exp(u * (math.log(t_max) - math.log(t_min))
                            + math.log(t_min))
                s = jnp.maximum(s, model["time_step_floor"])
                return s + jnp.log(-jnp.expm1(-s))
            return (2 * u - 1) * model["conv_kernel"] ** -0.5
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


def log_decays(model: dict, dt, a, fault: str | None = None):
    """``[L, heads]``: the logarithm of what each position's step decays
    the state by, ``dt_t A``; under ``"decay_bf16"`` the differences of the
    running sum inside each chunk of ``ssm_chunk`` positions after it was
    rounded to bfloat16."""
    logs = dt * a
    if fault != "decay_bf16":
        return logs
    length, chunk = logs.shape[0], model["ssm_chunk"]
    pad = -length % chunk
    sums = jnp.cumsum(jnp.pad(logs, ((0, pad), (0, 0))).reshape(
        -1, chunk, logs.shape[1]), axis=1)
    # bfloat16's 8 bits of exponent and 7 of mantissa, by the operation a
    # compiler may not simplify away (a cast there and back it may)
    sums = jax.lax.reduce_precision(sums, exponent_bits=8, mantissa_bits=7)
    steps = jnp.diff(sums, axis=1, prepend=jnp.zeros_like(sums[:, :1]))
    return steps.reshape(-1, logs.shape[1])[:length]


def recurrence(xs, dt, logs, b, c, d):
    """The state-space recurrence of one sequence, position by position:
    ``h_t = exp(logs_t) h_{t-1} + dt_t xs_t (x) B_t``, ``y_t = h_t C_t + D
    xs_t``, ``h_{-1} = 0``. ``xs`` ``[L, heads, P]``; ``dt``, ``logs``
    ``[L, heads]``; ``b``, ``c`` ``[L, groups, N]``, head ``h`` reading
    group ``h // (heads / groups)``; ``d`` ``[heads]``."""
    length, heads, p = xs.shape
    per = heads // b.shape[1]
    stretch = math.gcd(length, STRETCH)

    def one(h, at):
        x_t, dt_t, log_t, b_t, c_t = at
        b_t, c_t = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        h = (jnp.exp(log_t)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + d[:, None] * x_t

    @jax.checkpoint
    def run(h, positions):
        return jax.lax.scan(one, h, positions)

    cut = lambda v: v.reshape(length // stretch, stretch, *v.shape[1:])
    _, y = jax.lax.scan(run, jnp.zeros((heads, p, b.shape[-1]), xs.dtype),
                        tuple(cut(v) for v in (xs, dt, logs, b, c)))
    return y.reshape(length, heads, p)


def scan_check_inputs(model: dict, seed: int) -> dict:
    """What the recurrence is compared on alone, from
    ``numpy.random.default_rng([seed, 2])`` in this order, float32: ``xs``
    ``[L, heads, P]``, ``b`` and ``c`` ``[L, groups, N]`` (``silu`` of
    standard normals, as the convolution's output is), ``raw`` ``[L,
    heads]`` (standard normal: a step before its bias and softplus),
    ``dt_bias`` and ``A_log`` ``[heads]`` drawn as :func:`init` draws them
    from uniforms, ``d`` at one, and the readout ``[L, heads, P]`` (normal,
    0.01) that weighs the output into a loss. ``L`` is
    ``SCAN_CHECK_LENGTH``, or the model's ``seq_len`` where that is
    shorter."""
    rng = np.random.default_rng([seed, 2])
    length = min(SCAN_CHECK_LENGTH, model["seq_len"])
    heads, groups, n = (model["mamba_heads"], model["ssm_groups"],
                        model["ssm_state"])
    lo, hi = model["a_range"]
    t_min, t_max = model["time_step_min"], model["time_step_max"]

    def normal(*shape):
        return rng.standard_normal(shape, np.float32)

    def silu(x):
        return x / (1 + np.exp(-x))

    xs = silu(normal(length, heads, model["mamba_head_dim"]))
    b, c = silu(normal(length, groups, n)), silu(normal(length, groups, n))
    raw = normal(length, heads)
    step = np.maximum(np.exp(rng.random(heads, np.float32) * (
        math.log(t_max) - math.log(t_min)) + math.log(t_min)),
        model["time_step_floor"])
    return {"xs": xs, "b": b, "c": c, "raw": raw,
            "dt_bias": (step + np.log(-np.expm1(-step))).astype(np.float32),
            "A_log": np.log(lo + (hi - lo) * rng.random(
                heads, np.float32)).astype(np.float32),
            "d": np.ones(heads, np.float32),
            "readout": 0.01 * normal(length, heads, xs.shape[-1])}


@functools.lru_cache(maxsize=None)
def _scan_check_program(model_key: str, fault):
    """(dt_bias, A_log, the other inputs) -> the gradients of ``sum(readout
    * recurrence)`` to the two decay leaves, jitted."""
    model = json.loads(model_key)

    def loss(dt_bias, a_log, v):
        dt = jax.nn.softplus(v["raw"] + dt_bias)
        logs = log_decays(model, dt, -jnp.exp(a_log), fault)
        return jnp.sum(v["readout"] * recurrence(
            v["xs"], dt, logs, v["b"], v["c"], v["d"]))

    return jax.jit(jax.grad(loss, (0, 1)))


def scan_decay_grads(model: dict, seed: int,
                     fault: str | None = None) -> dict:
    """``{"dt_bias", "A_log"}``: what the recurrence alone gives the two
    decay leaves on :func:`scan_check_inputs`, on the host."""
    v = {k: jnp.asarray(a) for k, a in scan_check_inputs(model, seed).items()}
    got = _scan_check_program(_frozen(model), fault)(
        v.pop("dt_bias"), v.pop("A_log"), v)
    return dict(zip(("dt_bias", "A_log"), (np.asarray(g) for g in got)))


def mamba_layer(model: dict, p: dict, x, precision: str = "f32",
                fault: str | None = None):
    """``x + mixer(RMSNorm(x))`` on one sequence ``x`` [L, hidden]. Its
    three parts (projection and convolution; the recurrence; gate, norm and
    projection back) are each under a ``jax.checkpoint``, so that a
    backward pass holds one part's intermediates at a time."""
    inner, conv_dim = mamba_sizes(model)
    heads, groups, n = (model["mamba_heads"], model["ssm_groups"],
                        model["ssm_state"])
    length, taps = x.shape[0], model["conv_kernel"]
    mm = functools.partial(_mm, precision=precision)

    @jax.checkpoint
    def project(p, x):
        u = _rms(x, p["norm"], model["rms_norm_eps"])
        z, xbc, dt = jnp.split(mm(u, p["w_in"]), (inner, inner + conv_dim),
                               -1)
        # position t sees t - taps + 1 .. t; before the sequence, zeros
        padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
        conv = sum(padded[k:k + length] * p["conv_w"][:, k]
                   for k in range(taps))
        if fault != "no_conv_bias":
            conv = conv + p["conv_b"]
        xs, b, c = jnp.split(jax.nn.silu(conv), (inner, inner + groups * n),
                             -1)
        return (z, xs.reshape(length, heads, -1),
                b.reshape(length, groups, n), c.reshape(length, groups, n),
                jax.nn.softplus(dt + p["dt_bias"]))

    @jax.checkpoint
    def gate(p, x, y, z):
        y = y.reshape(length, inner) * jax.nn.silu(z)
        # the gate norm's statistics over each group's channels apart
        y = y.reshape(length, groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + model["rms_norm_eps"])
        return x + mm(y.reshape(length, inner) * p["gate_norm"], p["w_out"])

    z, xs, b, c, dt = project(p, x)
    logs = log_decays(model, dt, -jnp.exp(p["A_log"]), fault)
    return gate(p, x, recurrence(xs, dt, logs, b, c, p["D"]), z)


def attention_layer(model: dict, p: dict, x, precision: str = "f32",
                    fault: str | None = None):
    """``x + attention(RMSNorm(x))``: causal, grouped-query, no rotary
    embedding."""
    del fault
    heads, kvh, d = (model["num_heads"], model["num_kv_heads"],
                     model["head_dim"])
    s = x.shape[0]
    mm = functools.partial(_mm, precision=precision)
    u = _rms(x, p["norm"], model["rms_norm_eps"])
    split = lambda y, n: y.reshape(s, n, d).transpose(1, 0, 2)
    q, k, v = (split(mm(u, p["wq"]), heads), split(mm(u, p["wk"]), kvh),
               split(mm(u, p["wv"]), kvh))
    live = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    @jax.checkpoint
    def one_head(args):
        qh, g = args
        scores = mm(qh, k[g].T) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
        return mm(probs, v[g])

    a = jax.lax.map(one_head, (q, jnp.arange(heads) // (heads // kvh)))
    return x + mm(a.transpose(1, 0, 2).reshape(s, heads * d), p["wo"])


def routing(model: dict, logits, bias, fault: str | None = None):
    """[tokens, experts_held] weights and the rows each held expert takes:
    ``s = sigmoid(logits)``; the ``experts_per_token`` largest of ``s +
    bias`` are picked; a picked expert's weight is its ``s`` over the
    picked ones' sum (plus 1e-20), times ``routed_scaling_factor``."""
    k, first, held = (model["experts_per_token"], model["first_expert"],
                      model["experts_held"])
    scores = (jax.nn.softmax(logits, -1) if fault == "softmax_router"
              else jax.nn.sigmoid(logits))
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if model["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * model["routed_scaling_factor"]
    hit = ids[:, :, None] == (first + jnp.arange(held))[None, None, :]
    weights = jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)
    return weights, jnp.sum(jnp.any(hit, axis=1), axis=0)


def experts_layer(model: dict, p: dict, x, precision: str = "f32",
                  fault: str | None = None):
    """``x + experts(RMSNorm(x))`` on one sequence -> (x, rows per held
    expert): the held experts' weighted parts and the shared expert's."""
    mm = functools.partial(_mm, precision=precision)
    u = _rms(x, p["norm"], model["rms_norm_eps"])
    weights, rows = routing(
        model, jnp.matmul(u, p["router"], precision=HIGHEST),
        p["router_bias"], fault)

    @jax.checkpoint
    def add_expert(y, expert):
        # one trip of the loop over the held experts; its backward pass
        # computes the trip's products again
        w_up, w_down, weight = expert
        act = jnp.square(jax.nn.relu(mm(u, w_up)))
        return y + weight[:, None] * mm(act, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["w_up"], p["w_down"], weights.T))
    if fault != "no_shared":
        y = y + mm(jnp.square(jax.nn.relu(mm(u, p["shared_up"]))),
                   p["shared_down"])
    return x + y, rows


def layer(model: dict, kind: str, p: dict, x, precision: str = "f32",
          fault: str | None = None):
    """One layer of ``kind`` on one sequence -> (x, rows per held expert;
    zeros for a layer without experts)."""
    if kind == EXPERTS:
        return experts_layer(model, p, x, precision, fault)
    fn = mamba_layer if kind == MAMBA else attention_layer
    return (fn(model, p, x, precision, fault),
            jnp.zeros(model["experts_held"], jnp.int32))


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
def _programs(model_key: str, precision: str, fault):
    """The jitted pieces: (fwd(kind, p, x), bwd(kind, p, x, dy), head and
    loss with their gradients); one pair of programs a layer kind."""
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
    def head(final_norm, head_w, x, targets, weight):
        with jax.default_matmul_precision("highest"):
            (loss, logits), grads = jax.value_and_grad(
                lambda f, w, x: _head_loss(model, f, w, x, targets, weight,
                                           precision),
                argnums=(0, 1, 2), has_aux=True)(final_norm, head_w, x)
            return loss, logits, grads

    return fwd, bwd, head


def _head_rows(length: int) -> int:
    return HEAD_ROWS if length % HEAD_ROWS == 0 else length


def warm(model: dict, precision: str = "f32", fault: str | None = None):
    """Compiles a step's programs (a forward and a backward one a layer
    kind, the head's and the per-leaf ones) for sequences of the model's
    ``seq_len``, from shapes alone: nothing is placed on a device and
    nothing runs. The first call of each then finds it compiled (JAX keeps
    an executable with the lowering it was made from and, where its
    persistent compilation cache is on, on disk); a driver calls this in a
    thread of its own while its job's calls keep the chip and the main
    thread."""
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
    # the recurrence alone (``scan_decay_grads``), at its own length
    v = {k: f32(*a.shape) for k, a in scan_check_inputs(model, 0).items()}
    _scan_check_program(_frozen(model), fault).lower(
        v.pop("dt_bias"), v.pop("A_log"), v).compile()


def _layers(model: dict, params: dict):
    """Per layer, in depth order: (kind, leaf-name prefix, index along the
    period axis, the layer's leaves)."""
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
    """The depth indices of the expert layers."""
    return [i for i, kind in enumerate(kinds(model)) if kind == EXPERTS]


def loss_and_grads(model: dict, params: dict, batch,
                   precision: str = "f32", fault: str | None = None):
    """Loss of a batch [b, L], its gradient in every leaf, and the rows the
    held experts took, [expert layers, experts_held] summed over the
    batch."""
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
    return total, grads, rows[expert_layers(model)]


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
