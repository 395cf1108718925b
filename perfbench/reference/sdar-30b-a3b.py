"""Plain reference of ``sdar-30b-a3b``: the SDAR-30B-A3B-Chat decoder (48
identical layers of grouped-query attention with per-head q/k RMSNorm and a
128-expert top-8 layer, untied embedding and head) trained by block
diffusion, as one chip's share of an 8-way expert-parallel job: the experts
``first_expert .. first_expert + experts_held - 1`` of every layer and a
slice of the vocabulary. ``jax.numpy``, float32, every matrix product at
``Precision.HIGHEST``, the mask ``M`` as a dense matrix, a loop over the
held experts with every token multiplied by every one of them; no kernel,
nothing imported from the program.

So that a step at the cell's size fits beside the 7.75 GB of parameters
and Adam moments, it is computed in pieces: one sequence and one layer at a
time (the layer's backward pass recomputes its forward pass from the saved
layer input), attention one query head at a time.

Departures from the published model, all listed under ``assumed`` in the
configuration: block length, noise schedule, loss weight and mask id (the
published config gives none), and the cut itself.

``precision``: ``"f32"``, or ``"int8"``: both operands of every matrix
product rounded to symmetric per-tensor int8, the nearest precision below
the configuration's bfloat16 (``control.py``'s control). ``fault``:
``None``, ``"capacity"`` (each held expert takes at most as many rows of a
sequence as even routing would give it, the rest dropped: a capacity factor
of 1) or ``"causal"`` (a token-causal mask over the ``2 L`` positions in
place of ``M``): planted faults that the cell's limits have to catch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
TRAIN_NOISE, EVAL_NOISE = 0, 1


def param_shapes(model: dict) -> dict:
    n, h, d = model["num_layers"], model["hidden_size"], model["head_dim"]
    q, kv = model["num_heads"] * d, model["num_kv_heads"] * d
    e, f = model["experts_held"], model["expert_width"]
    v = model["vocab_size"]
    return {
        "embed": (v, h),
        "layers/attn_norm": (n, h), "layers/wq": (n, h, q),
        "layers/wk": (n, h, kv), "layers/wv": (n, h, kv),
        "layers/q_norm": (n, d), "layers/k_norm": (n, d),
        "layers/wo": (n, q, h), "layers/moe_norm": (n, h),
        "layers/router": (n, h, model["num_experts"]),
        "layers/w_gate": (n, e, h, f), "layers/w_up": (n, e, h, f),
        "layers/w_down": (n, e, f, h),
        "final_norm": (h,), "head": (h, v),
    }


def init(model: dict, seed: int) -> dict:
    """The weights a job of this seed starts from, by the program's
    documented rule (``training/tasks.BlockDiffLMTask.init_variables`` and
    ``models/blockdiff_lm.init_params``), re-derived: under one ``jit``, on
    ``rng = jax.random.key(seed, impl="rbg")``, leaf ``i`` of
    :func:`param_shapes`, in its order, is ``std * normal(split(rng,
    n_leaves)[i], shape, float32)``, ``std`` the configuration's
    ``init_std`` (``embed_init_std`` for the embedding); norms at one. On
    the default device."""
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
    """``n`` full sequences: ids uniform over the slice less the mask id."""
    rng = np.random.default_rng([seed, 1])
    ids = rng.integers(0, model["vocab_size"] - 1, (n, model["seq_len"]))
    ids = ids + (ids >= model["mask_token_id"])
    return ids.astype(np.int32)


def noise(seed: int, stream: int, index: int, batch: int, seq_len: int,
          block: int):
    """The program's documented rule (``training/data.py``), re-derived:
    ``(masked [batch, L] bool, t [batch, L] float32)``."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), stream), index)
    kt, ku = jax.random.split(key)
    t = 1.0 - jax.random.uniform(kt, (batch, seq_len // block), jnp.float32)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(ku, (batch, seq_len), jnp.float32) < t
    return np.asarray(masked), np.asarray(t)


def mask_matrix(seq_len: int, block: int, fault: str | None = None):
    """``M`` as a dense [2L, 2L] bool matrix, noisy copy first."""
    i = np.arange(2 * seq_len)
    if fault == "causal":
        return i[:, None] >= i[None, :]
    clean = i >= seq_len
    blk = (i % seq_len) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return ((~q_clean & ~k_clean & (q_blk == k_blk))
            | (~q_clean & k_clean & (k_blk < q_blk))
            | (q_clean & k_clean & (k_blk <= q_blk)))


def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


def _mm(a, b, precision: str):
    if precision == "int8":
        a, b = _int8(a), _int8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, theta):
    """[heads, s, d], both copies at positions 0..s/2-1; rotate-half."""
    s, d = x.shape[-2], x.shape[-1]
    pos = jnp.tile(jnp.arange(s // 2, dtype=jnp.float32), 2)
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


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


def layer(model: dict, p: dict, x, mask, precision: str = "f32",
          fault: str | None = None):
    """One decoder layer on one sequence ``x`` [2L, hidden] -> (x, rows per
    held expert). ``p`` holds the layer's leaves without the depth axis."""
    heads, kvh, d = (model["num_heads"], model["num_kv_heads"],
                     model["head_dim"])
    eps, s = model["rms_norm_eps"], x.shape[0]
    mm = functools.partial(_mm, precision=precision)
    h = _rms(x, p["attn_norm"], eps)
    split = lambda y, n: y.reshape(s, n, d).transpose(1, 0, 2)
    q = _rotary(_rms(split(mm(h, p["wq"]), heads), p["q_norm"], eps),
                model["rope_theta"])
    k = _rotary(_rms(split(mm(h, p["wk"]), kvh), p["k_norm"], eps),
                model["rope_theta"])
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
        # one trip of the loop over the held experts. A loop the compiler
        # sees as one: unrolled, 16 experts took it 2 s apiece. Its backward
        # pass computes a trip's products again: kept for all 16 trips they
        # are 2.9 GiB of the layer's backward program (4.25 GiB of
        # temporaries against 1.34), which the chip does not have beside
        # 10.3 GB of parameters, moments and gradients
        w_gate, w_up, w_down, weight = expert
        act = jax.nn.silu(mm(h, w_gate)) * mm(h, w_up)
        return y + weight[:, None] * mm(act, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], weights.T))
    return x + y, rows


def _layer_leaves(params: dict, i: int) -> dict:
    return {k[len("layers/"):]: v[i] for k, v in params.items()
            if k.startswith("layers/")}


def _head_loss(model, final_norm, head, x, ids, masked, t, precision):
    """The sequence's part of the loss: over the batch's tokens it is
    divided by later."""
    length = ids.shape[0]
    logits = _mm(_rms(x[:length], final_norm, model["rms_norm_eps"]), head,
                 precision)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, ids[:, None], -1)[:, 0]
    return jnp.sum(jnp.where(masked, nll / t, 0.0)), logits


@functools.lru_cache(maxsize=None)
def _jitted(model_items: tuple, precision: str, fault):
    """The three jitted pieces: a layer forward, a layer backward, head and
    loss with their gradients. ``M`` is an argument of the two that use it
    (as a constant of theirs its 64 MB were compiled into each program, a
    minute apiece)."""
    model = dict(model_items)

    @jax.jit
    def fwd(p, x, mask):
        return layer(model, p, x, mask, precision, fault)

    @jax.jit
    def bwd(p, x, dy, mask):
        _, vjp = jax.vjp(
            lambda p, x: layer(model, p, x, mask, precision, fault)[0], p, x)
        return vjp(dy)

    @jax.jit
    def head(final_norm, head_w, x, ids, masked, t):
        (loss, logits), grads = jax.value_and_grad(
            lambda f, w, x: _head_loss(model, f, w, x, ids, masked, t,
                                       precision),
            argnums=(0, 1, 2), has_aux=True)(final_norm, head_w, x)
        return loss, logits, grads

    return fwd, bwd, head


@functools.lru_cache(maxsize=None)
def _programs(model_items: tuple, precision: str, fault):
    """:func:`_jitted` with ``M`` on the device."""
    model = dict(model_items)
    fwd, bwd, head = _jitted(model_items, precision, fault)
    mask = jnp.asarray(mask_matrix(model["seq_len"], model["block_length"],
                                   fault))
    return (lambda p, x: fwd(p, x, mask),
            lambda p, x, dy: bwd(p, x, dy, mask), head)


def warm(model: dict, precision: str = "f32", fault: str | None = None):
    """Compiles a step's programs (the three of :func:`_jitted` and the
    per-leaf ones) for sequences of the model's ``seq_len``, from shapes
    alone: nothing is placed on a device
    and nothing runs. At the cell's size the TPU's compiler takes over a
    minute for them (a float32 product at ``HIGHEST`` is six passes, seconds
    to compile apiece), on four or five of the host's cores. The first
    call of each then finds it compiled (JAX keeps an executable with the
    lowering it was made from and, where its persistent compilation cache
    is on, on disk); a driver calls this in a thread of its own while its
    job's calls keep the chip and the main thread."""
    length, hidden = model["seq_len"], model["hidden_size"]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    p = {k[len("layers/"):]: f32(*shape[1:])
         for k, shape in param_shapes(model).items()
         if k.startswith("layers/")}
    x = f32(2 * length, hidden)
    mask = jax.ShapeDtypeStruct((2 * length, 2 * length), jnp.bool_)
    fwd, bwd, head = _jitted(_frozen(model), precision, fault)
    fwd.lower(p, x, mask).compile()
    bwd.lower(p, x, x, mask).compile()
    ids = jax.ShapeDtypeStruct((length,), jnp.int32)
    head.lower(f32(hidden), f32(hidden, model["vocab_size"]), x, ids,
               jax.ShapeDtypeStruct((length,), jnp.bool_),
               f32(length)).compile()
    # the small ones of a step, a second or so apiece: a leaf's part of the
    # gradient added in, Adam on a leaf, once a shape
    shapes = param_shapes(model)
    for shape in set(shapes.values()):
        leaf = f32(*shape)
        _adam_leaf.lower(leaf, leaf, leaf, leaf, f32(), f32()).compile()
    layer_index = jax.ShapeDtypeStruct((), jnp.int32, weak_type=True)
    for shape in {shape for name, shape in shapes.items()
                  if name.startswith("layers/")}:
        _add_at.lower(f32(*shape), layer_index, f32(*shape[1:])).compile()
    _add_at.lower(f32(*shapes["embed"]),
                  jax.ShapeDtypeStruct((2 * length,), jnp.int32),
                  x).compile()


def _frozen(model: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in model.items()
                        if not isinstance(v, (list, dict))))


def _sequence_inputs(model, params, ids, masked):
    noisy = jnp.where(masked, model["mask_token_id"], ids)
    both = jnp.concatenate([noisy, ids])
    return both, params["embed"][both]


def loss_and_grads(model: dict, params: dict, batch, masked, t,
                   precision: str = "f32", fault: str | None = None):
    """Loss of a batch [b, L], its gradient in every leaf, and the rows the
    held experts took, [layers, experts_held] summed over the batch."""
    fwd, bwd, head = _programs(_frozen(model), precision, fault)
    n = model["num_layers"]
    grads = {k: jnp.zeros_like(v) for k, v in params.items()}
    total, rows = 0.0, np.zeros((n, model["experts_held"]), np.int64)
    scale = 1.0 / batch.size
    for ids, m, tt in zip(jnp.asarray(batch), jnp.asarray(masked),
                          jnp.asarray(t)):
        both, x = _sequence_inputs(model, params, ids, m)
        inputs = []
        for i in range(n):
            inputs.append(x)
            x, r = fwd(_layer_leaves(params, i), x)
            rows[i] += np.asarray(r)
        loss, _, (g_norm, g_head, dx) = head(
            params["final_norm"], params["head"], x, ids, m, tt)
        total += float(loss) * scale
        grads["final_norm"] += g_norm * scale
        grads["head"] += g_head * scale
        dx = dx * scale
        for i in reversed(range(n)):
            g, dx = bwd(_layer_leaves(params, i), inputs[i], dx)
            for k, v in g.items():
                grads["layers/" + k] = _add_at(grads["layers/" + k], i, v)
        grads["embed"] = _add_at(grads["embed"], both, dx)
    return total, grads, rows


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_at(total, index, part):
    """``total[index] += part`` in place: the sum's buffer is donated, so a
    leaf of 0.6 GB is never held twice."""
    return total.at[index].add(part)


def _heads(model, params, batch, masked, t, precision, fault):
    """Per sequence of the batch, the forward pass alone: (its part of the
    loss, its logits)."""
    fwd, _, head = _programs(_frozen(model), precision, fault)
    for ids, m, tt in zip(jnp.asarray(batch), jnp.asarray(masked),
                          jnp.asarray(t)):
        _, x = _sequence_inputs(model, params, ids, m)
        for i in range(model["num_layers"]):
            x, _ = fwd(_layer_leaves(params, i), x)
        yield head(params["final_norm"], params["head"], x, ids, m, tt)[:2]


def forward(model: dict, params: dict, batch, masked, precision: str = "f32",
            fault: str | None = None):
    """Logits [b, L, vocab] at the noisy copy's positions."""
    ones = np.ones(batch.shape, np.float32)
    return jnp.stack([logits for _, logits in _heads(
        model, params, batch, masked, ones, precision, fault)])


def loss(model: dict, params: dict, batch, masked, t, precision: str = "f32",
         fault: str | None = None) -> float:
    """The batch's loss alone (validation)."""
    return sum(float(part) for part, _ in _heads(
        model, params, batch, masked, t, precision, fault)) / batch.size


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
    """One optimiser step on ``batch`` [b, L]; the noise is that of the
    job's ``opt["count"]``-th step. -> (params, opt, loss, grads, rows);
    ``params`` and ``opt`` as passed in are consumed. The gradient comes
    back on the host (numpy) where ``keep_grads``, else as ``None``."""
    masked, t = noise(seed, TRAIN_NOISE, opt["count"], batch.shape[0],
                      model["seq_len"], model["block_length"])
    value, grads, rows = loss_and_grads(model, params, batch, masked, t,
                                        precision, fault)
    kept = {k: np.asarray(v) for k, v in grads.items()} if keep_grads \
        else None
    params, opt = adam_update(dict(params), opt, grads, lr)
    return params, opt, value, kept, rows


def eval_loss(model: dict, seed: int, params: dict, batch,
              precision: str = "f32", fault: str | None = None) -> float:
    masked, t = noise(seed, EVAL_NOISE, 0, batch.shape[0], model["seq_len"],
                      model["block_length"])
    return loss(model, params, batch, masked, t, precision, fault)
