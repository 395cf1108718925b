"""Driver of the toy family: the "program" is a jitted ``jax.numpy`` step
written here, since this family tests the harness and not the platform. It
brings everything the harness asks a driver for: the five calls of a run
(``setup``, ``window``, ``end_to_end``, ``counters`` with ``model_flops``,
``check``), ``follow`` / ``readings`` / ``controls`` for ``control.py`` and
``abstract_step`` for the bytes-over-the-floor test and ``memory_probe.py``.

Set-up builds one jitted step, drives it from the seeded weights through
its first three steps on rows that all differ, and hands the same step and
state to the window; ``check`` lets the plain reference follow those three.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from perfbench.lib import compare

PROBE_STEPS = 3
MIN_WINDOW_STEPS = 8


@dataclasses.dataclass
class Job:
    cell: object
    step: object                # the jitted (params, x, y) -> (params, loss)
    params: dict                # the state the window continues from
    start: dict                 # the seeded weights
    rows: tuple                 # (inputs, targets) of the whole data set
    steps_done: int
    window_steps: int
    produced: dict


def _core_step(model: dict, lr: float):
    import jax
    import jax.numpy as jnp

    def loss_of(params, x, y):
        h = jnp.tanh(x @ params["dense0/kernel"] + params["dense0/bias"])
        out = h @ params["dense1/kernel"] + params["dense1/bias"]
        return jnp.mean((out - y) ** 2)

    def step(params, x, y):
        loss, grads = jax.value_and_grad(loss_of)(params, x, y)
        return {k: params[k] - lr * grads[k] for k in params}, loss

    return step


def _batch(job_rows, batch: int, i: int):
    x, y = job_rows
    at = (i * batch) % (len(x) - batch + 1)
    return x[at:at + batch], y[at:at + batch]


def _rows(cell):
    model, n = cell.config["model"], cell.traffic["dataset"]["rows"]
    rng = np.random.default_rng(cell.seed)
    x = rng.standard_normal((n, model["inputs"])).astype(np.float32)
    w = rng.standard_normal((model["inputs"], model["outputs"]))
    return x, (x @ w).astype(np.float32)


def window_steps(traffic: dict, seconds: float) -> int:
    w = traffic["window"]
    return max(MIN_WINDOW_STEPS, round(w["steps"] * seconds / w["at_seconds"]))


def _drive(job: Job, steps: int) -> list:
    """The one call that set-up's probe and the window both go through."""
    import jax

    batch, losses = job.cell.traffic["train"]["batch_size"], []
    for i in range(job.steps_done, job.steps_done + steps):
        job.params, loss = job.step(job.params, *_batch(job.rows, batch, i))
        losses.append(loss)
    job.steps_done += steps
    return [float(v) for v in jax.block_until_ready(losses)]


def setup(cell) -> Job:
    import jax

    model, lr = cell.config["model"], cell.config["train"]["learning_rate"]
    start = cell.reference.init(model, cell.seed)
    job = Job(cell, jax.jit(_core_step(model, lr)), dict(start), start,
              _rows(cell), 0, window_steps(cell.traffic, cell.seconds), {})
    first = _drive(job, 1)
    after_one = {k: np.asarray(v) for k, v in job.params.items()}
    rest = _drive(job, PROBE_STEPS - 1)
    job.produced = {
        "loss": first + rest,
        "grad": {k: (start[k] - after_one[k]) / lr for k in start},
        "params": {k: np.asarray(v) for k, v in job.params.items()},
    }
    return job


def window(job: Job) -> dict:
    start = time.perf_counter()
    losses = _drive(job, job.window_steps)
    return {"losses": losses, "optimizer_steps": len(losses),
            "train_phase_s": time.perf_counter() - start}


def end_to_end(job: Job, out: dict, window_s: float) -> dict:
    batch = job.cell.traffic["train"]["batch_size"]
    return {"train_img_per_s": out["optimizer_steps"] * batch / window_s}


def model_flops(model: dict, batch: int, steps: int) -> float:
    """Two matrix products a layer forward, twice that backward but for the
    first layer's input gradient; multiply-adds as 2."""
    first = 2.0 * batch * model["inputs"] * model["hidden"]
    second = 2.0 * batch * model["hidden"] * model["outputs"]
    return steps * (2 * first + 3 * second)


def counters(job: Job, out: dict, window_s: float) -> dict:
    batch = job.cell.traffic["train"]["batch_size"]
    return {"optimizer_steps": out["optimizer_steps"],
            "train_phase_s": out["train_phase_s"], "window_s": window_s,
            "batch": batch, "attempted": out["optimizer_steps"],
            "model_flops": model_flops(job.cell.config["model"], batch,
                                       out["optimizer_steps"])}


def follow(job: Job, precision: str = "f32", controls: bool = False) -> dict:
    """What the plain reference gets for the probe's steps, in the shape of
    ``job.produced``; :func:`controls` needs nothing beside it."""
    ref, model = job.cell.reference, job.cell.config["model"]
    lr = job.cell.config["train"]["learning_rate"]
    batch = job.cell.traffic["train"]["batch_size"]
    params, out = dict(job.start), {"loss": []}
    for i in range(PROBE_STEPS):
        params, loss, grads = ref.train_step(
            model, lr, params, *_batch(job.rows, batch, i), precision)
        out["loss"].append(loss)
        if i == 0:
            out["grad"] = grads
    out["params"] = params
    return out


def readings(job: Job, got: dict, want: dict) -> dict:
    def delta(tree):
        return {k: tree[k] - job.start[k] for k in job.start}

    return {
        "loss_gap": max(abs(g - w) / abs(w)
                        for g, w in zip(got["loss"], want["loss"])),
        "grad_gap": compare.worst_leaf_gap(got["grad"], want["grad"]),
        "update_gap": compare.worst_leaf_gap(delta(got["params"]),
                                             delta(want["params"])),
    }


def controls(job: Job, want: dict) -> dict:
    """``bf16``: the reference with its products' operands in bfloat16."""
    return {"bf16": follow(job, "bf16")}


def check(job: Job, out: dict) -> dict:
    numbers = readings(job, job.produced, follow(job))
    numbers["window_steps_missing"] = float(job.window_steps - sum(
        np.isfinite(v) for v in out["losses"]))
    return numbers


def abstract_step(cell):
    import jax
    import jax.numpy as jnp

    model, batch = cell.config["model"], cell.traffic["train"]["batch_size"]
    params = {k: jax.ShapeDtypeStruct(shape, jnp.float32)
              for k, shape in cell.reference.param_shapes(model).items()}
    return _core_step(model, cell.config["train"]["learning_rate"]), (
        params,
        jax.ShapeDtypeStruct((batch, model["inputs"]), jnp.float32),
        jax.ShapeDtypeStruct((batch, model["outputs"]), jnp.float32))
