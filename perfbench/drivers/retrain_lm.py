"""Driver for retraining traffic on token data: the window is one
``training.trainer.train_model`` call on a block-diffusion language model,
as ``drivers/retrain.py``'s is on a U-Net.

The job is one continuing ``train_model`` job under ``resume=True``, carried
from call to call by its own (streamed) checkpoints. It starts from nothing:
the program draws the job's weights from the seed by its documented rule
(``models/blockdiff_lm.init_params``), which the plain reference re-derives
on its own, as it does the noise.

1. the *probe*: the job's first three optimiser steps at the cell's batch
   and sequence length, as one-step epochs over the data set's first rows,
   in one call that validates after each and saves once, at its end: Adam's
   first moment then is 0.1 g3 + 0.09 g2 + 0.081 g1 of the three gradients,
   and the parameters have changed three times;
2. the *first epoch* over the whole data set through the window's own call,
   feed and program (the whole-epoch scan), saved once at its end;
3. the *window*: the same job for the traffic's ``window.epochs`` further
   epochs: it restores at its start, validates every epoch, saves once at
   its end and registers.

``check`` lets the plain reference follow the three probe steps and the
first ``EPOCH_STEPS`` steps of the first epoch (its weights and noise
re-derived from the documented rules, its rows in the program's documented
order) and compares losses, Adam's first moment, the parameters' change,
validation losses and the rows routed to the held experts. A reference step
takes 8.5 s on the chip (float32 at ``highest``, dense mask, every token
through every held expert), and a run has 360 s for set-up, window and
check together: the whole first epoch (24 steps) does not fit, so the
epoch's steps are followed as far as the time allows, by each step's loss
(``train_step_loss`` of the job's tracking run).

A run that finds no compiled program compiles for two to three minutes, one
program after another on four or five of the host's 13 cores: the probe's
two, the first epoch's two (40 s a pair) and the reference's (over a
minute). Only the probe's are needed at once. So ``setup`` starts two threads that
compile the others from their shapes (:func:`_compile_epoch`,
``reference.warm``), into JAX's persistent compilation cache, which
``run.py`` turns on: the first-epoch call and ``check`` then load what they
would have compiled. Both threads have ended before ``setup`` returns;
nothing of this runs beside the window.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import threading
import time

import numpy as np

from perfbench.drivers.retrain import _history, window_epochs
from perfbench.lib import compare, lm_flops, order

PROBE_STEPS = 3
FIRST_EPOCHS = 1
EPOCH_STEPS = 2     # steps of the first epoch that the reference follows


@dataclasses.dataclass
class Job:
    cell: object
    model: dict                 # the configuration's "model" group
    base_cfg: object            # TrainConfig without epochs
    tokens: np.ndarray          # [sequences, L] int32, the whole data set
    n_probe: int                # the probe's rows are the first n_probe
    steps_per_epoch: int
    epochs_done: int
    window_epochs: int
    produced: dict              # what the program produced: "probe", "epoch"
                                # (losses, rows, and norms of trees)
    call: object                # (epochs, tokens, register) -> TrainResult


def _norms(tree: dict, base: dict | None = None) -> dict:
    """leaf -> the norm of the leaf (less ``base``'s). What ``correct``
    compares of a moment or an update is each leaf's norm
    (``lib/compare.leaf_gaps``); it is taken where the leaves are (on the
    chip, the device: the host needed 13 s a tree), under one ``jit``, in
    float32, whose sum over a leaf's 1.5e8 elements is good to 1e-6 of the
    norm."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree, base):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            v if base is None else v - base[k]))) for k, v in tree.items()}

    return {k: float(v) for k, v in norms(dict(tree), base).items()}


def _built(model_cfg, train_cfg):
    """(task, model, optimiser, the job's train state as shapes), from the
    program."""
    import jax
    import optax

    from robotic_discovery_platform_tpu.training import tasks, trainer

    task = tasks.task_for(model_cfg)
    model, tx = task.build(model_cfg), optax.adam(train_cfg.learning_rate)
    return task, model, tx, jax.eval_shape(lambda: trainer.task_state(
        task, model, tx, jax.random.key(0), train_cfg))


def _epoch_programs(model_cfg, train_cfg, sequences: int) -> list:
    """[(train_epoch, args), (eval_epoch, args)]: the two whole-epoch scans
    that a ``train_model`` call over ``sequences`` resident sequences
    dispatches, built as the job builds them
    (``trainer.make_epoch_runners``), and their arguments as
    ``jax.ShapeDtypeStruct``s: the state, a split's rows, the job's seed
    once a row, the epoch's grid of row indices."""
    import jax
    import jax.numpy as jnp

    from robotic_discovery_platform_tpu.training import trainer

    task, model, tx, state = _built(model_cfg, train_cfg)
    runners = trainer.make_epoch_runners(
        model, tx, task.make_loss(train_cfg), task=task)

    def args(rows: int):
        grid = order.epoch_order(rows, train_cfg.batch_size, False, None)
        return (state,
                jax.ShapeDtypeStruct((rows, model_cfg.seq_len), jnp.int32),
                jax.ShapeDtypeStruct((rows,), jnp.int32),
                jax.ShapeDtypeStruct(grid.shape, jnp.int32))

    return [(fn, args(len(rows))) for fn, rows in zip(
        runners, order.train_val_split(
            sequences, train_cfg.validation_split, 0))]


def _compile_epoch(model_cfg, train_cfg, sequences: int) -> None:
    """Compiles :func:`_epoch_programs` from their shapes; nothing is placed
    and nothing runs."""
    for fn, args in _epoch_programs(model_cfg, train_cfg, sequences):
        fn.lower(*args).compile()


def _ahead(fn, *args) -> threading.Thread:
    """``fn(*args)`` in a thread of its own, started. A thread that fails
    prints its traceback and costs the run the time it would have saved,
    not its result."""
    thread = threading.Thread(target=fn, args=args, daemon=True,
                              name=f"perfbench-{fn.__name__}")
    thread.start()
    return thread


def _manager(cfg):
    from robotic_discovery_platform_tpu.training.checkpoint import (
        CheckpointManager)

    return CheckpointManager(cfg.checkpoint_dir, keep=cfg.keep_checkpoints)


def _read(cfg, abstract, step: int, what: str) -> dict:
    """One part of a saved state as flat arrays on the device: ``"params"``
    or ``"mu"`` (Adam's first moment)."""
    import jax

    only = abstract.replace(
        params=abstract.params if what == "params" else None,
        opt_state=(abstract.opt_state[0]._replace(count=None, nu=None),)
        if what == "mu" else None,
        batch_stats=None, epoch=None, best_val_loss=None)
    ckpt = _manager(cfg)
    try:
        state = ckpt.restore_streamed({"state": only}, step=step,
                                      place=jax.device_put)["state"]
    finally:
        ckpt.close()
    from flax.traverse_util import flatten_dict

    return flatten_dict(state.params if what == "params"
                        else state.opt_state[0].mu, sep="/")


def _probe_rows(batch: int, split: float, seed: int) -> int:
    """The fewest rows whose split leaves one full batch to train on."""
    for n in range(batch + 1, 4 * batch + 4):
        if len(order.train_val_split(n, split, seed)[0]) == batch:
            return n
    raise SystemExit(f"no probe of one batch of {batch} at split {split}")


def setup(cell) -> Job:
    """Everything before the window."""
    from robotic_discovery_platform_tpu.observability import instruments as obs
    from robotic_discovery_platform_tpu.training.trainer import train_model
    from robotic_discovery_platform_tpu.utils.config import (
        BlockDiffLMConfig, TrainConfig)

    clock = [time.time()]

    def lap(what):
        clock.append(time.time())
        print(f"perfbench set-up: {what} {clock[-1] - clock[-2]:.1f} s",
              file=sys.stderr)

    config, traffic = cell.config, cell.traffic
    data, work = traffic["dataset"], cell.workdir
    model = config["model"]
    if data["seq_len"] != model["seq_len"]:
        raise SystemExit("the traffic's sequences are not the model's")
    model_cfg = BlockDiffLMConfig(**model)
    base_cfg = TrainConfig(**{
        **config["train"], **traffic["train"],
        "seed": cell.seed % (2 ** 31 - 1),
        "tracking_uri": f"file:{work / 'mlruns'}",
        "checkpoint_dir": str(work / "checkpoints"),
    })
    batch, split = base_cfg.batch_size, base_cfg.validation_split
    n_probe = _probe_rows(batch, split, base_cfg.seed)
    tokens = cell.reference.tokens(model, cell.seed, data["sequences"])
    lap("data set")

    # what the probe does not need at once compiles beside it (the
    # module's docstring)
    epoch_ahead = _ahead(_compile_epoch, model_cfg, base_cfg, len(tokens))
    check_ahead = _ahead(cell.reference.warm, model)
    abstract = _built(model_cfg, base_cfg)[-1]

    def call(epochs, rows, register):
        # one save a call, at its end: checkpoint_every = the call's last
        # epoch
        cfg = dataclasses.replace(base_cfg, epochs=epochs,
                                  checkpoint_every=epochs)
        return train_model(cfg, model_cfg, arrays=(rows, None), resume=True,
                           register=register)

    def routed(fn):
        before = obs.MOE_ROUTED_ROWS.value
        out = fn()
        return out, obs.MOE_ROUTED_ROWS.value - before

    # no checkpoint yet: the job starts from the weights the program draws
    # from the seed
    res, rows = routed(lambda: call(PROBE_STEPS, tokens[:n_probe], False))
    lap("probe call")
    if res.epochs_run != PROBE_STEPS:
        raise SystemExit("probe did not run its steps")
    probe = {
        "loss": _history(res, "train_loss"),
        "val_loss": _history(res, "val_loss"),
        "rows": rows,
        "moment": _norms(_read(base_cfg, abstract, PROBE_STEPS, "mu")),
        "update": _norms(_read(base_cfg, abstract, PROBE_STEPS, "params"),
                         cell.reference.init(model, base_cfg.seed)),
    }
    lap("reading the probe's checkpoint")

    n_train = len(order.train_val_split(len(tokens), split,
                                        base_cfg.seed)[0])
    steps = math.ceil(n_train / batch)
    done = PROBE_STEPS + FIRST_EPOCHS
    epoch_ahead.join()
    lap("waiting for the first epoch's programs")
    res = call(done, tokens, False)
    lap("first-epoch call")
    if res.epochs_run != FIRST_EPOCHS:
        raise SystemExit("the first epoch did not run")
    epoch = {
        "step_loss": _history(res, "train_step_loss")[:EPOCH_STEPS],
        # read, not compared: the reference does not follow the epoch to
        # its end
        "loss": _history(res, "train_loss"),
        "val_loss": _history(res, "val_loss"),
    }
    check_ahead.join()
    lap("waiting for the reference's programs")
    return Job(cell, model, base_cfg, tokens, n_probe, steps, done,
               window_epochs(traffic, cell.seconds),
               {"probe": probe, "epoch": epoch}, call)


def window(job: Job) -> dict:
    from robotic_discovery_platform_tpu.observability import instruments as obs

    before, rows = obs.TRAIN_STEP.sum, obs.MOE_ROUTED_ROWS.value
    res = job.call(job.epochs_done + job.window_epochs, job.tokens, True)
    steps = res.epochs_run * job.steps_per_epoch
    return {
        "result": res,
        "optimizer_steps": steps,
        "sequences": steps * job.base_cfg.batch_size,
        "train_phase_s": (obs.TRAIN_STEP.sum - before) * job.steps_per_epoch,
        "routed_rows": obs.MOE_ROUTED_ROWS.value - rows,
    }


def end_to_end(job: Job, out: dict, window_s: float) -> dict:
    """One example of ``train_img_per_s`` is one sequence of ``seq_len``
    tokens."""
    return {"train_img_per_s": out["sequences"] / window_s}


def counters(job: Job, out: dict, window_s: float) -> dict:
    cfg = job.base_cfg
    n_val = len(order.train_val_split(len(job.tokens), cfg.validation_split,
                                      cfg.seed)[1])
    eval_batches = job.window_epochs * math.ceil(n_val / cfg.batch_size)
    steps = out["optimizer_steps"]
    return {"optimizer_steps": steps, "train_phase_s": out["train_phase_s"],
            "window_s": window_s, "window_epochs": job.window_epochs,
            "batch": cfg.batch_size, "eval_batches": eval_batches,
            "routed_rows": out["routed_rows"],
            "tokens_per_s": steps * cfg.batch_size * job.model["seq_len"]
            / window_s,
            "model_flops": lm_flops.window_flops(
                job.model, cfg.batch_size, steps, eval_batches,
                out["routed_rows"]),
            "attempted": steps}


def follow(job: Job, precision: str = "f32", fault: str | None = None,
           controls: bool = False, probe_only: bool = False) -> dict:
    """What the plain reference gets for the probe's steps and the first
    epoch's first ``EPOCH_STEPS``, in the shape of ``job.produced``.
    ``controls`` adds, under ``val_loss_stale``, validation with the
    parameters the job started from; ``probe_only`` stops after the probe
    (the faults' controls, which a probe number has to catch)."""
    ref, model, cfg = job.cell.reference, job.model, job.base_cfg
    split, batch, seed = cfg.validation_split, cfg.batch_size, cfg.seed
    params = ref.init(model, seed)
    opt = ref.adam_init(params)

    def step(params, opt, rows):
        params, opt, loss, _, taken = ref.train_step(
            model, cfg.learning_rate, seed, params, opt, rows, precision,
            fault)
        return params, opt, loss, int(taken.sum())

    def validation(params, rows):
        # the program's: the mean over full batches of each batch's mean,
        # the tail filled by repeating rows
        grid = order.epoch_order(len(rows), batch, False, None)
        return float(np.mean([ref.eval_loss(model, seed, params, rows[b],
                                            precision, fault)
                              for b in grid]))

    rows = job.tokens[:job.n_probe]
    tr, va = order.train_val_split(job.n_probe, split, seed)
    # the planted fault of the evaluation path, read before the first step
    # so that the starting parameters need not stay on the device
    stale = validation(params, rows[va]) if controls else None
    probe = {"loss": [], "val_loss": [], "rows": 0}
    rng = np.random.default_rng(seed)
    for _ in range(PROBE_STEPS):
        grid = order.epoch_order(len(tr), batch, True, rng)
        params, opt, loss, taken = step(params, opt, rows[tr][grid[0]])
        probe["loss"].append(loss)
        probe["rows"] += taken
        probe["val_loss"].append(validation(params, rows[va]))
        if controls:
            probe.setdefault("val_loss_stale", []).append(stale)
    probe["moment"] = _norms(opt["mu"])
    probe["update"] = _norms(params, ref.init(model, seed))
    if probe_only:
        return {"probe": probe}

    tr, va = order.train_val_split(len(job.tokens), split, seed)
    grid = order.epoch_order(len(tr), batch, True,
                             np.random.default_rng(seed))
    losses = []
    for b in grid[:EPOCH_STEPS]:
        params, opt, loss, _ = step(params, opt, job.tokens[tr][b])
        losses.append(loss)
    return {"probe": probe, "epoch": {"step_loss": losses}}


def readings(job: Job, got: dict, want: dict) -> dict:
    """Every number read: what ``got`` (the program, or a control put in
    its place) shows against ``want`` (the plain reference)."""
    def rel(got, want):
        return max(abs(g - w) / abs(w) for g, w in zip(got, want))

    gp, wp, ge, we = got["probe"], want["probe"], got["epoch"], want["epoch"]
    return {
        "loss_gap": rel(gp["loss"], wp["loss"]),
        "val_loss_gap": rel(gp["val_loss"], wp["val_loss"]),
        # the gradient as Adam keeps it: the first moment after the probe,
        # 0.1 g3 + 0.09 g2 + 0.081 g1, median and worst leaf
        "grad_gap": compare.median_leaf_gap(gp["moment"], wp["moment"]),
        "grad_worst_gap": compare.worst_leaf_gap(gp["moment"], wp["moment"]),
        # the parameters' change over the probe; an unchanged state reads 1
        "update_gap": compare.worst_leaf_gap(gp["update"], wp["update"]),
        # the rows the held experts took in the probe's three steps, the
        # program's counter against the reference's count: a dropped row, a
        # routing over the wrong experts
        "routed_rows_gap": abs(gp["rows"] - wp["rows"]) / wp["rows"],
        # each of the first epoch's first steps through the window's own
        # program: its feed, its order, the noise continuing the probe's
        "epoch_loss_gap": rel(ge["step_loss"], we["step_loss"]),
    }


def controls(job: Job, want: dict) -> dict:
    """name -> what stands in the program's place, for ``control.py`` to
    read against ``want`` (from ``follow(job, controls=True)``); each has to
    come out as not correct. ``int8``: the reference with both operands of
    every matrix product rounded to symmetric per-tensor int8, the nearest
    precision below the configuration's bfloat16. ``capacity``: tokens
    dropped at a capacity factor of 1. ``causal``: a token-causal mask in
    place of the block-diffusion mask. ``stale_eval``: validation on the
    parameters the job started from. The first three follow the probe
    alone and keep the sound reference's epoch steps: a probe number has to
    catch them."""
    planted = {"int8": {"precision": "int8"},
               "capacity": {"fault": "capacity"},
               "causal": {"fault": "causal"}}

    class OneAtATime(dict):
        """Each planted run is made when ``items()`` reaches it: a run
        takes a minute and most of the chip's memory."""

        def items(self):
            for name, kw in planted.items():
                yield name, {**want, **follow(job, probe_only=True, **kw)}
            yield "stale_eval", {**want, "probe": {
                **want["probe"], "val_loss": want["probe"]["val_loss_stale"]}}

    return OneAtATime.fromkeys([*planted, "stale_eval"])


def check(job: Job, out: dict) -> dict:
    """name -> value of every number read; the harness holds each that the
    cell's limits file names to its limit. The job's device state went with
    its ``train_model`` call: the reference has the chip to itself."""
    res = out.pop("result")
    window_losses = _history(res, "train_loss")
    epochs_run = res.epochs_run
    del res
    gc.collect()
    numbers = readings(job, job.produced, follow(job))
    numbers["window_epochs_missing"] = float(job.window_epochs - min(
        epochs_run, sum(math.isfinite(v) for v in window_losses)))
    return numbers


def _abstract(cell):
    """(the cell's model and training configurations, the data set's
    sequences). The kernels as on the chip: a process that sees a CPU would
    resolve "auto" to the dense forms."""
    from robotic_discovery_platform_tpu.utils.config import (
        BlockDiffLMConfig, TrainConfig)

    return (BlockDiffLMConfig(**cell.config["model"], kernel_impl="pallas"),
            TrainConfig(**cell.config["train"], **cell.traffic["train"]),
            cell.traffic["dataset"]["sequences"])


def abstract_step(cell):
    """(fn, args): one optimiser step of the timed program (the Pallas
    kernels included, which Mosaic compiles for a described chip) and its
    arguments as ``jax.ShapeDtypeStruct``s: the state, a batch of
    sequences, and the job's seed once a sequence."""
    import jax
    import jax.numpy as jnp

    from robotic_discovery_platform_tpu.training import trainer

    model_cfg, cfg, _ = _abstract(cell)
    task, model, tx, state = _built(model_cfg, cfg)
    rows = jax.ShapeDtypeStruct((cfg.batch_size, model_cfg.seq_len),
                                jnp.int32)
    seeds = jax.ShapeDtypeStruct(rows.shape[:1], rows.dtype)
    return trainer.core_train_step(model, tx, task.make_loss(cfg),
                                   task=task), (state, rows, seeds)


def abstract_epoch(cell):
    """(fn, args) of the whole-epoch scan the window dispatches."""
    return _epoch_programs(*_abstract(cell))[0]
