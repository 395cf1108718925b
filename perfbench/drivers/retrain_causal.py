"""Driver for retraining traffic on token data under a causal language
model: the window is one ``training.trainer.train_model`` call on a
sparse-expert decoder of window and full attention layers trained by
next-token prediction, as ``drivers/retrain_lm.py``'s is on a
block-diffusion one.

The scheme is that driver's, and what is not this family's own is imported
from it: one continuing job under ``resume=True`` that starts from the
weights the program draws from the seed; the *probe* (the job's first three
optimiser steps as one-step epochs in one call that saves once), the *first
epoch* through the window's own whole-epoch scan, the *window* (the
traffic's ``window.epochs`` further epochs: restore, validate every epoch,
save once, register); ``check`` lets the plain reference
(``reference/mellum2-12b-a2.5b.py``) follow the probe's three steps and the
first ``EPOCH_STEPS`` of the first epoch and compares losses, Adam's first
moment, the parameters' change, validation losses and the rows routed to
the held experts (``retrain_lm.readings``; a causal step draws no noise, so
the seed the reference is handed goes unread). ``setup`` compiles the first epoch's and the
reference's programs from their shapes in two threads beside the probe
call.

This family's own: the model configuration (``CausalLMConfig``, whose layer
pattern and rotary tables arrive as JSON lists and dicts), the operation
count (``lib/causal_lm_flops.py``), the planted faults, and two things that
this cell's numbers were found to hang on (``PERF.md`` section 6):

- **The probe has rows of its own.** A causal model memorises what it
  trains on (no noise stands between two passes over a row), so a row that a
  probe step trained on and that the data set's split then draws into the
  validation set keeps that split's loss falling while every other seed's
  rises; whether the window's last save is the job's best, and with it what
  the registry's write has to wait for and read, then flips with the seed.
  The data set's sequences follow the probe's in the seeded draw; the probe
  trains on its own and the data set holds none of them.
- **The window starts after a collection.** Set-up's calls leave cycles
  that hold device buffers, and the collector's pass over them (1.5 s in a
  traced window) otherwise falls where it likes. (A ``sync`` of set-up's
  14 GB of saves beside it was tried and bought nothing: the window's last
  act, the save and the registry's write, took 16 s with it and without,
  and on a slow disk it added 30 s to set-up.)
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time

import numpy as np

from perfbench.drivers import retrain_lm
from perfbench.drivers.retrain import _history, window_epochs
from perfbench.drivers.retrain_lm import (  # noqa: F401  (the contract)
    EPOCH_STEPS, FIRST_EPOCHS, PROBE_STEPS, _ahead, _built, _compile_epoch,
    _epoch_programs, _norms, _probe_rows, _read, end_to_end, readings,
    window)
from perfbench.lib import causal_lm_flops, order


@dataclasses.dataclass
class Job(retrain_lm.Job):
    probe_tokens: np.ndarray = None     # [n_probe, L]: the probe's own rows


def _configs(cell, **train):
    """(the cell's model configuration, its training configuration)."""
    from robotic_discovery_platform_tpu.utils.config import (
        CausalLMConfig, TrainConfig, from_dict)

    return (from_dict(CausalLMConfig, cell.config["model"]),
            TrainConfig(**{**cell.config["train"], **cell.traffic["train"],
                           **train}))


def setup(cell) -> Job:
    """Everything before the window (``retrain_lm.setup``, for this
    family's configuration)."""
    from robotic_discovery_platform_tpu.observability import instruments as obs
    from robotic_discovery_platform_tpu.training.trainer import train_model

    clock = [time.time()]

    def lap(what):
        clock.append(time.time())
        print(f"perfbench set-up: {what} {clock[-1] - clock[-2]:.1f} s",
              file=sys.stderr)

    data, work, model = cell.traffic["dataset"], cell.workdir, \
        cell.config["model"]
    if data["seq_len"] != model["seq_len"]:
        raise SystemExit("the traffic's sequences are not the model's")
    model_cfg, base_cfg = _configs(
        cell, seed=cell.seed % (2 ** 31 - 1),
        tracking_uri=f"file:{work / 'mlruns'}",
        checkpoint_dir=str(work / "checkpoints"))
    batch, split = base_cfg.batch_size, base_cfg.validation_split
    n_probe = _probe_rows(batch, split, base_cfg.seed)
    drawn = cell.reference.tokens(model, cell.seed,
                                  n_probe + data["sequences"])
    probe_tokens, tokens = drawn[:n_probe], drawn[n_probe:]
    lap("data set")

    # what the probe does not need at once compiles beside it
    epoch_ahead = _ahead(_compile_epoch, model_cfg, base_cfg, len(tokens))
    check_ahead = _ahead(cell.reference.warm, model)
    abstract = _built(model_cfg, base_cfg)[-1]

    def call(epochs, rows, register):
        # one save a call, at its end
        cfg = dataclasses.replace(base_cfg, epochs=epochs,
                                  checkpoint_every=epochs)
        return train_model(cfg, model_cfg, arrays=(rows, None), resume=True,
                           register=register)

    before = obs.MOE_ROUTED_ROWS.value
    res = call(PROBE_STEPS, probe_tokens, False)
    rows = obs.MOE_ROUTED_ROWS.value - before
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
    gc.collect()
    return Job(cell, model, base_cfg, tokens, n_probe,
               math.ceil(n_train / batch), done,
               window_epochs(cell.traffic, cell.seconds),
               {"probe": probe, "epoch": epoch}, call, probe_tokens)


def follow(job: Job, precision: str = "f32", fault: str | None = None,
           controls: bool = False, probe_only: bool = False) -> dict:
    """What the plain reference gets for the probe's steps (on the probe's
    own rows) and the first epoch's first ``EPOCH_STEPS`` (on the data
    set's), in the shape of ``job.produced``: ``retrain_lm.follow`` with the
    two calls' rows kept apart. ``controls`` adds, under
    ``val_loss_stale``, validation with the parameters the job started
    from; ``probe_only`` stops after the probe (the faults' controls, which
    a probe number has to catch)."""
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

    rows = job.probe_tokens
    tr, va = order.train_val_split(len(rows), split, seed)
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

    tr, _ = order.train_val_split(len(job.tokens), split, seed)
    grid = order.epoch_order(len(tr), batch, True,
                             np.random.default_rng(seed))
    losses = []
    for b in grid[:EPOCH_STEPS]:
        params, opt, loss, _ = step(params, opt, job.tokens[tr][b])
        losses.append(loss)
    return {"probe": probe, "epoch": {"step_loss": losses}}


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
            "model_flops": causal_lm_flops.window_flops(
                job.model, cfg.batch_size, steps, eval_batches,
                out["routed_rows"]),
            "attempted": steps}


def controls(job: Job, want: dict) -> dict:
    """name -> what stands in the program's place, for ``control.py`` to
    read against ``want`` (from ``follow(job, controls=True)``); each has to
    come out as not correct. ``int8``: the reference with both operands of
    every matrix product rounded to symmetric per-tensor int8, the nearest
    precision below the configuration's bfloat16. ``capacity``: tokens
    dropped at a capacity factor of 1. ``no_window``: the sliding layers
    under the full causal mask. ``plain_rope``: the full layers under the
    sliding layers' rotary table. ``stale_eval``: validation on the
    parameters the job started from. The first four follow the probe alone
    and keep the sound reference's epoch steps: a probe number has to catch
    them."""
    planted = {"int8": {"precision": "int8"},
               "capacity": {"fault": "capacity"},
               "no_window": {"fault": "no_window"},
               "plain_rope": {"fault": "plain_rope"}}

    class OneAtATime(dict):
        """Each planted run is made when ``items()`` reaches it: a run
        takes a minute and most of the chip's memory."""

        def items(self):
            for name, kw in planted.items():
                yield name, {**want, **follow(job, probe_only=True, **kw)}
            yield "stale_eval", {**want, "probe": {
                **want["probe"], "val_loss": want["probe"]["val_loss_stale"]}}

    return OneAtATime.fromkeys([*planted, "stale_eval"])


def _abstract(cell):
    """(the cell's model and training configurations, the data set's
    sequences). The kernels as on the chip: a process that sees a CPU would
    resolve "auto" to the dense forms."""
    model_cfg, cfg = _configs(cell)
    return (dataclasses.replace(model_cfg, kernel_impl="pallas"), cfg,
            cell.traffic["dataset"]["sequences"])


def abstract_step(cell):
    """(fn, args): one optimiser step of the timed program (the Pallas
    kernels included, which Mosaic compiles for a described chip) and its
    arguments as ``jax.ShapeDtypeStruct``s: the state, a batch of
    sequences, and the zero a sequence that stands for its targets."""
    import jax
    import jax.numpy as jnp

    from robotic_discovery_platform_tpu.training import trainer

    model_cfg, cfg, _ = _abstract(cell)
    task, model, tx, state = _built(model_cfg, cfg)
    rows = jax.ShapeDtypeStruct((cfg.batch_size, model_cfg.seq_len),
                                jnp.int32)
    zeros = jax.ShapeDtypeStruct(rows.shape[:1], rows.dtype)
    return trainer.core_train_step(model, tx, task.make_loss(cfg),
                                   task=task), (state, rows, zeros)


def abstract_epoch(cell):
    """(fn, args) of the whole-epoch scan the window dispatches."""
    return _epoch_programs(*_abstract(cell))[0]
