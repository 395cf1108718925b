"""Driver for retraining traffic on token data under a hybrid language model:
the window is one ``training.trainer.train_model`` call on a decoder of
state-space (Mamba-2), attention and sparse-expert layers trained by
next-token prediction, as ``drivers/retrain_causal.py``'s is on a decoder of
window and full attention layers.

The scheme is that driver's and ``drivers/retrain_lm.py``'s, and what is
not this family's own is imported from them: one continuing job under
``resume=True`` that starts from the weights the program draws from the
seed; the *probe* (the job's first three optimiser steps as one-step epochs
on rows of its own, in one call that saves once), the *first epoch* through
the window's own whole-epoch scan, the *window* (the traffic's
``window.epochs`` further epochs: restore, validate every epoch, save once,
register); ``check`` lets the plain reference
(``reference/nemotron-twotower-30b-a3b.py``, whose state-space layers are a
recurrence position by position) follow the probe's three steps and the
first ``EPOCH_STEPS`` of the first epoch (``retrain_causal.follow``) and
compares losses, Adam's first moment, the parameters' change, validation
losses and the rows routed to the held experts. ``setup`` compiles the
first epoch's and the reference's programs from their shapes in two threads
beside the probe call, and the window starts after a collection.

This family's own: the model configuration (``HybridLMConfig``), the
operation count (``lib/hybrid_lm_flops.py``), the planted faults, and
three more numbers (:func:`readings`): ``scan_decay_gap``, the program's
chunked scan against the reference's recurrence **alone**, on inputs of
the seed at the model's widths, by the gradients of the two leaves that
only the decays reach (:func:`scan_alone`); ``epochs_missing``, the epochs
the probe and the first epoch should have reported less those they did (the
other numbers pair what was produced with what was wanted entry by entry,
so a call that ran an epoch short would be compared on the epochs it ran
and pass); and ``decay_grad_gap``, those two leaves' gradients through the
whole model, which is read and no longer held to a limit: the program's
own bfloat16 through nine layers and three Adam steps moves the norm of
such a 64-number leaf by 0.2 to 1.05% of itself over 22 readings on the chip,
decays kept in bfloat16 by 1.6 to 2.4%, and no limit stands between those
with room; alone, the same fault is twenty times the program's reading.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time

from perfbench.drivers import retrain_causal, retrain_lm
from perfbench.drivers.retrain import _history, window_epochs
from perfbench.drivers.retrain_causal import Job
from perfbench.drivers.retrain_lm import (  # noqa: F401  (the contract)
    EPOCH_STEPS, FIRST_EPOCHS, PROBE_STEPS, _ahead, _built, _compile_epoch,
    _epoch_programs, _norms, _probe_rows, _read, end_to_end, window)
from perfbench.lib import hybrid_lm_flops, order


def _configs(cell, **train):
    """(the cell's model configuration, its training configuration)."""
    from robotic_discovery_platform_tpu.utils.config import (
        HybridLMConfig, TrainConfig, from_dict)

    return (from_dict(HybridLMConfig, cell.config["model"]),
            TrainConfig(**{**cell.config["train"], **cell.traffic["train"],
                           **train}))


def scan_alone(cell, model_cfg) -> dict:
    """``{"dt_bias", "A_log"}``: the gradients the program's scan
    (``ops/ssm_scan.ssm_scan``, its inputs in the configuration's compute
    type as the mixer hands them over) gives the two decay leaves on the
    reference's ``scan_check_inputs`` of the cell's seed, one sequence, under
    the reference's readout; what ``reference.scan_decay_grads`` gives from
    the recurrence position by position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from robotic_discovery_platform_tpu.ops.ssm_scan import ssm_scan

    v = {k: jnp.asarray(a) for k, a in cell.reference.scan_check_inputs(
        cell.config["model"], cell.seed).items()}
    dtype = jnp.dtype(model_cfg.compute_dtype)

    # the inputs are arguments, so that one compiled program serves every
    # seed (closed over, they would be constants of a program a seed)
    def loss(dt_bias, a_log, v):
        xs, b, c = (v[k].astype(dtype)[None] for k in ("xs", "b", "c"))
        dt = jax.nn.softplus(v["raw"] + dt_bias)
        y = ssm_scan(xs, dt[None], -jnp.exp(a_log), b, c, v["d"],
                     chunk=model_cfg.ssm_chunk)
        return jnp.sum(v["readout"] * y[0].astype(jnp.float32))

    got = jax.jit(jax.grad(loss, (0, 1)))(v.pop("dt_bias"), v.pop("A_log"), v)
    return dict(zip(("dt_bias", "A_log"), (np.asarray(g) for g in got)))


def follow(job: Job, precision: str = "f32", fault: str | None = None,
           **kw) -> dict:
    """``retrain_causal.follow`` and, under ``scan``, the recurrence alone
    (:func:`scan_alone`'s counterpart; it holds no matrix product, so a
    lower ``precision`` leaves it as it is)."""
    want = retrain_causal.follow(job, precision, fault, **kw)
    want["scan"] = job.cell.reference.scan_decay_grads(
        job.model, job.cell.seed, fault)
    return want


def setup(cell) -> Job:
    """Everything before the window (``retrain_causal.setup``, for this
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
    scan = scan_alone(cell, model_cfg)
    lap("the scan alone")

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
               {"probe": probe, "epoch": epoch, "scan": scan}, call,
               probe_tokens)


#: the mixer's leaves that only the decays reach: 64 numbers apiece
DECAY_LEAVES = ("dt_bias", "A_log")


def readings(job: Job, got: dict, want: dict) -> dict:
    """``retrain_lm.readings`` and three numbers more. ``scan_decay_gap``:
    the worse, over ``dt_bias`` and ``A_log``, of the distance between the
    two gradients of the scan alone (``got["scan"]``, ``want["scan"]``) as
    vectors, over the norm of the reference's. ``decay_grad_gap``, read
    and held to no limit (the module's docstring): the worst, over the
    mixers' ``dt_bias`` and ``A_log``, of the gap between the two norms of
    Adam's first moment over the reference's own norm of that leaf
    (``grad_worst_gap`` floors a leaf's norm at the median leaf's, a
    matrix's, under which these leaves' whole gradient disappears).
    ``epochs_missing``: the epochs that ``got`` lacks (the probe reports a
    loss and a validation loss a step, and the first epoch's followed steps
    a loss each)."""
    import numpy as np

    def finite(values):
        return sum(math.isfinite(v) for v in values)

    def apart(a, b):
        a, b = (np.asarray(v, np.float64) for v in (a, b))
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    gp, wp, ge = got["probe"], want["probe"], got["epoch"]
    decays = [abs(gp["moment"][k] - v) / v for k, v in wp["moment"].items()
              if k.rsplit("/", 1)[-1] in DECAY_LEAVES]
    missing = (2 * PROBE_STEPS + EPOCH_STEPS - finite(gp["loss"])
               - finite(gp["val_loss"]) - finite(ge["step_loss"]))
    return {**retrain_lm.readings(job, got, want),
            "scan_decay_gap": max(apart(got["scan"][k], v)
                                  for k, v in want["scan"].items()),
            "decay_grad_gap": max(decays), "epochs_missing": float(missing)}


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
            "model_flops": hybrid_lm_flops.window_flops(
                job.model, cfg.batch_size, steps, eval_batches,
                out["routed_rows"]),
            "attempted": steps}


#: the faults the plain reference plants on request (its docstring)
FAULTS = ("decay_bf16", "no_shared", "no_conv_bias", "softmax_router")


def controls(job: Job, want: dict) -> dict:
    """name -> what stands in the program's place, for ``control.py`` to
    read against ``want`` (from ``follow(job, controls=True)``); each has to
    come out as not correct. ``int8``: the reference with both operands of
    every matrix product rounded to symmetric per-tensor int8, the nearest
    precision below the configuration's bfloat16. ``decay_bf16``: the
    decays' running sums inside a chunk rounded to bfloat16. ``no_shared``:
    the shared expert left out. ``no_conv_bias``: the convolution's bias
    left out. ``softmax_router``: a softmax where the sigmoid belongs.
    ``stale_eval``: validation on the parameters the job started from.
    ``epoch_fewer``: the probe one epoch short. The planted runs follow the
    probe alone and keep the sound reference's epoch steps: a probe number
    has to catch them."""
    planted = {"int8": {"precision": "int8"},
               **{fault: {"fault": fault} for fault in FAULTS}}

    class OneAtATime(dict):
        """Each planted run is made when ``items()`` reaches it: a run
        takes a minute and most of the chip's memory."""

        def items(self):
            for name, kw in planted.items():
                yield name, {**want, **follow(job, probe_only=True, **kw)}
            probe = want["probe"]
            yield "stale_eval", {**want, "probe": {
                **probe, "val_loss": probe["val_loss_stale"]}}
            yield "epoch_fewer", {**want, "probe": {
                **probe, "loss": probe["loss"][:-1],
                "val_loss": probe["val_loss"][:-1]}}

    return OneAtATime.fromkeys([*planted, "stale_eval", "epoch_fewer"])


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
