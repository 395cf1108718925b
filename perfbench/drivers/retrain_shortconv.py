"""Driver for retraining traffic on token data under a language model of
short-convolution and attention operators: the window is one
``training.trainer.train_model`` call on a decoder whose layers are a gated
short convolution or grouped-query attention, then a dense MLP or sparse
experts, under a head tied to the embedding (LFM2), trained by next-token
prediction, as ``drivers/retrain_hybrid.py``'s is on a decoder of
state-space, attention and expert layers.

The scheme is that driver's, ``drivers/retrain_causal.py``'s and
``drivers/retrain_lm.py``'s, and what is not this family's own is imported
from them: one continuing job under ``resume=True`` that starts from the
weights the program draws from the seed; the *probe* (the job's first three
optimiser steps as one-step epochs on rows of its own, in one call that
saves once), the *first epoch* through the window's own whole-epoch scan,
the *window* (the traffic's ``window.epochs`` further epochs: restore,
validate every epoch, save once, register); ``check`` lets the plain
reference (``reference/lfm2-8b-a1b.py``) follow the probe's three steps and
the first ``EPOCH_STEPS`` of the first epoch (``retrain_causal.follow``)
and compares losses, Adam's first moment, the parameters' change,
validation losses and the rows routed to the held experts. ``setup``
compiles the first epoch's and the reference's programs from their shapes
in two threads beside the probe call, and the window starts after a
collection.

This family's own: the model configuration (a ``HybridLMConfig`` whose
pattern names two branches a published layer), the operation count
(``lib/shortconv_lm_flops.py``), the planted faults, and three things more.

- **The probe validates on a row it trains on**: its one validation row is
  a copy of a training row. The probe memorises its batch (the loss falls
  from 10.1 to 5.7 in three steps) while a held-out row of uniform ids
  moves by 5e-5 of itself, inside what the program's own bfloat16 reads, so
  that validation on the parameters the job started from (``stale_eval``,
  the siblings' control) could not be told from a sound one on a held-out
  row; on a trained row it reads what training did.
- ``shortconv_gap`` (:func:`readings`): the program's gate, convolution and
  gate (``models/hybrid_lm.shortconv_mix``, its inputs in the
  configuration's compute type as the operator hands them over) against the
  reference's three shifted sums **alone**, at the timed batch and length,
  on what the stack's first branch is handed for the probe's training
  batch at the job's start (``reference.shortconv_check_inputs``), by the
  gradient of the taps as a vector (:func:`shortconv_alone`): the
  operator's own sums are float32 on both sides there, so the number reads
  the order of those sums (1e-7) where sums kept in bfloat16 read 1e-3, a
  difference that the bfloat16 of the whole model's matrix products covers
  in every other number. It is the function the timed program calls,
  compiled alone: what the compiler makes of it inside the step's fusions
  no number here can see.
- ``epochs_missing``, the epochs the probe and the first epoch should have
  reported less those they did.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time

from perfbench.drivers import retrain_causal, retrain_lm
from perfbench.drivers.retrain import _history, window_epochs
from perfbench.drivers.retrain_hybrid import (  # noqa: F401  (the contract)
    abstract_epoch, abstract_step, _abstract, _configs)
from perfbench.drivers.retrain_lm import (  # noqa: F401  (the contract)
    EPOCH_STEPS, FIRST_EPOCHS, PROBE_STEPS, _ahead, _built, _compile_epoch,
    _epoch_programs, _norms, _probe_rows, _read, end_to_end, window)
from perfbench.lib import order, shortconv_lm_flops


@dataclasses.dataclass
class Job(retrain_causal.Job):
    conv_inputs: dict = None    # what the operator alone is compared on


def shortconv_alone(inputs: dict, model_cfg):
    """``[hidden, K]``: the gradient the program's gate, convolution and
    gate give the taps on ``inputs`` (the reference's
    ``shortconv_check_inputs``: whole sequences, the timed batch) under the
    reference's readout; what ``reference.shortconv_taps_grad`` gives from
    its shifted sums."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from robotic_discovery_platform_tpu.models.hybrid_lm import shortconv_mix

    dtype = jnp.dtype(model_cfg.compute_dtype)

    def loss(taps, v):
        b, c, xs = (v[k].astype(dtype) for k in ("b", "c", "xs"))
        return jnp.sum(v["readout"] * shortconv_mix(b, c, xs, taps).astype(
            jnp.float32))

    v = {k: jnp.asarray(a) for k, a in inputs.items()}
    return np.asarray(jax.jit(jax.grad(loss))(v.pop("taps"), v))


def follow(job: Job, precision: str = "f32", fault: str | None = None,
           **kw) -> dict:
    """``retrain_causal.follow`` and, under ``shortconv``, the operator
    alone (:func:`shortconv_alone`'s counterpart; it holds no matrix
    product, so a lower ``precision`` leaves it as it is)."""
    want = retrain_causal.follow(job, precision, fault, **kw)
    want["shortconv"] = job.cell.reference.shortconv_taps_grad(
        job.conv_inputs, fault)
    return want


def setup(cell) -> Job:
    """Everything before the window (``retrain_hybrid.setup``, with this
    family's operator alone in the scan's place)."""
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
    probe_tokens, tokens = drawn[:n_probe].copy(), drawn[n_probe:]
    # the probe validates on rows it trains on (the module's docstring)
    probe_tr, probe_va = order.train_val_split(n_probe, split, base_cfg.seed)
    probe_tokens[probe_va] = probe_tokens[probe_tr[:len(probe_va)]]
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
    start = cell.reference.init(model, base_cfg.seed)
    probe = {
        "loss": _history(res, "train_loss"),
        "val_loss": _history(res, "val_loss"),
        "rows": rows,
        "moment": _norms(_read(base_cfg, abstract, PROBE_STEPS, "mu")),
        "update": _norms(_read(base_cfg, abstract, PROBE_STEPS, "params"),
                         start),
    }
    lap("reading the probe's checkpoint")
    conv_inputs = cell.reference.shortconv_check_inputs(
        model, cell.seed, start, probe_tokens[probe_tr])
    del start
    alone = shortconv_alone(conv_inputs, model_cfg)
    lap("the short convolution alone")

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
               {"probe": probe, "epoch": epoch, "shortconv": alone}, call,
               probe_tokens, conv_inputs)


def readings(job: Job, got: dict, want: dict) -> dict:
    """``retrain_lm.readings`` and two numbers more. ``shortconv_gap``: the
    distance between the two gradients of the taps of the operator alone
    (``got["shortconv"]``, ``want["shortconv"]``) as vectors, over the norm
    of the reference's. ``epochs_missing``: the epochs that ``got`` lacks
    (the probe reports a loss and a validation loss a step, and the first
    epoch's followed steps a loss each)."""
    import numpy as np

    def finite(values):
        return sum(math.isfinite(v) for v in values)

    gp, ge = got["probe"], got["epoch"]
    a, b = (np.asarray(v["shortconv"], np.float64) for v in (got, want))
    missing = (2 * PROBE_STEPS + EPOCH_STEPS - finite(gp["loss"])
               - finite(gp["val_loss"]) - finite(ge["step_loss"]))
    return {**retrain_lm.readings(job, got, want),
            "shortconv_gap": float(np.linalg.norm(a - b)
                                   / np.linalg.norm(b)),
            "epochs_missing": float(missing)}


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
    held = shortconv_lm_flops.layers_of(
        job.model, shortconv_lm_flops.EXPERTS) * job.model["experts_held"]
    return {"optimizer_steps": steps, "train_phase_s": out["train_phase_s"],
            "window_s": window_s, "window_epochs": job.window_epochs,
            "batch": cfg.batch_size, "eval_batches": eval_batches,
            "routed_rows": out["routed_rows"],
            # a held expert's mean rows a step, at the job's start (the
            # probe's three steps) and over the window
            "rows_per_expert_start": job.produced["probe"]["rows"] / (
                PROBE_STEPS * held),
            "rows_per_expert_window": out["routed_rows"] / (steps * held)
            if steps else 0.0,
            "tokens_per_s": steps * cfg.batch_size * job.model["seq_len"]
            / window_s,
            "model_flops": shortconv_lm_flops.window_flops(
                job.model, cfg.batch_size, steps, eval_batches,
                out["routed_rows"]),
            "attempted": steps}


#: the faults the plain reference plants on request (its docstring)
FAULTS = ("conv_bf16", "tap_ahead", "no_c_gate", "no_qk_norm",
          "untied_grad")


def controls(job: Job, want: dict) -> dict:
    """name -> what stands in the program's place, for ``control.py`` to
    read against ``want`` (from ``follow(job, controls=True)``); each has to
    come out as not correct. ``int8``: the reference with both operands of
    every matrix product rounded to symmetric per-tensor int8, the nearest
    precision below the configuration's bfloat16; it follows the first
    epoch's steps too, where ``epoch_loss_gap`` reads how little precision
    moves the loss of a fresh row (``PERF.md`` section 2).
    ``conv_bf16``: the short convolution's gate product, taps and sums in
    bfloat16. ``tap_ahead``: a tap reading ``t + 1``. ``no_c_gate``: the
    ``C`` gate dropped. ``no_qk_norm``: q and k not normed.
    ``untied_grad``: the head's gradient kept from the embedding.
    ``stale_eval``: validation on the parameters the job started from.
    ``epoch_fewer``: the probe one epoch short. One fault is no control
    here, because no reading can see it at the cell's size: 1e-20 in the
    place of the router's 1e-6 moves a routed weight by under 5e-7 of
    itself (the reference plants it on request as ``norm_eps_tiny``, and
    the cell's test file reads it on the CPU). The planted faults follow the
    probe alone and keep the sound reference's epoch steps: a probe number
    has to catch them."""
    planted = {"int8": {"precision": "int8"},
               **{fault: {"fault": fault, "probe_only": True}
                  for fault in FAULTS}}

    class OneAtATime(dict):
        """Each planted run is made when ``items()`` reaches it: a run
        takes a minute and most of the chip's memory."""

        def items(self):
            for name, kw in planted.items():
                yield name, {**want, **follow(job, **kw)}
            probe = want["probe"]
            yield "stale_eval", {**want, "probe": {
                **probe, "val_loss": probe["val_loss_stale"]}}
            yield "epoch_fewer", {**want, "probe": {
                **probe, "loss": probe["loss"][:-1],
                "val_loss": probe["val_loss"][:-1]}}

    return OneAtATime.fromkeys([*planted, "stale_eval", "epoch_fewer"])
