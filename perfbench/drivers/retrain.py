"""Driver for retraining traffic: the window is one
``training.trainer.train_model`` call.

The job is one continuing ``train_model`` job under ``resume=True`` (the way
``training/supervisor.py`` starts every attempt), carried from call to call
by its own checkpoints:

1. set-up writes the step-0 checkpoint from weights the benchmark's plain
   reference makes from the seed (so the program and the reference start
   from the same numbers, and the reference takes nothing the program made);
2. set-up's *probe* call drives the job's first three optimiser steps at the
   cell's batch and image size -- one-step epochs over the data set's first
   rows -- so that ``train_model``'s per-epoch loss, checkpoints and
   validation show every single step;
3. set-up's *first epoch* continues over the whole data set for one epoch,
   through the window's own call and feed: it compiles, and is, what the
   window runs (the same whole-epoch scan or per-step program over the same
   shapes), and its loss, state and statistics are read from what
   ``train_model`` logged and checkpointed;
4. the *window* is the next call: the same job, the same state, and a fixed
   number of further epochs -- the traffic's ``window.epochs`` at the
   benchmark's ``run_seconds``, in proportion at another ``--seconds``, at
   least three. ``train_model`` takes epochs and not a duration, and a
   window sized by a timed epoch would change its amount of work whenever
   that time crossed a rounding edge.

``check`` then lets the plain reference follow the three probe steps and
every step of the first epoch, in the program's documented data order, and
compares loss, first gradient, update, batch statistics and Adam's moment.

Beside the five calls of a run (``setup``, ``window``, ``end_to_end``,
``counters``, ``check``) the driver exports what the benchmark's tools and
tests ask every driver for: ``abstract_step`` (one optimiser step as a
function and the shapes it is fed), ``controls`` (the control and the
planted faults that ``control.py`` judges) and, for ``memory_probe.py``
alone, ``abstract_epoch``. What is specific to the U-Net and its images
sits here and in ``reference/_unet.py``, ``lib/flops.py``, ``lib/scenes.py``
and ``lib/order.py``, not in the harness.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from perfbench.lib import compare, flops, order, scenes

PROBE_STEPS = 3
ADAM_B1 = 0.9       # optax.adam's default: after one step mu = (1 - b1) * g
FIRST_EPOCHS = 1    # the compared epoch: set-up's last call
MIN_WINDOW_EPOCHS = 3


@dataclasses.dataclass
class Job:
    cell: object
    base_cfg: object            # TrainConfig without epochs / data
    feed: dict                  # train_model keyword arguments of the full set
    steps_per_epoch: int
    epochs_done: int
    window_epochs: int
    produced: dict              # what the program produced: "probe", "epoch"
    rows: tuple                 # (images u8, masks u8) of the whole data set
    n_probe: int                # the probe's rows are the first n_probe
    start: tuple                # (params, stats) the job started from
    call: object                # (epochs, feed, register) -> TrainResult


def _write_pairs(directory: Path, imgs, masks) -> None:
    import cv2

    (directory / "images").mkdir(parents=True)
    (directory / "masks").mkdir(parents=True)
    fast = [cv2.IMWRITE_PNG_COMPRESSION, 1]

    def write(i):
        name = f"pair_{i:05d}.png"
        cv2.imwrite(str(directory / "images" / name), imgs[i][..., ::-1], fast)
        cv2.imwrite(str(directory / "masks" / name), masks[i, ..., 0], fast)

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(write, range(len(imgs))))


def _flat(tree) -> dict:
    from flax.traverse_util import flatten_dict

    return {k: np.asarray(v) for k, v in flatten_dict(tree, sep="/").items()}


def _payload(train_cfg, params: dict, stats: dict):
    """The program's checkpoint layout around the benchmark's weights."""
    import jax.numpy as jnp
    import optax
    from flax.traverse_util import unflatten_dict

    from robotic_discovery_platform_tpu.training.trainer import TrainState

    p = unflatten_dict({k: np.asarray(v) for k, v in params.items()}, sep="/")
    s = unflatten_dict({k: np.asarray(v) for k, v in stats.items()}, sep="/")
    state = TrainState(
        params=p, opt_state=optax.adam(train_cfg.learning_rate).init(p),
        batch_stats=s, epoch=jnp.asarray(0, jnp.int32),
        best_val_loss=jnp.asarray(jnp.inf, jnp.float32))
    import jax

    return jax.device_get(
        {"state": state, "best_params": p, "best_stats": s})


def _history(result, key):
    from robotic_discovery_platform_tpu import tracking

    return [m["value"] for m in tracking.get_metric_history(result.run_id, key)]


def _state_at(base_cfg, template, step):
    from robotic_discovery_platform_tpu.training.checkpoint import (
        CheckpointManager)

    ckpt = CheckpointManager(base_cfg.checkpoint_dir,
                             keep=base_cfg.keep_checkpoints)
    try:
        return ckpt.restore(template, step=step)["state"]
    finally:
        ckpt.close()


def window_epochs(traffic: dict, seconds: float) -> int:
    """The window's work: ``window.epochs`` at ``window.at_seconds`` (the
    benchmark's ``run_seconds``), in proportion at another length, never
    under three. A fixed amount of work, so a faster program shortens the
    window's time and not its epochs."""
    w = traffic["window"]
    return max(MIN_WINDOW_EPOCHS,
               round(w["epochs"] * seconds / w["at_seconds"]))


def setup(cell) -> Job:
    """Everything before the window."""
    from robotic_discovery_platform_tpu.training.checkpoint import (
        CheckpointManager)
    from robotic_discovery_platform_tpu.training.trainer import train_model
    from robotic_discovery_platform_tpu.utils.config import (
        ModelConfig, TrainConfig)

    clock = [time.time()]

    def lap(what):
        clock.append(time.time())
        print(f"perfbench set-up: {what} {clock[-1] - clock[-2]:.1f} s",
              file=sys.stderr)

    config, traffic = cell.config, cell.traffic
    data = traffic["dataset"]
    work = cell.workdir
    model_cfg = ModelConfig(**config["model"])
    base_cfg = TrainConfig(**{
        **config["train"], **traffic["train"],
        "seed": cell.seed % (2 ** 31 - 1),
        "tracking_uri": f"file:{work / 'mlruns'}",
        "checkpoint_dir": str(work / "checkpoints"),
    })
    batch, split = base_cfg.batch_size, base_cfg.validation_split
    n_probe = round(batch / (1.0 - split))
    if len(order.train_val_split(n_probe, split, base_cfg.seed)[0]) != batch:
        raise SystemExit(f"probe of {n_probe} rows is not one batch of {batch}")

    imgs, masks = scenes.generate(cell.seed, data["pairs"], data["height"],
                                  data["width"])
    if data["kind"] == "files":
        _write_pairs(work / "data", imgs, masks)
        _write_pairs(work / "probe", imgs[:n_probe], masks[:n_probe])
        feed = {"dataset_dir": str(work / "data")}
        probe_feed = {"dataset_dir": str(work / "probe")}
    else:
        feed = {"arrays": (imgs, masks)}
        probe_feed = {"arrays": (imgs[:n_probe], masks[:n_probe])}

    lap("data set")
    params0, stats0 = cell.reference.init(config["model"], cell.seed)
    start = _payload(base_cfg, params0, stats0)
    ckpt = CheckpointManager(base_cfg.checkpoint_dir,
                             keep=base_cfg.keep_checkpoints)
    ckpt.save(0, start)
    ckpt.close()
    start_flat = (_flat(start["state"].params),
                  _flat(start["state"].batch_stats))

    def call(epochs, feed, register):
        kw = dict(feed)
        cfg = dataclasses.replace(base_cfg, epochs=epochs)
        if "dataset_dir" in kw:
            cfg = dataclasses.replace(cfg, dataset_dir=kw.pop("dataset_dir"))
        return train_model(cfg, model_cfg, resume=True, register=register,
                           **kw)

    lap("seeded weights and step-0 checkpoint")
    res = call(PROBE_STEPS, probe_feed, False)
    lap("probe call")
    if res.epochs_run != PROBE_STEPS:
        raise SystemExit("probe did not run its steps")
    first = _state_at(base_cfg, start, 1)
    last = _state_at(base_cfg, start, PROBE_STEPS)
    probe = {
        "loss": _history(res, "train_loss"),
        "val_loss": _history(res, "val_loss"),
        "grad": {k: v / (1.0 - ADAM_B1)
                 for k, v in _flat(first.opt_state[0].mu).items()},
        "stats": _flat(first.batch_stats),
        "params": _flat(last.params),
    }
    del first, last
    lap("reading the probe's checkpoints")

    n_train = len(order.train_val_split(data["pairs"], split,
                                        base_cfg.seed)[0])
    steps = math.ceil(n_train / batch)
    done = PROBE_STEPS + FIRST_EPOCHS
    res = call(done, feed, True)
    lap("first-epoch call")
    if res.epochs_run != FIRST_EPOCHS:
        raise SystemExit("the first epoch did not run")
    after = _state_at(base_cfg, start, done)
    epoch = {
        "loss": _history(res, "train_loss"),
        "val_loss": _history(res, "val_loss"),
        "moment": _flat(after.opt_state[0].mu),
        "stats": _flat(after.batch_stats),
        "params": _flat(after.params),
    }
    del after
    lap("reading the first epoch's checkpoint")
    return Job(cell, base_cfg, feed, steps, done,
               window_epochs(traffic, cell.seconds),
               {"probe": probe, "epoch": epoch}, (imgs, masks), n_probe,
               start_flat, call)


def window(job: Job) -> dict:
    from robotic_discovery_platform_tpu.observability import instruments as obs

    before = obs.TRAIN_STEP.sum
    res = job.call(job.epochs_done + job.window_epochs, job.feed, True)
    steps = res.epochs_run * job.steps_per_epoch
    return {
        "result": res,
        "optimizer_steps": steps,
        "images": steps * job.base_cfg.batch_size,
        # the program's own per-epoch train-phase clock: one observation of
        # the mean step time per epoch
        "train_phase_s": (obs.TRAIN_STEP.sum - before) * job.steps_per_epoch,
    }


def end_to_end(job: Job, out: dict, window_s: float) -> dict:
    return {"train_img_per_s": out["images"] / window_s}


def counters(job: Job, out: dict, window_s: float) -> dict:
    data, cfg = job.cell.traffic["dataset"], job.base_cfg
    n_val = len(order.train_val_split(data["pairs"], cfg.validation_split,
                                      cfg.seed)[1])
    eval_batches = job.window_epochs * math.ceil(n_val / cfg.batch_size)
    return {"optimizer_steps": out["optimizer_steps"],
            "train_phase_s": out["train_phase_s"], "window_s": window_s,
            "window_epochs": job.window_epochs, "batch": cfg.batch_size,
            "eval_batches": eval_batches,
            "model_flops": model_flops(job.cell.config, cfg.batch_size,
                                       out["optimizer_steps"], eval_batches),
            "attempted": out["optimizer_steps"]}


def model_flops(config: dict, batch: int, steps: int,
                eval_batches: int) -> float:
    """The operations of the model that a window of ``steps`` optimiser
    steps and ``eval_batches`` validation batches asks for, whatever
    implements them (``lib/flops.py``: the convolutions' multiply-adds as 2,
    three passes a convolution in training and two for the first, one in
    evaluation; norms, activations, loss and optimiser not counted; a
    validation tail filled by repeated rows counts as the batch it runs)."""
    model, size = config["model"], config["train"]["img_size"]
    return (steps * flops.step_flops(model, size, batch)
            + eval_batches * flops.step_flops(model, size, batch,
                                              train=False))


def _normalise(job: Job, imgs, masks):
    """The loader's documented normalisation, plainly: files are resized to
    the model's input (area interpolation for images, nearest for masks);
    both kinds are scaled by 1/255."""
    size = job.base_cfg.img_size
    if imgs.shape[1:3] != (size, size):
        import cv2

        imgs = np.stack([cv2.resize(i, (size, size),
                                    interpolation=cv2.INTER_AREA)
                         for i in imgs])
        masks = np.stack([cv2.resize(m[..., 0], (size, size),
                                     interpolation=cv2.INTER_NEAREST)[..., None]
                          for m in masks])
    return (imgs.astype(np.float32) / 255.0,
            masks.astype(np.float32) / 255.0)


def follow(job: Job, precision: str = "f32", controls: bool = False) -> dict:
    """What the plain reference gets for the probe's steps and the first
    epoch's, in the shape of ``job.produced``. ``controls`` adds what
    :func:`controls` needs beside that: under ``val_loss_stale`` the
    evaluation path's planted fault, validation with the running
    statistics the job started from."""
    import jax
    import jax.numpy as jnp

    ref, mcfg = job.cell.reference, job.cell.config["model"]
    cfg = job.base_cfg
    lr, split, batch = cfg.learning_rate, cfg.validation_split, cfg.batch_size
    # rematerialised block by block, so that a float32 step at the cell's
    # batch fits beside nothing else (9.5 GiB for seg, 9.9 for unet-tconv)
    step = jax.jit(lambda p, o, s, x, y: ref.train_step(
        mcfg, lr, p, o, s, x, y, precision, remat=True))
    evaluate = jax.jit(lambda p, s, x, y: ref.bce_with_logits(
        ref.forward(mcfg, p, s, x, False, precision)[0], y))
    imgs, masks = job.rows

    def rows(idx):
        x, y = _normalise(job, imgs[idx], masks[idx])
        return jnp.asarray(x), jnp.asarray(y)

    def validation(params, stats, val_rows):
        # the program's: the mean over full batches of each batch's mean,
        # the tail filled by repeating rows
        grid = order.epoch_order(len(val_rows), batch, False, None)
        return float(np.mean([float(evaluate(params, stats,
                                             *rows(val_rows[b])))
                              for b in grid]))

    def validate(out, params, stats, val_rows):
        out["val_loss"].append(validation(params, stats, val_rows))
        if controls:
            out.setdefault("val_loss_stale", []).append(
                validation(params, stats0, val_rows))

    params = {k: jnp.asarray(v) for k, v in job.start[0].items()}
    stats = stats0 = {k: jnp.asarray(v) for k, v in job.start[1].items()}
    opt = ref.adam_init(params)

    tr, va = order.train_val_split(job.n_probe, split, cfg.seed)
    x, y = rows(tr)
    probe = {"loss": [], "val_loss": []}
    for i in range(PROBE_STEPS):
        params, opt, stats, loss = step(params, opt, stats, x, y)
        probe["loss"].append(float(loss))
        validate(probe, params, stats, va)
        if i == 0:
            probe["grad"] = {k: np.asarray(v) / (1.0 - ADAM_B1)
                             for k, v in opt["mu"].items()}
            probe["stats"] = {k: np.asarray(v) for k, v in stats.items()}
    probe["params"] = {k: np.asarray(v) for k, v in params.items()}

    # the first full epoch: every call of train_model seeds its order anew
    tr, va = order.train_val_split(len(imgs), split, cfg.seed)
    grid = order.epoch_order(len(tr), batch, True,
                             np.random.default_rng(cfg.seed))
    losses = []
    for b in grid:
        params, opt, stats, loss = step(params, opt, stats, *rows(tr[b]))
        losses.append(loss)
    epoch = {
        "loss": [float(np.mean([float(v) for v in losses]))],
        "val_loss": [],
        "moment": {k: np.asarray(v) for k, v in opt["mu"].items()},
        "stats": {k: np.asarray(v) for k, v in stats.items()},
        "params": {k: np.asarray(v) for k, v in params.items()},
    }
    validate(epoch, params, stats, va)
    return {"probe": probe, "epoch": epoch}


def readings(job: Job, got: dict, want: dict) -> dict:
    """Every number read: what ``got`` (the program, or a control put in
    its place) shows against ``want`` (the plain reference). A cell's limits
    file says which of them are compared, each with its limit."""
    p0, s0 = job.start

    def delta(tree, base):
        return {k: tree[k] - base[k] for k in base}

    def rel(got, want):
        return max(abs(g - w) / abs(w) for g, w in zip(got, want))

    gp, wp, ge, we = got["probe"], want["probe"], got["epoch"], want["epoch"]
    return {
        "loss_gap": rel(gp["loss"], wp["loss"]),
        "val_loss_gap": rel(gp["val_loss"], wp["val_loss"]),
        # the worst leaf of a first gradient is one of the first block's,
        # whose sums over two million pixels cancel: in `seg` it swings
        # threefold from seed to seed at any precision, so those cells
        # compare the median leaf (PERF.md)
        "grad_gap": compare.median_leaf_gap(gp["grad"], wp["grad"]),
        "grad_worst_gap": compare.worst_leaf_gap(gp["grad"], wp["grad"]),
        "stats_gap": compare.worst_leaf_gap(delta(gp["stats"], s0),
                                            delta(wp["stats"], s0)),
        "update_gap": compare.worst_leaf_gap(delta(gp["params"], p0),
                                             delta(wp["params"], p0)),
        "epoch_loss_gap": rel(ge["loss"], we["loss"]),
        # after a first epoch the running statistics lag the weights and
        # validation swings by percents at any precision: read, not compared
        "epoch_val_loss_gap": rel(ge["val_loss"], we["val_loss"]),
        "epoch_moment_gap": compare.median_leaf_gap(ge["moment"],
                                                    we["moment"]),
        # by the median leaf, which is steady from seed to seed
        "epoch_stats_gap": compare.median_leaf_gap(
            delta(ge["stats"], s0), delta(we["stats"], s0)),
        "epoch_stats_worst_gap": compare.worst_leaf_gap(
            delta(ge["stats"], s0), delta(we["stats"], s0)),
        # over the epoch alone, each side from where its own probe ended: a
        # step that returns its state unchanged reads 1
        "epoch_update_gap": compare.worst_leaf_gap(
            delta(ge["params"], gp["params"]),
            delta(we["params"], wp["params"])),
    }


def controls(job: Job, want: dict) -> dict:
    """name -> what stands in the program's place, in the shape of
    ``job.produced``, for ``control.py`` to read against ``want`` (from
    ``follow(job, controls=True)``); each has to come out as not correct.
    ``int8``: the reference itself with every convolution's operands
    rounded to symmetric per-tensor int8, the nearest precision below the
    configuration's bf16. ``stale_eval``: the evaluation path's planted
    fault, the reference itself but for its validation."""
    return {
        "int8": follow(job, "int8"),
        "stale_eval": {part: {**body, "val_loss": body["val_loss_stale"]}
                       for part, body in want.items()},
    }


def check(job: Job, out: dict) -> dict:
    """name -> value of every number read; the harness holds each that the
    cell's limits file names to its limit."""
    res = out.pop("result")
    window_losses = _history(res, "train_loss")
    epochs_run = res.epochs_run
    del res
    gc.collect()
    numbers = readings(job, job.produced, follow(job))
    numbers["window_epochs_missing"] = float(job.window_epochs - min(
        epochs_run, sum(math.isfinite(v) for v in window_losses)))
    return numbers


def _abstract(cell, **model_overrides):
    """(model, tx, loss, abstract state, batch, size) of a cell, nothing
    placed and nothing run."""
    import jax
    import optax

    from robotic_discovery_platform_tpu.models import losses
    from robotic_discovery_platform_tpu.models.unet import build_unet
    from robotic_discovery_platform_tpu.training import trainer
    from robotic_discovery_platform_tpu.utils.config import ModelConfig

    train = cell.config["train"]
    size, batch = train["img_size"], cell.traffic["train"]["batch_size"]
    model = build_unet(ModelConfig(**cell.config["model"], **model_overrides))
    tx = optax.adam(train["learning_rate"])
    state = jax.eval_shape(
        lambda: trainer.create_state(model, tx, jax.random.key(0), size))
    return model, tx, losses.make_loss_fn(train["loss"]), state, batch, size


def abstract_step(cell):
    """(fn, args): the function the timed program runs for one optimiser
    step and its arguments as ``jax.ShapeDtypeStruct``s, for a test or a
    tool to place, compile and, with arrays in their place, run. The XLA
    convolution path: what "auto" resolves to at this volume on a TPU (a
    process that sees a CPU would resolve it otherwise)."""
    import jax
    import jax.numpy as jnp

    from robotic_discovery_platform_tpu.training import trainer

    model, tx, loss_fn, state, batch, size = _abstract(cell, conv_impl="flax")
    return trainer.core_train_step(model, tx, loss_fn), (
        state,
        jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32),
        jax.ShapeDtypeStruct((batch, size, size, 1), jnp.float32))


def abstract_epoch(cell):
    """(fn, args) of the whole-epoch scan that the window dispatches for a
    resident data set, ``None`` where the job streams and the single step
    is its program. For ``memory_probe.py`` only."""
    import jax
    import jax.numpy as jnp

    from robotic_discovery_platform_tpu.training import trainer

    data, train = cell.traffic["dataset"], cell.traffic["train"]
    if data["kind"] != "arrays":
        return None
    model, tx, loss_fn, state, batch, size = _abstract(cell)
    n = len(order.train_val_split(data["pairs"], train["validation_split"],
                                  0)[0])
    grid = order.epoch_order(n, batch, False, None)
    train_epoch, _ = trainer.make_epoch_runners(model, tx, loss_fn)
    return train_epoch, (
        state,
        jax.ShapeDtypeStruct((n, size, size, 3), jnp.float32),
        jax.ShapeDtypeStruct((n, size, size, 1), jnp.float32),
        jax.ShapeDtypeStruct(grid.shape, jnp.int32))
