"""The optax/XLA trainer: the TPU-native ``train_model()``.

Capability-parity rebuild of the reference trainer (reference:
scripts/train_segmenter.py:103-210) with the same observable MLflow-contract
surface -- experiment "Actuator Segmentation", params
{learning_rate, batch_size, epochs, validation_split, image_size, ...},
per-epoch ``train_loss``/``val_loss``, final ``best_val_loss``, and a new
"Actuator-Segmenter" registry version selected by best validation loss --
plus the things the reference lacks (SURVEY.md sections 2.3, 5.3-5.4):

- a jitted, donated train step (optax Adam) instead of eager per-batch
  Python;
- mIoU / Dice validation metrics (the parity metric BASELINE.md demands);
- orbax checkpointing each epoch with ``resume=True`` restart;
- optional Dice+BCE loss (BASELINE.json config 2);
- optional data-parallel execution over a device mesh (parallel/ module)
  with gradient allreduce over ICI.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from robotic_discovery_platform_tpu import tracking
from robotic_discovery_platform_tpu.analysis import recompile
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.training import checkpoint as checkpoint_lib
from robotic_discovery_platform_tpu.training import data as data_lib
from robotic_discovery_platform_tpu.training import tasks as tasks_lib
from robotic_discovery_platform_tpu.training.checkpoint import CheckpointManager
from robotic_discovery_platform_tpu.utils import platforms, transferguard
from robotic_discovery_platform_tpu.utils.config import ModelConfig, TrainConfig
from robotic_discovery_platform_tpu.utils.logging import get_logger

log = get_logger(__name__)

#: The retraining job's phases (``rdp.train.*``, README "Profiling a
#: retraining job"): each is a host span of a ``jax.profiler`` trace and a
#: sample of ``rdp_train_phase_seconds{phase}``.
phases = obs.TRAIN_PHASES


class TrainState(struct.PyTreeNode):
    """Params + optimizer + norm statistics + progress counters, one pytree
    so orbax checkpoints and shardings apply uniformly."""

    params: Any
    opt_state: Any
    batch_stats: Any
    epoch: jnp.ndarray  # scalar int32
    best_val_loss: jnp.ndarray  # scalar f32


def task_state(task, model, tx, rng, cfg: TrainConfig) -> TrainState:
    """The initial state of a job of ``task`` (``batch_stats`` empty for a
    family without running statistics)."""
    params, batch_stats = task.init_variables(model, rng, cfg)
    return TrainState(
        params=params,
        opt_state=tx.init(params),
        batch_stats=batch_stats,
        epoch=jnp.asarray(0, jnp.int32),
        best_val_loss=jnp.asarray(jnp.inf, jnp.float32),
    )


def create_state(model, tx, rng, img_size: int) -> TrainState:
    """The segmenter's initial state (:func:`task_state` of the U-Net)."""
    return task_state(tasks_lib.UNET, model, tx, rng,
                      TrainConfig(img_size=img_size))


def core_train_step(model, tx, loss_fn: Callable, task=None):
    """Unjitted (state, x, y) -> (state, loss); the parallel layer jits this
    with explicit shardings, the single-device path with plain jit. What is
    trained comes from ``task`` (``training/tasks.py``; the segmenter's when
    not given); a task whose loss returns per-step numbers beside it makes
    the second result a dict, the loss under ``"loss"``."""
    task = tasks_lib.UNET if task is None else task

    def step(state: TrainState, x, y):
        # named scopes go into the operations' op_name metadata; the
        # backward pass keeps each under JAX's transpose(jvp(...)) prefix
        def compute(params):
            return task.train_loss(model, loss_fn, params, state, x, y)

        (loss, (updates, aux)), grads = jax.value_and_grad(
            compute, has_aux=True)(state.params)
        with jax.named_scope("rdp.optimizer"):
            grad_updates, opt_state = tx.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, grad_updates)
        new_state = state.replace(
            params=params,
            opt_state=opt_state,
            batch_stats=updates.get("batch_stats", state.batch_stats),
        )
        return new_state, ({"loss": loss, **aux} if aux else loss)

    return step


def make_train_step(model, tx, loss_fn: Callable, donate: bool = True,
                    task=None):
    """Single-device jitted train step: a NEW ``jax.jit`` object on every
    call (``train_model`` keeps one per configuration and batch shape
    through :func:`memoized_runners`).

    Trace-budgeted (analysis/recompile) at ONE trace for the object's life,
    which may be its process's: every batch is full (``data.epoch_order``
    pads the tail) and another batch or image size is another object, so a
    second trace is a leak -- a shape that changes inside a job, or a
    cache the object lost."""
    # transferguard.apply: under RDP_TRANSFER_GUARD, warm steps may move
    # no implicit bytes (prefetch_to_device is the sanctioned H2D path)
    return transferguard.apply(jax.jit(
        recompile.trace_guard("trainer.train_step", budget=1)(
            core_train_step(model, tx, loss_fn, **_task_kw(task))
        ),
        donate_argnums=(0,) if donate else (),
    ))


def _task_kw(task) -> dict:
    """The ``task`` argument of a builder, left out for the segmenter's: a
    builder put in a sound one's place (a test's planted fault) keeps the
    signature the builders had before there were tasks."""
    return {} if task is None or task is tasks_lib.UNET else {"task": task}


def core_eval_step(model, loss_fn: Callable, task=None):
    """Unjitted (state, x, y) -> the task's evaluation metrics, ``"loss"``
    among them (the segmenter's: loss, miou, dice, accuracy)."""
    task = tasks_lib.UNET if task is None else task

    @jax.named_scope("rdp.eval")
    def step(state: TrainState, x, y):
        return task.evaluate(model, loss_fn, state, x, y)

    return step


def make_eval_step(model, loss_fn: Callable, task=None):
    """Single-device jitted evaluation step, built and budgeted as
    :func:`make_train_step`'s."""
    return transferguard.apply(jax.jit(
        recompile.trace_guard("trainer.eval_step", budget=1)(
            core_eval_step(model, loss_fn, **_task_kw(task))
        )
    ))


def make_epoch_runners(model, tx, loss_fn: Callable, donate: bool = True,
                       task=None):
    """Whole-epoch runners: one compiled dispatch + one host fetch per epoch.

    The per-batch Python loop pays a host->device dispatch and a loss fetch
    every step. With the dataset resident on
    device, `lax.scan` over a pre-shuffled [n_batches, batch] index matrix
    runs the whole epoch on-chip -- the TPU-idiomatic shape for datasets
    that fit in HBM (the reference's Python loop form is
    train_segmenter.py:151-189). Single-device path; the mesh path keeps
    the per-step loop (per-host sharded batches arrive from the input
    pipeline).

    Returns ``(train_epoch, eval_epoch)``:
      train_epoch(state, xs, ys, order) -> (state, mean_loss), or for a
        task with per-step numbers (state, dict of their means, the mean
        loss under "loss", and each step's loss under "step_loss")
      eval_epoch(state, xs, ys, order) -> dict of mean metrics

    Two NEW ``jax.jit`` objects on every call, each budgeted at one trace
    as :func:`make_train_step`'s: ``train_model`` keeps a pair per
    configuration AND data-set size (:func:`memoized_runners`), so an
    ``order`` of another length reaching the same object is a leak.
    """
    step = core_train_step(model, tx, loss_fn, **_task_kw(task))
    estep = core_eval_step(model, loss_fn, **_task_kw(task))

    def train_epoch(state, xs, ys, order):
        def body(s, idx):
            s2, loss = step(s, xs[idx], ys[idx])
            return s2, loss

        state, losses = jax.lax.scan(body, state, order)
        out = jax.tree.map(lambda v: jnp.mean(v, axis=0), losses)
        if isinstance(out, dict):
            out["step_loss"] = losses["loss"]
        return state, out

    def eval_epoch(state, xs, ys, order):
        def body(_, idx):
            return None, estep(state, xs[idx], ys[idx])

        _, metrics = jax.lax.scan(body, None, order)
        return jax.tree.map(jnp.mean, metrics)

    return (
        transferguard.apply(jax.jit(
            recompile.trace_guard("trainer.train_epoch", budget=1)(
                train_epoch
            ),
            donate_argnums=(0,) if donate else (),
        )),
        transferguard.apply(jax.jit(
            recompile.trace_guard("trainer.eval_epoch", budget=1)(
                eval_epoch
            )
        )),
    )


def _unit_quotient(x):
    """``float32(x) / 255`` for whole ``x`` of at most 16 bits, rounded as
    IEEE division rounds it, from operations every backend rounds alike
    (products and sums; no division of ``x``). ``q0 = x * (1 / 255)`` is
    within a few units in the last place; its residual ``x - 255 q0`` is
    made exactly, by splitting ``q0`` into halves of 12 bits whose
    products with 255 are exact (Veltkamp's split by 2^12 + 1), and one
    correcting step ``q0 + residual / 255`` lands on the quotient. XLA
    writes ``x / 255.0`` as a product with the reciprocal, which differs
    from numpy's quotient in the last bit of 126 of the 256 codes on any
    backend, and a TPU's own float32 quotient by a divisor it is handed at
    run time is not correctly rounded either (255 codes differ, and 255 /
    255 reads 0.99999994); this reads ``data.unit_floats``'s value for
    every 8- and 16-bit code on the CPU backend (``tests/test_trainer.py``)
    and on a TPU v5e (PERF.md section 6, PR 40)."""
    unit = data_lib.UNIT_SCALE
    x = x.astype(jnp.float32)
    q0 = x * (1.0 / unit)
    split = q0 * 4097.0
    high = split - (split - q0)
    residual = (x - high * unit) - (q0 - high) * unit
    return q0 + residual * (1.0 / unit)


def make_row_floats(units: tuple, layouts: tuple):
    """The program that makes a resident data set's float32 rows on the
    device out of integer rows that crossed as integers
    (``data.IntegerRows``)::

        row_floats(rows, train_idx, val_idx) -> ((train, val), ...)

    one pair of float32 arrays ``[len(idx), *sample shape]`` per entry of
    ``rows``. ``rows[i]`` is the host array's block of bytes as a
    two-dimensional view and ``layouts[i]`` the ``(shape, axes)`` it was
    flattened from (``data.memory_view``): the form PJRT moves without
    re-laying it on its host threads (PERF.md section 5, PR 40). On the
    device each gets its shape and its order of axes back, is gathered by
    the two indices as integers and made float32: ``data.unit_floats``'s
    quotient where ``units[i]`` says so (:func:`_unit_quotient`), the cast
    where not.

    A NEW ``jax.jit`` object on every call, budgeted at one trace as
    :func:`make_train_step`'s; ``_ResidentScan.stage`` keeps one per rule
    and shape set (:func:`_kept_row_floats`)."""

    def row_floats(rows, train_idx, val_idx):
        def floats(flat, unit, shape, axes):
            x = flat.reshape(shape).transpose(np.argsort(axes))
            return tuple(
                _unit_quotient(x[idx]) if unit else x[idx].astype(jnp.float32)
                for idx in (train_idx, val_idx))

        return tuple(floats(flat, unit, *layout)
                     for flat, unit, layout in zip(rows, units, layouts))

    return transferguard.apply(jax.jit(
        recompile.trace_guard("trainer.row_floats", budget=1)(row_floats)))


#: Pairs of jitted runners the process keeps, one per configuration and
#: shape set, least recently used out first; a dropped pair releases its
#: executables, so this also bounds the programs a long-lived process holds
#: loaded. A serving process retrains one configuration, the benchmark one
#: at two data-set sizes, a test process many small ones in turn.
RUNNER_MEMO_BOUND = 4

#: A train state (params + optimiser state + statistics) above this many
#: bytes is never duplicated on the device: its checkpoints are fetched to
#: the host leaf by leaf before the next donated step, a restore places leaf
#: by leaf, and "best" is the saved step that holds it instead of a second
#: tree in HBM (``checkpoint.StreamedSaves``). A smaller state keeps its
#: on-device snapshot and best copy (``checkpoint.DeviceSnapshotSaves``):
#: the copy is a device-side pass, the fetch would be synchronous every
#: epoch at the 3.8 GB/s a v5e's host takes whole leaves. ``train_model``
#: reads it through this module at call time.
_DEVICE_SNAPSHOT_MAX_BYTES = 1024**3

#: An in-memory data set above this many bytes is not kept resident on the
#: device: under ``epoch_mode="auto"`` its epochs run step by step from the
#: host (a v5e has 16 GiB of HBM; this leaves room for parameters,
#: activations and the donated state's copy). The bytes are those of the
#: rows the device would hold (``_Data.resident_bytes``): uint8 pixels
#: count as their float32 rows, four times what arrived.
_SCAN_MAX_BYTES = 4 * 1024**3

_runner_memo_lock = threading.Lock()


class _Kept(tuple):
    """An entry of the memo: the ``(train, evaluate)`` pair of runners and,
    from the first job that asks, the shapes of the state such jobs start
    from. The key names all that those shapes follow from (task and model
    configuration; Adam's moments mirror the parameters whatever its
    rates), and flax traces ``init`` anew on every ``jax.eval_shape``:
    0.13 s a U-Net on the chip's host (PERF.md, PR 32)."""

    _state_shapes = None

    def state_shapes(self, fresh_state: Callable):
        """``jax.eval_shape(fresh_state)``, traced once an entry."""
        if self._state_shapes is None:
            self._state_shapes = jax.eval_shape(fresh_state)
        return self._state_shapes


@functools.lru_cache(maxsize=RUNNER_MEMO_BOUND)
def _kept_runners(family, builders, task, model_cfg, cfg, donate,
                  guard_mode, shapes) -> _Kept:
    """The memo behind :func:`memoized_runners`: every argument is part of
    the key, and model, optimiser and loss are built from it here, so a
    kept runner can close over nothing its key does not say. ``cfg`` is the
    job's ``TrainConfig`` cut down to what shapes the programs
    (:func:`_program_settings`)."""
    model = task.build(model_cfg)
    tx = optax.adam(cfg.learning_rate)
    loss_fn = task.make_loss(cfg)
    kw = _task_kw(task)
    if family == "epoch":
        return _Kept(builders[0](model, tx, loss_fn, donate=donate, **kw))
    return _Kept((builders[0](model, tx, loss_fn, donate=donate, **kw),
                  builders[1](model, loss_fn, **kw)))


@functools.lru_cache(maxsize=RUNNER_MEMO_BOUND)
def _kept_row_floats(builder, units, layouts, dtypes, splits, guard_mode):
    """The :func:`make_row_floats` program of one rule and shape set, kept
    as :func:`_kept_runners` keeps the runners: a repeated job's staging
    traces and compiles nothing. Every argument that shapes the program is
    in the key."""
    del dtypes, splits, guard_mode
    return builder(units, layouts)


def _floats_on_device(late, train_idx, val_idx) -> tuple:
    """The ``(train, val)`` float32 arrays of each of ``late``
    (``data.IntegerRows``), made on the device: the rows cross as the block
    of bytes the host holds (``data.memory_view``) and the kept program of
    their rules and shapes (:func:`make_row_floats`) gathers the two splits
    and converts there."""
    flats, layouts = zip(*(data_lib.memory_view(a.rows) for a in late))
    row_floats = _kept_row_floats(
        make_row_floats, tuple(a.unit for a in late), layouts,
        tuple(str(a.rows.dtype) for a in late),
        (len(train_idx), len(val_idx)),
        transferguard.resolve_transfer_guard())
    return row_floats(tuple(jax.device_put(flat) for flat in flats),
                      jax.device_put(train_idx), jax.device_put(val_idx))


def _program_settings(task, cfg: TrainConfig) -> TrainConfig:
    """A ``TrainConfig`` that differs from the default only in what the
    runners of ``task`` close over: the learning rate and whatever the
    task's loss reads (``task.memo_fields``). Epochs, directories and, for
    a task that does not read it, the seed stay out of the key."""
    return TrainConfig(
        learning_rate=cfg.learning_rate,
        **{name: getattr(cfg, name) for name in task.memo_fields})


def memoized_runners(family: str, cfg: TrainConfig, model_cfg,
                     shapes: tuple) -> _Kept:
    """``train_model``'s single-device runners, the same ``jax.jit``
    objects for every call whose program-shaping settings and shapes are
    equal, so that JAX's own per-object cache spares a repeated job the
    trace, the lowering and the load of programs its process already holds;
    the pair also keeps the shapes of the state such a job starts from
    (:class:`_Kept`).

    ``family`` is ``"epoch"`` (:func:`make_epoch_runners`) or ``"step"``
    (:func:`make_train_step` with :func:`make_eval_step`); either way a
    ``(train, evaluate)`` pair. The key is everything the runners close
    over, by value: the task (found from ``model_cfg``'s type) and
    ``model_cfg`` as the job uses it, the optimiser's and the loss's
    hyper-parameters, donation, the transfer guard's mode; the
    builder functions themselves, looked up through this module at call
    time, so that a replaced builder (a test's planted fault) is never
    served a sound entry nor leaves its own behind for a sound call; and
    ``shapes``, whatever decides the shapes the job will feed them (batch
    and sample shapes, for the epoch family the data set's size). One pair
    per shape set keeps each runner at the one trace its guard allows, and
    lets the bound count programs, not only configurations.
    """
    builders = ((make_epoch_runners,) if family == "epoch"
                else (make_train_step, make_eval_step))
    with _runner_memo_lock:     # exact counts, and no pair built twice
        built = _kept_runners.cache_info().misses
        task = tasks_lib.task_for(model_cfg)
        kept = _kept_runners(
            family, builders + (core_train_step, core_eval_step), task,
            model_cfg, _program_settings(task, cfg), cfg.donate_state,
            transferguard.resolve_transfer_guard(), shapes)
        built = _kept_runners.cache_info().misses > built
    obs.TRAIN_RUNNERS.labels(
        family=family, result="built" if built else "reused").inc()
    return kept


def prefetch_to_device(batches, put):
    """Stage batch k+1 onto the device while batch k's (async-dispatched,
    donated) train step runs: the generator keeps exactly one staged batch
    ahead, so host decode + H2D transfer overlap device compute instead of
    serializing into every step -- the training-side twin of the serving
    dispatcher's pipelined staging (serving/batching.py). ``put`` is the
    device placement (``jnp.asarray`` single-device,
    ``parallel.put_global_batch`` under a mesh); ``jax.device_put`` /
    ``jnp.asarray`` are themselves asynchronous, so staging costs the host
    only the enqueue.

    Two phases per batch: ``rdp.train.loader_wait`` is the wait on the
    input pipeline for the next host batch, ``rdp.train.h2d`` what ``put``
    costs the host; as many as the data set has batches, so neither is
    recorded in the job's timeline."""
    staged = None
    batches = iter(batches)
    while True:
        with phases.stage("rdp.train.loader_wait", timeline=False):
            batch = next(batches, None)
        if batch is None:
            break
        with phases.stage("rdp.train.h2d", timeline=False):
            nxt = (put(batch[0]), put(batch[1]))
        if staged is not None:
            yield staged
        staged = nxt
    if staged is not None:
        yield staged


class _Placement(NamedTuple):
    """Where a job's arrays live, decided once a call from ``mesh`` and the
    process's place in the job (:func:`_placement`)."""

    mesh: Any               # None: the default device alone
    single: bool            # no mesh, one process
    track: Any              # ``tracking`` on process 0, else :class:`_Untracked`
    divisor: int            # data-parallel world size a global batch divides by
    to_device: Callable     # a host batch onto the device(s)
    scalarize: Callable     # (value, dtype) -> a progress counter of the state
    sharding_of: Callable   # where a restored leaf lands; None = the host
    land: Callable          # a restored state, staged where it trains


class _Untracked:
    """What a process other than 0 of a multi-host job calls in
    ``tracking``'s place: every process runs the identical program, process
    0 alone writes tracking and the registry."""

    @staticmethod
    def start_run():
        return contextlib.nullcontext(
            tracking.ActiveRun(f"process-{jax.process_index()}"))

    @staticmethod
    def _nothing(*args, **kwargs):
        return None

    set_tracking_uri = set_experiment = log_params = log_metric = _nothing
    log_model = _nothing


def _placement(mesh, task, model_cfg):
    """``(placement, the model configuration the job trains)``. A mesh of
    the default device alone shards nothing (what ``serving/rollout.py``'s
    ``training_mesh()`` hands a one-chip replica's cycle): the same model
    as under any mesh, run by the single-device runners, which the process
    keeps.

    Multi-host: checkpoint save/restore are COLLECTIVE -- every process
    calls them and orbax coordinates its own cross-host barriers,
    writing/reading per-host shards (tensor-parallel state included).
    ``checkpoint_dir`` must be shared storage (GCS or a shared filesystem)
    in a multi-host job, as is standard on TPU pods."""
    track = tracking if jax.process_index() == 0 else _Untracked
    if mesh is not None:
        model_cfg = task.for_mesh(model_cfg)
        if mesh.size == 1 and mesh.devices.flat[0] == jax.devices()[0]:
            mesh = None
    if mesh is None:
        # restored leaves land on the host and are staged explicitly,
        # because a reused runner is warm from its first step and the
        # transfer guard exempts only a cold call's transfers
        return _Placement(
            mesh=None, single=jax.process_count() == 1, track=track,
            divisor=1, to_device=jnp.asarray, scalarize=jnp.asarray,
            sharding_of=lambda leaf: None, land=jax.device_put), model_cfg
    from robotic_discovery_platform_tpu import parallel
    from robotic_discovery_platform_tpu.parallel import mesh as mesh_lib

    rep = mesh_lib.replicated(mesh)
    return _Placement(
        mesh=mesh, single=False, track=track,
        divisor=mesh.shape.get("data", 1),
        to_device=functools.partial(
            parallel.put_global_batch, mesh,
            spatial=dict(mesh.shape).get("spatial", 1) > 1),
        # progress counters live replicated on the mesh so the saved state
        # is a consistent global array on every host
        scalarize=lambda v, dtype: jax.device_put(jnp.asarray(v, dtype), rep),
        # orbax lands each host's shards directly on its devices, under
        # the built state's (possibly tensor-parallel) shardings
        sharding_of=lambda leaf: leaf.sharding,
        land=lambda state: state), model_cfg


class _Data(NamedTuple):
    """A job's data set, split: in-memory arrays (``xs``, ``ys``) or a file
    data set (``ds``), one of the two ``None``. An array is the host rows a
    step takes, or ``data.IntegerRows`` that stand for float32 rows not
    made yet, and says of itself what the step will see: shapes, dtype and
    bytes here are those of the float32 rows, whatever arrived."""

    xs: Any
    ys: Any
    ds: Any
    train_idx: np.ndarray
    val_idx: np.ndarray

    def shapes(self, cfg: TrainConfig) -> tuple:
        """What decides every shape a single-device runner will be fed:
        full batches (``data.epoch_order``) of these samples."""
        return (max(cfg.batch_size, 1),) + (
            (cfg.img_size,) if self.ds is not None else
            (self.xs.shape[1:], str(self.xs.dtype),
             self.ys.shape[1:], str(self.ys.dtype)))

    def resident_bytes(self) -> int:
        """Bytes of the in-memory arrays as a resident job would hold them
        on the device (0 for a file data set): integer pixels count as the
        float32 rows they become, four times the bytes that arrived. No
        ``np.asarray``: that would copy (or device-fetch) the whole data
        set just to read a byte count."""
        return sum(
            int(a.nbytes) if hasattr(a, "nbytes") else
            int(np.prod(np.shape(a)) * np.dtype(np.float32).itemsize)
            for a in (self.xs, self.ys) if self.ds is None)


class _ResidentScan:
    """The epoch runner of a single device with the data set resident in
    HBM: a whole epoch is one ``lax.scan`` dispatch and one host fetch
    (:func:`make_epoch_runners`), the same ``jax.jit`` pair for every call
    of equal settings and split sizes (:func:`memoized_runners`). What is
    resident is the float32 (or token) rows of the two splits; integer
    pixels cross to the device as integers and are made float32 there
    (:meth:`stage`)."""

    def __init__(self, cfg, model_cfg, data: _Data, batch_size: int):
        self._cfg, self._data, self._batch_size = cfg, data, batch_size
        self._family = tasks_lib.task_for(model_cfg).name
        self._train_epoch, self._eval_epoch = self._kept = memoized_runners(
            "epoch", cfg, model_cfg,
            data.shapes(cfg) + (len(data.train_idx), len(data.val_idx)))

    def state_shapes(self, fresh_state: Callable):
        return self._kept.state_shapes(fresh_state)

    def initial_state(self, fresh_state: Callable):
        return fresh_state()

    def stage(self) -> None:
        """The two splits onto the device, by what each array is: rows the
        task handed on as ``data.IntegerRows`` cross as the integers they
        are and become float32 there (:func:`_floats_on_device`), so the
        host writes no copy of any kind and PJRT re-lays none; any other
        array is indexed on the host and placed as it is. The bytes that
        crossed, by form, go to ``rdp_train_staged_bytes_total`` and onto
        the ``rdp.train.stage_data`` span."""
        xs, ys, _, train_idx, val_idx = self._data
        late = [a for a in (xs, ys) if isinstance(a, data_lib.IntegerRows)]
        made = iter(_floats_on_device(late, train_idx, val_idx)
                    if late else ())
        crossed = ({"device_cast": sum(a.rows.nbytes for a in late)}
                   if late else {})
        splits = []
        for a in (xs, ys):
            if isinstance(a, data_lib.IntegerRows):
                splits.append(next(made))
                continue
            pair = jnp.asarray(a[train_idx]), jnp.asarray(a[val_idx])
            form = ("host_float" if jnp.issubdtype(pair[0].dtype, jnp.floating)
                    else "as_is")
            crossed[form] = crossed.get(form, 0) + sum(
                int(p.nbytes) for p in pair)
            splits.append(pair)
        self._train, self._val = zip(*splits)
        for form, nbytes in crossed.items():
            obs.TRAIN_STAGED_BYTES.labels(
                family=self._family, form=form).inc(nbytes)
        phases.annotate(bytes=sum(crossed.values()),
                        form="+".join(sorted(crossed)))
        self._order_rng = np.random.default_rng(self._cfg.seed)
        self._val_order = jnp.asarray(data_lib.epoch_order(
            len(val_idx), self._batch_size, False, self._order_rng))

    def train(self, state, epoch: int):
        del epoch   # the one generator draws every epoch's order in turn
        order = jnp.asarray(data_lib.epoch_order(
            len(self._data.train_idx), self._batch_size, True,
            self._order_rng))
        state, out = self._train_epoch(state, *self._train, order)
        step_losses = None
        if isinstance(out, dict):
            out = jax.device_get(out)
            train_loss = float(out["loss"])
            step_losses = out.pop("step_loss")
        else:
            train_loss, out = float(out), None
        return state, train_loss, out, step_losses, int(order.shape[0])

    def validate(self, state) -> dict:
        metrics = self._eval_epoch(state, *self._val, self._val_order)
        return {k: float(v) for k, v in metrics.items()}


class _Stepped:
    """The epoch runner of every other job (files, a mesh, ``epoch_mode``
    "stream", a data set over :data:`_SCAN_MAX_BYTES`): a dispatch a step,
    batch k+1 decoded and staged while the donated step of batch k runs
    (:func:`prefetch_to_device`), the losses fetched at the epoch's end so
    that nothing blocks per step. On one device the kept jitted pair of
    :func:`memoized_runners`; under a mesh
    ``parallel.parallelize_training``'s, which also shards the initial
    state it is handed."""

    def __init__(self, cfg, model_cfg, data: _Data, batch_size: int,
                 place: _Placement, model, tx, loss_fn,
                 fresh_state: Callable):
        self._cfg, self._data, self._batch_size = cfg, data, batch_size
        self._to_device, self._divisor = place.to_device, place.divisor
        self._kept = self._sharded_state = None
        if place.mesh is None:
            self._train_step, self._eval_step = self._kept = (
                memoized_runners("step", cfg, model_cfg, data.shapes(cfg)))
        else:
            from robotic_discovery_platform_tpu import parallel

            self._train_step, self._eval_step, self._sharded_state = (
                parallel.parallelize_training(
                    place.mesh, model, tx, loss_fn, fresh_state(),
                    donate=cfg.donate_state,
                    tp_min_channels=cfg.tp_min_channels))

    def state_shapes(self, fresh_state: Callable):
        """Of a single device's job: under a mesh the state has its
        shardings only once ``parallelize_training`` has built it."""
        return self._kept.state_shapes(fresh_state)

    def initial_state(self, fresh_state: Callable):
        return (fresh_state() if self._sharded_state is None
                else self._sharded_state)

    def stage(self) -> None:
        cfg, (xs, ys, ds, train_idx, val_idx) = self._cfg, self._data
        if ds is not None:
            self._train, self._val = (
                data_lib.StreamingBatches(
                    ds, idx, self._batch_size, shuffle=shuffle, seed=cfg.seed,
                    divisor=self._divisor, workers=cfg.loader_workers)
                for idx, shuffle in ((train_idx, True), (val_idx, False)))
        else:
            # fed from the host, so integer pixels become floats there
            xs, ys = data_lib.host_rows(xs), data_lib.host_rows(ys)
            self._train, self._val = (
                data_lib.Batches(
                    xs[idx], ys[idx], self._batch_size, shuffle=shuffle,
                    seed=cfg.seed, divisor=self._divisor)
                for idx, shuffle in ((train_idx, True), (val_idx, False)))

    def train(self, state, epoch: int):
        losses = []
        step_num = epoch * len(self._train)
        for dx, dy in prefetch_to_device(self._train, self._to_device):
            with jax.profiler.StepTraceAnnotation(
                    "rdp.train.step", step_num=step_num):
                state, loss = self._train_step(state, dx, dy)
            losses.append(loss)
            step_num += 1
        out = step_losses = None
        if losses and isinstance(losses[0], dict):
            losses = jax.device_get(losses)
            out = jax.tree.map(lambda *v: np.mean(v, axis=0), *losses)
            train_loss = float(out["loss"])
            step_losses = [l["loss"] for l in losses]
        else:
            train_loss = float(np.mean([float(l) for l in losses]))
        return state, train_loss, out, step_losses, len(losses)

    def validate(self, state) -> dict:
        agg: dict[str, list] = {}
        for bx, by in self._val:
            m = self._eval_step(
                state, self._to_device(bx), self._to_device(by))
            for k, v in m.items():
                agg.setdefault(k, []).append(float(v))
        return {k: float(np.mean(v)) for k, v in agg.items()}


def _epoch_runner(cfg, model_cfg, data: _Data, batch_size: int,
                  place: _Placement, model, tx, loss_fn,
                  fresh_state: Callable):
    """How a job's epochs run, decided once: a whole-epoch ``lax.scan``
    needs one device and the data set resident in HBM (in-memory arrays, no
    mesh); ``cfg.epoch_mode`` demands it ("scan"), takes it where the data
    set fits :data:`_SCAN_MAX_BYTES` ("auto"), or forgoes it ("stream")."""
    if cfg.epoch_mode not in ("auto", "scan", "stream"):
        raise ValueError(
            f"epoch_mode must be auto|scan|stream, got {cfg.epoch_mode!r}"
        )
    resident = data.ds is None and place.mesh is None
    nbytes = data.resident_bytes()
    fits = nbytes <= _SCAN_MAX_BYTES
    if cfg.epoch_mode == "scan" and not resident:
        raise ValueError(
            "epoch_mode='scan' needs an in-memory dataset and no mesh"
        )
    if cfg.epoch_mode == "auto" and resident and not fits:
        log.info(
            "dataset is %.1f GiB, over the %.0f GiB kept resident; using "
            "the streamed per-batch path",
            nbytes / 2**30, _SCAN_MAX_BYTES / 2**30)
    if resident and (cfg.epoch_mode == "scan"
                     or (cfg.epoch_mode == "auto" and fits)):
        return _ResidentScan(cfg, model_cfg, data, batch_size)
    return _Stepped(cfg, model_cfg, data, batch_size, place, model, tx,
                    loss_fn, fresh_state)


@dataclass
class TrainResult:
    run_id: str
    registry_version: int | None
    best_val_loss: float
    final_metrics: dict
    epochs_run: int
    wall_clock_s: float

    def to_jsonable(self) -> dict:
        """Plain-JSON form (metrics may be numpy/jax scalars) -- the ONE
        serialization the CLI and the supervisor child both use, so the
        two cannot drift."""
        return {
            "run_id": self.run_id,
            "registry_version": self.registry_version,
            "best_val_loss": float(self.best_val_loss),
            "final_metrics": {k: float(v)
                              for k, v in self.final_metrics.items()},
            "epochs_run": int(self.epochs_run),
            "wall_clock_s": round(float(self.wall_clock_s), 2),
        }


def _process_counts() -> dict:
    """What the process has counted so far of making executables and of
    ``train_model``'s own decisions, under the names a job's root span gives
    a call's differences of them (:func:`_recorded_job`). Process-wide: what
    another thread compiles meanwhile is in a call's difference."""
    counts = {f"jit_s.{stage}": obs.JIT_SECONDS.labels(stage=stage).value
              for stage in platforms.JIT_STAGES}
    for result in platforms.CACHE_RESULTS:
        counts[f"compile_cache.{result}"] = obs.COMPILE_CACHE.labels(
            result=result).value
    counts["traces"] = sum(s.value for s in obs.JIT_TRACES.samples())
    # by result, over the families
    for name, counter in (("state", obs.TRAIN_STATE),
                          ("runners", obs.TRAIN_RUNNERS)):
        for sample in counter.samples():
            key = f"{name}.{dict(sample.labels)['result']}"
            counts[key] = counts.get(key, 0.0) + sample.value
    return counts


@contextlib.contextmanager
def _recorded_job(cfg: TrainConfig):
    """The ``rdp.train.job`` phase of one ``train_model`` call as the root of
    a timeline in the flight recorder (``StageTimer.timeline``), which is
    yielded for the call to label. The root's attributes are counts at the
    call's boundaries: where the process stood when the call began
    (``process_age_s``, and ``process_jit_s``, ``process_cache_hits``,
    ``process_cache_misses``: every compile of the process until then,
    whichever thread made it) and, when it returns or raises, what the call
    added to :func:`_process_counts`: ``jit_s.<stage>`` seconds,
    ``compile_cache.hit`` / ``.miss``, ``traces`` over all guards, and
    ``state`` (``built`` | ``restored``) and ``runners`` (``built`` |
    ``reused``; absent under a mesh, which looks nothing up) as
    ``rdp_train_state_total`` and ``rdp_train_runner_cache_total`` counted
    them."""
    platforms.listen_to_compiles()
    with phases.timeline("rdp.train.job", {
            "checkpoint_dir": cfg.checkpoint_dir,
            "epochs": cfg.epochs}) as timeline:
        before = _process_counts()
        age = platforms.process_age_s()
        if age is not None:
            phases.annotate(process_age_s=f"{age:.2f}")
        jit_s = sum(before[f"jit_s.{s}"] for s in platforms.JIT_STAGES)
        phases.annotate(
            process_jit_s=f"{jit_s:.6f}",
            process_cache_hits=int(before["compile_cache.hit"]),
            process_cache_misses=int(before["compile_cache.miss"]))
        try:
            yield timeline
        finally:
            added = {name: value - before.get(name, 0.0)
                     for name, value in _process_counts().items()}
            decisions = ("state.", "runners.")
            # "state.restored" counted once: state=restored
            phases.annotate(
                **dict(name.split(".") for name, value in added.items()
                       if name.startswith(decisions) and value),
                **{name: f"{value:.6f}" if name.startswith("jit_s.")
                   else int(value) for name, value in added.items()
                   if not name.startswith(decisions)})


def train_model(
    cfg: TrainConfig = TrainConfig(),
    model_cfg: ModelConfig | Any = ModelConfig(),
    arrays: tuple | None = None,
    resume: bool = False,
    mesh=None,
    register: bool = True,
) -> TrainResult:
    """Train, track, checkpoint, and register -- the reference
    ``train_model()`` entry point rebuilt (train_segmenter.py:103-210).

    Args:
        cfg / model_cfg: configuration (defaults = reference constants).
            The type of ``model_cfg`` says what is trained
            (``training/tasks.py``): a ``ModelConfig`` the segmenter, a
            ``BlockDiffLMConfig`` a block-diffusion language model, a
            ``CausalLMConfig`` a causal one of window and full layers, a
            ``HybridLMConfig`` a causal one of one-branch layers (state-space
            mixers, short convolutions, attention, dense MLPs, experts).
        arrays: optional in-memory ((xs, ys)) dataset overriding
            ``cfg.dataset_dir`` (tests, synthetic smoke runs); for a token
            task ``(tokens [n, L] int32, None)``. Integer images and masks
            (coded {0,1} or {0,255}) train as the float32 the file loader
            makes of them; a single-device job keeps the set resident on
            the device where its float32 rows fit ``_SCAN_MAX_BYTES``
            (4 GiB, whatever dtype arrived), and pairs narrower than
            float32 then cross as integers and become float32 there.
        resume: restore the latest checkpoint under
            ``cfg.checkpoint_dir`` and continue from its epoch; a start
            from nothing where there is none. A single-device job restores
            into the shapes of its initial state, by either save path, and
            never builds that state (``rdp_train_state_total`` says which a
            call did). In a multi-host job the restore is collective (every
            process calls it, sharded leaves land on their home devices),
            so ``checkpoint_dir`` must be shared storage across hosts.
        mesh: optional ``jax.sharding.Mesh``; when given, batches are sharded
            over the mesh's "data" axis and gradients allreduce over ICI
            (see parallel/). A mesh of the default device alone trains
            the same model (XLA convolutions) without one.
        register: register the best model in the registry under
            ``cfg.registered_model_name``.

    The call is one ``rdp.train.job`` phase whose children tile it
    (:data:`phases`): init, restore, stage_data, then per epoch steps,
    validation, log, best_copy and the checkpoint hand-over, then register
    and flush; and, profiler or not, one timeline of those phases in the
    flight recorder (:func:`_recorded_job`; ``GET /debug/spans``), labelled
    ``family``, ``checkpoint_dir``, ``run_id``, ``resumed``, ``epochs``,
    with the bytes a restore or a checkpoint write moved and an epoch's
    ``steps`` (and ``routed_rows``) on their spans. Its three decisions are
    taken once, in init, and are objects from there on: where arrays live
    (:class:`_Placement`), how an epoch runs (:class:`_ResidentScan` or
    :class:`_Stepped`), and how the state is saved and the best candidate
    kept (``checkpoint.DeviceSnapshotSaves`` or ``checkpoint.StreamedSaves``).
    """
    with _recorded_job(cfg) as timeline:
        t_start = time.perf_counter()
        with phases.stage("rdp.train.init"):
            task = tasks_lib.task_for(model_cfg)
            timeline.labels["family"] = task.name
            if arrays is not None:
                xs, ys = task.prepare(arrays, cfg)
                n_samples, ds = len(xs), None
            else:
                xs = ys = None
                ds = task.file_data(cfg)
                n_samples = len(ds)
            data = _Data(xs, ys, ds, *data_lib.train_val_split(
                n_samples, cfg.validation_split, cfg.seed))
            if len(data.val_idx) == 0:
                raise ValueError("dataset too small for a validation split")
            if cfg.checkpoint_every < 1:
                # 0 would be a ZeroDivisionError deep in the epoch loop; negatives
                # would silently save every epoch
                raise ValueError(
                    f"checkpoint_every must be >= 1, got {cfg.checkpoint_every}"
                )

            place, model_cfg = _placement(mesh, task, model_cfg)
            # the global batch rounded up to a multiple of the data-parallel
            # world size, so every jit-sharded batch divides over the mesh
            batch_size = -(-max(cfg.batch_size, place.divisor)
                           // place.divisor) * place.divisor
            model = task.build(model_cfg)
            tx = optax.adam(cfg.learning_rate)
            loss_fn = task.make_loss(cfg)

            def fresh_state():
                return task_state(
                    task, model, tx, jax.random.key(cfg.seed), cfg)

            runner = _epoch_runner(cfg, model_cfg, data, batch_size, place,
                                   model, tx, loss_fn, fresh_state)

            # A job that will restore its state takes the state's shapes
            # and builds nothing: whichever save path wrote the checkpoint,
            # a resumed single-device job never makes the initial state it
            # would throw away. One that starts from nothing builds it; so
            # does any job under a mesh, where parallelize_training shards
            # a concrete one.
            ckpt = CheckpointManager(cfg.checkpoint_dir, keep=cfg.keep_checkpoints)
            resuming = resume and ckpt.latest_step() is not None
            timeline.labels["resumed"] = str(resuming)
            abstract_state = (runner.state_shapes(fresh_state)
                              if place.single else None)
            state = (None if resuming and place.single
                     else runner.initial_state(fresh_state))
            obs.TRAIN_STATE.labels(
                family=task.name,
                result="restored" if state is None else "built").inc()
            # How the state is saved, by its size (_DEVICE_SNAPSHOT_MAX_BYTES)
            saves = (
                checkpoint_lib.StreamedSaves(
                    ckpt, place.scalarize, abstract_state)
                if place.single and checkpoint_lib.tree_bytes(abstract_state)
                > _DEVICE_SNAPSHOT_MAX_BYTES
                else checkpoint_lib.DeviceSnapshotSaves(
                    ckpt, place.scalarize, place.sharding_of, place.land))

        if resuming:
            # after parallelize_training, so that under a mesh the template
            # carries the final (possibly TP-sharded) shardings
            with phases.stage("rdp.train.restore"):
                state = saves.restore(
                    abstract_state if state is None else state)
                log.info("resumed from checkpoint at epoch %d", int(state.epoch))

        with phases.stage("rdp.train.stage_data"):
            runner.stage()

        track = place.track
        with phases.stage("rdp.train.log"):
            track.set_tracking_uri(cfg.tracking_uri)
            track.set_experiment(cfg.experiment_name)
            run_ctx = track.start_run()

        registry_version = None
        final_metrics: dict = {}

        # close() on BOTH exits: an exception mid-training must still drain
        # any in-flight async save (abandoning the daemon worker would
        # silently lose the checkpoint it was writing) without masking the
        # original error; the clean path surfaces save failures by raising
        try:
            with run_ctx as run:
                timeline.labels["run_id"] = str(run.info.run_id)
                with phases.stage("rdp.train.log"):
                    track.log_params(
                        {
                            # exact reference param-name surface
                            # (train_segmenter.py:119-128)
                            "learning_rate": cfg.learning_rate,
                            "batch_size": batch_size,
                            "epochs": cfg.epochs,
                            "validation_split": cfg.validation_split,
                            "optimizer": "adam",
                            "backend": jax.default_backend(),
                            "num_devices": place.divisor,
                            **task.run_params(cfg, model_cfg),
                        }
                    )

                start_epoch = min(int(state.epoch), cfg.epochs)
                if int(state.epoch) >= cfg.epochs:
                    log.warning(
                        "checkpoint epoch %d >= cfg.epochs %d; nothing to train, "
                        "evaluating only", int(state.epoch), cfg.epochs,
                    )
                    with phases.stage("rdp.train.validation"):
                        final_metrics = runner.validate(state)
                for epoch in range(start_epoch, cfg.epochs):
                    with phases.stage("rdp.train.epoch", epoch=epoch):
                        t_epoch = time.perf_counter()
                        with phases.stage("rdp.train.steps"):
                            state, train_loss, out, step_losses, n_steps = (
                                runner.train(state, epoch))
                            # Train-phase throughput (the runner's fetch of
                            # the losses synced the device, so the window
                            # covers real step time). One histogram sample
                            # per epoch at the mean step time: the scan is
                            # one dispatch with no per-step boundary to
                            # time, and the stepped loop's per-step wall
                            # time is dispatch-only, so the epoch mean is
                            # the honest per-step number for both.
                            train_time = time.perf_counter() - t_epoch
                        phases.annotate(steps=n_steps)
                        if n_steps and train_time > 0:
                            obs.TRAIN_STEP.observe(train_time / n_steps)
                            obs.TRAIN_RATE.set(
                                n_steps * batch_size / train_time)
                            if out is not None:
                                task.observe(
                                    out, n_steps, batch_size, train_time,
                                    None if ds is not None else xs.shape[1:])

                        with phases.stage("rdp.train.validation"):
                            final_metrics = val = runner.validate(state)

                        with phases.stage("rdp.train.log"):
                            track.log_metric(
                                "train_loss", train_loss, step=epoch)
                            track.log_metric(
                                "val_loss", val["loss"], step=epoch)
                            for name in task.val_logged:
                                track.log_metric(
                                    f"val_{name}", val[name], step=epoch)
                            if out is not None:
                                # a task with per-step numbers: every
                                # step's loss, numbered through the epochs
                                for i, v in enumerate(step_losses):
                                    track.log_metric(
                                        "train_step_loss", float(v),
                                        step=epoch * n_steps + i)
                            log.info(
                                "epoch %d/%d train_loss=%.4f val_loss=%.4f "
                                "%s=%.4f (%.1fs)",
                                epoch + 1, cfg.epochs, train_loss, val["loss"],
                                task.val_logged[0], val[task.val_logged[0]],
                                time.perf_counter() - t_epoch,
                            )

                        # every checkpoint_every-th epoch and the last one
                        state = saves.epoch_done(
                            state, epoch, val["loss"],
                            saving=not ((epoch + 1) % cfg.checkpoint_every
                                        and epoch + 1 < cfg.epochs))

                with phases.stage("rdp.train.log"):
                    track.log_metric(
                        "best_val_loss", float(state.best_val_loss))

                if register:
                    with phases.stage("rdp.train.register"):
                        # on every process (a collective all-gather of any
                        # TP-sharded leaves); only process 0 writes
                        best = saves.best()
                        if best is not None:
                            registry_version = track.log_model(
                                task.variables(*best), model_cfg,
                                registered_model_name=cfg.registered_model_name,
                            )
                        if registry_version is not None:
                            log.info(
                                "registered %s version %s",
                                cfg.registered_model_name, registry_version,
                            )

                run_id = run.info.run_id

        except BaseException:
            # close without raising: a pending save failure must not mask the
            # already-propagating training exception (it is logged instead)
            with phases.stage("rdp.train.flush"):
                saves.close(raise_errors=False)
            raise
        else:
            with phases.stage("rdp.train.flush"):
                saves.close()
        return TrainResult(
            run_id=run_id,
            registry_version=registry_version,
            best_val_loss=float(state.best_val_loss),
            final_metrics=final_metrics,
            epochs_run=cfg.epochs - start_epoch,
            wall_clock_s=time.perf_counter() - t_start,
        )
