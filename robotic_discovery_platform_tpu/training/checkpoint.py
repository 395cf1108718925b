"""Orbax checkpoint/resume.

The reference has no mid-run durability at all: an interrupted training run
loses everything except the last best-model file (reference:
scripts/train_segmenter.py:148-189; SURVEY.md section 5.4). Here every epoch
checkpoints the full train state (params, optimizer state, batch stats,
epoch counter, best-val bookkeeping) through orbax -- which is also
sharding-aware, so the same path serves the data-parallel trainer.
The trainer gives ``restore`` shapes, not a state: a tree of
``jax.ShapeDtypeStruct``s, with the mesh's shardings where there is one
and none for the host, so a job that resumes builds no state to restore
into; a checkpoint whose shapes differ raises there.

Two save paths:

- ``save``: synchronous collective save. The multi-host path MUST use it
  (orbax coordinates cross-host barriers; every process calls in
  lockstep).
- ``save_async``: single-process overlap. The caller hands an
  INDEPENDENT on-device snapshot (``_copy_tree``); a single
  worker thread then pays the host fetch (~350 MB of
  params+optimizer+best-candidate at full width) and
  the disk write while the next epoch's compute runs on the chip. One
  save in flight at a time; ``wait``/``close`` drain.

A third path, for a state too large to hold twice on the device (a
decision of the trainer by the state's size,
``trainer._DEVICE_SNAPSHOT_MAX_BYTES``):

- ``save_streamed``: the calling thread fetches the tree to the host leaf
  by leaf, in pieces of at most ``STREAM_PIECE_BYTES``, before it returns
  (so the next donated step may run); the worker thread then writes one raw
  ``.npy`` file a leaf under ``<directory>/streamed/<step>/``. No device
  copy is made, and the host holds one copy until the write lands. Raw
  files because orbax's tensorstore writer compresses and sustains under
  0.1 GB/s a process where the disk takes ten times that, which at 8 GB of
  state is minutes against seconds. ``restore_streamed`` maps each file
  and places leaf after leaf, so neither the host nor the device ever
  holds a second copy. ``best`` marks the step whose checkpoint holds the
  best candidate; pruning keeps it, and ``restore_streamed(step=best)``
  with a template of the parameters alone reads just those leaves.
  ``streamed_files(template, step)`` names those leaves' files instead: a
  leaf file is written once and never changed, so the registry's artifact
  of the best candidate is hard links to them (:func:`link_leaves`), not a
  second copy read back and written again.

Which of them a job takes, and what it keeps as the best candidate, is a
**save policy**, chosen once a ``train_model`` call: :class:`DeviceSnapshotSaves`
(orbax, the candidate a second tree on the device) or :class:`StreamedSaves`
(leaf files, the candidate a saved step). The trainer asks a policy four
things: ``restore(like)``, ``epoch_done(state, epoch, val_loss, saving)``,
``best()`` and ``close(raise_errors)``.
"""

from __future__ import annotations

import json
import shutil
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from robotic_discovery_platform_tpu.observability import instruments as obs


#: the largest piece of a leaf that ``fetch_streamed`` asks the device for
#: (whole leaves came over at 3.8 GB/s on a v5e's host, slices of 0.1 GB at
#: 1.2: a piece is as large as a second copy of it may be)
STREAM_PIECE_BYTES = 1024**3
_STREAMED, _MANIFEST, _BEST = "streamed", "manifest.json", "best.json"


def tree_bytes(tree) -> int:
    """Bytes of a tree of arrays or of ``jax.ShapeDtypeStruct``s."""
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def fetch_streamed(tree):
    """``tree`` on the host as numpy arrays, leaf after leaf; a leaf above
    ``STREAM_PIECE_BYTES`` comes over in slices along its first axis, so the
    device stages one piece at a time and never a copy of the state."""

    def fetch(a):
        if not isinstance(a, jax.Array):
            return np.asarray(a)
        if a.nbytes <= STREAM_PIECE_BYTES or a.ndim == 0 or a.shape[0] == 1:
            return np.asarray(a)
        out = np.empty(a.shape, a.dtype)
        rows = max(1, int(STREAM_PIECE_BYTES // (a.nbytes // a.shape[0])))
        for i in range(0, a.shape[0], rows):
            out[i:i + rows] = np.asarray(a[i:i + rows])
        return out

    return jax.tree.map(fetch, tree)


def _leaf_files(tree) -> dict:
    """key path -> leaf, under names that are the same for a state and for
    any template cut out of it (the parameters alone)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): leaf for path, leaf in flat}


def write_leaves(directory: Path, tree) -> None:
    """``tree`` (host arrays) as one raw ``.npy`` file a leaf under
    ``directory``, with a manifest from key path to file; four writers."""
    directory.mkdir(parents=True)
    files = _leaf_files(tree)
    names = {key: f"{i:05d}.npy" for i, key in enumerate(files)}
    with ThreadPoolExecutor(4) as pool:
        list(pool.map(
            lambda key: np.save(directory / names[key], files[key]), files))
    (directory / _MANIFEST).write_text(json.dumps(names))


def leaf_files(directory: Path, template):
    """``template``'s tree (any sub-tree of what :func:`write_leaves` wrote,
    under the same keys) with the path of each leaf's file as the leaf."""
    names = json.loads((directory / _MANIFEST).read_text())

    def find(path, _):
        key = jax.tree_util.keystr(path)
        if key not in names:
            raise KeyError(f"{directory} holds no leaf {key}")
        return directory / names[key]

    return jax.tree_util.tree_map_with_path(find, template)


def are_leaf_files(tree) -> bool:
    """Whether ``tree`` holds files' paths (:func:`leaf_files`) and not
    arrays."""
    leaves = jax.tree.leaves(tree)
    return bool(leaves) and all(isinstance(leaf, Path) for leaf in leaves)


def link_leaves(directory: Path, files) -> None:
    """What :func:`write_leaves` would write for the arrays that the tree
    ``files`` names by their files (:func:`leaf_files`), as hard links to
    those files: no byte of a leaf is read or written. A leaf on another
    file system is copied."""
    from robotic_discovery_platform_tpu.tracking.store import link_or_copy

    directory.mkdir(parents=True)
    flat = _leaf_files(files)
    names = {key: f"{i:05d}.npy" for i, key in enumerate(flat)}
    for key, source in flat.items():
        link_or_copy(source, directory / names[key])
    (directory / _MANIFEST).write_text(json.dumps(names))


def read_leaves(directory: Path, template, place=None):
    """The leaves of ``template`` (arrays or ``ShapeDtypeStruct``s; any
    sub-tree of what :func:`write_leaves` wrote, under the same keys), each
    mapped from its file and handed to ``place`` (``None`` leaves numpy
    arrays) before the next is read."""
    names = json.loads((directory / _MANIFEST).read_text())

    def load(path, want):
        key = jax.tree_util.keystr(path)
        if key not in names:
            raise KeyError(f"{directory} holds no leaf {key}")
        a = np.load(directory / names[key], mmap_mode="r")
        if a.shape != tuple(want.shape) or a.dtype != want.dtype:
            raise ValueError(
                f"leaf {key}: saved {a.dtype}{a.shape}, wanted "
                f"{want.dtype}{tuple(want.shape)}")
        if place is None:
            return np.array(a)
        placed = place(a)
        jax.block_until_ready(placed)
        return placed

    return jax.tree_util.tree_map_with_path(load, template)


class CheckpointManager:
    def __init__(self, directory: str | Path, keep: int = 3):
        self._dir, self._keep = Path(directory).absolute(), keep
        self._mgr = ocp.CheckpointManager(
            Path(directory).absolute(),
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep, create=True, enable_async_checkpointing=False
            ),
        )
        self._pending: threading.Thread | None = None
        self._pending_error: BaseException | None = None

    def save(self, step: int, state: Any) -> None:
        self.wait()
        self._mgr.save(step, args=ocp.args.StandardSave(state))
        self._mgr.wait_until_finished()

    def save_async(self, step: int, state: Any) -> None:
        """Fetch-and-write ``state`` in the background. ``state``'s leaves
        must be buffers the training loop will NOT donate or mutate (pass
        an on-device copy). Single-process only -- the cross-host orbax
        barriers of a multi-host save must run on the main thread in
        lockstep across processes."""
        self.wait()  # one save in flight; surfaces the previous error
        # the worker's stages hang under the stage that hands the save over
        handed = obs.TRAIN_PHASES.handover()

        def work():
            try:
                with obs.TRAIN_PHASES.adopted(handed):
                    with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.fetch"):
                        host = jax.device_get(state)
                    with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.write"):
                        obs.TRAIN_PHASES.annotate(bytes=tree_bytes(host))
                        self._mgr.save(step, args=ocp.args.StandardSave(host))
                        self._mgr.wait_until_finished()
            except BaseException as exc:  # surfaced by the next wait()
                self._pending_error = exc

        self._pending = threading.Thread(
            target=work, name="checkpoint-save", daemon=True
        )
        self._pending.start()

    def wait(self) -> None:
        """Block until any in-flight async save lands; re-raise its error."""
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._pending_error is not None:
            exc, self._pending_error = self._pending_error, None
            raise exc

    # -- the streamed path ---------------------------------------------------

    def _streamed_steps(self) -> list[int]:
        home = self._dir / _STREAMED
        return sorted(int(p.name) for p in home.glob("[0-9]*")
                      if p.is_dir() and p.name.isdigit()
                      and (p / _MANIFEST).is_file())

    def best_step(self) -> int | None:
        """The streamed step marked as holding the best candidate."""
        self.wait()
        path = self._dir / _STREAMED / _BEST
        return json.loads(path.read_text())["step"] if path.is_file() \
            else None

    def save_streamed(self, step: int, state: Any,
                      best: bool = False) -> None:
        """Fetch ``state`` to the host now, piece by piece, and write it in
        the background; ``best`` marks this step as the best candidate's.
        Single-process only, as ``save_async``."""
        self.wait()
        with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.fetch"):
            host = fetch_streamed(state)
        home = self._dir / _STREAMED
        final, tmp = home / str(step), home / f"{step}.tmp"
        handed = obs.TRAIN_PHASES.handover()

        def work():
            try:
                with obs.TRAIN_PHASES.adopted(handed), \
                        obs.TRAIN_PHASES.stage("rdp.train.checkpoint.write"):
                    obs.TRAIN_PHASES.annotate(bytes=tree_bytes(host))
                    shutil.rmtree(tmp, ignore_errors=True)
                    write_leaves(tmp, host)
                    shutil.rmtree(final, ignore_errors=True)
                    tmp.rename(final)
                    if best:
                        (home / _BEST).write_text(json.dumps({"step": step}))
                    keep = set(self._streamed_steps()[-self._keep:])
                    marked = home / _BEST
                    if marked.is_file():
                        keep.add(json.loads(marked.read_text())["step"])
                    for old in set(self._streamed_steps()) - keep:
                        shutil.rmtree(home / str(old), ignore_errors=True)
            except BaseException as exc:  # surfaced by the next wait()
                self._pending_error = exc

        self._pending = threading.Thread(
            target=work, name="checkpoint-save", daemon=True
        )
        self._pending.start()

    def _streamed_dir(self, step: int | None) -> Path:
        """The directory of the streamed checkpoint of ``step`` (the
        latest), once no save is in flight."""
        self.wait()
        steps = self._streamed_steps()
        step = (steps[-1] if steps else None) if step is None else step
        if step is None or step not in steps:
            raise FileNotFoundError(f"no streamed checkpoint {step}")
        return self._dir / _STREAMED / str(step)

    def restore_streamed(self, template: Any, step: int | None = None,
                         place=None) -> Any:
        """The leaves of ``template`` (arrays or ``ShapeDtypeStruct``s; any
        sub-tree of what was saved, under the same keys) from a streamed
        checkpoint, each mapped from its file and handed to ``place`` (the
        device placement; ``None`` leaves numpy arrays) before the next is
        read."""
        return read_leaves(self._streamed_dir(step), template, place)

    def streamed_files(self, template: Any, step: int | None = None) -> Any:
        """``template``'s tree with each leaf the path of its file in the
        streamed checkpoint of ``step`` (:func:`leaf_files`); a save in
        flight lands first."""
        return leaf_files(self._streamed_dir(step), template)

    def latest_step(self) -> int | None:
        self.wait()
        steps = self._streamed_steps()
        orbax_step = self._mgr.latest_step()
        if steps and (orbax_step is None or steps[-1] > orbax_step):
            return steps[-1]
        return orbax_step

    def restore(self, template: Any, step: int | None = None) -> Any:
        """The orbax checkpoint of ``step`` (the latest) as ``template``'s
        tree: arrays or ``ShapeDtypeStruct``s, whose shardings say where a
        leaf lands (none: the host, as numpy). A leaf saved under another
        shape raises: orbax holds only sharded leaves to the template's."""
        self.wait()
        step = self._mgr.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError("no checkpoint to restore")
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(template))

        def held(path, want, got):
            if np.shape(got) != tuple(want.shape):
                raise ValueError(
                    f"leaf {jax.tree_util.keystr(path)}: saved "
                    f"{np.shape(got)}, wanted {tuple(want.shape)}")

        jax.tree_util.tree_map_with_path(held, template, restored)
        return restored

    def close(self, raise_errors: bool = True) -> None:
        """Drain any in-flight save and close the orbax manager (which is
        closed even if the drain raises). ``raise_errors=False`` logs a
        pending save failure instead of raising -- for cleanup paths that
        must not mask an already-propagating exception."""
        try:
            self.wait()
        except BaseException:
            if raise_errors:
                raise
            import logging

            logging.getLogger(__name__).exception(
                "async checkpoint save failed during close"
            )
        finally:
            self._mgr.close()


# -- the save policies --------------------------------------------------------

#: Independent device buffers for a pytree: safe to hold across later
#: donated train steps, and checkpointable as (possibly sharded) global
#: arrays. jit outputs never alias non-donated inputs, so every leaf is a
#: fresh buffer with its input sharding preserved. Module-level so the
#: compiled copy program is cached across improving epochs.
@jax.jit
def _copy_tree(tree):
    return jax.tree.map(jnp.copy, tree)


def _fetch_to_host(tree):
    """``device_get`` that first re-replicates any non-fully-replicated
    leaves (tensor-parallel shards) through ONE collective identity jit.
    Must be called on EVERY process of a multi-host job (the re-replication
    is an all-gather)."""

    def sharded(a):
        return (
            hasattr(a, "is_fully_replicated") and not a.is_fully_replicated
        )

    if any(sharded(a) for a in jax.tree.leaves(tree)):
        from robotic_discovery_platform_tpu.parallel import mesh as mesh_lib

        out_shardings = jax.tree.map(
            lambda a: mesh_lib.replicated(a.sharding.mesh)
            if sharded(a) else a.sharding,
            tree,
        )
        tree = jax.jit(lambda t: t, out_shardings=out_shardings)(tree)
    return jax.device_get(tree)


class _Saves:
    """What the two save policies share: the manager they save through
    and ``scalarize(value, dtype)``, the trainer's placement of a progress
    counter (``epoch``, ``best_val_loss``) where the state lives."""

    def __init__(self, ckpt: CheckpointManager, scalarize):
        self._ckpt, self._scalarize = ckpt, scalarize

    def close(self, raise_errors: bool = True) -> None:
        self._ckpt.close(raise_errors)


class DeviceSnapshotSaves(_Saves):
    """The policy of a state small enough to hold twice on the device (the
    U-Nets) and of every job under a mesh or of several processes.

    The best candidate's parameters and statistics are independent DEVICE
    buffers (:func:`_copy_tree`), so they survive donation of the live
    state and checkpoint as sharded global arrays under tensor parallelism;
    every checkpoint carries them beside the live state, so a resumed job
    registers the parameters that achieved ``best_val_loss``, not whatever
    the last epoch held. ``sharding_of(leaf)`` says where a restored leaf
    lands (a mesh's sharding; ``None`` the host, in half the time orbax
    takes to place leaves one by one: PERF.md, PR 32) and ``land(state)``
    stages what landed on the host."""

    def __init__(self, ckpt, scalarize, sharding_of, land):
        super().__init__(ckpt, scalarize)
        self._sharding_of, self._land = sharding_of, land
        # multi-host saves are collective: orbax's cross-host barriers must
        # run in lockstep on every process, on the main thread
        self._collective = jax.process_count() > 1
        self._best_params = self._best_stats = None

    def restore(self, like):
        """The latest checkpoint into the shapes of ``like`` (a state or
        its ``ShapeDtypeStruct``s); a checkpoint of other shapes raises."""
        wanted = {"state": like, "best_params": like.params,
                  "best_stats": like.batch_stats}
        obs.TRAIN_PHASES.annotate(bytes=tree_bytes(wanted))
        restored = self._ckpt.restore(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=self._sharding_of(a)),
            wanted))
        state = self._land(restored["state"])
        if np.isfinite(float(state.best_val_loss)):
            self._best_params = restored["best_params"]
            self._best_stats = restored["best_stats"]
        return state

    def epoch_done(self, state, epoch: int, val_loss: float, saving: bool):
        if val_loss < float(state.best_val_loss):
            with obs.TRAIN_PHASES.stage("rdp.train.best_copy"):
                state = state.replace(
                    best_val_loss=self._scalarize(val_loss, jnp.float32))
                self._best_params, self._best_stats = _copy_tree(
                    (state.params, state.batch_stats))
        state = state.replace(epoch=self._scalarize(epoch + 1, jnp.int32))
        if not saving:
            return state
        none_yet = self._best_params is None
        payload = {
            "state": state,
            "best_params": state.params if none_yet else self._best_params,
            "best_stats": state.batch_stats if none_yet else self._best_stats,
        }
        if self._collective:
            with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.snapshot"):
                self._ckpt.save(epoch + 1, payload)
            return state
        # One process: snapshot to independent device buffers (a cheap HBM
        # copy, and required: the live state is donated into the next
        # epoch's step), then a background worker pays the ONE bulk host
        # fetch and the disk write while the next epoch's compute runs.
        # Orbax pulling device arrays leaf by leaf would cost a round trip
        # a leaf (~270), a synchronous fetch ~350 MB of D2H every epoch.
        # The PREVIOUS save is waited for before the new snapshot is made:
        # otherwise three copies of the state (live, old snapshot, new
        # snapshot) coexist in HBM whenever saves outlast epochs.
        with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.wait"):
            self._ckpt.wait()
        with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.snapshot"):
            self._ckpt.save_async(epoch + 1, _copy_tree(payload))
        return state

    def best(self):
        """``(params, statistics)`` of the best candidate on the host, or
        ``None``. Collective: any tensor-parallel leaves are all-gathered,
        so every process of a multi-host job calls it."""
        if self._best_params is None:
            return None
        return (_fetch_to_host(self._best_params),
                _fetch_to_host(self._best_stats))


class StreamedSaves(_Saves):
    """The policy of a state too large to hold twice on the device
    (``sdar``): no on-device snapshot or best copy. The state comes to the
    host leaf by leaf before the next donated step and a background worker
    writes it while that step runs; a restore places leaf after leaf, so
    neither side ever holds a second copy; and only a saved epoch can be
    best: ``best_step`` names the checkpoint that holds the candidate, and
    the candidate is handed on as that checkpoint's files, so registering
    it moves no byte of it. ``shapes`` are the state's
    ``ShapeDtypeStruct``s. One process only."""

    def __init__(self, ckpt, scalarize, shapes):
        super().__init__(ckpt, scalarize)
        self._shapes = shapes
        self._best_step = ckpt.best_step()

    def restore(self, like):
        obs.TRAIN_PHASES.annotate(bytes=tree_bytes(like))
        return self._ckpt.restore_streamed(
            {"state": like}, place=jax.device_put)["state"]

    def epoch_done(self, state, epoch: int, val_loss: float, saving: bool):
        improved = val_loss < float(state.best_val_loss) and saving
        if improved:
            state = state.replace(
                best_val_loss=self._scalarize(val_loss, jnp.float32))
            self._best_step = epoch + 1
        state = state.replace(epoch=self._scalarize(epoch + 1, jnp.int32))
        if not saving:
            return state
        with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.wait"):
            self._ckpt.wait()
        with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.snapshot"):
            self._ckpt.save_streamed(
                epoch + 1, {"state": state}, best=improved)
        return state

    def best(self):
        """``(params, statistics)`` of the best candidate as the files of
        the checkpoint that holds it (:func:`leaf_files`; the write of this
        call's own save lands first), or ``None``."""
        if self._best_step is None:
            return None
        best = self._ckpt.streamed_files(
            {"state": self._shapes.replace(
                opt_state=None, epoch=None, best_val_loss=None)},
            step=self._best_step)["state"]
        return best.params, best.batch_stats
