"""Orbax checkpoint/resume.

The reference has no mid-run durability at all: an interrupted training run
loses everything except the last best-model file (reference:
scripts/train_segmenter.py:148-189; SURVEY.md section 5.4). Here every epoch
checkpoints the full train state (params, optimizer state, batch stats,
epoch counter, best-val bookkeeping) through orbax -- which is also
sharding-aware, so the same path serves the data-parallel trainer.

Two save paths:

- ``save``: synchronous collective save. The multi-host path MUST use it
  (orbax coordinates cross-host barriers; every process calls in
  lockstep).
- ``save_async``: single-process overlap. The caller hands an
  INDEPENDENT on-device snapshot (the trainer's ``_copy_tree``); a single
  worker thread then pays the host fetch (~350 MB of
  params+optimizer+best-candidate at full width) and
  the disk write while the next epoch's compute runs on the chip. One
  save in flight at a time; ``wait``/``close`` drain.
"""

from __future__ import annotations

import threading
from pathlib import Path
from typing import Any

import jax
import orbax.checkpoint as ocp

from robotic_discovery_platform_tpu.observability import instruments as obs


class CheckpointManager:
    def __init__(self, directory: str | Path, keep: int = 3):
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

        def work():
            try:
                with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.fetch"):
                    host = jax.device_get(state)
                with obs.TRAIN_PHASES.stage("rdp.train.checkpoint.write"):
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

    def latest_step(self) -> int | None:
        self.wait()
        return self._mgr.latest_step()

    def restore(self, template: Any, step: int | None = None) -> Any:
        self.wait()
        step = self._mgr.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError("no checkpoint to restore")
        return self._mgr.restore(step, args=ocp.args.StandardRestore(template))

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
