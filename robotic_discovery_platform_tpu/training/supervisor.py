"""Supervised, preemption-tolerant training.

The reference has no restart story at all: an interrupted
``train_segmenter.py`` run loses everything and must be relaunched by hand
(reference: scripts/train_segmenter.py:148-189; SURVEY.md sections 2.3
"Elastic / fault-tolerant training" and 5.3). This module supplies the
elastic piece on top of the per-epoch orbax checkpoints:

- ``run_supervised`` executes ``train_model`` in a child process and, when
  the child dies for any reason (host OOM, TPU runtime restart, preemption,
  SIGKILL), relaunches it with ``resume=True`` so training continues from
  the latest checkpoint instead of from scratch -- up to ``max_restarts``
  times.
- Fault injection (``fault_epoch``): the first child arms a watchdog that
  hard-kills the process right after the given epoch's checkpoint lands,
  simulating a mid-run preemption. A marker file makes the fault one-shot
  so the restarted child runs to completion. This is the fault-injection
  capability SURVEY.md section 5.3 notes the reference lacks, and it is how
  tests/test_supervisor.py proves the recovery path.

The child process is a fresh interpreter (``python -m
robotic_discovery_platform_tpu.training.supervisor <spec.json>``), so a
wedged TPU runtime or corrupted process state cannot leak across restarts.

One process per chip: the supervising PARENT never touches JAX (this
module imports no JAX at module level and ``run_supervised`` only spawns,
waits and reads files), so each child in turn is the one process that
holds the accelerator. A caller that has already initialised a JAX backend
in the parent must pass ``platform="cpu"`` -- a child that needs a chip its
parent holds fails or hangs.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path

from robotic_discovery_platform_tpu.utils.config import (
    ModelConfig,
    TrainConfig,
    from_dict,
)
from robotic_discovery_platform_tpu.utils.logging import get_logger

log = get_logger(__name__)


@dataclass
class SupervisedResult:
    """Final TrainResult fields plus how many restarts recovery needed."""

    run_id: str
    registry_version: int | None
    best_val_loss: float
    final_metrics: dict
    epochs_run: int
    restarts: int


def run_supervised(
    cfg: TrainConfig,
    model_cfg: ModelConfig = ModelConfig(),
    register: bool = True,
    max_restarts: int = 3,
    fault_epoch: int | None = None,
    platform: str | None = None,
    attempt_timeout_s: float | None = None,
) -> SupervisedResult:
    """Train to completion across child-process crashes.

    Every attempt (including the first) runs with ``resume=True``: with no
    checkpoint present that is a fresh start, with one present it continues
    from the last completed epoch, so the supervisor needs no special-casing
    between "first run" and "recovery run".

    ``platform`` pins the child's JAX platform through its environment
    (``JAX_PLATFORMS``, e.g. ``"cpu"``); when None the parent's
    ``JAX_PLATFORMS`` (if any) flows through. This function itself stays
    off JAX, so the child is the only process that opens the accelerator.

    ``attempt_timeout_s`` is a per-attempt watchdog: a child that exceeds it
    is killed and treated like a signal death (retryable, resumes from the
    last checkpoint), so a hung accelerator runtime becomes a bounded
    restart instead of a supervisor deadlock.
    """
    workdir = Path(tempfile.mkdtemp(prefix="rdp-supervise-"))
    result_path = workdir / "result.json"
    spec = {
        "train": dataclasses.asdict(cfg),
        "model": dataclasses.asdict(model_cfg),
        "register": register,
        "result_path": str(result_path),
    }
    if fault_epoch is not None:
        spec["fault"] = {
            "epoch": int(fault_epoch),
            "marker": str(workdir / "fault-fired"),
        }
    spec_path = workdir / "spec.json"
    spec_path.write_text(json.dumps(spec))

    child_env = dict(os.environ)
    if platform is not None:
        child_env["JAX_PLATFORMS"] = platform

    restarts = 0
    clean_failures = 0  # CONSECUTIVE rc=1-style exits; reset by signal death
    while True:
        try:
            rc = subprocess.run(
                [sys.executable, "-m",
                 "robotic_discovery_platform_tpu.training.supervisor",
                 str(spec_path)],
                env=child_env, timeout=attempt_timeout_s,
            ).returncode
        except subprocess.TimeoutExpired:
            # subprocess.run already killed the child; model it as a signal
            # death so the retry/fail-fast accounting below treats a hang
            # exactly like a preemption.
            rc = -9
            log.warning(
                "training child exceeded the %.0fs watchdog; killed",
                attempt_timeout_s,
            )
        if rc == 0:
            if not result_path.exists():
                raise RuntimeError(
                    "training child exited 0 without writing its result"
                )
            payload = json.loads(result_path.read_text())
            return SupervisedResult(restarts=restarts, **payload)
        restarts += 1
        # Fail fast on pre-training errors: a child that raises a clean
        # Python exception (rc == 1: bad dataset path, invalid config,
        # import error) without a COMPLETED checkpoint is almost certainly
        # deterministic -- retrying would pay full process bring-up
        # max_restarts times before surfacing the same error. Two
        # refinements over a bare "anything in checkpoint_dir" test:
        # - only a finalized orbax step counts as "training started"
        #   (digit-named step dir); stale tmp dirs from an interrupted save
        #   don't make a deterministic startup error burn all restarts.
        # - one clean-exit retry IS allowed first, because a transient
        #   failure in the pre-first-checkpoint window (flaky shared FS,
        #   tracking backend, MemoryError) also exits rc=1; only a SECOND
        #   consecutive clean failure with still no checkpoint is declared
        #   non-retryable.
        # Signal deaths (rc >= 128 or negative: SIGKILL preemption, OOM
        # kill, SIGTERM) and the injected fault always stay retryable, and
        # RESET the consecutive-clean-failure count -- a preemption
        # followed by one transient clean failure is not a deterministic
        # startup error.
        has_completed_step = _has_completed_step(Path(cfg.checkpoint_dir))
        died_by_signal = rc < 0 or rc >= 128 or rc == _FAULT_EXIT
        clean_failures = 0 if died_by_signal else clean_failures + 1
        if not has_completed_step and clean_failures >= 2:
            raise RuntimeError(
                f"training child failed twice before its first checkpoint "
                f"(rc={rc}); treating as a non-retryable startup error"
            )
        if restarts > max_restarts:
            raise RuntimeError(
                f"training failed {restarts} times (last rc={rc}); "
                f"last checkpoint retained under {cfg.checkpoint_dir}"
            )
        log.warning(
            "training child died (rc=%d); restart %d/%d resuming from the "
            "latest checkpoint in %s",
            rc, restarts, max_restarts, cfg.checkpoint_dir,
        )


# exit code the injected fault uses; distinct from real crash codes so logs
# are unambiguous
_FAULT_EXIT = 113


def _has_completed_step(ckpt_root: Path) -> bool:
    """True iff a FINALIZED orbax step exists: orbax writes into
    ``<step>.orbax-checkpoint-tmp-*`` and renames to the bare digit-named
    dir only on completion, so pure-digit entries are exactly the durable
    steps (the same test ``_arm_fault`` uses)."""
    if not ckpt_root.is_dir():
        return False
    return any(p.name.isdigit() for p in ckpt_root.iterdir())


def _arm_fault(fault: dict, checkpoint_dir: str) -> None:
    """One-shot preemption: hard-kill this process once the checkpoint for
    ``fault['epoch']`` exists (i.e. that epoch's work is durably saved)."""
    marker = Path(fault["marker"])
    if marker.exists():
        return
    marker.touch()
    target = int(fault["epoch"])
    ckpt_root = Path(checkpoint_dir).absolute()

    def watch() -> None:
        while True:
            try:
                steps = [int(p.name) for p in ckpt_root.iterdir()
                         if p.name.isdigit()]
            except FileNotFoundError:
                steps = []
            if steps and max(steps) >= target:
                os._exit(_FAULT_EXIT)
            time.sleep(0.05)

    # deliberately unowned: this watcher's whole job is to os._exit the
    # process -- there is no shutdown path left to join it from
    threading.Thread(target=watch, daemon=True).start()  # jaxlint: disable=JL012


def _child(spec_path: str) -> None:
    from robotic_discovery_platform_tpu.utils.platforms import (
        enable_compile_cache,
    )

    enable_compile_cache()

    from robotic_discovery_platform_tpu.training.trainer import train_model

    spec = json.loads(Path(spec_path).read_text())
    cfg = from_dict(TrainConfig, spec["train"])
    model_cfg = from_dict(ModelConfig, spec["model"])
    if "fault" in spec:
        _arm_fault(spec["fault"], cfg.checkpoint_dir)
    res = train_model(cfg, model_cfg, resume=True,
                      register=spec["register"])
    payload = res.to_jsonable()
    # SupervisedResult carries exactly the reference result surface
    payload.pop("wall_clock_s")
    Path(spec["result_path"]).write_text(json.dumps(payload))


if __name__ == "__main__":
    _child(sys.argv[1])
