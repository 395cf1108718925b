"""Tracing / profiling helpers.

The reference reserves a ``proc_time_ms`` wire field but never measures
anything (protos/vision.proto:34 vs services/vision_analysis/server.py:135-152)
and ships no profiler integration. Here both exist: lightweight host-side
stage timers (feeding ``proc_time_ms`` for real) and ``jax.profiler`` trace
capture around compiled steps.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable

import jax


@dataclass
class StageTimer:
    """Accumulates wall-clock per named stage. Thread-safe: a lock guards
    every mutation and read of the accumulators, so a timer shared across
    threads (the serving handler pool) cannot lose updates (the old
    version was only "per-stream" safe -- two threads racing ``+=`` on the
    same stage dropped samples).

    ``observer`` routes every closed stage into the metrics registry
    (``(stage_name, seconds)`` -- serving wires it to the
    ``rdp_stage_latency_seconds`` histogram), so per-stage timing feeds ONE
    system: the in-process summary and the exported histogram observe the
    same measurements. Called outside the lock; must not raise.

    A stage is also a host span of the ``jax.profiler`` trace
    (``TraceAnnotation``, on the clock of the device planes), so a
    profile taken around the timed code (:func:`jax_trace`,
    :func:`capture_profile`) shows the same stages by the same names.
    With no profiler session running the annotation is a flag check."""

    totals: dict = field(default_factory=lambda: defaultdict(float))
    counts: dict = field(default_factory=lambda: defaultdict(int))
    last: dict = field(default_factory=dict)
    observer: Callable[[str, float], None] | None = None
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    @contextlib.contextmanager
    def stage(self, name: str, **stats):
        """Time the body under ``name``; ``stats`` (``epoch=3``) ride on
        the profiler span only."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **stats):
                yield
        finally:
            self.observe(name, time.perf_counter() - t0)

    def observe(self, name: str, dt: float) -> None:
        """Record one externally-measured sample for ``name`` (the ingest
        path measures its handler-side wait itself and feeds it here, so
        pooled decode timing rides the same accumulators and observer as
        the context-managed stages)."""
        with self._lock:
            self.totals[name] += dt
            self.counts[name] += 1
            self.last[name] = dt
        if self.observer is not None:
            self.observer(name, dt)

    def last_ms(self, *names: str) -> float:
        with self._lock:
            return 1e3 * sum(self.last.get(n, 0.0) for n in names)

    def mean_ms(self, name: str) -> float:
        with self._lock:
            return self._mean_ms_locked(name)

    def _mean_ms_locked(self, name: str) -> float:
        c = self.counts.get(name, 0)
        return 1e3 * self.totals[name] / c if c else 0.0

    def summary(self) -> dict:
        with self._lock:
            return {
                n: {"mean_ms": self._mean_ms_locked(n),
                    "count": self.counts[n]}
                for n in self.totals
            }


@contextlib.contextmanager
def jax_trace(log_dir: str | None):
    """Capture a ``jax.profiler`` trace (TensorBoard-viewable) when ``log_dir``
    is set; no-op otherwise."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# one capture at a time: the jax profiler is process-global state, and two
# interleaved start/stop_trace calls corrupt both captures
_capture_lock = threading.Lock()


def capture_profile(log_dir: str, seconds: float = 1.0) -> str:
    """One on-demand ``jax.profiler`` capture into a fresh timestamped
    subdirectory of ``log_dir``; returns that subdirectory.

    This is the ``GET /debug/profile?seconds=N`` backend: a live server's
    traffic during the window lands in the trace, and a small jitted op
    runs inside it so the capture is non-empty even on an idle server
    (tests assert exactly that). Raises RuntimeError when a capture is
    already in progress -- the caller surfaces that as HTTP 409 rather
    than corrupting the running capture."""
    if not _capture_lock.acquire(blocking=False):
        raise RuntimeError("a profile capture is already in progress")
    try:
        import jax.numpy as jnp

        target = os.path.join(
            log_dir, time.strftime("%Y%m%d-%H%M%S") + f"-{os.getpid()}"
        )
        os.makedirs(target, exist_ok=True)
        deadline = time.monotonic() + max(0.0, float(seconds))
        with jax_trace(target):
            # guarantee at least one device event in the window
            jax.block_until_ready(jnp.square(jnp.arange(64.0)))
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                time.sleep(min(0.05, remaining))
        return target
    finally:
        _capture_lock.release()
