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
from typing import Any, Callable

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
    With no profiler session running the annotation is a flag check.

    And, inside :meth:`timeline`, a record of a span tree kept in memory:
    each stage of the thread that opened the timeline (and of a thread
    that :meth:`adopted` one of its stages) appends one ``SpanRecord``
    with the stage that was open around it as its parent. One pair of
    ``time.monotonic_ns`` readings feeds the sample and the record, around
    the profiler span, so a traced run holds the same interval on both
    clocks. The finished timeline goes to ``recorder`` (a
    ``FlightRecorder``), pinned."""

    totals: dict = field(default_factory=lambda: defaultdict(float))
    counts: dict = field(default_factory=lambda: defaultdict(int))
    last: dict = field(default_factory=dict)
    observer: Callable[[str, float], None] | None = None
    recorder: Any = None
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)
    # per thread: the open ``timeline`` and its innermost open ``span``
    _open: threading.local = field(default_factory=threading.local,
                                   repr=False, compare=False)

    @contextlib.contextmanager
    def stage(self, name: str, *, timeline: bool = True, **stats):
        """Time the body under ``name``; ``stats`` (``epoch=3``) ride on
        the profiler span and the timeline's record. ``timeline=False``
        keeps a stage that runs once a step or a batch out of the open
        timeline, which stays a few hundred records whatever the data
        set."""
        tl = getattr(self._open, "timeline", None) if timeline else None
        if tl is not None:
            parent = self._open.span
            record = self._open.span = tl.span(
                name, 0, parent=parent,
                thread=threading.current_thread().name, **stats)
        # read next to the profiler span's own start: whatever runs between
        # the two (another thread may take the interpreter for 5 ms there)
        # is a disagreement of the two clocks about this stage
        start = time.monotonic_ns()
        if tl is not None:
            record.start_ns = start
        try:
            with jax.profiler.TraceAnnotation(name, **stats):
                yield
        finally:
            end = time.monotonic_ns()
            if tl is not None:
                record.end(end)
                self._open.span = parent
            self.observe(name, (end - start) / 1e9)

    @contextlib.contextmanager
    def timeline(self, name: str, labels: dict | None = None):
        """The body as the root stage ``name`` of a new ``Timeline``, which
        is yielded (its ``labels`` may still grow) and, when the body
        returns or raises (``Timeline.fail``), recorded and pinned."""
        from robotic_discovery_platform_tpu.observability.recorder import (
            Timeline,
        )

        outer = self.handover()
        tl = self._open.timeline = Timeline(name, labels)
        self._open.span = None
        try:
            with self.stage(name):
                yield tl
        except BaseException as exc:
            tl.fail(exc)
            raise
        finally:
            self._open.timeline, self._open.span = outer
            if self.recorder is not None:
                self.recorder.pin(self.recorder.record(tl))

    def handover(self) -> tuple:
        """The calling thread's open timeline and innermost stage, for a
        thread it starts to hang its own stages under (:meth:`adopted`)."""
        return (getattr(self._open, "timeline", None),
                getattr(self._open, "span", None))

    @contextlib.contextmanager
    def adopted(self, handover: tuple):
        """On a worker thread: its stages are children of the stage that
        handed the work over."""
        self._open.timeline, self._open.span = handover
        try:
            yield
        finally:
            self._open.timeline = self._open.span = None

    def annotate(self, **attributes) -> None:
        """Counts at the boundary (``bytes=``, ``steps=``): attributes of
        the calling thread's innermost open record; nothing outside a
        timeline."""
        span = getattr(self._open, "span", None)
        if span is not None:
            span.attributes.update(
                (k, str(v)) for k, v in attributes.items())

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
