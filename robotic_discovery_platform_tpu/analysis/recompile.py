"""Recompilation guard: trace budgets for hot jitted entry points.

A silent retrace in the serving path costs a full XLA compile's worth of
frames; this module makes every retrace observable and budgeted. Usage
-- wrap the Python function UNDER ``jax.jit`` so the wrapper body runs
exactly once per trace (i.e. per jit-cache miss)::

    @jax.jit
    @recompile.trace_guard("pipeline.frame_analyzer", budget=4)
    def analyze(variables, frame, ...): ...

Each ``trace_guard`` call creates one :class:`GuardStats` instance and
registers it under ``name`` (several instances may share a name: a
hot-reloaded serving engine legitimately builds a fresh jit cache; the
trainer keeps one pair of runners per configuration and shape set).
Budgets are enforced PER INSTANCE -- one engine's cache, one budget --
for as long as the instance lives, which may be its process's.

When an instance exceeds its budget the guard logs a warning with the
offending abstract shapes; under strict mode (``RDP_RECOMPILE_STRICT=1``
or :func:`strict`) it raises :class:`RecompileBudgetExceeded` instead,
which surfaces as a trace-time error at the call that retraced.

``budget=None`` means the module default (:data:`DEFAULT_BUDGET`, 1):
a hot path that has not declared a budget is expected to compile once.

Every counted trace also increments ``rdp_jit_traces_total{fn=<name>}``
and runs under a ``jax.profiler`` host span ``rdp.jit.trace`` (stat
``fn``), so a profile shows which call re-traced and for how long.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from contextlib import contextmanager
from typing import Any, Callable

from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.utils.logging import get_logger

log = get_logger(__name__)

#: Traces allowed for a guard that declared no explicit budget.
DEFAULT_BUDGET = 1

_lock = threading.Lock()
_registry: dict[str, list["GuardStats"]] = {}
_strict_override: bool | None = None


class RecompileBudgetExceeded(RuntimeError):
    """A guarded hot path retraced beyond its declared budget."""


@dataclasses.dataclass
class GuardStats:
    name: str
    budget: int | None
    traces: int = 0
    shapes: list = dataclasses.field(default_factory=list)

    @property
    def effective_budget(self) -> int:
        return self.budget if self.budget is not None else DEFAULT_BUDGET


def _resolve_strict() -> bool:
    """RDP_RECOMPILE_STRICT resolver: test-hook override wins, then env."""
    if _strict_override is not None:
        return _strict_override
    return os.environ.get("RDP_RECOMPILE_STRICT", "0") not in (
        "0", "false", "off", "",
    )


@contextmanager
def strict(enabled: bool = True):
    """Force strict (raise-on-exceed) mode within a scope -- test hook."""
    global _strict_override
    prev = _strict_override
    _strict_override = enabled
    try:
        yield
    finally:
        _strict_override = prev


def _describe(args: tuple, kwargs: dict) -> str:
    def one(a: Any) -> str:
        shape = getattr(a, "shape", None)
        if shape is not None:
            return f"{getattr(a, 'dtype', '?')}{list(shape)}"
        if isinstance(a, (list, tuple, dict)):
            return f"{type(a).__name__}[{len(a)}]"
        return type(a).__name__

    parts = [one(a) for a in args] + [
        f"{k}={one(v)}" for k, v in kwargs.items()
    ]
    return "(" + ", ".join(parts) + ")"


def _is_tracing(args: tuple, kwargs: dict) -> bool:
    import jax

    return any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves((args, kwargs))
    )


def trace_guard(
    name: str, budget: int | None = None, traced_only: bool = True
) -> Callable:
    """Budget the number of traces (jit-cache misses) of a hot path.

    ``traced_only`` (default) counts an invocation only when at least one
    argument is an abstract tracer -- i.e. the body is running as part of
    a trace, not eagerly -- so eager callers (interpret-mode tests,
    debugging) never consume budget.
    """

    def decorator(fn: Callable) -> Callable:
        import functools

        stats = GuardStats(name=name, budget=budget)
        with _lock:
            _registry.setdefault(name, []).append(stats)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if traced_only and not _is_tracing(args, kwargs):
                return fn(*args, **kwargs)
            signature = _describe(args, kwargs)
            with _lock:
                stats.traces += 1
                stats.shapes.append(signature)
                traces = stats.traces
            obs.JIT_TRACES.labels(fn=name).inc()
            limit = stats.effective_budget
            if traces > limit:
                msg = (
                    f"hot path {name!r} retraced: trace {traces} > budget "
                    f"{limit}. Arg signatures seen: "
                    f"{'; '.join(stats.shapes[-min(traces, 4):])}. Every "
                    "retrace is a fresh XLA compile on the serving path -- "
                    "stabilize the argument shapes/dtypes (or raise the "
                    "declared budget if this shape set is intended)."
                )
                if _resolve_strict():
                    raise RecompileBudgetExceeded(msg)
                log.warning(msg)
            import jax

            # a host span of the profiler's trace around the traced Python:
            # the PjitFunction span that holds one is a call that traced
            with jax.profiler.TraceAnnotation("rdp.jit.trace", fn=name):
                return fn(*args, **kwargs)

        wrapper.__trace_guard__ = stats
        return wrapper

    return decorator


def stats_for(name: str) -> list[GuardStats]:
    with _lock:
        return list(_registry.get(name, []))


def total_traces(name: str) -> int:
    return sum(s.traces for s in stats_for(name))


def snapshot() -> dict[str, list[dict]]:
    """Registry state as plain data (diagnostics / metrics export)."""
    with _lock:
        return {
            name: [
                {
                    "traces": s.traces,
                    "budget": s.effective_budget,
                    "shapes": list(s.shapes),
                }
                for s in entries
            ]
            for name, entries in _registry.items()
        }


def over_budget() -> dict[str, int]:
    """name -> worst per-instance overshoot, for every guard over budget."""
    out: dict[str, int] = {}
    with _lock:
        for name, entries in _registry.items():
            worst = max(
                (s.traces - s.effective_budget for s in entries), default=0
            )
            if worst > 0:
                out[name] = worst
    return out


def reset() -> None:
    """Drop every registered guard's counters (test isolation)."""
    with _lock:
        _registry.clear()
