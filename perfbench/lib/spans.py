"""The program's own spans and named scopes in the traced window.

``lib/trace.py`` reduces a trace to busy, idle and per-operation time and
keeps only an event's name, start and duration. The readers of the metrics
that PR 25 added need more of each event: the host thread a span ran on and
its stats (``epoch=3``, ``fn=trainer.train_epoch``), and, for a device
operation, the ``jax.named_scope`` path it was compiled under (which the
trace keeps apart from the operation's event: ``lib/xplane_hlo.py``). This
module loads the same file once more for them, into the same plain document
with one more field per event,

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns, stats], ...]}]}]}

where ``stats`` is a dict, and a device operation's scope path sits under
``stats["scope"]``. ``tests/perfbench/data/recorded_spans.json`` is a small
example worked by hand.

Everything is clipped to the harness's ``perfbench.window`` span. A program
without the spans (the parent of the PR that added them) gives empty
answers, never an error: a reader then returns ``None``.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from pathlib import Path

from perfbench.lib import trace, xplane_hlo

#: "transpose(jvp(rdp.loss))" -> "rdp.loss"
_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")
MODULES_LINE = "XLA Modules"
#: host threads are kept where one of these put a span on them
OWN_SPANS = ("rdp.", trace.WINDOW_SPAN)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    thread: str          # its line of the host plane: "<name>#<ordinal>"
    start: int           # ns on the trace's clock, clipped to the window
    end: int
    stats: dict

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9

    def holds(self, other: "Span") -> bool:
        return (self.thread == other.thread and self is not other
                and self.start <= other.start and other.end <= self.end)


def under(scope: str, path: str) -> bool:
    """Whether ``scope`` is one component of a scope path, as itself or
    inside JAX's transformation prefixes: ``rdp.loss`` is a component of
    "jit(step)/transpose(jvp(rdp.loss))/mul"."""
    return any(_WRAPPERS.sub("", part) == scope for part in path.split("/"))


def device_events(ops, modules, programs: dict) -> list:
    """The document's events of a device's operations: each with the scope
    of its instruction in the program that was running when it began. A
    program's runs are the events of the "XLA Modules" line, named
    "jit_train_epoch(<id>)" as its HLO is filed; ``fusion.3`` is one thing
    in the training program and another in the evaluation's."""
    runs = sorted((int(m.start_ns), int(m.start_ns + m.duration_ns),
                   programs.get(m.name, {})) for m in modules)
    starts = [r[0] for r in runs]
    events = []
    for e in ops:
        at = int(e.start_ns)
        k = bisect.bisect_right(starts, at) - 1
        scopes = runs[k][2] if k >= 0 and at < runs[k][1] else {}
        instruction = e.name[1:].split(" = ", 1)[0]
        events.append([trace.op_name(e.name), at, int(e.duration_ns),
                       {"scope": scopes.get(instruction, "")}])
    return events


def load_xplane(path: Path) -> dict:
    """The document above from the profiler's file. Of the host it keeps
    the threads on which the program or the harness put a span (the
    transfer and completion threads hold millions of events and none of
    theirs), with stats for the program's spans; of the device, the
    operations, each with the scope that ``xplane_hlo`` finds for it."""
    from jax.profiler import ProfileData

    programs = xplane_hlo.program_scopes(path)
    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        lines = []
        if plane.name.startswith(trace.DEVICE_PLANE):
            by_name = {line.name: line for line in plane.lines}
            if trace.OPS_LINE in by_name:
                modules = by_name.get(MODULES_LINE)
                lines.append({"name": trace.OPS_LINE, "events": device_events(
                    by_name[trace.OPS_LINE].events,
                    modules.events if modules is not None else (), programs)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if any(e.name.startswith(OWN_SPANS) for e in line.events):
                    lines.append({"name": line.name, "events": [
                        [e.name, int(e.start_ns), int(e.duration_ns),
                         dict(e.stats) if e.name.startswith("rdp.") else {}]
                        for e in line.events]})
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _merged_ns(intervals) -> int:
    return sum(end - start for start, end in trace._union(intervals))


def merged_seconds(spans) -> float:
    """Seconds that the spans cover, overlaps counted once."""
    return _merged_ns((s.start, s.end) for s in spans) / 1e9


class Spans:
    """Host spans and scoped device operations of one window."""

    def __init__(self, doc: dict):
        self.host: list[Span] = []
        self.device: list[tuple] = []       # (scope path, name, start, end)
        self.window = self.main = None
        found = []
        for plane in doc["planes"]:
            for k, line in enumerate(plane["lines"]):
                thread = f"{line['name'].split('/')[0] or 'thread'}#{k}"
                for name, start, dur, *rest in line["events"]:
                    if name == trace.WINDOW_SPAN:
                        found.append((dur, start, thread))
        if not found:
            return
        dur, lo, self.main = max(found)
        hi = lo + dur
        self.window = (lo, hi)
        first_device = True
        for plane in doc["planes"]:
            device = plane["name"].startswith(trace.DEVICE_PLANE)
            if device and not first_device:
                continue                    # one chip's operations, as gaps
            first_device = first_device and not device
            for k, line in enumerate(plane["lines"]):
                thread = f"{line['name'].split('/')[0] or 'thread'}#{k}"
                for name, start, dur, *rest in line["events"]:
                    s, e = max(start, lo), min(start + dur, hi)
                    if e <= s or name == trace.WINDOW_SPAN:
                        continue
                    stats = rest[0] if rest else {}
                    if not device:
                        self.host.append(Span(name, thread, s, e, stats))
                    elif (line["name"] == trace.OPS_LINE and
                          name.split(" ")[-1] not in trace.CONTROL_FLOW):
                        self.device.append(
                            (stats.get("scope", ""), name, s, e))

    @property
    def instrumented(self) -> bool:
        """Whether the program put spans of its own into the window."""
        return any(s.name.startswith("rdp.") for s in self.host)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    def named(self, names, thread: str | None = None) -> list:
        """Spans whose name is one of ``names`` (a string is one name; a
        name that ends in ``*`` is a prefix), on ``thread`` if given."""
        names = (names,) if isinstance(names, str) else tuple(names)
        exact = {n for n in names if not n.endswith("*")}
        prefixes = tuple(n[:-1] for n in names if n.endswith("*"))
        return [s for s in self.host
                if (s.name in exact or (prefixes and s.name.startswith(prefixes)))
                and (thread is None or s.thread == thread)]

    def seconds(self, names, thread: str | None = None) -> float:
        """Summed duration inside the window of the spans of these names."""
        return sum(s.seconds for s in self.named(names, thread))

    def self_seconds(self, name: str, thread: str | None = None) -> float:
        """A span's own time: its duration less the part of it that other
        spans of its thread, lying inside it, cover (choosing-metrics
        section 4); summed over the spans of this name."""
        total = 0
        for span in self.named(name, thread):
            inside = [(s.start, s.end) for s in self.host if span.holds(s)]
            total += span.end - span.start - _merged_ns(inside)
        return total / 1e9

    def holding(self, outer, inner) -> list:
        """The ``outer`` spans that hold an ``inner`` span on their thread."""
        inner = self.named(inner)
        return [o for o in self.named(outer)
                if any(o.holds(i) for i in inner)]

    def device_seconds(self, scope: str) -> float:
        """Device time of the operations compiled under a named scope."""
        return sum(e - s for path, _, s, e in self.device
                   if under(scope, path)) / 1e9


@functools.lru_cache(maxsize=2)
def _load(path: str) -> Spans:
    return Spans(load_xplane(Path(path)))


def of(ctx) -> Spans:
    """The spans of a traced run's window; the file is read once however
    many readers ask. Called while the run's work directory still exists."""
    return _load(str(trace.find_xplane(ctx.cell.workdir / "trace")))
