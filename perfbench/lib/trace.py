"""Reduction of a profiler trace (``*.xplane.pb``) to the device's busy and
idle time, per-operation time, and the idle gaps named by what the host was
doing. The arithmetic works on a plain document,

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

which :func:`load_xplane` makes from the profiler's file and which
``tests/perfbench`` keeps a small recorded example of.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

#: the span the harness itself puts around the measured window
WINDOW_SPAN = "perfbench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
CONTROL_FLOW = ("while", "conditional", "call")

def profiler_options():
    """Host spans (TraceMe) on, the Python call tracer off: it slows the
    host about twofold and writes millions of events."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options



def load_xplane(path: Path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


_SHAPE = re.compile(r"\b[a-z]+[0-9]+\[([0-9,]+)\]")


def op_name(text: str) -> str:
    """A device operation's event is named by its whole HLO instruction,
    "%fusion.3 = bf16[...] fusion(...), kind=kOutput, ..."; keep
    "fusion.3 fusion": the instruction's name and its opcode. An output
    fusion (what XLA's TPU backend makes of a convolution or a matrix
    product with what it fuses into it) also keeps the 4-D shapes of its
    result and operands, "fusion.3 fusion kOutput 32x256x256x64 3x3x64x64",
    since its name alone does not say what it computes."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    if ", kind=kOutput" in rest:
        shapes = [d.replace(",", "x") for d in _SHAPE.findall(rest)
                  if d.count(",") == 3]
        return " ".join([name, "fusion", "kOutput"] + shapes)
    # the opcode is the first bare word that is followed by "(" after the
    # result type, which may itself hold parentheses: (f32[2], u32[])
    depth, start = 0, 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            if ch == "(" and depth == 0 and i > start and \
                    rest[start:i].replace("-", "").isalpha():
                return f"{name} {rest[start:i]}"
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            start = i + 1
    return name


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise SystemExit(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over the device planes
    devices: int
    op_seconds: dict              # operation name -> seconds, summed
    gaps: list                    # (seconds, host activity) per idle gap

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n=10):
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:n]]

    def top_gaps(self, n=10):
        by_name: dict = {}
        for seconds, name in self.gaps:
            by_name[name] = by_name.get(name, 0.0) + seconds
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:n]]


def _window(doc):
    spans = [(s, s + d) for p in doc["planes"] for ln in p["lines"]
             for name, s, d in ln["events"] if name == WINDOW_SPAN]
    if not spans:
        raise SystemExit(f"the trace holds no {WINDOW_SPAN!r} span")
    return max(spans, key=lambda se: se[1] - se[0])


def _host_events(doc, lo, hi):
    """Host spans that overlap the window, from every host thread, named
    "thread: span" (the thread's name without its id)."""
    hosts = [p for p in doc["planes"] if p["name"].startswith("/host:")]
    return [(s, s + d, f"{ln['name'].split('/')[0] or 'thread'}: {name}")
            for p in hosts for ln in p["lines"]
            for name, s, d in ln["events"]
            if name != WINDOW_SPAN and d > 0 and s < hi and s + d > lo]


def _name_gap(host, start, end):
    """The host activity of an idle gap: the shortest host span that covers
    the gap's middle, i.e. the innermost thing the host was in."""
    mid = (start + end) / 2
    covering = [(e - s, name) for s, e, name in host if s <= mid < e]
    return min(covering)[1] if covering else "host: no span (Python)"


def reduce(doc: dict, min_gap_ns: int = 100_000) -> Summary:
    lo, hi = _window(doc)
    device_planes = [p for p in doc["planes"]
                     if p["name"].startswith(DEVICE_PLANE)]
    busy_ns, op_ns, gaps = [], {}, []
    host = _host_events(doc, lo, hi)
    for plane in device_planes:
        ops = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
        events = [(name, s, d) for ln in ops for name, s, d in ln["events"]]
        inside = _clip([(s, s + d) for _, s, d in events], lo, hi)
        merged = _union(inside)
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, d in events:
            part = min(s + d, hi) - max(s, lo)
            # a loop or a branch spans the operations of its body, which
            # are events of their own
            if part > 0 and name.split(" ")[-1] not in CONTROL_FLOW:
                op_ns[name] = op_ns.get(name, 0) + part
        if plane is device_planes[0]:
            edges = [lo] + [t for se in merged for t in se] + [hi]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e - s >= min_gap_ns:
                    gaps.append(((e - s) / 1e9, _name_gap(host, s, e)))
    n = len(device_planes)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_ns) / 1e9 / n if n else 0.0,
        devices=n,
        op_seconds={k: v / 1e9 / n for k, v in op_ns.items()},
        gaps=gaps)
