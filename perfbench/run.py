"""The benchmark's one command:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell that ``BENCHMARK.json`` names, lets its driver set up (all of
which counts as ``setup_s``, from the start of this process), times the
driver's window on the host's clock, reads the device's memory peak, lets
the driver compare the timed path with the plain reference, and prints the
contract's last line. It runs on the machine it is started on and fails,
printing no result, where JAX finds no TPU or fewer chips than the cell asks.
"""

from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.lib import compare, spec, trace  # noqa: E402


def _devices(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind})")
    return devices


class MemoryWatch:
    """The device's memory counters over the window, each on its own. The
    TPU allocator books live buffers (``bytes_in_use``) and the scratch of
    the program that is running (``bytes_reserved``) apart; it keeps a peak
    of each, and a thread samples the two together twenty times a second,
    since the footprint at an instant is their sum and the two peaks need
    not fall together."""

    def __init__(self, devices, period_s: float = 0.05):
        self.devices, self.period_s = devices, period_s
        self.in_use = self.reserved = self.together = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="perfbench-memory-watch")

    def sample(self):
        for d in self.devices:
            s = d.memory_stats() or {}
            live, scratch = (int(s.get("bytes_in_use", 0)),
                             int(s.get("bytes_reserved", 0)))
            self.in_use = max(self.in_use, live,
                              int(s.get("peak_bytes_in_use", 0)))
            self.reserved = max(self.reserved, scratch,
                                int(s.get("peak_bytes_reserved", 0)))
            self.together = max(self.together, live + scratch)

    def _run(self):
        while not self._stop.wait(self.period_s):
            self.sample()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.sample()

    @property
    def peak(self) -> int:
        return max(self.in_use, self.together)

    def parts(self) -> dict:
        return {"peak_bytes_in_use": self.in_use,
                "peak_bytes_reserved": self.reserved,
                "peak_in_use_plus_reserved_sampled": self.together}


def run_cell(bench: spec.Bench, workload: str, seed: int, seconds: float,
             traced: bool, require_chip: bool = True, t0: float | None = None):
    """One run of one cell; returns the result line as a dict."""
    t0 = time.time() if t0 is None else t0
    chips = bench.workload(workload)["chips"]

    import jax

    from robotic_discovery_platform_tpu.utils import platforms

    platforms.enable_compile_cache()
    devices = _devices(chips, require_chip)
    workdir = bench.root / ".perfbench_runs" / f"{workload}-{os.getpid()}"
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    cell = bench.cell(workload, seed, seconds, workdir)
    driver = bench.driver(cell.traffic["driver"])
    try:
        job = driver.setup(cell)
        setup_s = time.time() - t0
        if traced:
            jax.profiler.start_trace(str(workdir / "trace"),
                                     profiler_options=trace.profiler_options())
        watch = MemoryWatch(devices[:chips])
        try:
            with watch, jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                start = time.perf_counter()
                out = driver.window(job)
                window_s = time.perf_counter() - start
        finally:
            if traced:
                jax.profiler.stop_trace()
        peak = watch.peak
        print(f"perfbench memory: {watch.parts()}", file=sys.stderr)
        counters = driver.counters(job, out, window_s)
        values = dict(driver.end_to_end(job, out, window_s), setup_s=setup_s)
        start = time.perf_counter()
        numbers = driver.check(job, out)
        check_s = time.perf_counter() - start
        correct, table = compare.judge(numbers, bench.limits(workload))

        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        line = {"correct": correct, "attempted": counters["attempted"],
                "failed": counters.get("failed", 0)}
        if traced:
            summary = trace.reduce(trace.load_xplane(
                trace.find_xplane(workdir / "trace")))
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
            ctx = types.SimpleNamespace(
                trace=summary, counters=counters, device=device, cell=cell,
                peaks=bench.peaks(devices[0].device_kind) if require_chip
                else None)
            values = {}
            for metric in bench.doc["per_layer"]:
                if bench.reports(metric, workload):
                    got = bench.reader(metric["name"]).read(ctx)
                    if got is not None:
                        values[metric["name"]] = got
            line["breakdown"] = {"device_ops": summary.top_ops(),
                                 "idle_gaps": summary.top_gaps()}
            listed = bench.doc["per_layer"]
        else:
            listed = bench.doc["end_to_end"]
        units = {m["name"]: m["unit"] for m in listed}
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in values.items() if k in units}
        line["device"] = device
        line["window"] = {"check_s": check_s, "memory": watch.parts(),
                          **{k: v for k, v in counters.items()
                             if k not in ("attempted", "failed")}}
        line["read"] = numbers
        line["compared"] = table
        return line
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    line = run_cell(spec.Bench(ROOT), args.workload, args.seed, args.seconds,
                    bool(args.trace), t0=T0)
    for name, row in line["compared"].items():
        print(f"compared {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
