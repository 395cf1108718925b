"""Readings that the limits of ``correct`` are set from, and the control's
verdict, on the chip at a cell's own size, many seeds to a process:

    python3 perfbench/control.py --workload <name> --seeds 11,12,13 --controls 3

For each seed the cell's driver sets up (whatever of the timed path
``correct`` compares), the plain reference follows it, and every number of
``correct`` is read: the program against the reference, and, for the first
``--controls`` seeds, each thing that the driver's ``controls(job, want)``
puts in the program's place: the control (the reference itself, computed in
the nearest precision below the one the configuration states) and the
faults the driver plants. A row holds the readings of each under the name
the driver gives it.

Each is judged with the cell's own limits file (``limits/<cell>.json``):
the program has to come out correct, every control and fault not. The exit
code is 1 where one of them does not. A limit belongs above the program's
largest and below the controls' smallest reading. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.lib import compare, spec  # noqa: E402


WINDOW_ONLY = "window_"     # a number so named needs a window: not read here


def read_seed(bench, workload: str, seed: int, control: bool,
              workdir: Path) -> dict:
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    cell = bench.cell(workload, seed, 0.0, workdir)
    driver = bench.driver(cell.traffic["driver"])
    try:
        job = driver.setup(cell)
        want = driver.follow(job, controls=control)
        row = {"seed": seed,
               "program": driver.readings(job, job.produced, want)}
        if control:
            for name, got in driver.controls(job, want).items():
                row[name] = driver.readings(job, got, want)
        return row
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _readings(rows) -> list:
    """(seed, who, numbers) of every reading: the program's and, under the
    names the driver gave them, the controls'."""
    return [(r["seed"], who, numbers) for r in rows
            for who, numbers in r.items() if who != "seed"]


def verdicts(rows, limits) -> dict:
    """who -> [(seed, correct, numbers over their limit)], by the cell's own
    limits."""
    limits = {k: v for k, v in limits.items()
              if not k.startswith(WINDOW_ONLY)}
    out = {}
    for seed, who, numbers in _readings(rows):
        ok, table = compare.judge(numbers, limits)
        over = sorted(k for k, t in table.items()
                      if not t["value"] <= t["limit"])
        out.setdefault(who, []).append((seed, ok, over))
    return out


def summarise(rows) -> dict:
    """number -> the program's largest reading and each control's smallest."""
    read = {}
    for _, who, numbers in _readings(rows):
        for name, value in numbers.items():
            read.setdefault(name, {}).setdefault(who, []).append(value)
    return {name: {f"{who}_max" if who == "program" else f"{who}_min":
                   (max if who == "program" else min)(values)
                   for who, values in by.items()}
            for name, by in read.items()}


def passed(judged: dict) -> bool:
    """The program sound on every seed, every control and fault caught."""
    return all(ok == (who == "program")
               for who, rows in judged.items() for _, ok, _ in rows)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    from perfbench.run import _devices
    from robotic_discovery_platform_tpu.utils import platforms

    platforms.enable_compile_cache()
    bench = spec.Bench(ROOT)
    _devices(bench.workload(args.workload)["chips"], True)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rows.append(read_seed(
            bench, args.workload, seed, i < args.controls,
            ROOT / ".perfbench_runs" / f"control-{args.workload}"))
        print(json.dumps(rows[-1]), flush=True)
    summary = summarise(rows)
    judged = verdicts(rows, bench.limits(args.workload))
    print(json.dumps({"workload": args.workload, "summary": summary,
                      "verdicts": judged}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"rows": rows, "summary": summary, "verdicts": judged}, indent=1))
    return 0 if passed(judged) else 1


if __name__ == "__main__":
    sys.exit(main())
