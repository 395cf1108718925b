"""Readings that the limits of ``correct`` are set from, and the control's
verdict, on the chip at a cell's own size, many seeds to a process:

    python3 perfbench/control.py --workload <name> --seeds 11,12,13 --controls 3

For each seed the cell's driver sets up (the job's first steps and first
whole epoch through the program), the plain reference follows them, and
every number of ``correct`` is read: the program against the reference,
and, for the first ``--controls`` seeds,

- the control against the reference: the reference itself, put in the
  program's place and computed in the nearest precision below the one the
  configuration states (int8 for bf16);
- the evaluation path's planted fault: the reference validating with the
  running statistics the job started from.

Each is judged with the cell's own limits file (``limits/<cell>.json``):
the program has to come out correct, the control and the fault not. The
exit code is 1 where one of them does not. A limit belongs above the
program's largest and below the control's smallest reading. The benchmark's
own runs never run this.
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


CONTROL = "int8"
WINDOW_ONLY = ("window_epochs_missing",)    # needs a window: not read here


def read_seed(bench, workload: str, seed: int, control: bool,
              workdir: Path) -> dict:
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    cell = bench.cell(workload, seed, 0.0, workdir)
    driver = bench.driver(cell.traffic["driver"])
    try:
        job = driver.setup(cell)
        want = driver.follow(job, stale_eval=control)
        row = {"seed": seed,
               "program": driver.readings(job, job.produced, want)}
        if control:
            row["control"] = driver.readings(
                job, driver.follow(job, CONTROL), want)
            # the reference itself, but for its validation
            stale = {part: {**body, "val_loss": body["val_loss_stale"]}
                     for part, body in want.items()}
            row["stale_eval"] = driver.readings(job, stale, want)
        return row
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def verdicts(rows, limits) -> dict:
    """who -> [(seed, correct, numbers over their limit)], by the cell's own
    limits."""
    limits = {k: v for k, v in limits.items() if k not in WINDOW_ONLY}
    out = {}
    for who in ("program", "control", "stale_eval"):
        for r in rows:
            if who in r:
                ok, table = compare.judge(r[who], limits)
                over = sorted(k for k, t in table.items()
                              if not t["value"] <= t["limit"])
                out.setdefault(who, []).append((r["seed"], ok, over))
    return out


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
    names = list(rows[0]["program"])
    summary = {n: {"program_max": max(r["program"][n] for r in rows),
                   "control_min": min((r["control"][n] for r in rows
                                       if "control" in r), default=None),
                   "stale_eval_min": min((r["stale_eval"][n] for r in rows
                                          if "stale_eval" in r), default=None)}
               for n in names}
    judged = verdicts(rows, bench.limits(args.workload))
    print(json.dumps({"workload": args.workload, "summary": summary,
                      "verdicts": judged}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"rows": rows, "summary": summary, "verdicts": judged}, indent=1))
    sound = all(ok for _, ok, _ in judged.get("program", []))
    caught = not any(ok for who in ("control", "stale_eval")
                     for _, ok, _ in judged.get(who, []))
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
