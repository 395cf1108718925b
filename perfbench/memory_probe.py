"""What the device's memory counters count, shown on the chip:

    python3 perfbench/memory_probe.py --workload <name>

Asks the cell's driver for its programs as functions and shapes
(``abstract_step``: one optimiser step at the cell's batch; where the
driver has it, ``abstract_epoch``: what the window dispatches, such as the
whole-epoch scan for a resident data set), compiles each, prints the
compiler's ``memory_analysis()`` for that executable, runs it three times
on zeros of those shapes, and prints the allocator's counters before and
after, so that each counter can be set beside the bytes the compiler
planned: ``bytes_in_use`` beside the arguments and results,
``bytes_reserved`` beside the temporaries. Nothing is donated, so a result
is a second copy of the state beside its argument. Nothing here is timed;
the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.lib import spec  # noqa: E402

GIB = 2 ** 30


def _counters(device) -> dict:
    s = device.memory_stats() or {}
    return {k: round(int(s.get(k, 0)) / GIB, 4) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved")}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from perfbench.run import _devices

    bench = spec.Bench(ROOT)
    device = _devices(bench.workload(args.workload)["chips"], True)[0]
    cell = bench.cell(args.workload, 0, 0.0, ROOT / ".perfbench_runs")
    driver = bench.driver(cell.traffic["driver"])
    programs = {"one optimiser step": driver.abstract_step(cell)}
    epoch = getattr(driver, "abstract_epoch", lambda cell: None)(cell)
    if epoch is not None:
        programs["the window's program"] = epoch

    def watch_run(fn):
        """Run ``fn`` with a thread sampling in-use and reserved together."""
        seen = {"in_use": 0.0, "reserved": 0.0, "together": 0.0}
        stop = threading.Event()

        def sample():
            while not stop.wait(0.02):
                c = _counters(device)
                seen["in_use"] = max(seen["in_use"], c["bytes_in_use"])
                seen["reserved"] = max(seen["reserved"], c["bytes_reserved"])
                seen["together"] = max(
                    seen["together"], c["bytes_in_use"] + c["bytes_reserved"])

        t = threading.Thread(target=sample, daemon=True)
        t.start()
        try:
            jax.block_until_ready(fn())
        finally:
            stop.set()
            t.join()
        return seen

    def report(name, compiled, run):
        m = compiled.memory_analysis()
        before = _counters(device)
        seen = watch_run(run)
        print(json.dumps({
            "program": name,
            "compiler_GiB": {
                "temp": round(m.temp_size_in_bytes / GIB, 4),
                "arguments": round(m.argument_size_in_bytes / GIB, 4),
                "output": round(m.output_size_in_bytes / GIB, 4),
                "alias": round(m.alias_size_in_bytes / GIB, 4),
                "code": round(m.generated_code_size_in_bytes / GIB, 4)},
            "before_GiB": before, "sampled_while_running_GiB": seen,
            "after_GiB": _counters(device)}), flush=True)

    print(json.dumps({"start_GiB": _counters(device)}), flush=True)
    for name, (fn, shapes) in programs.items():
        compiled = jax.jit(fn).lower(*shapes).compile()
        arrays = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shapes)

        def thrice():
            for _ in range(3):
                out = compiled(*arrays)
            return out

        report(name, compiled, thrice)
        del compiled, arrays
    return 0


if __name__ == "__main__":
    sys.exit(main())
