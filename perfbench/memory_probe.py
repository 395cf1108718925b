"""What the device's memory counters count, shown on the chip:

    python3 perfbench/memory_probe.py --workload <name> [--batches 32,64]

Builds the cell's own train program the way ``train_model`` does (the
whole-epoch scan for a resident data set, the single step otherwise, and
the single step at each of ``--batches``), compiles it, prints the
compiler's ``memory_analysis()`` for that executable, runs it, and prints
the allocator's counters before and after, so that each counter can be set
beside the bytes the compiler planned: ``bytes_in_use`` beside the
arguments and results, ``bytes_reserved`` beside the temporaries. Nothing
here is timed; the benchmark's own runs never run this.
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

from perfbench.lib import order, spec  # noqa: E402

GIB = 2 ** 30


def _counters(device) -> dict:
    s = device.memory_stats() or {}
    return {k: round(int(s.get(k, 0)) / GIB, 4) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved")}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--batches", default="32,64")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import optax

    from perfbench.run import _devices
    from robotic_discovery_platform_tpu.models import losses
    from robotic_discovery_platform_tpu.models.unet import build_unet
    from robotic_discovery_platform_tpu.training import trainer
    from robotic_discovery_platform_tpu.utils.config import ModelConfig

    bench = spec.Bench(ROOT)
    entry = bench.workload(args.workload)
    device = _devices(entry["chips"], True)[0]
    config, traffic = bench.config(entry["config"]), bench.traffic(
        entry["traffic"])
    size, data = config["train"]["img_size"], traffic["dataset"]
    model = build_unet(ModelConfig(**config["model"]))
    tx = optax.adam(config["train"]["learning_rate"])
    loss_fn = losses.make_loss_fn(config["train"]["loss"])

    def state():
        return trainer.create_state(model, tx, jax.random.key(0), size)

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
    for batch in sorted(int(b) for b in args.batches.split(",")):
        x = jnp.zeros((batch, size, size, 3), jnp.float32)
        y = jnp.zeros((batch, size, size, 1), jnp.float32)
        compiled = trainer.make_train_step(model, tx, loss_fn).lower(
            state(), x, y).compile()

        def steps():
            s = state()
            for _ in range(3):      # the state is donated, as in the job
                s, loss = compiled(s, x, y)
            return loss

        report(f"train step, batch {batch}", compiled, steps)
        del x, y, compiled
        if batch == traffic["train"]["batch_size"] \
                and data["kind"] == "arrays":
            split = traffic["train"]["validation_split"]
            n = len(order.train_val_split(data["pairs"], split, 0)[0])
            xs = jnp.zeros((n, size, size, 3), jnp.float32)
            ys = jnp.zeros((n, size, size, 1), jnp.float32)
            grid = jnp.asarray(order.epoch_order(
                n, batch, False, None).astype("int32"))
            train_epoch, _ = trainer.make_epoch_runners(model, tx, loss_fn)
            compiled = train_epoch.lower(state(), xs, ys, grid).compile()
            report(f"whole-epoch scan, batch {batch}, {len(grid)} steps",
                   compiled, lambda: compiled(state(), xs, ys, grid))
            del xs, ys, grid, compiled
    return 0


if __name__ == "__main__":
    sys.exit(main())
