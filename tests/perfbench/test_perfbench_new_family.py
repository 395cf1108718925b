"""A configuration of another model family enters the benchmark by new
files and new entries alone. ``conftest.toy_root`` copies ``BENCHMARK.json``
and ``perfbench/`` and lays ``data/toy_family`` in (a two-layer perceptron
on vectors: configuration, plain reference, traffic, driver, limits and one
reader with a ``workloads`` list); ``test_perfbench_spec.py`` runs every one
of its checks on that tree too. Here: the harness runs the toy cell, the
tools that every cell's limits are set with take it, the bytes-over-the-
floor test finds it, and no file that came from the repository's
``perfbench/`` was edited to let it in."""

import json
from pathlib import Path

import pytest

from perfbench import control, run
from perfbench.lib import spec

ROOT = Path(__file__).resolve().parents[2]
CELL = "toy-mlp.toy-steps"


def benchmark_files(root: Path) -> dict:
    """relative path -> bytes of every file of ``root``'s ``perfbench/``."""
    home = root / "perfbench"
    return {str(f.relative_to(home)): f.read_bytes()
            for f in sorted(home.rglob("*"))
            if f.is_file() and "__pycache__" not in f.parts}


@pytest.fixture(scope="module")
def bench(toy_root):
    return spec.Bench(toy_root)


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_the_harness_runs_the_toy_cell(bench, traced):
    line = json.loads(json.dumps(run.run_cell(
        bench, CELL, 2_900_000_051, 0.2, traced, require_chip=False)))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 8 and line["failed"] == 0
    assert set(line["compared"]) == set(bench.limits(CELL))
    if traced:
        assert {"busy_s", "window_s"} <= set(line["device"])
        # counters read on any platform: the shared reader and the family's
        # own; a CPU run gives no device metric, so no step_mfu
        assert set(line["metrics"]) == {"outside_steps_share", "toy_step_us"}
        assert line["metrics"]["toy_step_us"]["unit"] == "us"
        assert line["window"]["model_flops"] == 8 * 64 * 2.0 * (
            2 * 16 * 32 + 3 * 32 * 4)
    else:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}
        assert line["metrics"]["train_img_per_s"]["value"] > 0


def test_the_existing_cells_owe_the_toy_reader_nothing(bench):
    toy = next(m for m in bench.doc["per_layer"] if m["name"] == "toy_step_us")
    assert [w["name"] for w in bench.doc["workloads"]
            if bench.reports(toy, w["name"])] == [CELL]
    # and the toy cell owes no reader that knows a U-Net by its shapes
    owed = {m["name"] for m in bench.doc["per_layer"]
            if bench.reports(m, CELL)}
    assert owed == {"outside_steps_share", "step_device_ms",
                    "device_idle_share", "peak_hbm_gib", "step_mfu",
                    "toy_step_us"}


def test_control_takes_the_toy_drivers_controls(bench, tmp_path):
    rows = [control.read_seed(bench, CELL, seed, i < 2, tmp_path / "work")
            for i, seed in enumerate((11, 2_900_000_077, 13))]
    assert [sorted(r) for r in rows] == [
        ["bf16", "program", "seed"]] * 2 + [["program", "seed"]]
    judged = control.verdicts(rows, bench.limits(CELL))
    assert [ok for _, ok, _ in judged["program"]] == [True] * 3
    assert [ok for _, ok, _ in judged["bf16"]] == [False] * 2
    assert control.passed(judged)
    summary = control.summarise(rows)
    assert set(summary["loss_gap"]) == {"program_max", "bf16_min"}
    assert summary["loss_gap"]["bf16_min"] > 3 * summary["loss_gap"][
        "program_max"]
    # a control that the limits let through fails the tool
    assert not control.passed(control.verdicts(
        rows, {k: 1.0 for k in bench.limits(CELL)}))


def test_the_floor_test_finds_the_toy_cell_through_abstract_step(toy_root):
    import jax
    import test_perfbench_cells_fit as fit

    ids = [c.id for c in fit.cases(toy_root)]
    assert ids == [c.id for c in fit.cases()] + [
        "toy-mlp-toy-steps:toy-mlp.toy-steps"]
    fn, args = fit.abstract_step(toy_root, CELL)
    assert [jax.tree.map(lambda a: (a.shape, a.dtype.name), a)
            for a in args[1:]] == [((64, 16), "float32"), ((64, 4), "float32")]
    # the shapes are those the function takes: it lowers and compiles, here
    # for the CPU (the toy is far under the floor and is no cell of the
    # repository's, so it is not held to it)
    params, loss = jax.eval_shape(fn, *args)
    assert loss.shape == () and set(params) == set(args[0])
    assert jax.jit(fn).lower(*args).compile().memory_analysis() is not None


def test_nothing_of_the_benchmark_was_edited_to_let_the_family_in(toy_root):
    """Last in the file, after the runs above: the copy still holds every
    file of the repository's ``perfbench/``, byte for byte, and the entries
    ``BENCHMARK.json`` had, in their places."""
    ours, copy = benchmark_files(ROOT), benchmark_files(toy_root)
    assert {k: copy.get(k) for k in ours} == ours
    added = sorted(set(copy) - set(ours))
    assert added == ["configs/toy-mlp.json", "drivers/toy.py",
                     "layer_metrics/toy_step_us.py",
                     "limits/toy-mlp.toy-steps.json",
                     "reference/toy-mlp.py", "traffic/toy-steps.json"]
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((Path(toy_root) / "BENCHMARK.json").read_text())
    assert set(new) == set(doc)
    for key, value in doc.items():
        if key in ("configs", "workloads", "per_layer"):
            assert new[key][:len(value)] == value and len(new[key]) == \
                len(value) + 1
        else:
            assert new[key] == value
