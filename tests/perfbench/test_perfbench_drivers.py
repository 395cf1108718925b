"""The harness end to end on the CPU at a tiny size: the contract's last
line, no device metric from a CPU run, ``correct`` false when the timed
path is broken underneath or the control stands in the program's place,
and a non-zero exit without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import run
from perfbench.lib import compare, order, spec

ROOT = Path(__file__).resolve().parents[2]
# float32 compute at this size, so that the limits can be tight enough for
# the int8 control to fail them: at bf16 a 32x32, batch-8 gradient is noise
LIMITS = {"loss_gap": 1e-3, "val_loss_gap": 1e-3, "grad_gap": 0.01,
          "stats_gap": 1e-3, "update_gap": 0.5, "epoch_loss_gap": 1e-3,
          "epoch_stats_gap": 1e-3, "epoch_update_gap": 0.5, "window_epochs_missing": 0}


def tiny_bench(bilinear: bool, kind: str) -> spec.Bench:
    bench = spec.Bench(ROOT)
    side = (32, 32) if kind == "arrays" else (48, 64)
    config = {
        "model": {"in_channels": 3, "num_classes": 1, "base_features": 8,
                  "bilinear": bilinear, "norm": "batch",
                  "compute_dtype": "float32", "init": "torch"},
        "train": {"img_size": 32, "learning_rate": 1e-4, "loss": "bce"}}
    traffic = {"driver": "retrain",
               "dataset": {"kind": kind, "pairs": 30, "height": side[0],
                           "width": side[1]},
               "train": {"batch_size": 8, "validation_split": 0.2},
               "window": {"epochs": 3, "at_seconds": 0.2}}
    bench.config = lambda name: config
    bench.traffic = lambda name: traffic
    bench.limits = lambda name: dict(LIMITS)
    return bench


def cell_of(bench, workload, tmp_path, seed=5):
    tmp_path.mkdir(parents=True, exist_ok=True)
    return bench.cell(workload, seed, 0.2, tmp_path)


@pytest.fixture(scope="module")
def traced_line():
    return run.run_cell(tiny_bench(True, "arrays"), "seg.retrain-resident",
                        3_000_000_019, 0.2, True, require_chip=False)


def test_last_line_has_the_contracts_keys(traced_line):
    line = json.loads(json.dumps(traced_line))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 3 * 3          # three epochs of three steps
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"} and row["value"] <= row["limit"]


def test_a_cpu_run_reports_no_device_metric(traced_line):
    assert traced_line["device"]["platform"] == "cpu"
    assert traced_line["device"]["busy_s"] == 0.0
    per_layer = {m["name"]: m for m in spec.Bench(ROOT).doc["per_layer"]}
    for name in traced_line["metrics"]:
        assert per_layer[name]["source"] != "device_trace", name
    assert "outside_steps_share" in traced_line["metrics"]


def test_files_cell_on_the_other_decoder():
    line = run.run_cell(tiny_bench(False, "files"),
                        "unet-tconv.retrain-resident", 7, 0.2, False,
                        require_chip=False)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}
    assert line["metrics"]["train_img_per_s"]["unit"] == "img/s"
    assert "breakdown" not in line and "busy_s" not in line["device"]
    assert not (ROOT / ".perfbench_runs"
                / f"unet-tconv.retrain-resident-{os.getpid()}").exists()


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_a_broken_whole_epoch_program_is_not_correct(monkeypatch, fault):
    """The fault sits only in the program the window drives, the scan over
    a whole epoch's steps: the probe's one-step epochs stay sound, and the
    first epoch's comparison has to see it."""
    from robotic_discovery_platform_tpu.training import trainer

    sound_step, sound_runners = (trainer.core_train_step,
                                 trainer.make_epoch_runners)

    def broken_step(model, tx, loss_fn):
        step = sound_step(model, tx, loss_fn)

        def unchanged(state, x, y):
            return state, step(state, x, y)[1]

        def half(state, x, y):
            n = x.shape[0] // 2
            return step(state, x[:n], y[:n])

        return unchanged if fault == "state_unchanged" else half

    def runners(model, tx, loss_fn, donate=True):
        good, evaluate = sound_runners(model, tx, loss_fn, donate)
        with monkeypatch.context() as m:
            m.setattr(trainer, "core_train_step", broken_step)
            bad, _ = sound_runners(model, tx, loss_fn, donate)
        return (lambda state, xs, ys, order: (
            bad if order.shape[0] > 1 else good)(state, xs, ys, order),
            evaluate)

    monkeypatch.setattr(trainer, "make_epoch_runners", runners)
    line = run.run_cell(tiny_bench(True, "arrays"), "seg.retrain-resident",
                        11, 0.2, False, require_chip=False)
    assert line["correct"] is False
    bad = {k for k, row in line["compared"].items()
           if not row["value"] <= row["limit"]}
    assert ("epoch_update_gap" if fault == "state_unchanged"
            else "epoch_loss_gap") in bad
    assert all(k.startswith("epoch_") for k in bad)     # the probe is sound


def test_a_window_that_runs_short_is_not_correct(monkeypatch):
    """A fault in the window's call alone: it trains one epoch fewer than
    it was asked for."""
    import dataclasses

    from robotic_discovery_platform_tpu.training import trainer

    sound, calls = trainer.train_model, []

    def short(cfg, *args, **kwargs):
        calls.append(cfg.epochs)
        if len(calls) == 3:         # probe, first epoch, window
            cfg = dataclasses.replace(cfg, epochs=cfg.epochs - 1)
        return sound(cfg, *args, **kwargs)

    monkeypatch.setattr(trainer, "train_model", short)
    line = run.run_cell(tiny_bench(True, "arrays"), "seg.retrain-resident",
                        13, 0.2, False, require_chip=False)
    assert line["correct"] is False and line["attempted"] == 2 * 3
    assert line["compared"]["window_epochs_missing"]["value"] == 1.0


DECODERS = [(True, "seg.retrain-resident"),
            (False, "unet-tconv.retrain-resident")]


@pytest.fixture(scope="module", params=DECODERS, ids=["seg", "unet-tconv"])
def probed(request, tmp_path_factory):
    """(driver, job after set-up, what the reference gets, what the driver
    puts in the program's place as controls) for a decoder."""
    bilinear, workload = request.param
    bench = tiny_bench(bilinear, "arrays")
    driver = bench.driver("retrain")
    job = driver.setup(cell_of(bench, workload,
                               tmp_path_factory.mktemp("probe")))
    want = driver.follow(job, controls=True)
    return driver, job, want, driver.controls(job, want)


READ_IN_SETUP = {k: v for k, v in LIMITS.items()
                 if k != "window_epochs_missing"}


def test_the_int8_control_is_not_correct(probed):
    driver, job, want, controls = probed
    sound, table = compare.judge(
        driver.readings(job, job.produced, want), READ_IN_SETUP)
    assert sound, table
    control = driver.readings(job, controls["int8"], want)
    ok, table = compare.judge(control, READ_IN_SETUP)
    assert not ok and control["grad_gap"] > LIMITS["grad_gap"], table


def test_validation_on_stale_statistics_is_not_correct(probed):
    """The evaluation path's fault, planted in the reference put in the
    program's place: validation with the running statistics the job
    started from moves ``val_loss_gap`` and nothing else."""
    driver, job, want, controls = probed
    ok, table = compare.judge(
        driver.readings(job, controls["stale_eval"], want), READ_IN_SETUP)
    over = {k for k, row in table.items() if not row["value"] <= row["limit"]}
    assert not ok and over == {"val_loss_gap"}, table


def test_the_drivers_controls_are_judged_as_before(probed):
    """``control.py`` asks the driver which controls and planted faults it
    has: exactly the two it had of its own, with the verdicts it gave."""
    from perfbench import control

    driver, job, want, controls = probed
    assert list(controls) == ["int8", "stale_eval"]
    row = {"seed": 5, "program": driver.readings(job, job.produced, want),
           **{name: driver.readings(job, got, want)
              for name, got in controls.items()}}
    judged = control.verdicts([row], LIMITS)    # the window's number left out
    assert judged["program"] == [(5, True, [])]
    (_, ok, over), = judged["int8"]
    assert not ok and "grad_gap" in over
    assert judged["stale_eval"] == [(5, False, ["val_loss_gap"])]
    assert control.passed(judged)
    assert not control.passed({**judged, "int8": [(5, True, [])]})
    assert not control.passed({**judged, "program": [(5, False, ["a"])]})
    assert set(control.summarise([row])["loss_gap"]) == {
        "program_max", "int8_min", "stale_eval_min"}


@pytest.mark.parametrize("traffic", ["retrain-resident", "retrain-files"])
def test_abstract_step_has_the_shapes_the_windows_call_feeds(traffic):
    """At the real sizes, nothing placed and nothing run: batch 32 of
    256x256 float32 rows whichever way the data set reaches the step (the
    files' 640x480 frames are resized by the loader), the state of the
    configuration's own parameters."""
    import types

    import jax

    bench = spec.Bench(ROOT)
    driver, body = bench.driver("retrain"), bench.config("unet-tconv")
    cell = types.SimpleNamespace(config=body, traffic=bench.traffic(traffic))
    fn, (state, x, y) = driver.abstract_step(cell)
    assert (x.shape, x.dtype.name) == ((32, 256, 256, 3), "float32")
    assert (y.shape, y.dtype.name) == ((32, 256, 256, 1), "float32")
    leaves = jax.tree.leaves(state.params)
    assert sum(leaf.size for leaf in leaves) == body["parameters"]
    assert {leaf.dtype.name for leaf in leaves} == {"float32"}
    assert callable(fn)
    # the window's own program, for memory_probe.py: the whole-epoch scan
    # over the resident rows, none where the job streams
    epoch = driver.abstract_epoch(cell)
    if traffic == "retrain-files":
        assert epoch is None
    else:
        _, (_, xs, ys, grid) = epoch
        assert xs.shape == (819, 256, 256, 3) and ys.shape[-1] == 1
        assert (grid.shape, grid.dtype.name) == ((26, 32), "int32")


def test_control_verdicts_go_by_the_cells_limits():
    from perfbench import control

    rows = [{"seed": 1, "program": {"a_gap": 0.1, "read_only": 9.0},
             "control": {"a_gap": 0.5, "read_only": 9.0}},
            {"seed": 2, "program": {"a_gap": 0.3}}]
    got = control.verdicts(rows, {"a_gap": 0.2, "window_epochs_missing": 0})
    assert got == {"program": [(1, True, []), (2, False, ["a_gap"])],
                   "control": [(1, False, ["a_gap"])]}


def test_judge_compares_what_the_limits_name():
    ok, table = compare.judge({"a": 1.0, "b": 5.0}, {"a": 2.0})
    assert ok and table == {"a": {"value": 1.0, "limit": 2.0}}
    assert not compare.judge({"b": 5.0}, {"a": 2.0})[0]      # missing
    assert not compare.judge({"a": float("nan")}, {"a": 2.0})[0]
    assert not compare.judge({"a": 1.0}, {})[0]              # nothing held


@pytest.mark.parametrize("seconds,epochs", [(50, 12), (25, 6), (10, 3),
                                            (1, 3)])
def test_the_window_is_a_fixed_number_of_epochs(seconds, epochs):
    bench = spec.Bench(ROOT)
    driver = bench.driver("retrain")
    for name in ("retrain-resident", "retrain-files"):
        assert driver.window_epochs(bench.traffic(name), seconds) == epochs
    assert bench.traffic("retrain-resident")["window"]["at_seconds"] == \
        bench.doc["run_seconds"]


def test_the_reference_starts_where_the_program_starts(probed):
    """Names and the first gradient agree leaf by leaf, for both decoders:
    the weights the benchmark makes reach the program whole."""
    _, job, want, _ = probed
    got, want = job.produced["probe"]["grad"], want["probe"]["grad"]
    assert set(got) == set(want)
    for k, ref in want.items():
        scale = max(np.abs(ref).max(), 1e-6)
        assert np.abs(got[k] - ref).max() <= 1e-2 * scale, k


def test_data_order_copy_matches_the_program():
    from robotic_discovery_platform_tpu.training import data

    for n, seed in ((40, 0), (1024, 3_000_000_019 % (2 ** 31 - 1))):
        for got, want in zip(order.train_val_split(n, 0.2, seed),
                             data.train_val_split(n, 0.2, seed)):
            assert (got == want).all()
    a = order.epoch_order(102, 32, True, np.random.default_rng(4))
    b = data.epoch_order(102, 32, True, np.random.default_rng(4))
    assert a.shape == (4, 32) and (a == b).all()


def test_no_tpu_is_a_failure_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         "seg.retrain-resident", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr
