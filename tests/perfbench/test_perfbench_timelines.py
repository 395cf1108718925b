"""``lib/timelines.py`` and the seven readers that PR 39 added, on timelines
made by hand with the program's own ``Timeline`` (what ``GET /debug/spans``
serves): set-up's two calls and the window's, each reader's number worked
out from their spans and attributes; no timeline and another run's timelines
give ``None``, as does a run that required no chip; and, end to end on the
CPU at a tiny size, the driver's three real calls are found and read."""

import math
import types
from pathlib import Path

import pytest

from perfbench.lib import spec, timelines

ROOT = Path(__file__).resolve().parents[2]
S = 1_000_000_000      # the records' clock is in ns
SETUP = ("setup_before_job_s", "setup_job_s", "setup_compile_s",
         "setup_cache_misses", "setup_state_io_s")
WINDOW = ("checkpoint_write_mb_per_s", "routed_rows_per_step")
SHARED = ["seg.retrain-resident", "unet-tconv.retrain-resident",
          "nemotron-twotower-30b-a3b.hybrid-8k-resident"]


def _call(workdir, at, took, root_attributes, spans):
    """One ``train_model`` call's timeline: the root from ``at`` for
    ``took`` seconds, then ``(name, start, seconds, thread, attributes)``
    rows, starts counted from the root's."""
    from robotic_discovery_platform_tpu.observability.recorder import Timeline

    tl = Timeline("rdp.train.job", {
        "checkpoint_dir": str(workdir / "checkpoints"), "family": "toy"})
    root = tl.span("rdp.train.job", at * S, (at + took) * S,
                   thread="MainThread", **root_attributes)
    for name, start, seconds, thread, attributes in spans:
        end = None if seconds is None else int((at + start + seconds) * S)
        tl.span(name, int((at + start) * S), end, parent=root, thread=thread,
                **attributes)
    return tl


def run_of(workdir):
    """Set-up's probe and first-epoch calls and the window's call of a run
    whose work directory is ``workdir``."""
    probe = _call(workdir, 100, 10.0, dict(
        process_age_s="20.50", process_jit_s="1.500000",
        process_cache_misses=0), [
        ("rdp.train.restore", 0.0, 0.5, "MainThread", {"bytes": 4 * 10**9}),
        ("rdp.train.checkpoint.wait", 3.0, 0.125, "MainThread", {}),
        ("rdp.train.checkpoint.snapshot", 3.125, 0.25, "MainThread", {}),
        # the writer's own time is nobody's wait, nor is a span of one of
        # the names on another thread
        ("rdp.train.checkpoint.write", 3.5, 2.0, "checkpoint-save",
         {"bytes": 4 * 10**9}),
        ("rdp.train.checkpoint.wait", 3.5, 8.0, "checkpoint-save", {}),
        ("rdp.train.flush", 9.0, 0.5, "MainThread", {}),
    ])
    first = _call(workdir, 112, 8.0, dict(
        process_age_s="32.50", process_jit_s="21.000000",
        process_cache_misses=3), [
        ("rdp.train.restore", 0.0, 0.25, "MainThread", {"bytes": 4 * 10**9}),
        ("rdp.train.checkpoint.snapshot", 5.0, 0.25, "MainThread", {}),
        ("rdp.train.register", 6.0, 1.0, "MainThread", {}),
        ("rdp.train.flush", 7.0, 0.5, "MainThread", {}),
    ])
    window = _call(workdir, 125, 40.0, dict(
        process_age_s="45.50", process_jit_s="33.250000",
        process_cache_misses=7), [
        ("rdp.train.restore", 0.0, 0.25, "MainThread", {"bytes": 4 * 10**9}),
        ("rdp.train.epoch", 1.0, 15.0, "MainThread",
         {"epoch": 4, "steps": 24, "routed_rows": 2_400_000}),
        ("rdp.train.checkpoint.write", 16.0, 2.0, "checkpoint-save",
         {"bytes": 3 * 10**9}),
        ("rdp.train.epoch", 17.0, 15.0, "MainThread",
         {"epoch": 5, "steps": 24, "routed_rows": 2_640_000}),
        ("rdp.train.checkpoint.write", 32.0, 4.0, "checkpoint-save",
         {"bytes": 3 * 10**9}),
        # a write still open, and one that counted no bytes, say nothing
        ("rdp.train.checkpoint.write", 36.0, None, "checkpoint-save",
         {"bytes": 3 * 10**9}),
        ("rdp.train.checkpoint.write", 36.0, 1.0, "checkpoint-save", {}),
    ])
    return probe, first, window


WANT = {
    "setup_before_job_s": 20.5,
    "setup_job_s": 10.0 + 8.0,
    "setup_compile_s": 33.25,
    "setup_cache_misses": 7,
    # the job's thread alone: 0.5 + 0.125 + 0.25 + 0.5, then 0.25 + 0.25 +
    # 1.0 + 0.5
    "setup_state_io_s": 1.375 + 2.0,
    # 6e9 bytes in the 6 s of the two closed writes that counted theirs
    "checkpoint_write_mb_per_s": 1000.0,
    # 5,040,000 rows over 48 steps
    "routed_rows_per_step": 105_000.0,
}


@pytest.fixture
def recorder(monkeypatch):
    """A recorder of the test's own in the program's place."""
    from robotic_discovery_platform_tpu.observability import (
        recorder as recorder_lib)

    mine = recorder_lib.FlightRecorder(capacity=4)
    monkeypatch.setattr(recorder_lib, "RECORDER", mine)
    return mine


def ctx_of(workdir, on_chip=True):
    """What ``run.py`` hands a reader, as far as these read it: the peaks
    of the chip the run required, ``None`` where it required none."""
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(workdir=workdir),
        peaks=spec.Bench(ROOT).peaks("TPU v5 lite") if on_chip else None)


def reader(metric):
    return spec.Bench(ROOT).reader(metric)


@pytest.mark.parametrize("metric", SETUP + WINDOW)
def test_each_reader_by_hand(recorder, tmp_path, metric):
    for tl in run_of(tmp_path / "run"):
        recorder.pin(recorder.record(tl))       # as train_model leaves them
    # the dispatch path's timelines and another run's calls lie around them
    recorder.record_event("watchdog_restart", error="stalled")
    for tl in run_of(tmp_path / "another"):
        recorder.pin(recorder.record(tl))
    assert reader(metric).read(ctx_of(tmp_path / "run")) \
        == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", SETUP + WINDOW)
def test_a_program_without_timelines_gives_nothing(recorder, tmp_path,
                                                   metric):
    """The parent of PR 39: a recorder that only the dispatch path feeds."""
    recorder.record_event("watchdog_restart", error="stalled")
    assert reader(metric).read(ctx_of(tmp_path)) is None


@pytest.mark.parametrize("metric", SETUP + WINDOW)
def test_another_work_directorys_timelines_are_ignored(recorder, tmp_path,
                                                       metric):
    for tl in run_of(tmp_path / "another"):
        recorder.pin(recorder.record(tl))
    assert reader(metric).read(ctx_of(tmp_path / "run")) is None
    # nor is a sibling whose name begins alike under the run's directory
    assert reader(metric).read(ctx_of(tmp_path / "an")) is None


def test_the_last_call_is_the_windows_and_the_rest_are_set_ups(
        recorder, tmp_path):
    probe, first, window = run_of(tmp_path)
    recorder.pin(recorder.record(window))
    setup, last = timelines.calls(ctx_of(tmp_path))
    assert setup == [] and last["seq"] == window.seq
    # a window with no set-up before it: nothing to say of set-up
    assert [reader(m).read(ctx_of(tmp_path)) for m in SETUP] == [None] * 5
    assert reader("routed_rows_per_step").read(ctx_of(tmp_path)) == 105_000
    # pinned and still in the ring: one timeline, not two
    recorder.pin(recorder.record(probe))
    assert [t["seq"] for t in timelines.recorded()] == [window.seq, probe.seq]


def test_a_call_that_routed_nothing_reports_no_rows(recorder, tmp_path):
    probe, first, _ = run_of(tmp_path)
    for tl in (probe, first):
        recorder.pin(recorder.record(tl))
    assert reader("routed_rows_per_step").read(ctx_of(tmp_path)) is None
    # the last call has no write at all, or one that took no time
    assert reader("checkpoint_write_mb_per_s").read(ctx_of(tmp_path)) is None


@pytest.mark.parametrize("metric", SETUP + WINDOW)
def test_every_new_entry_lists_its_cells(metric):
    """The pin tests of ``sdar``, ``mellum`` and the toy cell hold each
    cell's metrics to a fixed set: the new entries stay off them, and none
    goes without a list (which would owe it to every cell)."""
    bench = spec.Bench(ROOT)
    entry = next(m for m in bench.doc["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == (
        SHARED if metric != "routed_rows_per_step" else SHARED[2:])
    assert entry["moves"] == ("setup_s" if metric in SETUP
                              else "train_img_per_s")
    assert entry["source"] in ("program_span", "program_counter")
    assert not {"sdar-30b-a3b.blockdiff-4k-resident",
                "mellum2-12b-a2.5b.causal-8k-resident",
                "toy-mlp.toy-steps"} & set(entry["workloads"])


@pytest.mark.parametrize("metric", SETUP + WINDOW)
def test_a_run_that_required_no_chip_reports_nothing(recorder, tmp_path,
                                                     metric):
    """Set-up's seconds, the cache's misses and the disk's rate are the
    measured machine's: a run of the tests' sizes on the CPU writes none
    of them under the cell's name, as it writes no device metric."""
    for tl in run_of(tmp_path):
        recorder.pin(recorder.record(tl))
    assert reader(metric).read(ctx_of(tmp_path, on_chip=False)) is None


def test_the_drivers_three_calls_are_read_end_to_end(tmp_path):
    """The U-Net driver at the drivers test's tiny size, its real
    ``train_model`` calls into the program's own recorder: set-up's two and
    the window's are found by the run's work directory and every reader of
    the cell finds its number."""
    import test_perfbench_drivers as drivers

    bench = drivers.tiny_bench(True, "arrays")
    cell = drivers.cell_of(bench, "seg.retrain-resident", tmp_path / "run",
                           seed=2_900_000_011)
    driver = bench.driver(cell.traffic["driver"])
    job = driver.setup(cell)
    out = driver.window(job)
    ctx = ctx_of(cell.workdir)
    setup, window = timelines.calls(ctx)
    assert [t["labels"]["epochs"] for t in setup + [window]] == [
        "3", "4", str(4 + job.window_epochs)]
    assert {t["labels"]["family"] for t in setup} == {"unet"}
    assert window["labels"]["run_id"] == out["result"].run_id
    got = {m: reader(m).read(ctx) for m in SETUP + WINDOW}
    assert got.pop("routed_rows_per_step") is None      # a U-Net routes none
    assert all(math.isfinite(v) and v >= 0 for v in got.values()), got
    assert got["setup_job_s"] == pytest.approx(sum(
        t["duration_ms"] for t in setup) / 1e3)
    assert 0 < got["setup_state_io_s"] < got["setup_job_s"]
    # every executable of set-up was made before the window's call began
    # (the attributes are written to six decimals)
    assert got["setup_compile_s"] + 1e-5 >= sum(
        float(timelines.root(t)["attributes"][f"jit_s.{stage}"])
        for t in setup for stage in ("jaxpr_trace", "jaxpr_to_mlir_module",
                                     "backend_compile"))
    assert got["setup_cache_misses"] == 0       # no cache is asked on a CPU
    assert got["checkpoint_write_mb_per_s"] > 0
    # and the run's line, which required no chip, carries none of them
    assert all(reader(m).read(ctx_of(cell.workdir, on_chip=False)) is None
               for m in SETUP + WINDOW)
