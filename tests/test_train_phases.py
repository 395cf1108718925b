"""What the retraining job says about itself: the ``rdp.train.*`` phase
spans of a ``train_model`` call in a ``jax.profiler`` trace (names, nesting,
threads, tiling), the ``rdp_train_phase_seconds`` histogram the same stages
feed, the timeline of the same stages that every call leaves in the flight
recorder (parent links, the checkpoint thread's hand-over, the counts on the
root and at the spans' boundaries, a call that raises), the compile
counters, the jitted runners kept across calls (what
shares an entry of the memo, what builds anew, its bound, a replaced
builder, a one-device mesh, the trace guards' budget), and the named
scopes of the compiled step."""

import dataclasses
import json
import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perfbench.lib import spans as spans_lib, trace as trace_lib
from robotic_discovery_platform_tpu import tracking
from robotic_discovery_platform_tpu.analysis import recompile
from robotic_discovery_platform_tpu.models import losses as losses_lib
from robotic_discovery_platform_tpu.models.unet import build_unet
from robotic_discovery_platform_tpu.observability import (
    exposition, instruments as obs, recorder as recorder_lib)
from robotic_discovery_platform_tpu.observability.registry import (
    MetricsRegistry)
from robotic_discovery_platform_tpu.training import synthetic, trainer
from robotic_discovery_platform_tpu.utils import platforms
from robotic_discovery_platform_tpu.utils.config import ModelConfig, TrainConfig
from robotic_discovery_platform_tpu.utils.profiling import StageTimer

TINY_MODEL = ModelConfig(base_features=8, compute_dtype="float32")
#: the children of rdp.train.job that a resumed call with a registry write
#: runs once each, in this order, around its epochs
JOB_PHASES = ("rdp.train.init", "rdp.train.restore", "rdp.train.stage_data",
              "rdp.train.register", "rdp.train.flush")
EPOCH_PHASES = ("rdp.train.steps", "rdp.train.validation", "rdp.train.log",
                "rdp.train.checkpoint.wait", "rdp.train.checkpoint.snapshot")
STEP_PHASES = ("rdp.train.loader_wait", "rdp.train.h2d", "rdp.train.step")
WORKER_PHASES = ("rdp.train.checkpoint.fetch", "rdp.train.checkpoint.write")
TILING = JOB_PHASES + ("rdp.train.epoch",)


def phase_sum(name):
    return obs.TRAIN_PHASE.labels(phase=name).sum


def jit_seconds():
    return sum(obs.JIT_SECONDS.labels(stage=s).value
               for s in ("jaxpr_trace", "jaxpr_to_mlir_module",
                         "backend_compile"))


GUARDS = {"epoch": ("trainer.train_epoch", "trainer.eval_epoch"),
          "step": ("trainer.train_step", "trainer.eval_step")}
#: what a ``train_model`` call adds to ``runner_counts``: new runners (a
#: train and an evaluation one, a guard instance each, each traced once),
#: or an earlier call's, which trace nothing they have run before
BUILT = dict(built=1, reused=0, traces=2, guards=2)
REUSED = dict(built=0, reused=1, traces=0, guards=0)


def runner_counts(family):
    """What the process has counted for one runner family: look-ups of the
    memo by result, traces of the family's two guards, guard instances."""
    out = {result: obs.TRAIN_RUNNERS.labels(
        family=family, result=result).value for result in ("built", "reused")}
    out["traces"] = sum(obs.JIT_TRACES.labels(fn=g).value
                        for g in GUARDS[family])
    out["guards"] = sum(len(recompile.stats_for(g)) for g in GUARDS[family])
    return out


def counted(family, call):
    """``call()``'s result and what it added to ``runner_counts``."""
    before = runner_counts(family)
    out = call()
    after = runner_counts(family)
    return out, {k: after[k] - before[k] for k in before}


def job_timelines(checkpoint_dir):
    """The ``rdp.train.job`` timelines that the flight recorder has pinned
    for the calls of one job, oldest first, as ``GET /debug/spans`` shows
    them."""
    return [t for t in recorder_lib.RECORDER.snapshot()["pinned"]
            if t["name"] == "rdp.train.job"
            and t["labels"]["checkpoint_dir"] == str(checkpoint_dir)]


def records(timeline, names):
    names = (names,) if isinstance(names, str) else names
    return [s for s in timeline["spans"] if s["name"] in names]


def forget_runners():
    """An empty memo: what an earlier test of this process kept (the tiny
    model and the default hyper-parameters are everybody's) must not decide
    whether a call here builds."""
    trainer._kept_runners.cache_clear()
    trainer._kept_row_floats.cache_clear()


def conversion_traces():
    """Traces of the program that makes a resident data set's integer rows
    float32 on the device (``trainer.make_row_floats``): one a first call
    on uint8 pairs, beside the runners' two."""
    return obs.JIT_TRACES.labels(fn="trainer.row_floats").value


def traced(tmp_path, call):
    """``call()`` inside the harness's window span under a profiler session
    with the harness's options; the window's spans."""
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=trace_lib.profiler_options())
    try:
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
            out = call()
    finally:
        jax.profiler.stop_trace()
    return out, spans_lib.Spans(spans_lib.load_xplane(
        trace_lib.find_xplane(tmp_path / "trace")))


#: epochs of the resumed call: long enough (3 s here) that the job's own
#: time, a few hundredths of a second of scheduling between its phases, is
#: under the 2% that the spans must tile it within, now that the call
#: re-traces nothing; the benchmark's window runs as many
MORE = 12


@pytest.fixture(scope="module", params=["scan", "stream"])
def job(request, tmp_path_factory):
    """A first ``train_model`` call of two epochs, then the same job resumed
    for ``MORE`` under a profiler session: what ``perfbench`` measures."""
    tmp = tmp_path_factory.mktemp(request.param)
    platforms.enable_compile_cache()    # CPU: only the compile counters
    forget_runners()
    cfg = TrainConfig(
        epochs=2, batch_size=4, img_size=32, validation_split=0.25,
        tracking_uri=f"file:{tmp}/mlruns", checkpoint_dir=f"{tmp}/ckpt",
        loader_workers=2)
    feed = {}
    if request.param == "scan":
        imgs, masks = synthetic.generate_arrays(16, 32, 32, seed=3)
        feed["arrays"] = (imgs, masks)
    else:
        synthetic.generate_dataset(tmp / "data", 16, 48, 64, seed=3)
        cfg = dataclasses.replace(cfg, dataset_dir=str(tmp / "data"))
    family = "epoch" if request.param == "scan" else "step"
    seconds, conversions = [jit_seconds()], [conversion_traces()]
    _, first = counted(family,
                       lambda: trainer.train_model(cfg, TINY_MODEL, **feed))
    seconds.append(jit_seconds())
    conversions.append(conversion_traces())
    before = {name: phase_sum(name) for name in TILING + ("rdp.train.job",)}
    counts = [trainer._process_counts()]
    (result, spans), second = counted(family, lambda: traced(
        tmp, lambda: trainer.train_model(
            dataclasses.replace(cfg, epochs=2 + MORE), TINY_MODEL, resume=True,
            **feed)))
    counts.append(trainer._process_counts())
    seconds.append(jit_seconds())
    conversions.append(conversion_traces())
    observed = {name: phase_sum(name) - before[name] for name in before}
    return dict(mode=request.param, spans=spans, result=result,
                observed=observed, counted=(first, second),
                conversions=np.diff(conversions).tolist(),
                jit_seconds=seconds, process_counts=counts,
                timelines=job_timelines(cfg.checkpoint_dir))


def one(spans, name):
    found = spans.named(name)
    assert len(found) == 1, (name, len(found))
    return found[0]


def test_span_names_and_nesting(job):
    spans, main = job["spans"], job["spans"].main
    whole = one(spans, "rdp.train.job")
    assert whole.thread == main
    epochs = spans.named("rdp.train.epoch")
    assert [e.stats["epoch"] for e in epochs] == list(range(2, 2 + MORE))
    starts = []
    for name in JOB_PHASES:
        phase = one(spans, name)
        assert whole.holds(phase) and phase.thread == main
        assert not any(e.holds(phase) for e in epochs), name
        starts.append(phase.start)
    # init, restore, stage_data before the epochs; register, flush after
    assert starts == sorted(starts)
    assert starts[2] < epochs[0].start and epochs[-1].end <= starts[3]
    for epoch in epochs:
        assert whole.holds(epoch)
        for name in EPOCH_PHASES:
            inside = [s for s in spans.named(name, main) if epoch.holds(s)]
            assert len(inside) == 1, (name, epoch.stats)
    # the copy, when it ran, ran inside an epoch
    for copy in spans.named("rdp.train.best_copy"):
        assert any(e.holds(copy) for e in epochs)
    steps = spans.named("rdp.train.steps")
    per_step = {name: spans.named(name) for name in STEP_PHASES}
    if job["mode"] == "scan":
        assert not any(per_step.values())
    else:
        # 12 training rows at batch 4: three steps an epoch, each a wait, a
        # placement and a dispatch; one more wait finds the epoch's end
        assert [len(per_step[n]) for n in STEP_PHASES] \
            == [4 * MORE, 3 * MORE, 3 * MORE]
        assert [s.stats["step_num"] for s in per_step["rdp.train.step"]] \
            == list(range(6, 6 + 3 * MORE))
        for name in STEP_PHASES:
            for span in per_step[name]:
                assert any(s.holds(span) for s in steps), name


def test_the_phases_tile_the_job(job):
    spans = job["spans"]
    whole = spans.seconds("rdp.train.job")
    assert whole > 0
    assert spans.self_seconds("rdp.train.job") < 0.02 * whole
    assert whole == pytest.approx(job["result"].wall_clock_s, rel=0.05)


def test_worker_spans_sit_on_their_own_threads(job):
    spans, main = job["spans"], job["spans"].main
    workers = {s.thread for s in spans.named(WORKER_PHASES)}
    # one save at a time, each on a thread of its own, never the job's
    assert len(spans.named(WORKER_PHASES)) == 2 * MORE \
        and main not in workers
    for fetch in spans.named("rdp.train.checkpoint.fetch"):
        write = [w for w in spans.named("rdp.train.checkpoint.write",
                                        fetch.thread)
                 if w.start >= fetch.end]
        assert write, "a fetch without its write on the same thread"
    decodes = spans.named("rdp.loader.decode")
    if job["mode"] == "scan":
        assert not decodes
    else:
        # each epoch three training batches and one validation batch
        assert len(decodes) == 4 * MORE
        assert main not in {d.thread for d in decodes}


def test_phase_histogram_sums_to_the_jobs_wall_clock(job):
    observed = job["observed"]
    whole = observed["rdp.train.job"]
    assert whole == pytest.approx(job["result"].wall_clock_s, rel=0.05)
    assert sum(observed[name] for name in TILING) == pytest.approx(
        whole, rel=0.05)
    # the histogram and the trace time the same stages
    assert whole == pytest.approx(job["spans"].seconds("rdp.train.job"),
                                  rel=0.05)


def test_a_repeated_call_reuses_its_runners_and_the_counters_say_so(job):
    """``train_model`` keeps its jitted runners by configuration
    (``trainer.memoized_runners``): the first call builds them and traces
    each guard once; the resumed call, same configuration and shapes and
    only ``epochs`` changed, gets the same ``jax.jit`` objects back and
    traces, lowers and compiles nothing."""
    first, second = job["counted"]
    assert (first, second) == (BUILT, REUSED)
    spent = np.diff(job["jit_seconds"])
    # (the resumed call's own first-time eager operations: a fraction of a
    # millisecond, where one runner's trace alone takes a hundred)
    assert spent[0] > 0 and spent[1] < 0.01 * spent[0]
    # the traced call is the resumed one: no call in it traced
    assert not job["spans"].named("rdp.jit.trace")
    assert not job["spans"].holding("PjitFunction*", "rdp.jit.trace")


def test_every_call_leaves_one_pinned_timeline_of_its_phases(job):
    """The resumed call with a registry write: the spans of its timeline are
    ``JOB_PHASES`` and the epochs' phases, each with the parent that the
    profiler's trace of the same call shows by nesting."""
    first, timeline = job["timelines"]
    assert first["labels"]["resumed"] == "False" and first["error"] is None
    assert timeline["error"] is None and timeline["labels"] == {
        "family": "unet", "checkpoint_dir": first["labels"]["checkpoint_dir"],
        "run_id": job["result"].run_id, "resumed": "True",
        "epochs": str(2 + MORE)}
    root, by_id = timeline["spans"][0], {
        s["span_id"]: s for s in timeline["spans"]}
    assert root["name"] == "rdp.train.job" and root["parent_id"] is None
    assert all(s["parent_id"] in by_id and s["end_ns"] is not None
               for s in timeline["spans"][1:])
    for name in JOB_PHASES:
        (phase,) = records(timeline, name)
        assert phase["parent_id"] == root["span_id"]
    epochs = records(timeline, "rdp.train.epoch")
    assert [e["attributes"]["epoch"] for e in epochs] \
        == [str(n) for n in range(2, 2 + MORE)]
    assert {e["attributes"]["steps"] for e in epochs} == {"3"}
    for epoch in epochs:
        assert epoch["parent_id"] == root["span_id"]
        for name in EPOCH_PHASES:
            inside = [s for s in records(timeline, name)
                      if s["parent_id"] == epoch["span_id"]]
            assert len(inside) == 1, (name, epoch["attributes"])
    # and the trace of the same call nests the same way: the k-th span of a
    # name on the job's thread is held by the span its record calls parent
    spans, main = job["spans"], job["spans"].main
    nth = {}
    for record in timeline["spans"]:
        if record["attributes"]["thread"] != root["attributes"]["thread"]:
            continue
        found = spans.named(record["name"], main)
        k = nth[record["name"]] = nth.get(record["name"], -1) + 1
        record["traced"] = found[k]
        assert len(found) == len(records(timeline, record["name"]))
        if record is not root:
            parent = by_id[record["parent_id"]]
            assert parent["traced"].holds(found[k]), record["name"]
            # one interval on both clocks: the record's readings are taken
            # around the profiler's span
            assert record["duration_ms"] / 1e3 == pytest.approx(
                found[k].seconds, abs=5e-3)


def test_the_checkpoint_threads_records_hang_under_the_hand_over(job):
    timeline = job["timelines"][1]
    by_id = {s["span_id"]: s for s in timeline["spans"]}
    workers = records(timeline, WORKER_PHASES)
    assert len(workers) == 2 * MORE
    for record in workers:
        handed = by_id[record["parent_id"]]
        assert handed["name"] == "rdp.train.checkpoint.snapshot"
        assert handed["start_ns"] <= record["start_ns"]
        assert record["attributes"]["thread"] == "checkpoint-save" \
            != handed["attributes"]["thread"]
    # each save's two records under the one snapshot that handed it over
    assert len({r["parent_id"] for r in workers}) == MORE
    # the bytes at the boundary: every write and the restore move the same
    # tree (state, best parameters and statistics)
    (restore,) = records(timeline, "rdp.train.restore")
    moved = {r["attributes"]["bytes"]
             for r in records(timeline, "rdp.train.checkpoint.write")}
    assert moved == {restore["attributes"]["bytes"]} and int(moved.pop()) > 0


def test_a_timeline_holds_no_record_a_step_or_a_batch(job):
    """Stream mode runs ``rdp.train.loader_wait`` / ``.h2d`` once a step and
    ``rdp.loader.decode`` once a batch: histogram samples and profiler spans
    (above), never records, so both modes leave the same timeline."""
    for timeline in job["timelines"]:
        assert not records(timeline, STEP_PHASES + ("rdp.loader.decode",))
    names = [s["name"] for s in job["timelines"][1]["spans"]]
    assert set(names) == set(TILING + EPOCH_PHASES + WORKER_PHASES + (
        "rdp.train.job",)) | ({"rdp.train.best_copy"} & set(names))
    assert len(names) <= 12 + 9 * MORE


def test_the_roots_counts_are_the_programs_counters(job):
    first, second = (t["spans"][0]["attributes"] for t in job["timelines"])
    before, after = job["process_counts"]
    # where the process stood when the resumed call began
    assert float(second["process_jit_s"]) == pytest.approx(
        job["jit_seconds"][1], abs=1e-5)
    assert int(second["process_cache_hits"]) == before["compile_cache.hit"]
    assert int(second["process_cache_misses"]) \
        == before["compile_cache.miss"]
    assert float(second["process_age_s"]) >= float(first["process_age_s"]) \
        + job["timelines"][0]["duration_ms"] / 1e3 - 0.02
    # and what the call added
    for name, value in after.items():
        if name.startswith("jit_s."):
            assert float(second[name]) == pytest.approx(
                value - before[name], abs=1e-5)
        elif name.startswith("compile_cache."):
            assert int(second[name]) == value - before[name]
    # the resident job's uint8 pairs: its first call also traces the
    # program that makes them float32 on the device, the second nothing
    assert job["conversions"] == [job["mode"] == "scan", 0]
    for attributes, added, converted, state in (
            (first, BUILT, job["conversions"][0], "built"),
            (second, REUSED, 0, "restored")):
        assert int(attributes["traces"]) == added["traces"] + converted
        assert attributes["runners"] == (
            "built" if added["built"] else "reused")
        assert attributes["state"] == state
    # as rdp_train_state_total counted the resumed call
    assert after["state.restored"] - before.get("state.restored", 0) == 1
    assert after["state.built"] == before["state.built"]


def test_the_timelines_seconds_are_the_histograms(job):
    timeline = job["timelines"][1]
    for name, seconds in job["observed"].items():
        assert sum(r["duration_ms"] for r in records(timeline, name)) / 1e3 \
            == pytest.approx(seconds, abs=1e-6), name


def test_debug_spans_serves_the_jobs_timeline(job):
    server = exposition.MetricsServer(
        0, MetricsRegistry(), host="127.0.0.1").start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/debug/spans",
                timeout=5) as r:
            served = json.loads(r.read())
    finally:
        server.stop()
    seqs = {t["seq"] for t in served["pinned"]}
    assert {t["seq"] for t in job["timelines"]} <= seqs


def test_without_a_call_the_recorder_holds_what_it_held(tmp_path):
    before = recorder_lib.RECORDER.snapshot()
    with obs.TRAIN_PHASES.stage("rdp.train.steps"):
        obs.TRAIN_PHASES.annotate(steps=3)
    trainer.memoized_runners("epoch", TrainConfig(), TINY_MODEL, (4, 32))
    after = recorder_lib.RECORDER.snapshot()
    assert [t["seq"] for t in after["pinned"]] \
        == [t["seq"] for t in before["pinned"]]
    assert after["recorded_total"] == before["recorded_total"]


def tiny_job(tmp_path, **changes):
    """One epoch of the tiny model over 16 resident pairs; ``changes`` to
    the ``TrainConfig``, ``model`` for another ``ModelConfig``, ``pairs``
    for another data-set size, ``mesh`` for a device mesh."""
    imgs, masks = synthetic.generate_arrays(
        changes.pop("pairs", 16), 32, 32, seed=3)
    model = changes.pop("model", TINY_MODEL)
    mesh = changes.pop("mesh", None)
    cfg = dataclasses.replace(TrainConfig(
        epochs=1, batch_size=4, img_size=32, validation_split=0.25,
        tracking_uri=f"file:{tmp_path}/mlruns",
        checkpoint_dir=f"{tmp_path}/ckpt"), **changes)
    return trainer.train_model(cfg, model, arrays=(imgs, masks), mesh=mesh,
                               register=False)


@pytest.mark.parametrize("changes,expected", [
    # what the runners close over builds anew, and the new jit objects trace
    (dict(learning_rate=3e-4), BUILT),
    (dict(loss="bce_dice"), BUILT),
    (dict(loss="bce_dice", dice_weight=0.25), BUILT),
    (dict(donate_state=False), BUILT),
    (dict(model=ModelConfig(base_features=8, compute_dtype="float32",
                            norm="group")), BUILT),
    # so does what decides their shapes: one pair, one shape set, one trace
    (dict(pairs=24), BUILT),
    (dict(batch_size=2), BUILT),
    # what they do not close over shares the entry, and nothing traces
    (dict(epochs=2), REUSED),
    (dict(seed=7, checkpoint_every=2, keep_checkpoints=1), REUSED),
    # equal by value is equal: another ModelConfig object, the same entry
    (dict(model=ModelConfig(base_features=8, compute_dtype="float32")),
     REUSED),
], ids=["learning_rate", "loss", "dice_weight", "donate_state", "model",
        "data_set_size", "batch_size", "epochs", "seed_and_checkpoints",
        "equal_model_config"])
def test_what_shares_a_memo_entry_and_what_builds_anew(tmp_path, changes,
                                                      expected):
    forget_runners()
    with recompile.strict():    # a second trace by any runner would raise
        _, base = counted("epoch", lambda: tiny_job(tmp_path / "base"))
        assert base == BUILT
        _, changed = counted(
            "epoch", lambda: tiny_job(tmp_path / "changed", **changes))
    assert changed == expected


def test_the_memo_is_bounded_and_drops_the_least_recently_used():
    forget_runners()
    bound = trainer.RUNNER_MEMO_BOUND

    def look_up(rate, family="epoch"):
        return counted(family, lambda: trainer.memoized_runners(
            family, TrainConfig(learning_rate=rate), TINY_MODEL, (4, 32)))

    def size():
        return trainer._kept_runners.cache_info().currsize

    rates = [1e-4 * (k + 1) for k in range(bound)]
    kept = [look_up(rate)[0] for rate in rates]
    assert size() == bound
    # the two families never share an entry; one more pushes the oldest out
    (train_step, eval_step), added = look_up(rates[1], "step")
    assert added["built"] == 1 and train_step is not kept[1][0]
    assert size() == bound
    again, added = look_up(rates[1])
    assert added["reused"] == 1 and again[0] is kept[1][0] \
        and again[1] is kept[1][1]
    again, added = look_up(rates[0])
    assert added["built"] == 1 and again[0] is not kept[0][0]
    assert size() == bound


def test_a_replaced_builder_is_honoured_and_leaves_nothing_behind(
        tmp_path, monkeypatch):
    """The memo looks ``make_epoch_runners`` up through the module at call
    time and keys on the function: a planted fault (as
    ``tests/perfbench``'s broken whole-epoch program) is never served the
    sound entry, and the sound call after it never the fault's."""
    forget_runners()
    sound_runners = trainer.make_epoch_runners
    sound, _ = counted("epoch", lambda: tiny_job(tmp_path / "sound"))
    ran = []

    def unchanged(model, tx, loss_fn, donate=True):
        train_epoch, eval_epoch = sound_runners(model, tx, loss_fn, False)

        def broken(state, xs, ys, order):
            ran.append(order.shape)
            return state, train_epoch(state, xs, ys, order)[1]

        return broken, eval_epoch

    with monkeypatch.context() as patch:
        patch.setattr(trainer, "make_epoch_runners", unchanged)
        planted, added = counted(
            "epoch", lambda: tiny_job(tmp_path / "planted"))
        assert ran and added["built"] == 1 and added["reused"] == 0
        # the state went nowhere: validation saw the initial weights
        assert planted.final_metrics["loss"] != sound.final_metrics["loss"]
    served, added = counted("epoch", lambda: tiny_job(tmp_path / "after"))
    assert added == REUSED
    assert len(ran) == 1
    assert served.final_metrics == sound.final_metrics


def test_every_legitimate_shape_has_a_pair_of_its_own_and_a_repeat_is_flagged(
        tmp_path):
    """One pair of runners per shape set, one trace a runner: under the
    strict setting a third and a fourth data-set size pass (a later cycle's
    data set has another size), a size seen before brings its pair back
    with nothing to trace and no new guard instance, and what is flagged is
    a runner that traces a second time -- here the signature it has already
    traced, its cache lost."""
    forget_runners()
    with recompile.strict():
        for pairs in (16, 24, 32, 40):
            _, added = counted(
                "epoch", lambda: tiny_job(tmp_path / str(pairs), pairs=pairs))
            assert added == BUILT
        assert not {"trainer.train_epoch", "trainer.eval_epoch"} \
            & set(recompile.over_budget())
        for pairs in (24, 40, 24):
            _, added = counted("epoch", lambda: tiny_job(
                tmp_path / f"again{pairs}", pairs=pairs))
            assert added == REUSED
        _, eval_epoch = trainer.memoized_runners(
            "epoch", TrainConfig(), TINY_MODEL,
            (4, (32, 32, 3), "float32", (32, 32, 1), "float32", 18, 6))
        eval_epoch.clear_cache()    # what jax.clear_caches() does to it
        with pytest.raises(recompile.RecompileBudgetExceeded,
                           match="trainer.eval_epoch"):
            tiny_job(tmp_path / "lost", pairs=24)
    assert recompile.over_budget()["trainer.eval_epoch"] == 1


@pytest.mark.parametrize("strict", [True, False])
def test_a_shape_that_changes_inside_a_job_is_flagged(
        tmp_path, monkeypatch, caplog, strict):
    """A leak: each epoch's ``order`` one batch shorter than the last, so
    the whole-epoch program is traced and compiled anew every epoch. The
    guard raises under the strict setting, and warns otherwise, at the
    second trace."""
    forget_runners()
    sound = trainer.data_lib.epoch_order
    shuffled = []

    def shrinking(n, batch_size, shuffle, rng):
        order = sound(n, batch_size, shuffle, rng)
        if not shuffle:
            return order        # the validation split's, made once
        shuffled.append(len(order))
        return order[:len(order) - len(shuffled) + 1]

    monkeypatch.setattr(trainer.data_lib, "epoch_order", shrinking)
    with recompile.strict(strict), caplog.at_level("WARNING"):
        if strict:
            with pytest.raises(recompile.RecompileBudgetExceeded,
                               match="trainer.train_epoch"):
                tiny_job(tmp_path, epochs=3)
            assert len(shuffled) == 2
        else:
            tiny_job(tmp_path, epochs=3)
            assert caplog.text.count("'trainer.train_epoch' retraced") == 2
    assert recompile.over_budget()["trainer.train_epoch"] == (
        1 if strict else 2)


def one_device_mesh():
    from robotic_discovery_platform_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_serving_mesh(1)


def test_a_mesh_of_the_default_device_alone_trains_without_one(tmp_path):
    """``serving/rollout.py``'s ``training_mesh()`` hands a one-chip
    replica's retraining cycle ``make_serving_mesh(1)``: nothing to shard,
    so the job is the single-device one, kept runners included, of the
    model any mesh trains (XLA convolutions), to the last digit."""
    forget_runners()
    xla_convs = dataclasses.replace(TINY_MODEL, conv_impl="flax")
    plain, added = counted(
        "epoch", lambda: tiny_job(tmp_path / "plain", model=xla_convs))
    assert added == BUILT
    before = len(recompile.stats_for("parallel.train_step"))
    meshed, added = counted("epoch", lambda: tiny_job(
        tmp_path / "meshed", mesh=one_device_mesh()))
    assert added == REUSED
    assert len(recompile.stats_for("parallel.train_step")) == before
    assert meshed.final_metrics == plain.final_metrics


def test_the_rollout_cycle_reuses_its_step_runners(tmp_path):
    """What the rollout manager's drift cycles do in the serving process
    (``serving/rollout.py`` ``_retrain``): the same ``TrainConfig`` and
    ``ModelConfig`` over the data directory, which has grown since the last
    cycle, under the one-chip replica's mesh. Every batch is full, so the
    second cycle feeds the step runners the first one's shapes: they come
    back from the memo and trace nothing."""
    from robotic_discovery_platform_tpu.workflows.retraining import (
        run_retraining_pipeline,
    )

    forget_runners()
    cfg = TrainConfig(
        epochs=1, batch_size=4, img_size=32, validation_split=0.25,
        tracking_uri=f"file:{tmp_path}/mlruns", loader_workers=2,
        checkpoint_dir=f"{tmp_path}/ckpt", dataset_dir=str(tmp_path / "data"))
    cycles = []
    with recompile.strict():
        for pairs in (16, 24):
            synthetic.generate_dataset(tmp_path / "data", pairs, 48, 64,
                                       seed=3)
            result, added = counted("step", lambda: run_retraining_pipeline(
                cfg, model_cfg=TINY_MODEL, mesh=one_device_mesh()))
            assert result.succeeded, result.message
            cycles.append(added)
    assert cycles == [BUILT, REUSED]


def test_an_exception_in_an_epoch_closes_every_span(tmp_path, monkeypatch):
    imgs, masks = synthetic.generate_arrays(16, 32, 32, seed=3)
    cfg = TrainConfig(
        epochs=2, batch_size=4, img_size=32, validation_split=0.25,
        tracking_uri=f"file:{tmp_path}/mlruns",
        checkpoint_dir=f"{tmp_path}/ckpt")
    sound = tracking.log_metric

    def failing(key, value, step=None):
        if key == "val_loss" and step == 1:
            raise RuntimeError("the tracking store went away")
        return sound(key, value, step=step)

    monkeypatch.setattr(tracking, "log_metric", failing)
    counted = obs.TRAIN_PHASE.labels(phase="rdp.train.job").count

    def call():
        with pytest.raises(RuntimeError, match="went away"):
            trainer.train_model(cfg, TINY_MODEL, arrays=(imgs, masks))
        with jax.profiler.TraceAnnotation("after"):
            pass

    _, spans = traced(tmp_path, call)
    assert obs.TRAIN_PHASE.labels(phase="rdp.train.job").count == counted + 1
    whole = one(spans, "rdp.train.job")
    epochs = spans.named("rdp.train.epoch")
    assert [e.stats["epoch"] for e in epochs] == [0, 1]
    # the span that raised, its epoch and the job all ended, the drain of the
    # checkpoint worker ran inside the job, and what follows is outside it
    logs = [s for s in spans.named("rdp.train.log") if epochs[1].holds(s)]
    assert len(logs) == 1 and whole.holds(epochs[1])
    flush = one(spans, "rdp.train.flush")
    assert whole.holds(flush) and flush.start >= epochs[1].end
    assert not whole.holds(one(spans, "after"))
    assert spans.self_seconds("rdp.train.job") < 0.02 * whole.seconds + 0.05
    # and the call's timeline is pinned as failed, every record closed, the
    # root's counts taken on the way out
    (timeline,) = job_timelines(cfg.checkpoint_dir)
    assert timeline["error"] == "RuntimeError: the tracking store went away"
    assert all(s["end_ns"] is not None for s in timeline["spans"])
    assert [e["attributes"]["epoch"]
            for e in records(timeline, "rdp.train.epoch")] == ["0", "1"]
    root = timeline["spans"][0]["attributes"]
    assert root["state"] == "built" and "traces" in root
    assert timeline["labels"]["resumed"] == "False" \
        and timeline["labels"]["run_id"]
    assert records(timeline, "rdp.train.flush")[0]["parent_id"] \
        == timeline["spans"][0]["span_id"]


def test_a_stage_is_a_profiler_span_and_a_histogram_sample(tmp_path):
    seen = []
    timer = StageTimer(observer=lambda name, dt: seen.append((name, dt)))

    def call():
        with timer.stage("rdp.test.outer", batch=7):
            with timer.stage("rdp.test.inner"):
                pass

    _, spans = traced(tmp_path, call)
    outer, inner = one(spans, "rdp.test.outer"), one(spans, "rdp.test.inner")
    assert outer.holds(inner) and outer.stats == {"batch": 7}
    assert [name for name, _ in seen] == ["rdp.test.inner", "rdp.test.outer"]
    assert outer.seconds == pytest.approx(seen[1][1], abs=5e-3)
    # and with no session running it is a timer as before
    with timer.stage("rdp.test.outer"):
        pass
    assert timer.summary()["rdp.test.outer"]["count"] == 2


def test_worker_threads_records_lose_nothing_under_contention():
    """More workers than cores, each adopting the stage that started it, a
    short switch interval: every stage of every thread is one closed record
    under its hand-over, and the opening thread's own nesting is intact."""
    import sys
    import threading

    rec = recorder_lib.FlightRecorder(capacity=4)
    timer = StageTimer(recorder=rec)
    workers, stages = 16, 50

    def work(handed, k):
        with timer.adopted(handed):
            for i in range(stages):
                with timer.stage("rdp.test.worker", worker=k, i=i):
                    timer.annotate(bytes=i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with timer.timeline("rdp.test.job", {"kind": "stress"}) as timeline:
            with timer.stage("rdp.test.handover"):
                threads = [threading.Thread(
                    target=work, args=(timer.handover(), k))
                    for k in range(workers)]
                for t in threads:
                    t.start()
                for i in range(stages):
                    with timer.stage("rdp.test.own"):
                        pass
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    (pinned,) = rec.pinned()
    assert pinned is timeline and timeline.error is None
    root, handover = timeline.spans[:2]
    assert (root.name, handover.name, handover.parent_id) == (
        "rdp.test.job", "rdp.test.handover", root.span_id)
    mine = [s for s in timeline.spans if s.name == "rdp.test.worker"]
    assert len(mine) == workers * stages
    assert all(s.parent_id == handover.span_id and s.end_ns is not None
               and s.attributes["bytes"] == s.attributes["i"] for s in mine)
    assert len({(s.attributes["worker"], s.attributes["i"])
                for s in mine}) == workers * stages
    own = [s for s in timeline.spans if s.name == "rdp.test.own"]
    assert len(own) == stages \
        and all(s.parent_id == handover.span_id for s in own)
    assert timer.summary()["rdp.test.worker"]["count"] == workers * stages
    # outside the timeline the timer records nothing more
    with timer.stage("rdp.test.own"):
        timer.annotate(bytes=1)
    assert len(timeline.spans) == 2 + workers * stages + stages


CACHE_CONFIG = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_traceback_in_locations_limit")


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    """The ``op_name`` of every instruction of the COMPILED train and
    evaluation steps, in a process set up as the entry points set theirs up:
    ``enable_compile_cache()`` as it runs on the chip. The lowered text holds
    the scopes whatever the location settings; the executable, which is what
    a profile names its operations by, does not."""
    saved = {name: getattr(jax.config, name) for name in CACHE_CONFIG}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(platforms, "_cpu_pinned", lambda: False)
        patch.setenv("JAX_COMPILATION_CACHE_DIR",
                     str(tmp_path_factory.mktemp("cache")))
        try:
            platforms.enable_compile_cache()
            model = build_unet(ModelConfig(base_features=8))
            tx = optax.adam(1e-4)
            loss = losses_lib.make_loss_fn("bce", 0.5)
            state = trainer.create_state(model, tx, jax.random.key(0), 32)
            x, y = jnp.zeros((2, 32, 32, 3)), jnp.zeros((2, 32, 32, 1))
            train = jax.jit(trainer.core_train_step(model, tx, loss)).lower(
                state, x, y).compile().as_text()
            evaluate = jax.jit(trainer.core_eval_step(model, loss)).lower(
                state, x, y).compile().as_text()
        finally:
            for name, value in saved.items():
                jax.config.update(name, value)
    return {"train": set(re.findall(r'op_name="([^"]+)"', train)),
            "eval": set(re.findall(r'op_name="([^"]+)"', evaluate))}


@pytest.mark.parametrize("program,scope,backward", [
    ("train", "rdp.forward", True), ("train", "rdp.loss", True),
    ("train", "rdp.optimizer", False), ("train", "rdp.conv3x3", False),
    ("train", "rdp.unet.inc", True), ("train", "rdp.unet.down4", True),
    ("train", "rdp.unet.up1", True), ("train", "rdp.unet.head", True),
    ("eval", "rdp.eval", False), ("eval", "rdp.unet.up4", False),
])
def test_the_compiled_step_holds_the_named_scopes(compiled, program, scope,
                                                  backward):
    paths = [p for p in compiled[program] if spans_lib.under(scope, p)]
    assert paths, f"no operation of the {program} step is under {scope}"
    if backward:
        # the backward pass keeps the scope under JAX's prefix
        assert any("transpose(jvp(" in p for p in paths)
    if scope == "rdp.conv3x3":
        # forward, dx and dw of a block's convolution, inside its block
        assert any(spans_lib.under("rdp.unet.down1", p) for p in paths)
    if scope == "rdp.optimizer":
        assert not any(spans_lib.under("rdp.forward", p) for p in paths)
