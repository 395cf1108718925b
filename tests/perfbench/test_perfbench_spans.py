"""``lib/spans.py`` and the readers that PR 25 added, on the small document
``data/recorded_spans.json``: clipping to the window, self time, the thread
filter, scoped device time and every new metric come out as worked out by
hand from its events (times below in us; the file holds ns).

The window is [1000, 101000). The job's main thread is the line that holds
the window span, ``python3#0``; ``python3#1`` is the checkpoint thread,
``python3#2`` and ``python3#3`` two loader threads."""

import json
import types
from pathlib import Path

import pytest

from perfbench.lib import spans as spans_lib, spec, trace

ROOT = Path(__file__).resolve().parents[2]
DOC = json.loads((Path(__file__).parent / "data"
                  / "recorded_spans.json").read_text())
US = 1e-6
REAL_LOAD = spans_lib.load_xplane


@pytest.fixture(scope="module")
def spans():
    return spans_lib.Spans(DOC)


@pytest.fixture(scope="module")
def ctx(spans, tmp_path_factory):
    """What ``run.py`` hands a reader, around the recorded document."""
    workdir = tmp_path_factory.mktemp("cell")
    (workdir / "trace").mkdir()
    path = workdir / "trace" / "recorded.xplane.pb"
    path.touch()
    spans_lib._load.cache_clear()
    plain = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
            for ln in p["lines"]]} for p in DOC["planes"]]}
    return types.SimpleNamespace(
        trace=trace.reduce(plain), cell=types.SimpleNamespace(workdir=workdir),
        counters={"optimizer_steps": 8}, path=path)


@pytest.fixture(autouse=True)
def recorded_file(monkeypatch):
    monkeypatch.setattr(spans_lib, "load_xplane", lambda path: DOC)


def test_window_main_thread_and_clipping(spans):
    assert spans.window == (1_000_000, 101_000_000)
    assert spans.window_s == pytest.approx(100_000 * US)
    assert spans.main == "python3#0" and spans.instrumented
    # the window span itself is not one of the host's spans
    assert not spans.named(trace.WINDOW_SPAN)
    # python3#2's first decode began 500 before the window: 100 of its 600
    # are inside; python3#3's last runs 1000 past the end: 500 of 1500
    decodes = spans.named("rdp.loader.decode")
    assert [round(d.seconds / US) for d in decodes] == [100, 3000, 1500, 500]
    assert spans.seconds("rdp.loader.decode") == pytest.approx(5100 * US)


def test_thread_filter(spans):
    assert spans.seconds("rdp.loader.decode", "python3#2") == pytest.approx(
        3100 * US)
    assert spans.seconds("rdp.loader.decode", spans.main) == 0.0
    on_saver = spans.named("rdp.train.checkpoint.*", "python3#1")
    assert [s.name.rsplit(".", 1)[1] for s in on_saver] == [
        "fetch", "write", "fetch", "write"]
    # the prefix takes wait and snapshot on the main thread too
    assert len(spans.named("rdp.train.checkpoint.*")) == 8
    assert spans.named("rdp.train.epoch")[1].stats == {"epoch": 5}


def test_self_time(spans):
    # the job, 98000, less init 8000, restore 5000, stage_data 4000, the
    # run's log 500, two epochs 70000, register 4000, flush 5500: the 500
    # before the first epoch and the 500 after the flush are its own
    assert spans.self_seconds("rdp.train.job") == pytest.approx(1000 * US)
    # epoch 4: 40000 - (30000 + 5000 + 500 + 500 + 500 + 2000) = 1500;
    # epoch 5: 30000 - (22000 + 4000 + 500 + 1500 + 1500) = 500. The
    # checkpoint thread's spans overlap them and take nothing away
    assert spans.self_seconds("rdp.train.epoch") == pytest.approx(2000 * US)
    # steps of epoch 4 holds two nested PjitFunction spans of one call,
    # counted once: 30000 - 12000
    first, second = spans.named("rdp.train.steps")
    assert first.holds(spans.named("rdp.jit.trace")[0])
    assert not second.holds(spans.named("rdp.jit.trace")[0])
    assert spans.self_seconds("rdp.train.steps") == pytest.approx(
        (18000 + 22000 - 200 - 3000) * US)


def test_calls_that_traced(spans):
    held = spans.holding("PjitFunction*", "rdp.jit.trace")
    # both records of epoch 4's train_epoch call and the eval_epoch call;
    # epoch 5's train_epoch call (200) traced nothing
    assert [round(s.seconds / US) for s in held] == [12000, 11800, 2000]
    assert spans_lib.merged_seconds(held) == pytest.approx(14000 * US)


@pytest.mark.parametrize("scope,want_us", [
    # fusion.3 (1500) and fusion.4 (500) in epoch 4, fusion.3 (1500) in
    # epoch 5, and 500 of the fusion.3 that runs 500 past the window; the
    # second chip's plane is not counted
    ("rdp.optimizer", 4000),
    # forward 4000 and, under transpose(jvp(...)), backward 5000
    ("rdp.forward", 9000),
    # ... both of the first block's convolution, and validation's 2500
    ("rdp.unet.inc", 11500), ("rdp.conv3x3", 11500),
    ("rdp.eval", 2500),
    # a scope that no operation carries; a part of a name is no scope
    ("rdp.snapshot", 0), ("rdp.unet", 0),
])
def test_device_seconds_by_scope(spans, scope, want_us):
    assert spans.device_seconds(scope) == pytest.approx(want_us * US)


@pytest.mark.parametrize("path,scope,inside", [
    ("jit(step)/transpose(jvp(rdp.loss))/mul", "rdp.loss", True),
    ("jit(step)/jvp(rdp.forward)/UNet/rdp.unet.up1/Up_0/dot", "rdp.unet.up1",
     True),
    ("jit(step)/jvp(rdp.forward)/UNet/rdp.unet.up1/Up_0/dot", "rdp.unet.up",
     False),
    ("jit(step)/rdp.optimizer/add", "step", True),
    ("", "rdp.loss", False),
])
def test_scope_paths(path, scope, inside):
    assert spans_lib.under(scope, path) is inside


def test_an_operations_scope_is_its_instructions_in_the_running_program():
    """``fusion.3`` is the optimiser's in the training program and the
    metrics' in the evaluation's: the program that was running when the
    operation began decides, and an operation outside every run has none."""
    event = lambda name, start, dur: types.SimpleNamespace(  # noqa: E731
        name=name, start_ns=start, duration_ns=dur)
    text = "%fusion.3 = f32[64]{0} fusion(f32[64]{0} %p), kind=kLoop"
    programs = {"jit_train_epoch(7)": {"fusion.3": "jit(t)/rdp.optimizer/mul"},
                "jit_eval_epoch(9)": {"fusion.3": "jit(e)/rdp.eval/mean",
                                      "copy.1": "jit(e)/rdp.eval/copy"}}
    modules = [event("jit_eval_epoch(9)", 500, 100),
               event("jit_train_epoch(7)", 100, 300),
               event("jit__copy_tree(11)", 700, 50)]
    ops = [event(text, 50, 10), event(text, 100, 10), event(text, 399, 10),
           event(text, 400, 10), event(text, 520, 10), event(text, 710, 10),
           event("%copy.1 = f32[4]{0} copy(f32[4]{0} %p)", 530, 5)]
    got = spans_lib.device_events(ops, modules, programs)
    assert [e[0] for e in got] == ["fusion.3 fusion"] * 6 + ["copy.1 copy"]
    assert [e[3]["scope"] for e in got] == [
        "", "jit(t)/rdp.optimizer/mul", "jit(t)/rdp.optimizer/mul", "",
        "jit(e)/rdp.eval/mean", "", "jit(e)/rdp.eval/copy"]
    assert spans_lib.device_events(ops[:1], [], programs)[0][3] == {
        "scope": ""}


def test_the_profilers_file_holds_each_programs_scopes(tmp_path):
    """A real trace, taken here on the CPU: the HLO that the profiler files
    beside the events gives each instruction's scope, and ``load_xplane``
    keeps the threads with the program's spans, stats and all."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib import xplane_hlo

    @jax.jit
    def scoped_program(x):
        with jax.named_scope("rdp.forward"):
            y = jnp.sin(x) @ x
        with jax.named_scope("rdp.optimizer"):
            return y * 2.0 + 1.0

    x = jnp.ones((64, 64))
    scoped_program(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=trace.profiler_options())
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("rdp.train.epoch", epoch=3):
                scoped_program(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = trace.find_xplane(tmp_path)
    programs = xplane_hlo.program_scopes(path)
    mine = [v for k, v in programs.items() if k.startswith("jit_scoped_program(")]
    assert len(mine) == 1
    paths = set(mine[0].values())
    assert any(spans_lib.under("rdp.optimizer", p) for p in paths)
    assert any(spans_lib.under("rdp.forward", p) and p.endswith("dot_general")
               for p in paths)
    doc = REAL_LOAD(path)           # past this module's recorded file
    kept = [ln for p in doc["planes"] for ln in p["lines"]]
    assert len(kept) == 1           # the one thread that holds our spans
    by_name = {e[0]: e for e in kept[0]["events"]}
    assert by_name["rdp.train.epoch"][3] == {"epoch": 3}
    assert trace.WINDOW_SPAN in by_name


@pytest.mark.parametrize("message,want", [
    # field 1 varint 150; field 2 bytes "hi"; field 3 fixed32 skipped;
    # field 4 fixed64 skipped; field 2 again
    (bytes([0x08, 0x96, 0x01, 0x12, 0x02, 0x68, 0x69, 0x1D, 1, 2, 3, 4,
            0x21, 1, 2, 3, 4, 5, 6, 7, 8, 0x12, 0x00]),
     [(1, 150), (2, b"hi"), (2, b"")]),
    (b"", []),
])
def test_wire_format_fields(message, want):
    from perfbench.lib import xplane_hlo

    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in xplane_hlo.fields(memoryview(message))]
    assert got == want


@pytest.mark.parametrize("metric,want", [
    # the job, 98000, less its two epochs, 40000 + 30000
    ("job_fixed_s", 28000 * US),
    ("recompile_s", 14000 * US),
    # waits 500 + 1500, snapshots 2000 + 1500, the flush 5500
    ("checkpoint_stall_s", 11000 * US),
    # 5000 + 4000 of the window's 100000
    ("validation_share", 9.0),
    # six gaps of 400, 31500, 8200, 2400, 21600, 20000; the first, before
    # the job has begun, is the only one that no host span covers
    ("unattributed_idle_share", 100 * 400 / 84100),
    # 4000 under rdp.optimizer (above) over the counter's 8 steps, in ms
    ("optimizer_ms", 0.5),
])
def test_each_new_metric_by_hand(ctx, metric, want):
    assert spec.Bench(ROOT).reader(metric).read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("gaps,want", [
    # a parent's name on a gap means the phase the host was in has no span:
    # the job's and an epoch's own seconds count with "no span"
    ([(2.0, "host: no span (Python)"), (1.0, "python3: rdp.train.epoch"),
      (0.5, "python3: rdp.train.job"), (6.5, "python3: rdp.train.init")],
     35.0),
    # a phase's name, JAX's own spans and another thread's are attributions
    ([(3.0, "python3: rdp.jit.trace"), (1.0, "python3: rdp.train.epochs"),
      (1.0, "main: DeferredTpuAllocator::Allocate")], 0.0),
    ([], None),
])
def test_a_gap_named_by_the_job_or_an_epoch_is_unattributed(gaps, want):
    got = spec.Bench(ROOT).reader("unattributed_idle_share").read(
        types.SimpleNamespace(trace=types.SimpleNamespace(gaps=gaps)))
    assert got == (want if want is None else pytest.approx(want))


def test_idle_gaps_are_named_by_the_programs_phases(ctx):
    # by lib/trace.py, untouched: the innermost span over a gap's middle,
    # from whichever thread -- the checkpoint worker's where it is busy
    assert [name for _, name in ctx.trace.gaps] == [
        "host: no span (Python)", "python3: rdp.train.stage_data",
        "python3: rdp.train.steps", "python3: rdp.train.best_copy",
        "python3: rdp.train.checkpoint.write",
        "python3: rdp.train.checkpoint.fetch"]


@pytest.mark.parametrize("metric", [
    "job_fixed_s", "recompile_s", "checkpoint_stall_s", "validation_share",
    "unattributed_idle_share", "optimizer_ms"])
def test_a_program_without_the_spans_gives_nothing(ctx, metric, monkeypatch):
    """The parent of PR 25 has no span, scope or device plane of these
    names: every reader returns None there and none raises."""
    bare = {"planes": [{"name": "/host:CPU", "lines": [{
        "name": "python3/100", "events": [
            ["perfbench.window", 1_000_000, 100_000_000, {}],
            ["PjitFunction(train_epoch)", 20_500_000, 12_000_000, {}]]}]}]}
    monkeypatch.setattr(spans_lib, "load_xplane", lambda path: bare)
    spans_lib._load.cache_clear()
    try:
        empty = types.SimpleNamespace(
            trace=types.SimpleNamespace(gaps=[]), cell=ctx.cell,
            counters={"optimizer_steps": 8})
        assert spec.Bench(ROOT).reader(metric).read(empty) is None
    finally:
        spans_lib._load.cache_clear()


def test_the_file_is_read_once_for_all_readers(ctx, monkeypatch):
    calls = []
    monkeypatch.setattr(spans_lib, "load_xplane",
                        lambda path: calls.append(path) or DOC)
    spans_lib._load.cache_clear()
    for metric in ("job_fixed_s", "recompile_s", "validation_share"):
        spec.Bench(ROOT).reader(metric).read(ctx)
    assert calls == [ctx.path]
