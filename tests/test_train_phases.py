"""What the retraining job says about itself: the ``rdp.train.*`` phase
spans of a ``train_model`` call in a ``jax.profiler`` trace (names, nesting,
threads, tiling), the ``rdp_train_phase_seconds`` histogram the same stages
feed, the compile counters, and the named scopes of the compiled step."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perfbench.lib import spans as spans_lib, trace as trace_lib
from robotic_discovery_platform_tpu import tracking
from robotic_discovery_platform_tpu.models import losses as losses_lib
from robotic_discovery_platform_tpu.models.unet import build_unet
from robotic_discovery_platform_tpu.observability import instruments as obs
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


@pytest.fixture(scope="module", params=["scan", "stream"])
def job(request, tmp_path_factory):
    """A first ``train_model`` call of two epochs, then the same job resumed
    for two more under a profiler session: what ``perfbench`` measures."""
    tmp = tmp_path_factory.mktemp(request.param)
    platforms.enable_compile_cache()    # CPU: only the compile counters
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
    guard = "trainer.train_epoch" if request.param == "scan" \
        else "trainer.train_step"
    counts = [obs.JIT_TRACES.labels(fn=guard).value]
    seconds = [jit_seconds()]
    trainer.train_model(cfg, TINY_MODEL, **feed)
    counts.append(obs.JIT_TRACES.labels(fn=guard).value)
    seconds.append(jit_seconds())
    before = {name: phase_sum(name) for name in TILING + ("rdp.train.job",)}
    result, spans = traced(tmp, lambda: trainer.train_model(
        dataclasses.replace(cfg, epochs=4), TINY_MODEL, resume=True, **feed))
    counts.append(obs.JIT_TRACES.labels(fn=guard).value)
    seconds.append(jit_seconds())
    observed = {name: phase_sum(name) - before[name] for name in before}
    return dict(mode=request.param, spans=spans, result=result,
                observed=observed, traces=counts, jit_seconds=seconds)


def one(spans, name):
    found = spans.named(name)
    assert len(found) == 1, (name, len(found))
    return found[0]


def test_span_names_and_nesting(job):
    spans, main = job["spans"], job["spans"].main
    whole = one(spans, "rdp.train.job")
    assert whole.thread == main
    epochs = spans.named("rdp.train.epoch")
    assert [e.stats["epoch"] for e in epochs] == [2, 3]
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
    # both epochs improved on a loss of infinity or did not: the copy, when
    # it ran, ran inside an epoch
    for copy in spans.named("rdp.train.best_copy"):
        assert any(e.holds(copy) for e in epochs)
    steps = spans.named("rdp.train.steps")
    per_step = {name: spans.named(name) for name in STEP_PHASES}
    if job["mode"] == "scan":
        assert not any(per_step.values())
    else:
        # 12 training rows at batch 4: three steps an epoch, each a wait, a
        # placement and a dispatch; one more wait finds the epoch's end
        assert [len(per_step[n]) for n in STEP_PHASES] == [8, 6, 6]
        assert [s.stats["step_num"] for s in per_step["rdp.train.step"]] \
            == [6, 7, 8, 9, 10, 11]
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
    assert len(spans.named(WORKER_PHASES)) == 4 and main not in workers
    for fetch in spans.named("rdp.train.checkpoint.fetch"):
        write = [w for w in spans.named("rdp.train.checkpoint.write",
                                        fetch.thread)
                 if w.start >= fetch.end]
        assert write, "a fetch without its write on the same thread"
    decodes = spans.named("rdp.loader.decode")
    if job["mode"] == "scan":
        assert not decodes
    else:
        # two epochs of three training batches and one validation batch
        assert len(decodes) == 8
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


def test_every_call_retraces_its_runner_and_the_counters_say_so(job):
    """``make_epoch_runners`` / ``make_train_step`` build new ``jax.jit``
    objects on every ``train_model`` call, so the second call of a process
    traces again what the first compiled: pinned here as a count, so that
    the PR that keeps the runners across calls has to change it."""
    first, second = np.diff(job["traces"])
    assert (first, second) == (1, 1)
    assert np.all(np.diff(job["jit_seconds"]) > 0)
    held = job["spans"].holding("PjitFunction*", "rdp.jit.trace")
    fn = "train_epoch" if job["mode"] == "scan" else "step"
    assert f"PjitFunction({fn})" in {s.name for s in held}
    guards = {s.stats["fn"] for s in job["spans"].named("rdp.jit.trace")}
    assert guards == ({"trainer.train_epoch", "trainer.eval_epoch"}
                      if job["mode"] == "scan"
                      else {"trainer.train_step", "trainer.eval_step"})


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
