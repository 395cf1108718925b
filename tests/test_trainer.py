"""Trainer tests: end-to-end train->track->register on synthetic data,
loss descent, checkpoint resume, and where a job's state comes from (built
by a start from nothing, restored into its shapes by a resumed job)."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from robotic_discovery_platform_tpu import tracking
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.tracking import api as tracking_api
from robotic_discovery_platform_tpu.training import (
    data as data_lib, synthetic, tasks as tasks_lib, trainer)
from robotic_discovery_platform_tpu.training.checkpoint import (
    CheckpointManager)
from robotic_discovery_platform_tpu.utils.config import (
    BlockDiffLMConfig, ModelConfig, TrainConfig)


TINY_MODEL = ModelConfig(base_features=8, compute_dtype="float32")


def tiny_cfg(tmp_path, **kw):
    defaults = dict(
        epochs=2,
        batch_size=4,
        img_size=32,
        learning_rate=1e-3,
        tracking_uri=f"file:{tmp_path}/mlruns",
        checkpoint_dir=f"{tmp_path}/ckpt",
        validation_split=0.25,
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


@pytest.fixture(scope="module")
def arrays():
    imgs, masks = synthetic.generate_arrays(16, 32, 32, seed=3)
    return imgs.astype(np.float32) / 255.0, masks.astype(np.float32) / 255.0


def test_train_registers_and_tracks(tmp_path, arrays):
    cfg = tiny_cfg(tmp_path)
    res = trainer.train_model(cfg, TINY_MODEL, arrays=arrays)
    assert res.registry_version == 1
    assert np.isfinite(res.best_val_loss)
    assert res.epochs_run == 2
    # exact reference metric-name surface
    hist = tracking.get_metric_history(res.run_id, "train_loss")
    assert [h["step"] for h in hist] == [0, 1]
    assert tracking.get_metric_history(res.run_id, "val_loss")
    assert tracking.get_metric_history(res.run_id, "best_val_loss")
    # registered model loads and runs
    model, variables = tracking.load_model("models:/Actuator-Segmenter/latest")
    y = model.apply(variables, jnp.zeros((1, 32, 32, 3)), train=False)
    assert y.shape == (1, 32, 32, 1)
    assert "miou" in res.final_metrics


def test_integer_masks_0_255_normalized_other_codings_rejected(tmp_path):
    """In-memory integer masks follow the file loader's convention: {0,255}
    is scaled to {0,1}, {0,1} passes through, and any other coding (class
    indices like {0,2}) is rejected loudly instead of being silently scaled
    to ~K/255 near-zero targets (round-4 advice)."""
    imgs, masks = synthetic.generate_arrays(8, 32, 32, seed=3)
    cfg = tiny_cfg(tmp_path, epochs=1)
    # uint8 images + 0/255 masks train fine (the /255 path)
    res = trainer.train_model(
        cfg, TINY_MODEL, arrays=(imgs, masks), register=False
    )
    assert np.isfinite(res.best_val_loss)
    bad = (masks > 0).astype(np.uint8) * 2  # {0, 2} class coding
    with pytest.raises(ValueError, match="integer masks"):
        trainer.train_model(
            tiny_cfg(tmp_path, epochs=1), TINY_MODEL,
            arrays=(imgs, bad), register=False,
        )


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.int32, np.int64])
def test_integer_rows_are_scaled_in_one_pass_to_the_last_bit(dtype):
    """``data.unit_floats`` (what ``prepare`` hands integer images and
    0/255 masks to) is ``float32(rows) / 255`` exactly, whatever the block
    edges: fewer rows than threads, none, and a count no thread divides."""
    rng = np.random.default_rng(0)
    for n in (0, 1, 5, 19):
        rows = rng.integers(0, 256, (n, 6, 6, 3)).astype(dtype)
        got = data_lib.unit_floats(rows)
        want = np.asarray(rows, np.float32) / 255.0
        assert got.dtype == np.float32 and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    imgs, masks = synthetic.generate_arrays(5, 32, 32, seed=3)
    xs, ys = tasks_lib.UNET.prepare((imgs.astype(dtype), masks.astype(dtype)),
                                    TrainConfig())
    np.testing.assert_array_equal(xs, imgs.astype(np.float32) / 255.0)
    np.testing.assert_array_equal(ys, masks.astype(np.float32) / 255.0)


def test_loss_decreases(tmp_path, arrays):
    cfg = tiny_cfg(tmp_path, epochs=5)
    res = trainer.train_model(cfg, TINY_MODEL, arrays=arrays, register=False)
    hist = tracking.get_metric_history(res.run_id, "train_loss")
    values = [h["value"] for h in hist]
    assert values[-1] < values[0]


def test_resume_from_checkpoint(tmp_path, arrays):
    cfg1 = tiny_cfg(tmp_path, epochs=1)
    trainer.train_model(cfg1, TINY_MODEL, arrays=arrays, register=False)
    cfg2 = tiny_cfg(tmp_path, epochs=3)
    res = trainer.train_model(
        cfg2, TINY_MODEL, arrays=arrays, resume=True, register=False
    )
    assert res.epochs_run == 2  # 3 total - 1 already done


# -- where a job's state comes from -------------------------------------------

#: the children of rdp.train.job that a resumed call with a registry write
#: runs once each (tests/test_train_phases.py pins their order and nesting)
JOB_PHASES = ("rdp.train.init", "rdp.train.restore", "rdp.train.stage_data",
              "rdp.train.register", "rdp.train.flush")
TINY_LM = BlockDiffLMConfig(compute_dtype="float32", kernel_impl="xla")
#: name -> (the configuration trained, one of other leaf shapes under the
#: same keys): tiny likenesses of the benchmark's three configurations, the
#: language model's state through the streamed save path
STATE_JOBS = {
    "seg": (TINY_MODEL, dataclasses.replace(TINY_MODEL, base_features=16)),
    "unet-tconv": (
        dataclasses.replace(TINY_MODEL, bilinear=False),
        dataclasses.replace(TINY_MODEL, bilinear=False, base_features=16)),
    "lm-streamed": (TINY_LM, dataclasses.replace(TINY_LM, expert_width=48)),
}


def state_job(name, arrays, tmp):
    """``(epochs, ...) -> TrainResult``: calls of one job under ``tmp``,
    each continuing it unless told ``resume=False``."""
    lm = name == "lm-streamed"
    if lm:
        arrays = (np.random.default_rng(5).integers(
            0, TINY_LM.mask_token_id, (20, TINY_LM.seq_len)), None)

    def call(epochs, model_cfg=STATE_JOBS[name][0], resume=True,
             register=False):
        cfg = tiny_cfg(tmp, epochs=epochs, batch_size=2 if lm else 4)
        return trainer.train_model(cfg, model_cfg, arrays=arrays,
                                   resume=resume, register=register)

    return call


def spying_on(run, states):
    """A train runner that keeps, on the host, every state it is handed."""
    def spying(state, *batch):
        states.append(jax.device_get(state))
        return run(state, *batch)

    return spying


def assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


@contextlib.contextmanager
def watched(name, model_cfg, spy=False):
    """What a ``train_model`` call does about its state: ``built`` counts
    the calls of ``task.init_variables`` that made values (under
    ``jax.eval_shape`` it returns tracers), ``counter`` is what the call
    added to ``rdp_train_state_total``, and with ``spy`` the whole-epoch
    runner keeps every state it is handed. The tiny language model's state
    counts as too large to hold twice, as the real one is."""
    task = tasks_lib.task_for(model_cfg)
    sound_init, sound_runners = task.init_variables, trainer.make_epoch_runners
    seen = {"built": 0, "states": []}

    def init_variables(model, rng, cfg):
        out = sound_init(model, rng, cfg)
        seen["built"] += not any(isinstance(leaf, jax.core.Tracer)
                                 for leaf in jax.tree.leaves(out))
        return out

    def make_epoch_runners(*args, **kw):
        train_epoch, eval_epoch = sound_runners(*args, **kw)
        return spying_on(train_epoch, seen["states"]), eval_epoch

    def counter():
        return {result: obs.TRAIN_STATE.labels(
            family=task.name, result=result).value
            for result in ("built", "restored")}

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(task, "init_variables", init_variables)
        if spy:
            patch.setattr(trainer, "make_epoch_runners", make_epoch_runners)
        if name == "lm-streamed":
            patch.setattr(trainer, "_DEVICE_SNAPSHOT_MAX_BYTES", 1000)
            patch.setattr(tracking_api, "_LEAF_FILES_ABOVE", 1000)
        before = counter()
        yield seen
        seen["counter"] = {k: v - before[k] for k, v in counter().items()}


def saved_at(model_cfg, cfg, step, streamed):
    """What the job's checkpoint of ``step`` holds, by either save path,
    read the way a resumed job read it before it restored into shapes: into
    a state built for the purpose, on the host. ``(state, best_params,
    best_stats)``."""
    task = tasks_lib.task_for(model_cfg)
    built = jax.device_get(trainer.task_state(
        task, task.build(model_cfg), optax.adam(cfg.learning_rate),
        jax.random.key(cfg.seed), cfg))
    ckpt = CheckpointManager(cfg.checkpoint_dir, keep=cfg.keep_checkpoints)
    try:
        if streamed:
            state, best = (
                ckpt.restore_streamed({"state": built}, step=at)["state"]
                for at in (step, ckpt.best_step()))
            return state, best.params, best.batch_stats
        got = ckpt.restore({"state": built, "best_params": built.params,
                            "best_stats": built.batch_stats}, step=step)
        return got["state"], got["best_params"], got["best_stats"]
    finally:
        ckpt.close()


@pytest.fixture(scope="module", params=list(STATE_JOBS))
def resumed(request, arrays, tmp_path_factory):
    """One job in three calls, each watched: ``resume=True`` on an empty
    checkpoint directory (two epochs), a resumed call with nothing left to
    train that registers, a resumed call that trains a third epoch under
    the spy; then the first call again as a plain start elsewhere."""
    name = request.param
    model_cfg = STATE_JOBS[name][0]
    tmp = tmp_path_factory.mktemp(name)
    call = state_job(name, arrays, tmp)
    out = {"name": name, "call": call}
    with watched(name, model_cfg) as out["first_seen"]:
        out["first"] = call(2)
    phases = {p: obs.TRAIN_PHASE.labels(phase=p).count for p in JOB_PHASES}
    with watched(name, model_cfg) as out["idle_seen"]:
        out["idle"] = call(2, register=True)
        latest = f"models:/{TrainConfig().registered_model_name}/latest"
        _, out["registered"] = tracking.load_model(latest)
        # names of each leaf file of the registered version, as of now
        out["registered_links"] = [
            p.stat().st_nlink for p in sorted(
                (tracking.resolve_model_uri(latest) / "variables").glob(
                    "*.npy"))]
    out["idle_phases"] = {p: obs.TRAIN_PHASE.labels(phase=p).count - n
                          for p, n in phases.items()}
    with watched(name, model_cfg, spy=True) as out["more_seen"]:
        out["more"] = call(3)
    out["saved"] = saved_at(model_cfg, tiny_cfg(tmp), 2,
                            name == "lm-streamed")
    with watched(name, model_cfg) as out["plain_seen"]:
        out["plain"] = state_job(name, arrays, tmp / "plain")(
            2, resume=False)
    return out


def test_a_resumed_job_builds_no_state_and_the_counter_says_so(resumed):
    """``fresh_state()`` never runs on a resumed single-device job,
    whichever save path wrote its checkpoint: its shapes are enough."""
    for which in ("idle", "more"):
        seen = resumed[f"{which}_seen"]
        assert seen["built"] == 0, which
        assert seen["counter"] == {"built": 0, "restored": 1}, which
    assert (resumed["idle"].epochs_run, resumed["more"].epochs_run) == (0, 1)


def test_a_resumed_job_trains_from_the_saved_state_bit_for_bit(resumed):
    """Params, ``opt_state``, ``batch_stats``, ``epoch`` and
    ``best_val_loss``, as the resumed call's first epoch is handed them."""
    handed, = resumed["more_seen"]["states"]
    saved, _, _ = resumed["saved"]
    assert int(saved.epoch) == 2 and np.isfinite(float(saved.best_val_loss))
    assert_trees_equal(handed, saved)


def test_a_resumed_job_registers_the_saved_best_candidate(resumed):
    """``best_params`` / ``best_stats``: a resumed call with nothing left
    to train registers what the checkpoint holds as the best so far."""
    _, best_params, best_stats = resumed["saved"]
    assert resumed["idle"].registry_version == 1
    task = tasks_lib.task_for(STATE_JOBS[resumed["name"]][0])
    want = task.variables(best_params, best_stats)
    assert jax.tree.structure(resumed["registered"]) \
        == jax.tree.structure(want)
    jax.tree.map(np.testing.assert_array_equal, resumed["registered"], want)


def test_a_streamed_candidate_is_registered_as_links_to_its_checkpoint(
        resumed):
    """A state too large to hold twice registers its best candidate as
    hard links to the leaf files of the checkpoint that holds it: the run's
    artifact, the registry's version and the checkpoint are one copy on the
    disk. A U-Net's artifact is a file of its own."""
    links = resumed["registered_links"]
    if resumed["name"] != "lm-streamed":
        assert not links
        return
    assert links and all(n == 3 for n in links)


def test_a_checkpoint_of_other_shapes_raises_before_any_step(resumed):
    """The restore is held to the configuration's shapes: a resumed call
    under a configuration of other widths raises there and trains nothing."""
    other = STATE_JOBS[resumed["name"]][1]
    with watched(resumed["name"], other, spy=True) as seen:
        with pytest.raises(ValueError, match="shape|saved"):
            resumed["call"](4, model_cfg=other)
    assert seen["states"] == [] and seen["built"] == 0


def test_resume_with_no_checkpoint_is_a_start_from_nothing(resumed):
    """``resume=True`` on an empty checkpoint directory (the supervisor's
    first attempt) builds its state and trains as a plain first call."""
    for which in ("first", "plain"):
        seen = resumed[f"{which}_seen"]
        assert seen["built"] == 1, which
        assert seen["counter"] == {"built": 1, "restored": 0}, which
    first, plain = resumed["first"], resumed["plain"]
    assert first.epochs_run == plain.epochs_run == 2
    assert first.final_metrics == plain.final_metrics
    assert first.best_val_loss == plain.best_val_loss


def test_a_resumed_job_runs_the_pinned_phases_once_each(resumed):
    """The ``rdp.train.*`` children of a resumed call's job, by the
    histogram the spans feed: init, restore, stage_data, register, flush."""
    assert resumed["idle_phases"] == dict.fromkeys(JOB_PHASES, 1)


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["device-snapshot", "streamed"])
@pytest.mark.parametrize("epoch_mode", ["auto", "stream"])
def test_each_epoch_runner_under_each_save_policy_resumes_and_registers(
        tmp_path, arrays, epoch_mode, streamed):
    """``train_model``'s two seams, every pairing, on a model with batch
    statistics: the whole-epoch scan and the per-step loop, the device
    snapshot and (by the threshold the trainer reads) the streamed save.
    Two epochs, then the job resumed for a third that registers: the
    resumed call's first step is handed the saved state bit for bit, what
    is registered is the best epoch's parameters AND statistics, and
    ``streamed/`` exists exactly when told."""
    cfg = tiny_cfg(tmp_path, epoch_mode=epoch_mode)
    family = "epoch" if epoch_mode == "auto" else "step"
    handed = []

    def call(epochs, **kw):
        return trainer.train_model(
            dataclasses.replace(cfg, epochs=epochs), TINY_MODEL,
            arrays=arrays, resume=True, **kw)

    sound_epoch, sound_step = (trainer.make_epoch_runners,
                               trainer.make_train_step)

    def epoch_runners(*args, **kw):
        train_epoch, eval_epoch = sound_epoch(*args, **kw)
        return spying_on(train_epoch, handed), eval_epoch

    def lookups():
        return sum(obs.TRAIN_RUNNERS.labels(family=family, result=r).value
                   for r in ("built", "reused"))

    with pytest.MonkeyPatch.context() as patch:
        if streamed:
            patch.setattr(trainer, "_DEVICE_SNAPSHOT_MAX_BYTES", 1000)
        before = lookups()
        first = call(2, register=False)
        assert lookups() == before + 1      # the runner this mode names
        patch.setattr(trainer, "make_epoch_runners", epoch_runners)
        patch.setattr(
            trainer, "make_train_step",
            lambda *args, **kw: spying_on(sound_step(*args, **kw), handed))
        second = call(3, register=True)
    assert (first.epochs_run, second.epochs_run) == (2, 1)
    assert (tmp_path / "ckpt" / "streamed").is_dir() == streamed

    saved, _, _ = saved_at(TINY_MODEL, cfg, 2, streamed)
    assert int(saved.epoch) == 2 and saved.batch_stats
    assert_trees_equal(handed[0], saved)

    losses = [m["value"] for result in (first, second)
              for m in tracking.get_metric_history(result.run_id, "val_loss")]
    assert len(losses) == 3
    best = int(np.argmin(losses))   # the first epoch to reach the least
    assert second.best_val_loss == pytest.approx(losses[best], rel=1e-6)
    of_best, _, _ = saved_at(TINY_MODEL, cfg, best + 1, streamed)
    last, best_params, best_stats = saved_at(TINY_MODEL, cfg, 3, streamed)
    assert int(last.epoch) == 3
    assert_trees_equal((best_params, best_stats),
                       (of_best.params, of_best.batch_stats))
    assert second.registry_version == 1
    _, registered = tracking.load_model(
        f"models:/{cfg.registered_model_name}/latest")
    assert set(registered) == {"params", "batch_stats"}
    assert_trees_equal(registered, tasks_lib.UNET.variables(
        of_best.params, of_best.batch_stats))


@pytest.mark.parametrize("name", ["seg", "unet-tconv"])
def test_a_repeated_call_traces_and_counts_no_convolution_again(
        tmp_path, arrays, name):
    """``rdp_train_conv_dispatch_total`` is sampled while a step is traced:
    a first ``train_model`` call under ``conv_impl="auto"`` builds its
    runners, traces each once and counts the train step's eighteen 3x3
    convolutions (``xla`` here: this process sees no TPU); the same job
    resumed gets the runners back (``reused``) and adds no trace and no
    dispatch sample. Evaluation is ``train=False`` and counts none."""
    from robotic_discovery_platform_tpu.analysis import recompile

    def counts():
        out = {r: obs.TRAIN_RUNNERS.labels(family="epoch", result=r).value
               for r in ("built", "reused")}
        out["traces"] = sum(obs.JIT_TRACES.labels(fn=g).value for g in (
            "trainer.train_epoch", "trainer.eval_epoch"))
        out.update({impl: obs.TRAIN_CONV_DISPATCH.labels(impl=impl).value
                    for impl in ("pallas", "xla")})
        return out

    def added(call):
        before = counts()
        call()
        return {k: v - before[k] for k, v in counts().items()}

    trainer._kept_runners.cache_clear()     # what earlier tests kept
    call = state_job(name, arrays, tmp_path)
    assert STATE_JOBS[name][0].conv_impl == "auto"
    with recompile.strict():
        assert added(lambda: call(1)) == dict(
            built=1, reused=0, traces=2, pallas=0, xla=18)
        assert added(lambda: call(2)) == dict(
            built=0, reused=1, traces=0, pallas=0, xla=0)


def test_checkpoint_every_skips_intermediate_saves(tmp_path, arrays):
    """checkpoint_every=2 over 5 epochs saves steps {2, 4, 5}: every second
    epoch plus the final epoch unconditionally."""
    from pathlib import Path

    cfg = tiny_cfg(tmp_path, epochs=5, checkpoint_every=2)
    trainer.train_model(cfg, TINY_MODEL, arrays=arrays, register=False)
    steps = sorted(
        int(p.name) for p in Path(cfg.checkpoint_dir).iterdir()
        if p.name.isdigit()
    )
    assert steps == [2, 4, 5], steps


def test_dice_loss_variant(tmp_path, arrays):
    cfg = tiny_cfg(tmp_path, loss="bce_dice")
    res = trainer.train_model(cfg, TINY_MODEL, arrays=arrays, register=False)
    assert np.isfinite(res.best_val_loss)


def test_checkpoint_every_zero_rejected(tmp_path, arrays):
    """0 would be a ZeroDivisionError deep in the epoch loop; negatives
    would silently save every epoch (round-3 advice)."""
    for bad in (0, -1):
        cfg = tiny_cfg(tmp_path, checkpoint_every=bad)
        with pytest.raises(ValueError, match="checkpoint_every"):
            trainer.train_model(cfg, TINY_MODEL, arrays=arrays,
                                register=False)


def test_dataset_too_small(tmp_path):
    xs = np.zeros((1, 32, 32, 3), np.float32)
    ys = np.zeros((1, 32, 32, 1), np.float32)
    with pytest.raises(ValueError):
        trainer.train_model(tiny_cfg(tmp_path), TINY_MODEL, arrays=(xs, ys))


def test_file_dataset_roundtrip(tmp_path):
    from robotic_discovery_platform_tpu.training.data import PairedSegmentationData

    synthetic.generate_dataset(tmp_path / "ds", n=4, h=64, w=64)
    ds = PairedSegmentationData(tmp_path / "ds", img_size=32)
    assert len(ds) == 4
    xs, ys = ds.as_arrays()
    assert xs.shape == (4, 32, 32, 3) and ys.shape == (4, 32, 32, 1)
    assert 0.0 <= xs.min() and xs.max() <= 1.0
    assert set(np.unique(ys)) <= {0.0, 1.0}
    # masks are non-trivial
    assert ys.mean() > 0.01


def test_streaming_batches_match_in_memory(tmp_path):
    from robotic_discovery_platform_tpu.training.data import (
        Batches, PairedSegmentationData, StreamingBatches)

    synthetic.generate_dataset(tmp_path / "ds", n=6, h=64, w=64)
    ds = PairedSegmentationData(tmp_path / "ds", img_size=32)
    xs, ys = ds.as_arrays()
    idx = np.arange(len(ds))
    streamed = list(StreamingBatches(ds, idx, 4, shuffle=False, workers=2))
    in_mem = list(Batches(xs, ys, 4, shuffle=False))
    assert len(streamed) == len(in_mem) == 2
    for (sx, sy), (mx, my) in zip(streamed, in_mem):
        np.testing.assert_array_equal(sx, mx)
        np.testing.assert_array_equal(sy, my)


def test_streaming_batches_tiny_subset_pads(tmp_path):
    from robotic_discovery_platform_tpu.training.data import (
        PairedSegmentationData, StreamingBatches)

    synthetic.generate_dataset(tmp_path / "ds", n=3, h=64, w=64)
    ds = PairedSegmentationData(tmp_path / "ds", img_size=32)
    # a 1-sample subset with batch 4 must wrap-pad, not crash
    batches = list(StreamingBatches(ds, [0], 4, shuffle=False))
    assert len(batches) == 1
    bx, by = batches[0]
    assert bx.shape == (4, 32, 32, 3) and by.shape == (4, 32, 32, 1)
    np.testing.assert_array_equal(bx[0], bx[1])


def test_streaming_batches_surface_decode_errors(tmp_path):
    from robotic_discovery_platform_tpu.training.data import (
        PairedSegmentationData, StreamingBatches)

    synthetic.generate_dataset(tmp_path / "ds", n=2, h=64, w=64)
    ds = PairedSegmentationData(tmp_path / "ds", img_size=32)
    (tmp_path / "ds" / "images" / ds.names[0]).write_bytes(b"not an image")
    with pytest.raises(IOError):
        list(StreamingBatches(ds, [0, 1], 2, shuffle=False))


def test_scan_epoch_matches_stream(tmp_path, arrays):
    """The one-dispatch-per-epoch lax.scan path and the per-batch loop are
    the same computation: same shuffle order (shared epoch_order + seed),
    same losses/metrics to float tolerance."""
    res_scan = trainer.train_model(
        tiny_cfg(tmp_path, epochs=2, checkpoint_dir=f"{tmp_path}/c1",
                 epoch_mode="scan"),
        TINY_MODEL, arrays=arrays, register=False)
    res_stream = trainer.train_model(
        tiny_cfg(tmp_path, epochs=2, checkpoint_dir=f"{tmp_path}/c2",
                 epoch_mode="stream"),
        TINY_MODEL, arrays=arrays, register=False)
    h_scan = tracking.get_metric_history(res_scan.run_id, "train_loss")
    h_stream = tracking.get_metric_history(res_stream.run_id, "train_loss")
    np.testing.assert_allclose(
        [h["value"] for h in h_scan], [h["value"] for h in h_stream],
        rtol=1e-4,
    )
    # mIoU thresholds predictions at 0.5, so float-order differences can
    # flip individual pixels -- compare loosely
    np.testing.assert_allclose(
        res_scan.final_metrics["miou"], res_stream.final_metrics["miou"],
        atol=5e-3,
    )


def test_train_model_streams_from_disk(tmp_path):
    synthetic.generate_dataset(tmp_path / "ds", n=8, h=64, w=64)
    cfg = tiny_cfg(tmp_path, epochs=1, dataset_dir=str(tmp_path / "ds"))
    res = trainer.train_model(cfg, TINY_MODEL, register=False)
    assert np.isfinite(res.best_val_loss)
    assert "miou" in res.final_metrics


@pytest.mark.slow
def test_training_cli_module_main(tmp_path):
    """`python -m robotic_discovery_platform_tpu.training` is the reference's
    train_segmenter.py entry point as a CLI: section.field overrides, JSON
    result line on stdout, clean error for a missing dataset."""
    import json
    import os
    import subprocess
    import sys

    synthetic.generate_dataset(tmp_path / "ds", n=8, h=64, w=64)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [
        sys.executable, "-m", "robotic_discovery_platform_tpu.training",
        "--train.epochs", "1", "--train.batch_size", "4",
        "--train.img_size", "32", "--train.validation_split", "0.25",
        "--train.dataset_dir", str(tmp_path / "ds"),
        "--train.tracking_uri", f"file:{tmp_path}/mlruns",
        "--train.checkpoint_dir", str(tmp_path / "ckpt"),
        "--model.base_features", "8", "--model.compute_dtype", "float32",
        "--no-register",
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-800:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["epochs_run"] == 1
    assert out["registry_version"] is None
    assert np.isfinite(out["best_val_loss"])

    bad_cmd = list(cmd)
    bad_cmd[bad_cmd.index(str(tmp_path / "ds"))] = str(tmp_path / "missing")
    bad = subprocess.run(bad_cmd, capture_output=True, text=True, env=env,
                         timeout=600)
    assert bad.returncode == 2
    assert "images/ and masks/" in bad.stderr
    assert "Traceback" not in bad.stderr  # one-line CLI error, not a dump
