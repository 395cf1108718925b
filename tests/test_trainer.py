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
    """``data.unit_floats`` (what integer images and 0/255 masks become,
    wherever they are made floats) is ``float32(rows) / 255`` exactly,
    whatever the block edges: fewer rows than threads, none, and a count no
    thread divides. ``prepare`` hands rows narrower than float32 on as they
    arrived, with the rule that stands for those floats, and makes the
    wider ones' floats itself."""
    rng = np.random.default_rng(0)
    for n in (0, 1, 5, 19):
        rows = rng.integers(0, 256, (n, 6, 6, 3)).astype(dtype)
        got = data_lib.unit_floats(rows)
        want = np.asarray(rows, np.float32) / 255.0
        assert got.dtype == np.float32 and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    imgs, masks = synthetic.generate_arrays(5, 32, 32, seed=3)
    imgs, masks = imgs.astype(dtype), masks.astype(dtype)
    xs, ys = tasks_lib.UNET.prepare((imgs, masks), TrainConfig())
    narrow = np.dtype(dtype).itemsize < 4
    for got, rows in ((xs, imgs), (ys, masks)):
        assert isinstance(got, data_lib.IntegerRows) == narrow
        if narrow:
            assert got.rows is rows and got.unit
        # what it stands for, and says of itself
        assert (got.dtype, got.shape, len(got)) == (
            np.float32, rows.shape, len(rows))
        assert got.nbytes == 4 * rows.size
        np.testing.assert_array_equal(
            data_lib.host_rows(got), rows.astype(np.float32) / 255.0)
    # masks coded {0, 1} are a plain cast, by the same two routes
    _, ys = tasks_lib.UNET.prepare((imgs, masks // 255), TrainConfig())
    assert isinstance(ys, data_lib.IntegerRows) == narrow
    assert not (narrow and ys.unit)
    np.testing.assert_array_equal(
        data_lib.host_rows(ys), (masks // 255).astype(np.float32))


# -- how a resident data set's rows reach the device --------------------------

def every_code_pairs(n=16, size=32):
    """``n`` synthetic pairs whose images hold every one of the 256 codes in
    every row of the data set (so in both splits), masks coded 0/255."""
    imgs, masks = synthetic.generate_arrays(n, size, size, seed=3)
    codes = np.resize(np.arange(256, dtype=np.uint8), (size // 2, size, 3))
    imgs[:, :size // 2] = codes
    assert all(len(np.unique(img)) == 256 for img in imgs)
    return imgs, masks


@contextlib.contextmanager
def rows_handed():
    """What the whole-epoch runners of the ``train_model`` calls inside are
    handed as resident rows, as they are on the device: ``seen["train"]``
    and ``seen["val"]``, each ``(xs, ys)`` of the newest call, and
    ``seen["calls"]``, how often each runner was entered."""
    sound = trainer.make_epoch_runners
    seen = {"calls": 0}

    def make_epoch_runners(*args, **kw):
        runners = sound(*args, **kw)

        def spying(name, run):
            def spy(state, xs, ys, order):
                seen[name] = (xs, ys)
                seen["calls"] += 1
                return run(state, xs, ys, order)
            return spy

        return tuple(spying(name, run)
                     for name, run in zip(("train", "val"), runners))

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trainer, "make_epoch_runners", make_epoch_runners)
        yield seen


def staged_bytes(family="unet"):
    """``rdp_train_staged_bytes_total`` of one family, by form."""
    return {form: obs.TRAIN_STAGED_BYTES.labels(
        family=family, form=form).value
        for form in ("device_cast", "host_float", "as_is")}


def added_bytes(before, family="unet"):
    return {form: value - before[form]
            for form, value in staged_bytes(family).items()
            if value != before[form]}


def assert_bitwise(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def in_device_order(rows):
    """``rows`` with the strides an array fetched from a TPU has: equal
    values, the channel axis outside the two spatial ones in memory."""
    return np.ascontiguousarray(rows.transpose(0, 3, 1, 2)).transpose(
        0, 2, 3, 1)


@pytest.mark.parametrize("rows, shares", [
    (np.arange(2 * 3 * 4 * 5, dtype=np.uint8).reshape(2, 3, 4, 5), True),
    (in_device_order(
        np.arange(2 * 3 * 4 * 5, dtype=np.uint8).reshape(2, 3, 4, 5)), True),
    (np.asfortranarray(np.arange(24, dtype=np.uint16).reshape(2, 3, 4)),
     True),
    (np.arange(2 * 6 * 4, dtype=np.uint8).reshape(2, 6, 4)[:, ::2], False),
    (np.broadcast_to(np.uint8(7), (3, 2, 2)), False),
], ids=["c-order", "device-order", "fortran-order", "strided-slice",
        "broadcast"])
def test_memory_view_is_the_rows_bytes_and_how_to_read_them(rows, shares):
    """``data.memory_view``: a C-contiguous two-dimensional view of the
    block the array holds, whatever its order of axes, with the shape and
    axes that read it back; only rows that are no dense block are copied."""
    flat, (shape, axes) = data_lib.memory_view(rows)
    assert flat.ndim == 2 and flat.flags.c_contiguous
    assert flat.dtype == rows.dtype and flat.size == rows.size
    assert np.shares_memory(flat, rows) == shares
    np.testing.assert_array_equal(
        flat.reshape(shape).transpose(np.argsort(axes)), rows)


@pytest.mark.parametrize("dtype, mask_codes", [
    (np.uint8, 255), (np.uint8, 1), (np.uint16, 255), (np.uint16, 1),
    (np.int32, 255), (np.int64, 1), (np.float32, 1), ("device-order", 255)])
def test_the_resident_rows_are_unit_floats_bit_for_bit(
        tmp_path, dtype, mask_codes):
    """Whatever the pairs' dtype, the four arrays the epoch runners are
    handed are ``unit_floats(rows)[idx]`` to the last bit, over all 256
    codes, and masks coded {0, 1} their plain cast. Integers narrower than
    float32 crossed as integers, in whatever order of axes the host held
    them, and were made float32 by the kept program on the device; wider
    ones and floats took the host path."""
    imgs, masks = every_code_pairs()
    want_x = data_lib.unit_floats(imgs)
    want_y = (masks > 0).astype(np.float32)
    np.testing.assert_array_equal(want_y, data_lib.unit_floats(masks))
    if dtype is np.float32:
        fed = want_x, want_y
    elif dtype == "device-order":
        # uint8 pairs as a TPU hands them back: they cross without a copy
        dtype, fed = np.uint8, (in_device_order(imgs), in_device_order(masks))
        assert not fed[0].flags.c_contiguous
    else:
        fed = imgs.astype(dtype), (masks // 255 * mask_codes).astype(dtype)
    cfg = tiny_cfg(tmp_path, epochs=1)
    before = staged_bytes()
    with rows_handed() as seen:
        trainer.train_model(cfg, TINY_MODEL, arrays=fed, register=False)
    split = data_lib.train_val_split(len(imgs), cfg.validation_split,
                                     cfg.seed)
    for name, idx in zip(("train", "val"), split):
        xs, ys = seen[name]
        assert_bitwise(xs, want_x[idx])
        assert_bitwise(ys, want_y[idx])
    narrow = np.dtype(dtype).itemsize < 4
    assert added_bytes(before) == (
        {"device_cast": float(fed[0].nbytes + fed[1].nbytes)} if narrow
        else {"host_float": 4.0 * (imgs.size + masks.size)})


@pytest.mark.parametrize("dtype", [np.uint8, np.int8, np.uint16, np.int16])
def test_the_device_program_reads_unit_floats_for_every_code(dtype):
    """``make_row_floats`` alone, over every value a dtype narrower than
    float32 holds: its quotient is ``unit_floats``'s to the last bit (XLA's
    own ``x / 255.0`` is a product with the reciprocal and is not), 255
    reads exactly 1, and the cast rule is the cast; rows come back in the
    order of the index they were gathered by."""
    info = np.iinfo(dtype)
    rows = np.arange(info.min, info.max + 1).astype(dtype).reshape(-1, 64)
    train_idx = np.random.default_rng(0).permutation(len(rows))
    val_idx = train_idx[:1]
    (unit, unit_val), (cast, _) = trainer.make_row_floats(
        (True, False), (((len(rows), 8, 8), (0, 1, 2)),
                        (rows.shape, (0, 1))))(
            (rows, rows), train_idx, val_idx)
    assert_bitwise(unit, data_lib.unit_floats(
        rows[train_idx].reshape(-1, 8, 8)))
    assert_bitwise(unit_val, data_lib.unit_floats(
        rows[val_idx].reshape(-1, 8, 8)))
    assert_bitwise(cast, rows[train_idx].astype(np.float32))
    if info.max >= 255:
        assert np.asarray(unit).ravel()[
            np.flatnonzero(rows[train_idx].ravel() == 255)[0]] == 1.0


def test_integer_pairs_train_as_their_floats_do_bit_for_bit(tmp_path):
    """One epoch on uint8 pairs and the same job on those pairs scaled to
    float32 by ``unit_floats`` beforehand: equal metrics, equal state."""
    imgs, masks = synthetic.generate_arrays(16, 32, 32, seed=3)
    results, states = [], []
    for name, fed in (("integers", (imgs, masks)),
                      ("floats", (data_lib.unit_floats(imgs),
                                  data_lib.unit_floats(masks)))):
        cfg = tiny_cfg(tmp_path / name, epochs=1)
        results.append(trainer.train_model(cfg, TINY_MODEL, arrays=fed,
                                           register=False))
        states.append(saved_at(TINY_MODEL, cfg, 1, False))
    assert results[0].final_metrics == results[1].final_metrics
    assert results[0].best_val_loss == results[1].best_val_loss
    assert_trees_equal(states[0], states[1])


def test_a_mask_of_another_coding_raises_before_anything_is_staged(
        tmp_path, monkeypatch):
    imgs, masks = synthetic.generate_arrays(8, 32, 32, seed=3)
    staged = []
    monkeypatch.setattr(trainer._ResidentScan, "stage",
                        lambda self: staged.append(self))
    with pytest.raises(ValueError, match="integer masks"):
        trainer.train_model(tiny_cfg(tmp_path, epochs=1), TINY_MODEL,
                            arrays=(imgs, masks // 255 * 2), register=False)
    assert not staged


def test_token_rows_reach_the_runner_as_the_integers_they_are(tmp_path):
    """A language-model task's ``prepare`` states no rule: its int32 rows
    are placed untouched, and the counter says ``as_is``."""
    tokens = np.random.default_rng(5).integers(
        0, TINY_LM.mask_token_id, (20, TINY_LM.seq_len))
    cfg = tiny_cfg(tmp_path, epochs=1, batch_size=2)
    before = staged_bytes("blockdiff_lm")
    with rows_handed() as seen:
        trainer.train_model(cfg, TINY_LM, arrays=(tokens, None),
                            register=False)
    split = data_lib.train_val_split(len(tokens), cfg.validation_split,
                                     cfg.seed)
    for name, idx in zip(("train", "val"), split):
        xs, ys = (np.asarray(a) for a in seen[name])
        assert xs.dtype == ys.dtype == np.int32
        np.testing.assert_array_equal(xs, tokens[idx])
        np.testing.assert_array_equal(ys, np.full(len(idx), cfg.seed))
    assert added_bytes(before, "blockdiff_lm") == {
        "as_is": 4.0 * (tokens.size + len(tokens))}


def test_a_streamed_job_is_fed_float_batches_from_the_host(tmp_path):
    """``epoch_mode="stream"`` (``_Stepped``) keeps the host path: uint8
    pairs reach the step as the float32 batches ``Batches`` cuts from
    ``unit_floats``'s rows, and nothing is counted as staged."""
    imgs, masks = every_code_pairs()
    cfg = tiny_cfg(tmp_path, epochs=1, epoch_mode="stream")
    sound, fed = trainer.make_train_step, []

    def make_train_step(*args, **kw):
        step = sound(*args, **kw)

        def spy(state, x, y):
            fed.append((x, y))
            return step(state, x, y)
        return spy

    before = staged_bytes()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trainer, "make_train_step", make_train_step)
        trainer.train_model(cfg, TINY_MODEL, arrays=(imgs, masks),
                            register=False)
    train_idx, _ = data_lib.train_val_split(
        len(imgs), cfg.validation_split, cfg.seed)
    want = list(data_lib.Batches(
        data_lib.unit_floats(imgs)[train_idx],
        data_lib.unit_floats(masks)[train_idx], cfg.batch_size, seed=cfg.seed))
    assert len(fed) == len(want) == 3
    for (x, y), (want_x, want_y) in zip(fed, want):
        assert_bitwise(x, want_x)
        assert_bitwise(y, want_y)
    assert added_bytes(before) == {}


def test_a_data_set_is_sized_by_the_floats_the_device_would_hold(
        tmp_path, monkeypatch):
    """``resident_bytes()`` and ``shapes()`` of uint8 pairs are those of
    their float32 rows, so a uint8 data set whose floats are over
    ``_SCAN_MAX_BYTES`` is streamed, not slipped under the limit at a byte
    an element; and its runners are keyed as the float job's."""
    imgs, masks = synthetic.generate_arrays(16, 32, 32, seed=3)
    cfg = tiny_cfg(tmp_path, epochs=1)
    split = data_lib.train_val_split(16, cfg.validation_split, cfg.seed)
    as_integers, as_floats = (
        trainer._Data(*tasks_lib.UNET.prepare(pair, cfg), None, *split)
        for pair in ((imgs, masks), (data_lib.unit_floats(imgs),
                                     data_lib.unit_floats(masks))))
    assert as_integers.resident_bytes() == as_floats.resident_bytes() \
        == 4 * (imgs.size + masks.size)
    assert as_integers.shapes(cfg) == as_floats.shapes(cfg)

    def lookups():
        return {family: sum(
            obs.TRAIN_RUNNERS.labels(family=family, result=r).value
            for r in ("built", "reused")) for family in ("epoch", "step")}

    # between the bytes that arrive and the bytes that would be resident
    monkeypatch.setattr(trainer, "_SCAN_MAX_BYTES", 2 * (imgs.size + masks.size))
    before, staged = lookups(), staged_bytes()
    trainer.train_model(cfg, TINY_MODEL, arrays=(imgs, masks), register=False)
    assert lookups() == {"epoch": before["epoch"], "step": before["step"] + 1}
    assert added_bytes(staged) == {}


def test_staging_is_counted_and_annotated_once_a_call_and_traced_once(
        tmp_path):
    """``rdp_train_staged_bytes_total{family, form}`` takes one sample a
    ``stage()``, the bytes that crossed, and the ``rdp.train.stage_data``
    span of the call's timeline carries the same ``bytes`` and ``form``; a
    second call of equal shapes gets the kept conversion program back and
    traces it no second time."""
    from robotic_discovery_platform_tpu.analysis import recompile
    from robotic_discovery_platform_tpu.observability import recorder

    imgs, masks = synthetic.generate_arrays(16, 32, 32, seed=3)
    cfg = tiny_cfg(tmp_path)

    def traces():
        return obs.JIT_TRACES.labels(fn="trainer.row_floats").value

    def stage_spans():
        return [span["attributes"] for t in recorder.RECORDER.snapshot()[
            "pinned"] if t["labels"].get("checkpoint_dir")
            == cfg.checkpoint_dir for span in t["spans"]
            if span["name"] == "rdp.train.stage_data"]

    trainer._kept_row_floats.cache_clear()      # what earlier tests kept
    moved = imgs.nbytes + masks.nbytes
    with recompile.strict():
        for call, traced in enumerate((1, 0)):
            before, n = staged_bytes(), traces()
            trainer.train_model(
                dataclasses.replace(cfg, epochs=call + 1), TINY_MODEL,
                arrays=(imgs, masks), resume=True, register=False)
            assert added_bytes(before) == {"device_cast": float(moved)}
            assert traces() - n == traced
    spans = stage_spans()
    assert len(spans) == 2
    for attributes in spans:
        assert (attributes["bytes"], attributes["form"]) == (
            str(moved), "device_cast")
    # floats cross as floats, and the span says so
    before = staged_bytes()
    trainer.train_model(
        dataclasses.replace(cfg, epochs=3), TINY_MODEL, resume=True,
        arrays=(data_lib.unit_floats(imgs), data_lib.unit_floats(masks)),
        register=False)
    assert added_bytes(before) == {"host_float": 4.0 * moved}
    assert (stage_spans()[-1]["bytes"], stage_spans()[-1]["form"]) == (
        str(4 * moved), "host_float")


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
