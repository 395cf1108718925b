"""What the ``mellum2-12b-a2.5b`` configuration brought into the benchmark, on
the CPU: the reference's parameter count, ``lib/causal_lm_flops.py`` against
hand counts, the three readers on a small trace document worked by hand, and
the cell end to end through the harness at the unit tests' size (program in
float32 against the reference, every control caught)."""

import json
import math
import re
import types
from pathlib import Path

import pytest

from perfbench import control, run
from perfbench.lib import causal_lm_flops as flops, spans as spans_lib, spec
from perfbench.lib import trace

ROOT = Path(__file__).resolve().parents[2]
CONFIG, CELL = "mellum2-12b-a2.5b", "mellum2-12b-a2.5b.causal-8k-resident"
BODY = json.loads((ROOT / "perfbench" / "configs"
                   / f"{CONFIG}.json").read_text())
MODEL = BODY["model"]
SLIDING, FULL = "sliding_attention", "full_attention"
US = 1_000          # the document's times are in ns


def test_the_configuration_is_the_published_one_cut_to_a_period_and_a_share():
    assert BODY["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "layer_types", "mlp_layer_types"]
    assert BODY["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                 "vocab_size": 98304}
    # the widths, the router's outputs, the experts a token, the window and
    # both rotary descriptions: published
    m, yarn = MODEL, BODY["rope_parameters"][FULL]
    assert (m["hidden_size"], m["expert_width"], m["head_dim"],
            m["num_heads"], m["num_kv_heads"], m["sliding_window"]) == (
        BODY["hidden_size"], BODY["moe_intermediate_size"], BODY["head_dim"],
        BODY["num_attention_heads"], BODY["num_key_value_heads"],
        BODY["sliding_window"]) == (2304, 896, 128, 32, 4, 1024)
    assert (m["num_experts"], m["experts_per_token"]) == (
        64, BODY["num_experts_per_tok"]) == (64, 8)
    assert m["sliding_rope"] == {"theta": BODY["rope_parameters"][SLIDING][
        "rope_theta"]}
    assert m["full_rope"] == {
        "theta": yarn["rope_theta"], "factor": yarn["factor"],
        "original_max_position": yarn["original_max_position_embeddings"],
        "beta_fast": yarn["beta_fast"], "beta_slow": yarn["beta_slow"],
        "attention_factor": yarn["attention_factor"]}
    assert yarn["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1)
    # the cut, inside the guide's floors: one whole period of four layers,
    # 16 >= 8 experts, a quarter >= an eighth of the vocabulary
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) == (
        BODY["num_hidden_layers"], BODY["num_experts"], BODY["vocab_size"]) \
        == (4, 16, 24576)
    assert m["layer_types"] == BODY["layer_types"] == [SLIDING] * 3 + [FULL]
    assert BODY["mlp_layer_types"] == ["sparse"] * 4
    assert m["vocab_size"] * 8 >= 98304 and m["experts_held"] >= 8
    for word in ("q_norm_k_norm", "mtp_head", "aux_loss", "loss", "optimizer",
                 "init", "packing", "data_set"):
        assert BODY["assumed"][word]
    assert "4 chips share each layer" in BODY["deployment"]
    assert "six further stages" in BODY["deployment"]
    shapes = spec.Bench(ROOT).reference(CONFIG).param_shapes(m)
    per_layer = sum(math.prod(v) for k, v in shapes.items()
                    if k.startswith("layers/0/"))
    assert per_layer == 120_476_160
    assert sum(math.prod(v) for v in shapes.values()) == BODY["parameters"] \
        == 4 * per_layer + 2 * 56_623_104 + 2_304 == 595_153_152


def test_operation_counts_against_a_hand_count():
    assert flops.live_pairs(SLIDING, 8192, 1024) == 7_864_832
    assert flops.live_pairs(FULL, 8192, 1024) == 33_558_528
    # a window longer than the sequence is the causal mask
    assert flops.live_pairs(SLIDING, 32, 64) == flops.live_pairs(FULL, 32, 0)
    assert (flops.layers_of(MODEL, SLIDING), flops.layers_of(MODEL, FULL)) \
        == (3, 1)
    # one layer, one sequence, forward: 8,192 positions through q and o
    # (2304 x 4096 each), k and v (2304 x 512 each) and the router
    assert flops.projection_flops(MODEL) == 2 * 8192 * 2304 * (
        2 * 4096 + 2 * 512 + 64)
    assert flops.attention_flops(MODEL, SLIDING) == 4 * 128 * 32 * 7_864_832
    assert flops.attention_flops(MODEL, FULL) == 4 * 128 * 32 * 33_558_528
    assert flops.head_flops(MODEL) == 2 * 8192 * 2304 * 24576
    assert flops.expert_flops(MODEL, 2048) == 2048 * 3 * 2 * 2304 * 896
    dense = 4 * flops.projection_flops(MODEL) \
        + 3 * flops.attention_flops(MODEL, SLIDING) \
        + flops.attention_flops(MODEL, FULL) + flops.head_flops(MODEL)
    assert flops.dense_forward_flops(MODEL) == dense
    # a step of 2 sequences whose experts take 32,768 rows a layer (2 a
    # token): the issue's 8.15 TFLOP forward, 24.5 a step
    step = flops.window_flops(MODEL, 2, 1, 0, 4 * 32768)
    assert step == 2 * 3 * dense + 3 * flops.expert_flops(MODEL, 4 * 32768)
    assert step == pytest.approx(24.5e12, rel=5e-3)
    # validation batches are forward passes, their rows at the steps' mean
    assert flops.window_flops(MODEL, 2, 4, 2, 4000.0) == \
        2 * (3 * 4 + 2) * dense + flops.expert_flops(
            MODEL, 4000.0 * (3 + 2 / 4))
    # bytes: q and o are 8192 x 32 x 128 x 2, k and v 8192 x 4 x 128 x 2
    q, kv = 8192 * 4096 * 2, 8192 * 512 * 2
    assert flops.attention_bytes(MODEL, False) == 2 * q + 2 * kv
    assert flops.attention_bytes(MODEL, True) == 6 * q + 6 * kv
    # the window layers of a window are bound by operations on a v5e
    peaks = spec.Bench(ROOT).peaks("TPU v5 lite")
    assert flops.attention_least_seconds(MODEL, SLIDING, 2, 4, 2, peaks) == \
        3 * 2 * (3 * 4 + 2) * flops.attention_flops(MODEL, SLIDING) / 197e12


# -- the readers, on a document worked by hand --------------------------------
def _op(name, start_us, dur_us, scope):
    return [name, start_us * US, dur_us * US, {"scope": scope}]


STEP = "jit(train_epoch)/while/body/"
EVAL = "jit(eval_epoch)/while/body/rdp.eval/"
DOC = {"planes": [
    {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        _op("custom-call.1 custom-call", 10_000, 3_000, STEP
            + "checkpoint/rdp.lm.layer/rdp.attn.window/pallas_call"),
        _op("custom-call.2 custom-call", 20_000, 9_000, STEP
            + "transpose(jvp(checkpoint))/rdp.lm.layer/rdp.attn.window/"
            "pallas_call"),
        _op("custom-call.3 custom-call", 30_000, 4_000, STEP
            + "checkpoint/rdp.lm.layer/rdp.attn.causal/pallas_call"),
        _op("custom-call.4 custom-call", 35_000, 6_000, STEP
            + "transpose(jvp(checkpoint))/rdp.lm.layer/rdp.attn.causal/"
            "pallas_call"),
        _op("fusion.5 fusion", 42_000, 2_000, STEP
            + "while/body/checkpoint/rdp.lm.head/dot_general"),
        _op("fusion.6 fusion", 45_000, 500, STEP
            + "while/body/checkpoint/rdp.loss/reduce_max"),
        _op("fusion.7 fusion", 46_000, 3_500, STEP
            + "transpose(jvp(while))/body/checkpoint/rdp.lm.head/"
            "dot_general"),
        _op("fusion.8 fusion", 50_000, 3_000, STEP + "rdp.optimizer/mul"),
        _op("custom-call.9 custom-call", 60_000, 1_000,
            EVAL + "rdp.lm.layer/rdp.attn.window/pallas_call"),
        _op("custom-call.10 custom-call", 62_000, 2_000,
            EVAL + "rdp.lm.layer/rdp.attn.causal/pallas_call"),
        _op("fusion.11 fusion", 65_000, 2_000,
            EVAL + "while/body/checkpoint/rdp.lm.head/dot_general"),
    ]}]},
    {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [trace.WINDOW_SPAN, 0, 100_000 * US, {}],
        ["rdp.train.job", 1_000 * US, 98_000 * US, {}],
    ]}]},
]}
COUNTERS = {"optimizer_steps": 4, "eval_batches": 2, "batch": 2,
            "routed_rows": 4 * 4 * 32768.0, "window_s": 0.1}
NEW = ("attn_window_roofline", "attn_causal_roofline", "lm_head_ms")


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace" / "recorded.xplane.pb").touch()
    spans_lib._load.cache_clear()
    monkeypatch.setattr(spans_lib, "load_xplane", lambda path: DOC)
    bench = spec.Bench(ROOT)
    yield types.SimpleNamespace(
        trace=None, counters=dict(COUNTERS), peaks=bench.peaks("TPU v5 lite"),
        cell=types.SimpleNamespace(workdir=tmp_path,
                                   config={"model": MODEL}))
    spans_lib._load.cache_clear()


def _reader(name):
    return spec.Bench(ROOT).reader(name)


def test_the_attention_rooflines_read_each_kinds_scope(ctx):
    # 3 + 9 ms of training and 1 ms of evaluation under rdp.attn.window:
    # three layers, two sequences, 3 x 4 + 2 passes
    flop = 3 * 2 * (3 * 4 + 2) * flops.attention_flops(MODEL, SLIDING)
    assert _reader("attn_window_roofline").read(ctx) == pytest.approx(
        100.0 * (flop / 197e12) / 0.013)
    # 4 + 6 + 2 ms under rdp.attn.causal: one layer
    flop = 1 * 2 * (3 * 4 + 2) * flops.attention_flops(MODEL, FULL)
    assert _reader("attn_causal_roofline").read(ctx) == pytest.approx(
        100.0 * (flop / 197e12) / 0.012)


def test_lm_head_ms_is_the_heads_and_the_losss_device_time_a_step(ctx):
    # 2 + 0.5 + 3.5 ms of training and 2 ms of evaluation, 4 steps
    assert _reader("lm_head_ms").read(ctx) == pytest.approx(8.0 / 4)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_scopes_reads_nothing(ctx, monkeypatch,
                                                    metric):
    """As the parent commit's traced run, or another family's cell: no
    scope of this family, and no error."""
    empty = {"planes": [{"name": "/host:CPU", "lines": [{
        "name": "python3",
        "events": [[trace.WINDOW_SPAN, 0, 100_000 * US, {}]]}]}]}
    monkeypatch.setattr(spans_lib, "load_xplane", lambda path: empty)
    spans_lib._load.cache_clear()
    assert _reader(metric).read(ctx) is None        # this family, no scope
    ctx.counters = {"optimizer_steps": 4, "window_s": 0.1}
    ctx.cell.config = {"model": {"base_features": 64}}
    assert _reader(metric).read(ctx) is None        # another family


@pytest.mark.parametrize("metric", NEW[:2])
def test_another_familys_configuration_has_no_layer_kinds(ctx, metric):
    """The block-diffusion model's: no roofline of a kind it has not."""
    ctx.cell.config = {"model": spec.Bench(ROOT).config("sdar-30b-a3b")[
        "model"]}
    assert _reader(metric).read(ctx) is None


def test_the_new_readers_list_the_new_cell_alone():
    bench = spec.Bench(ROOT)
    for name in NEW:
        entry = next(m for m in bench.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_img_per_s"
    owed = {m["name"] for m in bench.doc["per_layer"]
            if bench.reports(m, CELL)}
    # the five without a list, the six generic ones of the job, compile,
    # device and optimiser layers that took the cell onto theirs, and its
    # own three
    assert owed == {"outside_steps_share", "step_device_ms",
                    "device_idle_share", "peak_hbm_gib", "step_mfu",
                    "job_fixed_s", "recompile_s", "checkpoint_stall_s",
                    "validation_share", "unattributed_idle_share",
                    "optimizer_ms", *NEW}
    assert bench.workload(CELL)["chips"] == 1
    traffic = bench.traffic("causal-8k-resident")
    assert traffic["dataset"] == {"kind": "tokens", "sequences": 60,
                                  "seq_len": 8192}
    assert traffic["train"]["batch_size"] == 2
    assert (traffic["window"]["epochs"], traffic["window"]["at_seconds"]) \
        == (4, bench.doc["run_seconds"])
    assert set(bench.limits(CELL)) == set(LIMITS)


# -- what set-up compiles ahead, from shapes alone ------------------------------
TINY = {**MODEL, "vocab_size": 64, "hidden_size": 64, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "num_experts": 8,
        "experts_per_token": 2, "experts_held": 2, "expert_width": 32,
        "seq_len": 32, "layer_types": [SLIDING, FULL] * 2,
        "sliding_window": 8, "sliding_rope": {"theta": 100.0},
        "full_rope": {"theta": 100.0, "factor": 4.0,
                      "original_max_position": 64, "beta_fast": 4.0,
                      "beta_slow": 0.25,
                      "attention_factor": 0.1 * math.log(4.0) + 1}}


def test_warm_compiles_what_the_references_first_step_would(caplog):
    """``reference.warm`` compiles the layer's two programs (one pair
    serves both layer kinds), the head's and the per-leaf ones from shapes;
    the step that follows finds them compiled."""
    import logging

    import jax
    import numpy as np

    ref = spec.Bench(ROOT).reference(CONFIG)
    model = {**TINY, "seq_len": 48}     # a size no other test compiles
    heavy = ("fwd", "bwd", "head", "_adam_leaf", "_add_at")

    def compiled():
        names = [found.group(1) for found in (
            re.match(r"Compiling jit\((\w+)\)", r.getMessage())
            for r in caplog.records) if found]
        caplog.clear()
        return [n for n in names if n in heavy]

    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        ref.warm(model)
        assert set(compiled()) == set(heavy)
        params = ref.init(model, 5)
        names = set(params)
        params, opt, loss, _, taken = ref.train_step(
            model, 1e-4, 5, params, ref.adam_init(params),
            ref.tokens(model, 5, 2))
        assert compiled() == []
    assert np.isfinite(loss) and set(params) == names and opt["count"] == 1
    assert taken.shape == (4, 2)


def test_the_epoch_programs_are_the_jobs_two_scans_at_its_shapes():
    bench = spec.Bench(ROOT)
    driver = bench.driver("retrain_causal")
    cell = types.SimpleNamespace(
        config=bench.config(CONFIG),
        traffic=bench.traffic("causal-8k-resident"))
    model_cfg, cfg, sequences = driver._abstract(cell)
    assert model_cfg.kernel_impl == "pallas" and sequences == 60
    assert model_cfg.layer_types == (SLIDING,) * 3 + (FULL,)
    assert model_cfg.full_rope.factor == 16.0
    (train, t_args), (evaluate, e_args) = driver._epoch_programs(
        model_cfg, cfg, sequences)
    assert [a.shape for a in t_args[1:]] == [(48, 8192), (48,), (24, 2)]
    assert [a.shape for a in e_args[1:]] == [(12, 8192), (12,), (6, 2)]
    assert {a.dtype.name for a in t_args[1:] + e_args[1:]} == {"int32"}
    assert hasattr(train, "lower") and hasattr(evaluate, "lower")
    fn, args = driver.abstract_epoch(cell)
    assert [a.shape for a in args[1:]] == [a.shape for a in t_args[1:]]
    fn, (state, rows, zeros) = driver.abstract_step(cell)
    assert rows.shape == (2, 8192) and zeros.shape == (2,) and callable(fn)


# -- the cell through the harness, at the unit tests' size ---------------------
# val_loss_gap: three steps at 1e-4 move the validation loss by 3e-5 to 7e-5
# of itself (what stale_eval reads); the program reads 2e-7
LIMITS = {"loss_gap": 1e-4, "val_loss_gap": 1e-5, "grad_gap": 1e-3,
          "grad_worst_gap": 1e-2, "update_gap": 0.1, "routed_rows_gap": 1e-3,
          "epoch_loss_gap": 1e-4, "window_epochs_missing": 0}


def tiny_bench() -> spec.Bench:
    """The cell's files with the unit tests' sizes in the configuration's
    and the traffic's place: float32 compute, so that the limits can be
    tight enough for every control to fail them."""
    bench = spec.Bench(ROOT)
    config = {"model": {**TINY, "compute_dtype": "float32",
                        "moe_chunk_rows": 64},
              "train": {"learning_rate": 1e-4}}
    traffic = {**bench.traffic("causal-8k-resident"),
               "dataset": {"kind": "tokens", "sequences": 20, "seq_len": 32},
               "window": {"epochs": 3, "at_seconds": 0.2}}
    bench.config = lambda name: config
    bench.traffic = lambda name: traffic
    bench.limits = lambda name: dict(LIMITS)
    return bench


def _streamed(patch):
    """At the tests' size the state is streamed only if told so."""
    from robotic_discovery_platform_tpu.training import trainer

    patch.setattr(trainer, "_DEVICE_SNAPSHOT_MAX_BYTES", 1000)


@pytest.fixture(scope="module")
def traced_line():
    with pytest.MonkeyPatch.context() as patch:
        _streamed(patch)
        return json.loads(json.dumps(run.run_cell(
            tiny_bench(), CELL, 3_000_000_019, 0.2, True,
            require_chip=False)))


def test_the_harness_runs_the_cell_and_finds_it_correct(traced_line):
    line = traced_line
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 3 * 8 and line["failed"] == 0
    assert set(line["compared"]) == set(LIMITS)
    assert line["window"]["routed_rows"] > 0
    assert line["window"]["model_flops"] > 0
    assert line["window"]["eval_batches"] == 3 * 2
    assert line["window"]["batch"] == 2 and line["window"]["window_s"] > 0
    assert line["window"]["train_phase_s"] > 0
    assert line["metrics"] and "setup_s" not in line["metrics"]


def test_a_cpu_run_of_the_cell_reports_no_device_metric(traced_line):
    got = {k: v["value"] for k, v in traced_line["metrics"].items()}
    assert set(got) == {"outside_steps_share", "job_fixed_s", "recompile_s",
                        "checkpoint_stall_s", "validation_share"}
    assert 0 < got["validation_share"] < 100
    assert got["job_fixed_s"] > 0 and got["recompile_s"] == 0


def test_every_control_of_the_cell_is_caught(tmp_path, monkeypatch):
    _streamed(monkeypatch)
    bench = tiny_bench()
    row = control.read_seed(bench, CELL, 13, True, tmp_path / "work")
    assert set(row) == {"seed", "program", "int8", "capacity", "no_window",
                        "plain_rope", "stale_eval"}
    judged = control.verdicts([row], bench.limits(CELL))
    assert control.passed(judged), judged
    caught = {who: set(rows[0][2]) for who, rows in judged.items()}
    assert caught["program"] == set()
    assert "routed_rows_gap" in caught["capacity"]
    assert {"grad_gap", "update_gap"} <= caught["int8"]
    # a wrong mask or a wrong table in one kind of layer reaches the
    # gradients of attention's matrices before it reaches the loss
    assert "grad_worst_gap" in caught["no_window"]
    assert "grad_worst_gap" in caught["plain_rope"]
    assert caught["stale_eval"] == {"val_loss_gap"}


def test_the_probe_trains_on_rows_the_data_set_does_not_hold(tmp_path,
                                                             monkeypatch):
    """A row a probe step trained on must not turn up in the data set's
    validation split (the model memorises it, and whether the window's
    last save is the job's best then flips with the seed): the probe's rows
    are the first of the seeded draw, the data set's the ones after."""
    import numpy as np

    _streamed(monkeypatch)
    bench = tiny_bench()
    cell = bench.cell(CELL, 13, 0.2, tmp_path / "work")
    (tmp_path / "work").mkdir()
    job = bench.driver("retrain_causal").setup(cell)
    assert job.probe_tokens.shape == (job.n_probe, 32) == (3, 32)
    assert job.tokens.shape == (20, 32)
    np.testing.assert_array_equal(
        np.concatenate([job.probe_tokens, job.tokens]),
        cell.reference.tokens(cell.config["model"], 13, 23))
    assert len(job.produced["probe"]["loss"]) == 3
    assert len(job.produced["epoch"]["step_loss"]) == 2
