"""What the ``sdar-30b-a3b`` configuration brought into the benchmark, on
the CPU: the reference's parameter count, ``lib/lm_flops.py`` against hand
counts, the four readers on a small trace document worked by hand, and the
cell end to end through the harness at the unit tests' size (program in
float32 against the reference, every control caught)."""

import json
import re
import types
from pathlib import Path

import pytest

from perfbench import control, run
from perfbench.lib import lm_flops, spans as spans_lib, spec, trace

ROOT = Path(__file__).resolve().parents[2]
CELL = "sdar-30b-a3b.blockdiff-4k-resident"
MODEL = json.loads((ROOT / "perfbench" / "configs"
                    / "sdar-30b-a3b.json").read_text())["model"]
US = 1_000          # the document's times are in ns


def test_the_configuration_is_the_published_one_cut_three_ways():
    body = json.loads((ROOT / "perfbench" / "configs"
                       / "sdar-30b-a3b.json").read_text())
    assert body["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    # the widths, the router's outputs and the experts a token: published
    m = body["model"]
    assert (m["hidden_size"], m["expert_width"], m["head_dim"],
            m["num_heads"], m["num_kv_heads"]) == (
        body["hidden_size"], body["moe_intermediate_size"], body["head_dim"],
        body["num_attention_heads"], body["num_key_value_heads"])
    assert (m["num_experts"], m["experts_per_token"]) == (
        128, body["num_experts_per_tok"])
    # the cut, inside the guide's floors
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) == (
        body["num_hidden_layers"], body["num_experts"], body["vocab_size"])
    assert m["num_layers"] >= 4 and m["experts_held"] >= 8
    assert m["vocab_size"] * 8 >= 151936
    shapes = spec.Bench(ROOT).reference("sdar-30b-a3b").param_shapes(m)
    per_layer = sum(
        __import__("math").prod(v[1:]) for k, v in shapes.items()
        if k.startswith("layers/"))
    assert per_layer == 94_638_336
    assert body["parameters"] == 6 * per_layer + 2 * 38_895_616 + 2_048 \
        == 645_623_296


@pytest.mark.parametrize("length,block", [(4096, 4), (32, 4), (24, 8)])
def test_live_pairs_are_l_squared_plus_l_b(length, block):
    assert lm_flops.live_pairs(length, block) == length * length \
        + length * block


def test_operation_counts_against_a_hand_count():
    assert lm_flops.live_pairs(4096, 4) == 16_793_600
    # one layer, one sequence, forward: 8,192 positions through q and o
    # (2048 x 4096 each), k and v (2048 x 512 each) and the router
    assert lm_flops.projection_flops(MODEL) == 2 * 8192 * 2048 * (
        2 * 4096 + 2 * 512 + 128)
    assert lm_flops.attention_flops(MODEL) == 4 * 128 * 32 * 16_793_600
    assert lm_flops.head_flops(MODEL) == 2 * 4096 * 2048 * 18992
    assert lm_flops.expert_flops(MODEL, 1024) == 1024 * 3 * 2 * 2048 * 768
    dense = 6 * (lm_flops.projection_flops(MODEL)
                 + lm_flops.attention_flops(MODEL)) \
        + lm_flops.head_flops(MODEL)
    assert lm_flops.dense_forward_flops(MODEL) == dense
    # a step of 2 sequences whose experts take 16,384 rows a layer: the
    # issue's 25.9 TFLOP
    step = lm_flops.window_flops(MODEL, 2, 1, 0, 6 * 16384)
    assert step == 2 * 3 * dense + 3 * lm_flops.expert_flops(
        MODEL, 6 * 16384)
    assert step == pytest.approx(25.9e12, rel=2e-3)
    # validation batches are forward passes, their rows at the steps' mean
    assert lm_flops.window_flops(MODEL, 2, 4, 2, 4000.0) == \
        2 * (3 * 4 + 2) * dense + lm_flops.expert_flops(
            MODEL, 4000.0 * (3 + 2 / 4))
    # bytes: q and o are 8192 x 32 x 128 x 2, k and v 8192 x 4 x 128 x 2
    q, kv = 8192 * 4096 * 2, 8192 * 512 * 2
    assert lm_flops.attention_bytes(MODEL, False) == 2 * q + 2 * kv
    assert lm_flops.attention_bytes(MODEL, True) == 6 * q + 6 * kv
    assert lm_flops.expert_bytes(MODEL, 1, 0) == 16 * 3 * 2048 * 768 * 2


# -- the readers, on a document worked by hand --------------------------------
# window [0, 100 ms); one training program whose operations sit under the new
# scopes (forward, and backward under JAX's transpose(jvp(...)) prefix), one
# evaluation program; the job's phases on the main thread
def _op(name, start_us, dur_us, scope):
    return [name, start_us * US, dur_us * US, {"scope": scope}]


STEP = "jit(train_epoch)/while/body/"
DOC = {"planes": [
    {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        _op("custom-call.1 custom-call", 10_000, 6_000, STEP
            + "jvp(rdp.lm.layer)/rdp.attn.blockdiff/pallas_call"),
        _op("custom-call.2 custom-call", 20_000, 14_000, STEP
            + "transpose(jvp(rdp.lm.layer))/rdp.attn.blockdiff/pallas_call"),
        _op("custom-call.3 custom-call", 40_000, 2_000, STEP
            + "jvp(rdp.lm.layer)/rdp.moe.experts/pallas_call"),
        _op("fusion.4 fusion", 42_000, 500, STEP
            + "jvp(rdp.lm.layer)/rdp.moe.experts/convert_element_type"),
        _op("fusion.5 fusion", 43_000, 1_500, STEP
            + "jvp(rdp.lm.layer)/rdp.moe.route/sort"),
        _op("fusion.6 fusion", 45_000, 2_500, STEP
            + "transpose(jvp(rdp.lm.layer))/rdp.moe.route/scatter-add"),
        _op("fusion.7 fusion", 50_000, 3_000, STEP + "rdp.optimizer/mul"),
        _op("custom-call.8 custom-call", 60_000, 4_000,
            "jit(eval_epoch)/while/body/rdp.eval/rdp.lm.layer/"
            "rdp.attn.blockdiff/pallas_call"),
    ]}]},
    {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [trace.WINDOW_SPAN, 0, 100_000 * US, {}],
        ["rdp.train.job", 1_000 * US, 98_000 * US, {}],
        ["rdp.train.restore", 2_000 * US, 5_000 * US, {}],
        ["rdp.train.epoch", 8_000 * US, 60_000 * US, {"epoch": "4"}],
        ["rdp.train.checkpoint.wait", 66_000 * US, 100 * US, {}],
        ["rdp.train.checkpoint.snapshot", 66_100 * US, 1_900 * US, {}],
        ["rdp.train.register", 70_000 * US, 4_000 * US, {}],
        ["rdp.train.flush", 80_000 * US, 9_000 * US, {}],
    ]}, {"name": "checkpoint-save", "events": [
        ["rdp.train.checkpoint.write", 68_000 * US, 20_000 * US, {}],
    ]}]},
]}
COUNTERS = {"optimizer_steps": 4, "eval_batches": 2, "batch": 2,
            "routed_rows": 4 * 6 * 16384.0, "window_s": 0.1}


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


def test_attn_roofline_reads_forward_and_backward_under_its_scope(ctx):
    # 6 + 14 ms of training, 4 ms of evaluation under rdp.attn.blockdiff
    flops = 6 * 2 * (3 * 4 + 2) * lm_flops.attention_flops(MODEL)
    want = 100.0 * (flops / 197e12) / 0.024
    assert _reader("attn_roofline").read(ctx) == pytest.approx(want)


def test_expert_gmm_roofline_reads_the_rows_the_program_counted(ctx):
    rows = COUNTERS["routed_rows"] * (3 + 2 / 4)
    want = 100.0 * (lm_flops.expert_flops(MODEL, rows) / 197e12) / 0.0025
    assert _reader("expert_gmm_roofline").read(ctx) == pytest.approx(want)
    ctx.counters.pop("routed_rows")
    assert _reader("expert_gmm_roofline").read(ctx) is None


def test_moe_route_ms_is_device_time_a_step(ctx):
    assert _reader("moe_route_ms").read(ctx) == pytest.approx(4.0 / 4)


def test_state_io_s_sums_the_main_threads_phases(ctx):
    # restore 5 + wait 0.1 + snapshot 1.9 + register 4 + flush 9 ms; the
    # writer thread's 20 ms are not the job's
    assert _reader("state_io_s").read(ctx) == pytest.approx(0.020)


@pytest.mark.parametrize("metric", ["attn_roofline", "expert_gmm_roofline",
                                    "moe_route_ms", "state_io_s"])
def test_a_program_without_the_scopes_reads_nothing(ctx, monkeypatch,
                                                    metric):
    """As the parent commit's traced run of another family's cell: no span,
    no scope, no counter of this family, and no error."""
    empty = {"planes": [{"name": "/host:CPU", "lines": [{
        "name": "python3",
        "events": [[trace.WINDOW_SPAN, 0, 100_000 * US, {}]]}]}]}
    monkeypatch.setattr(spans_lib, "load_xplane", lambda path: empty)
    spans_lib._load.cache_clear()
    ctx.counters = {"optimizer_steps": 4, "window_s": 0.1}
    ctx.cell.config = {"model": {"base_features": 64}}
    assert _reader(metric).read(ctx) is None


def test_the_new_readers_list_the_new_cell_alone():
    bench = spec.Bench(ROOT)
    for name in ("attn_roofline", "expert_gmm_roofline", "moe_route_ms",
                 "state_io_s"):
        entry = next(m for m in bench.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_img_per_s"
    owed = {m["name"] for m in bench.doc["per_layer"]
            if bench.reports(m, CELL)}
    # the five without a list, the six of the job, compile, device and
    # optimiser layers that took the cell onto theirs, and its own four
    assert owed == {"outside_steps_share", "step_device_ms",
                    "device_idle_share", "peak_hbm_gib", "step_mfu",
                    "job_fixed_s", "recompile_s", "checkpoint_stall_s",
                    "validation_share", "unattributed_idle_share",
                    "optimizer_ms",
                    "attn_roofline", "expert_gmm_roofline", "moe_route_ms",
                    "state_io_s"}


# -- what set-up compiles ahead, from shapes alone ------------------------------
TINY = {**MODEL, "vocab_size": 64, "hidden_size": 64, "num_layers": 2,
        "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "num_experts": 8,
        "experts_per_token": 2, "experts_held": 2, "expert_width": 32,
        "seq_len": 32, "mask_token_id": 63}


def test_warm_compiles_what_the_references_first_step_would(caplog):
    """``reference.warm`` compiles the layer's two programs, the head's and
    the per-leaf ones from shapes; the step that follows finds them compiled
    (JAX keeps the executable with the lowering it was made from)."""
    import logging

    import jax
    import numpy as np

    ref = spec.Bench(ROOT).reference("sdar-30b-a3b")
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
    assert taken.shape == (2, 2)

def test_the_epoch_programs_are_the_jobs_two_scans_at_its_shapes():
    """What set-up compiles beside the probe: the first epoch's two scans
    as ``trainer.make_epoch_runners`` builds them, over the two splits of
    the cell's 60 sequences."""
    bench = spec.Bench(ROOT)
    driver = bench.driver("retrain_lm")
    cell = types.SimpleNamespace(
        config=bench.config("sdar-30b-a3b"),
        traffic=bench.traffic("blockdiff-4k-resident"))
    (train, t_args), (evaluate, e_args) = driver._epoch_programs(
        *driver._abstract(cell))
    assert [a.shape for a in t_args[1:]] == [(48, 4096), (48,), (24, 2)]
    assert [a.shape for a in e_args[1:]] == [(12, 4096), (12,), (6, 2)]
    assert {a.dtype.name for a in t_args[1:] + e_args[1:]} == {"int32"}
    assert hasattr(train, "lower") and hasattr(evaluate, "lower")
    # abstract_epoch is the first of the two
    fn, args = driver.abstract_epoch(cell)
    assert [a.shape for a in args[1:]] == [a.shape for a in t_args[1:]]


# -- the cell through the harness, at the unit tests' size ---------------------
LIMITS = {"loss_gap": 1e-4, "val_loss_gap": 1e-4, "grad_gap": 1e-3,
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
    traffic = {**bench.traffic("blockdiff-4k-resident"),
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


def test_a_cpu_run_of_the_cell_reports_no_device_metric(traced_line):
    # counters and spans read on any platform (the job's own readers find
    # this family's phases as they find a U-Net's); no device time on a CPU
    got = {k: v["value"] for k, v in traced_line["metrics"].items()}
    assert set(got) == {"outside_steps_share", "state_io_s", "job_fixed_s",
                        "recompile_s", "checkpoint_stall_s",
                        "validation_share"}
    # the save's stalls are part of the state's traffic, which is part of
    # the job's work outside its epochs
    assert 0 < got["checkpoint_stall_s"] < got["state_io_s"]
    assert 0 < got["validation_share"] < 100
    assert got["job_fixed_s"] > 0 and got["recompile_s"] == 0


def test_every_control_of_the_cell_is_caught(tmp_path, monkeypatch):
    _streamed(monkeypatch)
    bench = tiny_bench()
    # a seed whose first step overfills an expert at a capacity factor of 1
    row = control.read_seed(bench, CELL, 13, True, tmp_path / "work")
    assert set(row) == {"seed", "program", "int8", "capacity", "causal",
                        "stale_eval"}
    judged = control.verdicts([row], bench.limits(CELL))
    assert control.passed(judged), judged
    caught = {who: set(rows[0][2]) for who, rows in judged.items()}
    assert caught["program"] == set()
    assert "routed_rows_gap" in caught["capacity"]
    # with the embedding leading the residual stream (the configuration's
    # embed_init_std) the loss hardly feels the mask; the gradients of
    # attention's matrices and the updates they drive do
    assert {"grad_worst_gap", "update_gap"} <= caught["causal"]
    assert {"grad_gap", "update_gap"} <= caught["int8"]
    assert caught["stale_eval"] == {"val_loss_gap"}
