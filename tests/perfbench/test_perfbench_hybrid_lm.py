"""What the ``nemotron-twotower-30b-a3b`` configuration brought into the
benchmark, on the CPU: the configuration against the published one, the
reference's parameter count, ``lib/hybrid_lm_flops.py`` against hand counts,
the three readers on a small trace document worked by hand, and the cell end
to end through the harness at the unit tests' size (program in float32
against the reference, every control caught)."""

import json
import math
import re
import types
from pathlib import Path

import pytest

from perfbench import control, run
from perfbench.lib import hybrid_lm_flops as flops, spans as spans_lib, spec
from perfbench.lib import trace

ROOT = Path(__file__).resolve().parents[2]
CONFIG = "nemotron-twotower-30b-a3b"
CELL = "nemotron-twotower-30b-a3b.hybrid-8k-resident"
BODY = json.loads((ROOT / "perfbench" / "configs"
                   / f"{CONFIG}.json").read_text())
MODEL = BODY["model"]
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
US = 1_000          # the document's times are in ns


def test_the_configuration_is_the_published_one_cut_to_a_stage_and_a_share():
    assert BODY["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size", "hybrid_override_pattern"]
    assert BODY["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern": PATTERN}
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (
        23, 23, 6) and len(PATTERN) == 52
    # the first nine layers of the published pattern
    assert BODY["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME" \
        == MODEL["layer_pattern"]
    m = MODEL
    # every width, the heads, the state, the chunk, the convolution, the
    # router's outputs, the experts a token and the scaling: published
    assert (m["hidden_size"], m["expert_width"], m["shared_expert_width"],
            m["head_dim"], m["num_heads"], m["num_kv_heads"]) == (
        BODY["hidden_size"], BODY["moe_intermediate_size"],
        BODY["moe_shared_expert_intermediate_size"], BODY["head_dim"],
        BODY["num_attention_heads"], BODY["num_key_value_heads"]) == (
        2688, 1856, 3712, 128, 32, 2)
    assert (m["mamba_heads"], m["mamba_head_dim"], m["ssm_groups"],
            m["ssm_state"], m["conv_kernel"], m["ssm_chunk"]) == (
        BODY["mamba_num_heads"], BODY["mamba_head_dim"], BODY["n_groups"],
        BODY["ssm_state_size"], BODY["conv_kernel"], BODY["chunk_size"]) == (
        64, 64, 8, 128, 4, 128)
    assert (m["num_experts"], m["experts_per_token"],
            m["routed_scaling_factor"], m["norm_topk_prob"]) == (
        128, BODY["num_experts_per_tok"], BODY["routed_scaling_factor"],
        BODY["norm_topk_prob"]) == (128, 6, 2.5, True)
    assert (m["time_step_min"], m["time_step_max"], m["time_step_floor"],
            m["rms_norm_eps"]) == (
        BODY["time_step_min"], BODY["time_step_max"],
        BODY["time_step_floor"], BODY["layer_norm_epsilon"])
    assert (m["router_scoring"], m["expert_act"]) == ("sigmoid", "relu2")
    assert BODY["mlp_hidden_act"] == "relu2" and BODY["n_shared_experts"] == 1
    # the cut, at the guide's floors for experts and vocabulary and above
    # it for depth
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) == (
        BODY["num_hidden_layers"], BODY["n_routed_experts"],
        BODY["vocab_size"]) == (9, 8, 16384)
    assert m["vocab_size"] * 8 == 131072 and m["experts_held"] >= 8
    assert list(BODY["assumed"])[0] == "denoiser_tower"
    for word in ("denoiser_tower", "rope", "gate_norm", "router_bias",
                 "aux_loss", "init", "optimizer", "packing", "data_set"):
        assert BODY["assumed"][word]
    assert "rescale_prenorm_residual" in BODY["assumed"]["init"]
    assert "16 chips share each layer" in BODY["deployment"]
    assert "experts 0..7" in BODY["deployment"]
    assert "rows 0..16383" in BODY["deployment"]
    assert "layers 0..8" in BODY["deployment"]
    shapes = spec.Bench(ROOT).reference(CONFIG).param_shapes(m)

    def layer(j):
        return sum(math.prod(v) for k, v in shapes.items()
                   if k.startswith(f"layers/{j}/"))

    assert (layer(0), layer(1), layer(5)) == (38_744_896, 100_125_440,
                                              23_399_040)
    assert sum(math.prod(v) for v in shapes.values()) == BODY["parameters"] \
        == 4 * 38_744_896 + 4 * 100_125_440 + 23_399_040 \
        + 2 * 44_040_192 + 2_688 == 666_963_456


def test_every_published_number_is_in_the_file_under_its_own_key():
    """The catalog's ``config`` of this model, key by key: equal, or named
    in ``reduced``."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["source_url"] in BODY["source"])
    differs = {k for k, v in row["config"].items() if BODY.get(k) != v}
    assert differs == set(BODY["reduced"])


def test_operation_counts_against_a_hand_count():
    assert [flops.layers_of(MODEL, k) for k in (
        flops.MAMBA, flops.EXPERTS, flops.ATTENTION)] == [4, 4, 1]
    # one mixer, one sequence, forward: 8,192 positions through 2688 ->
    # 4096 + 6144 + 64 and 4096 -> 2688
    assert flops.mamba_projection_flops(MODEL) == 2 * 8192 * 2688 * (
        10304 + 4096)
    # the chunked scan a position: C B^T 128 x 8 x 128, its product with xs
    # 128 x 4096, the chunk's state and the carried state's part 4096 x 128
    assert flops.scan_flops(MODEL) == 2 * 8192 * (
        128 * 8 * 128 + 128 * 4096 + 2 * 4096 * 128)
    assert flops.attention_projection_flops(MODEL) == 2 * 8192 * 2688 * (
        2 * 4096 + 2 * 256)
    assert flops.attention_flops(MODEL) == 4 * 128 * 32 * (8192 * 8193 // 2)
    assert flops.dense_expert_flops(MODEL) == 2 * 8192 * 2688 * (
        128 + 2 * 3712)
    assert flops.routed_flops(MODEL, 768) == 768 * 2 * 2 * 2688 * 1856
    assert flops.head_flops(MODEL) == 2 * 8192 * 2688 * 16384
    dense = 4 * (flops.mamba_projection_flops(MODEL)
                 + flops.scan_flops(MODEL)) \
        + flops.attention_projection_flops(MODEL) \
        + flops.attention_flops(MODEL) \
        + 4 * flops.dense_expert_flops(MODEL) + flops.head_flops(MODEL)
    assert flops.dense_forward_flops(MODEL) == dense
    # the issue's count: 715 MFLOP a token forward with a sixteenth of the
    # routed load (6 x 8 / 128 rows a token and expert layer), of which the
    # four mixers are 45%
    rows = 4 * 16384 * 6 * 8 / 128
    token = (2 * dense + flops.routed_flops(MODEL, rows)) / 16384
    assert token == pytest.approx(715e6, rel=5e-3)
    mixers = 4 * (flops.mamba_projection_flops(MODEL)
                  + flops.scan_flops(MODEL)) / 8192
    assert mixers / token == pytest.approx(0.45, abs=0.005)
    step = flops.window_flops(MODEL, 2, 1, 0, rows)
    assert step == 2 * 3 * dense + 3 * flops.routed_flops(MODEL, rows)
    # validation batches are forward passes, their rows at the steps' mean
    assert flops.window_flops(MODEL, 2, 4, 2, 4000.0) == \
        2 * (3 * 4 + 2) * dense + flops.routed_flops(
            MODEL, 4000.0 * (3 + 2 / 4))
    # bytes: xs and y 8192 x 4096 x 2, B and C 8192 x 1024 x 2 each, dt
    # 8192 x 64 x 4
    xs, bc, dt = 8192 * 4096 * 2, 2 * 8192 * 1024 * 2, 8192 * 64 * 4
    assert flops.scan_bytes(MODEL, False) == 2 * xs + bc + dt
    assert flops.scan_bytes(MODEL, True) == 5 * xs + 3 * bc + 3 * dt
    # the scans of a window are bound by bytes on a v5e
    peaks = spec.Bench(ROOT).peaks("TPU v5 lite")
    assert flops.scan_least_seconds(MODEL, 2, 4, 2, peaks) == 4 * 2 * (
        4 * flops.scan_bytes(MODEL, True)
        + 2 * flops.scan_bytes(MODEL, False)) / 819e9
    assert 4 * 2 * (3 * 4 + 2) * flops.scan_flops(MODEL) / 197e12 \
        < flops.scan_least_seconds(MODEL, 2, 4, 2, peaks)


# -- the readers, on a document worked by hand --------------------------------
def _op(name, start_us, dur_us, scope):
    return [name, start_us * US, dur_us * US, {"scope": scope}]


STEP = "jit(train_epoch)/while/body/"
EVAL = "jit(eval_epoch)/while/body/rdp.eval/"
LAYER = "checkpoint/rdp.lm.layer/"
BACK = "transpose(jvp(checkpoint))/rdp.lm.layer/"
DOC = {"planes": [
    {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        _op("fusion.1 fusion", 10_000, 2_000, STEP + LAYER
            + "rdp.ssm.proj/dot_general"),
        _op("fusion.2 fusion", 13_000, 500, STEP + LAYER + "rdp.ssm.conv/mul"),
        _op("fusion.3 fusion", 14_000, 4_000, STEP + LAYER
            + "rdp.ssm.scan/dot_general"),
        _op("fusion.4 fusion", 19_000, 1_000, STEP + LAYER
            + "rdp.ssm.scan/while/body/mul"),
        _op("fusion.5 fusion", 21_000, 500, STEP + LAYER + "rdp.ssm.gate/mul"),
        _op("fusion.6 fusion", 22_000, 9_000, STEP + BACK
            + "rdp.ssm.scan/dot_general"),
        _op("fusion.7 fusion", 32_000, 3_000, STEP + BACK
            + "rdp.ssm.proj/dot_general"),
        _op("fusion.8 fusion", 36_000, 1_500, STEP + LAYER
            + "rdp.moe.route/top_k"),
        _op("custom-call.9 custom-call", 38_000, 2_500, STEP + LAYER
            + "while/body/rdp.moe.experts/jit(gmm)/pallas_call"),
        _op("fusion.10 fusion", 41_000, 3_000, STEP + LAYER
            + "rdp.moe.shared/dot_general"),
        _op("fusion.11 fusion", 45_000, 5_000, STEP + BACK
            + "rdp.moe.shared/dot_general"),
        _op("fusion.12 fusion", 51_000, 3_000, STEP + "rdp.optimizer/mul"),
        _op("fusion.13 fusion", 60_000, 2_000,
            EVAL + "rdp.lm.layer/rdp.ssm.scan/dot_general"),
        _op("fusion.14 fusion", 63_000, 1_000,
            EVAL + "rdp.lm.layer/rdp.moe.shared/dot_general"),
    ]}]},
    {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [trace.WINDOW_SPAN, 0, 100_000 * US, {}],
        ["rdp.train.job", 1_000 * US, 98_000 * US, {}],
    ]}]},
]}
COUNTERS = {"optimizer_steps": 4, "eval_batches": 2, "batch": 2,
            "routed_rows": 4 * 4 * 6144.0, "window_s": 0.1}
NEW = ("ssm_scan_roofline", "ssm_mixer_ms", "moe_block_ms")


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


def test_the_scans_roofline_reads_the_scans_scope(ctx):
    # 4 + 1 + 9 ms of training and 2 ms of evaluation under rdp.ssm.scan:
    # four layers, two sequences, 4 steps and 2 validation batches, bytes
    least = 4 * 2 * (4 * flops.scan_bytes(MODEL, True)
                     + 2 * flops.scan_bytes(MODEL, False)) / 819e9
    got = _reader("ssm_scan_roofline").read(ctx)
    assert got == pytest.approx(100.0 * least / 0.016)
    # what no implementation could pass: the least time in the time read
    ctx.counters["optimizer_steps"] = 1
    assert _reader("ssm_scan_roofline").read(ctx) < got


def test_the_mixers_time_is_all_four_scopes_a_step(ctx):
    # proj 2 + 3, conv 0.5, scan 4 + 1 + 9 + 2, gate 0.5: 22 ms, 4 steps
    assert _reader("ssm_mixer_ms").read(ctx) == pytest.approx(22.0 / 4)


def test_the_expert_blocks_time_is_route_experts_and_shared_a_step(ctx):
    # route 1.5, experts 2.5, shared 3 + 5 + 1: 13 ms, 4 steps
    assert _reader("moe_block_ms").read(ctx) == pytest.approx(13.0 / 4)


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


@pytest.mark.parametrize("other", ["sdar-30b-a3b", "mellum2-12b-a2.5b"])
def test_another_familys_program_reads_no_scan_and_no_shared_expert(
        ctx, monkeypatch, other):
    """The attention-then-experts models': routing and experts under their
    scopes, no state-space scan and no shared expert: the mixers' two read
    nothing, and the expert block's time is what those two scopes took."""
    theirs = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            _op("fusion.1 fusion", 10_000, 2_000, STEP + LAYER
                + "rdp.moe.route/top_k"),
            _op("custom-call.2 custom-call", 13_000, 2_000, STEP + LAYER
                + "while/body/rdp.moe.experts/jit(gmm)/pallas_call")]}]},
        DOC["planes"][1]]}
    monkeypatch.setattr(spans_lib, "load_xplane", lambda path: theirs)
    spans_lib._load.cache_clear()
    ctx.cell.config = {"model": spec.Bench(ROOT).config(other)["model"]}
    for metric in NEW[:2]:
        assert _reader(metric).read(ctx) is None, metric
    assert _reader("moe_block_ms").read(ctx) == pytest.approx(4.0 / 4)


def test_the_new_readers_list_the_new_cell_alone():
    bench = spec.Bench(ROOT)
    for name in NEW:
        entry = next(m for m in bench.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_img_per_s"
        assert entry["source"] == "device_trace"
    owed = {m["name"] for m in bench.doc["per_layer"]
            if bench.reports(m, CELL)}
    # the five without a list, the six generic ones of the job, compile,
    # device and optimiser layers that took the cell onto theirs, and its
    # own three; at least these, so that a later PR that puts the cell on
    # a shared reader's list needs no edit here
    assert owed >= {"outside_steps_share", "step_device_ms",
                    "device_idle_share", "peak_hbm_gib", "step_mfu",
                    "job_fixed_s", "recompile_s", "checkpoint_stall_s",
                    "validation_share", "unattributed_idle_share",
                    "optimizer_ms", *NEW}
    entry = bench.workload(CELL)
    assert (entry["chips"], entry["config"], entry["traffic"]) == (
        1, CONFIG, "hybrid-8k-resident")
    assert len(entry["why"]) <= 200
    config = next(c for c in bench.doc["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == BODY["reduced"]
    assert config["source"] in BODY["source"]
    traffic = bench.traffic("hybrid-8k-resident")
    assert traffic["driver"] == "retrain_hybrid"
    assert traffic["dataset"] == {"kind": "tokens", "sequences": 60,
                                  "seq_len": 8192}
    assert traffic["train"]["batch_size"] == 2
    assert (traffic["window"]["epochs"], traffic["window"]["at_seconds"]) \
        == (4, bench.doc["run_seconds"])
    assert set(bench.limits(CELL)) == set(LIMITS)


# -- what set-up compiles ahead, from shapes alone ------------------------------
TINY = {**MODEL, "vocab_size": 64, "hidden_size": 64, "num_layers": 8,
        "layer_pattern": "ME*E" * 2, "seq_len": 32, "mamba_heads": 4,
        "mamba_head_dim": 16, "ssm_groups": 2, "ssm_state": 16,
        "ssm_chunk": 8, "time_step_min": 0.05, "time_step_max": 0.5,
        "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "num_experts": 8,
        "experts_per_token": 2, "experts_held": 2, "expert_width": 32,
        "shared_expert_width": 64}


def test_warm_compiles_what_the_references_first_step_would(caplog):
    """``reference.warm`` compiles a forward and a backward program a layer
    kind, the head's and the per-leaf ones from shapes; the step that
    follows finds them compiled."""
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
        first = compiled()
        assert set(first) == set(heavy)
        assert (first.count("fwd"), first.count("bwd")) == (3, 3)
        params = ref.init(model, 5)
        names = set(params)
        params, opt, loss, _, taken = ref.train_step(
            model, 1e-4, 5, params, ref.adam_init(params),
            ref.tokens(model, 5, 2))
        assert compiled() == []
    assert np.isfinite(loss) and set(params) == names and opt["count"] == 1
    assert taken.shape == (4, 2)        # the expert layers alone


def test_the_epoch_programs_are_the_jobs_two_scans_at_its_shapes():
    bench = spec.Bench(ROOT)
    driver = bench.driver("retrain_hybrid")
    cell = types.SimpleNamespace(
        config=bench.config(CONFIG),
        traffic=bench.traffic("hybrid-8k-resident"))
    model_cfg, cfg, sequences = driver._abstract(cell)
    assert model_cfg.kernel_impl == "pallas" and sequences == 60
    assert model_cfg.layer_pattern == tuple(
        {"M": "mamba", "E": "experts", "*": "attention"}[c]
        for c in "MEMEM*EME")
    assert model_cfg.conv_dim == 6144 and model_cfg.mamba_inner == 4096
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
    for name in ("setup", "window", "end_to_end", "counters", "check",
                 "follow", "readings", "controls", "abstract_step"):
        assert callable(getattr(driver, name)), name


# -- the cell through the harness, at the unit tests' size ---------------------
# val_loss_gap: three steps at 1e-4 move the validation loss by 3e-5 to 1e-4
# of itself (what stale_eval reads); the program reads 1e-7. update_gap and
# grad_worst_gap: the program reads 2e-7 of either, the decays' sums in
# bfloat16 5e-5 and 8e-5 (A_log's moment, the convolution's change: a leaf's
# gap is between two norms, which a few percent in some decays move little).
# scan_decay_gap, the scan alone by the gradients of dt_bias and A_log as
# vectors: the program in float32 reads 1e-6 (the order of its sums), the
# decays' sums in bfloat16 a few percent
LIMITS = {"loss_gap": 1e-4, "val_loss_gap": 1e-5, "grad_gap": 1e-3,
          "grad_worst_gap": 1e-5, "update_gap": 1e-5, "routed_rows_gap": 1e-3,
          "epoch_loss_gap": 1e-4, "scan_decay_gap": 1e-4,
          "epochs_missing": 0, "window_epochs_missing": 0}
CONTROLS = ("int8", "decay_bf16", "no_shared", "no_conv_bias",
            "softmax_router", "stale_eval", "epoch_fewer")


def tiny_bench() -> spec.Bench:
    """The cell's files with the unit tests' sizes in the configuration's
    and the traffic's place: float32 compute, so that the limits can be
    tight enough for every control to fail them."""
    bench = spec.Bench(ROOT)
    config = {"model": {**TINY, "compute_dtype": "float32",
                        "moe_chunk_rows": 64},
              "train": {"learning_rate": 1e-4}}
    traffic = {**bench.traffic("hybrid-8k-resident"),
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
    assert set(row) == {"seed", "program", *CONTROLS}
    judged = control.verdicts([row], bench.limits(CELL))
    assert control.passed(judged), judged
    caught = {who: set(rows[0][2]) for who, rows in judged.items()}
    assert caught["program"] == set()
    assert {"grad_gap", "update_gap"} <= caught["int8"]
    # a sum of decays kept in bfloat16 reaches the mixer's own leaves
    assert {"scan_decay_gap", "grad_worst_gap"} <= caught["decay_bf16"]
    assert "grad_worst_gap" in caught["no_shared"]
    assert {"loss_gap", "grad_worst_gap"} <= caught["no_conv_bias"]
    assert "grad_worst_gap" in caught["softmax_router"]
    assert caught["stale_eval"] == {"val_loss_gap"}
    assert caught["epoch_fewer"] == {"epochs_missing"}


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_the_scan_alone_is_held_to_the_recurrence_alone(seed):
    """``scan_decay_gap``'s two sides: the program's chunked scan on the
    reference's inputs of the seed gives the two decay leaves the gradients
    the recurrence gives them, to the order of its sums in float32 and to
    bfloat16's mantissa in bfloat16; the recurrence with its decays' sums
    kept in bfloat16 is further off than either, and the inputs are the
    seed's."""
    import types

    import numpy as np

    from perfbench.drivers import retrain_hybrid
    from robotic_discovery_platform_tpu.utils.config import (
        HybridLMConfig, from_dict)

    ref = spec.Bench(ROOT).reference(CONFIG)
    cell = types.SimpleNamespace(reference=ref, config={"model": TINY},
                                 seed=seed)
    want = ref.scan_decay_grads(TINY, seed)
    assert {k: v.shape for k, v in want.items()} == {
        "dt_bias": (4,), "A_log": (4,)}

    def apart(got):
        return max(np.linalg.norm(got[k] - want[k])
                   / np.linalg.norm(want[k]) for k in want)

    program = {dtype: apart(retrain_hybrid.scan_alone(cell, from_dict(
        HybridLMConfig, {**TINY, "compute_dtype": dtype})))
        for dtype in ("float32", "bfloat16")}
    planted = apart(ref.scan_decay_grads(TINY, seed, "decay_bf16"))
    assert program["float32"] < 1e-5 < program["bfloat16"] < 1e-2 < planted
    assert apart(ref.scan_decay_grads(TINY, seed + 1)) > 0.1
    drawn = ref.scan_check_inputs(MODEL, seed)
    assert drawn["xs"].shape == (ref.SCAN_CHECK_LENGTH, 64, 64)
    assert drawn["b"].shape == drawn["c"].shape == (1024, 8, 128)


def test_the_probe_trains_on_rows_the_data_set_does_not_hold(tmp_path,
                                                             monkeypatch):
    import numpy as np

    _streamed(monkeypatch)
    bench = tiny_bench()
    cell = bench.cell(CELL, 13, 0.2, tmp_path / "work")
    (tmp_path / "work").mkdir()
    job = bench.driver("retrain_hybrid").setup(cell)
    assert job.probe_tokens.shape == (job.n_probe, 32) == (3, 32)
    assert job.tokens.shape == (20, 32)
    np.testing.assert_array_equal(
        np.concatenate([job.probe_tokens, job.tokens]),
        cell.reference.tokens(cell.config["model"], 13, 23))
    assert len(job.produced["probe"]["loss"]) == 3
    assert len(job.produced["epoch"]["step_loss"]) == 2
