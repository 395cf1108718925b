"""What the ``lfm2-8b-a1b`` configuration brought into the benchmark, on the
CPU: the configuration against the published one, the reference's parameter
count, ``lib/shortconv_lm_flops.py`` against hand counts, the three readers
on a small trace document worked by hand, the operator alone, and the cell
end to end through the harness at the unit tests' size (program in float32
against the reference, every control caught)."""

import json
import math
import types
from pathlib import Path

import pytest

from perfbench import control, run
from perfbench.lib import shortconv_lm_flops as flops, spans as spans_lib
from perfbench.lib import compare, spec, trace

ROOT = Path(__file__).resolve().parents[2]
CONFIG = "lfm2-8b-a1b"
CELL = "lfm2-8b-a1b.shortconv-8k-resident"
TRAFFIC = "shortconv-8k-resident"
BODY = json.loads((ROOT / "perfbench" / "configs"
                   / f"{CONFIG}.json").read_text())
MODEL = BODY["model"]
TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4 \
    + ["full_attention", "conv", "conv"] * 2
US = 1_000          # the document's times are in ns


def test_the_configuration_is_the_published_one_cut_to_a_stage_and_a_share():
    assert BODY["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "layer_types"]
    assert BODY["published"] == {
        "num_hidden_layers": 24, "num_experts": 32, "vocab_size": 65536,
        "layer_types": TYPES}
    assert len(TYPES) == 24 and TYPES.count("full_attention") == 6
    # the first seven layers of the published list, two branches each
    assert BODY["layer_types"] == TYPES[:7] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention"]
    dense = BODY["num_dense_layers"]
    letters = "".join(
        ("*" if kind == "full_attention" else "c")
        + ("m" if i < dense else "E") for i, kind in enumerate(TYPES[:7]))
    assert letters == MODEL["layer_pattern"] == "cmcm*EcEcEcE*E"
    m = MODEL
    assert m["num_layers"] == 2 * BODY["num_hidden_layers"] == 14
    # every width, the heads and their size, the taps, theta, eps, the
    # router's outputs, the experts a token and the scaling: published
    assert (m["hidden_size"], m["mlp_width"], m["expert_width"],
            m["num_heads"], m["num_kv_heads"]) == (
        BODY["hidden_size"], BODY["intermediate_size"],
        BODY["moe_intermediate_size"], BODY["num_attention_heads"],
        BODY["num_key_value_heads"]) == (2048, 7168, 1792, 32, 8)
    assert m["head_dim"] == BODY["hidden_size"] // BODY[
        "num_attention_heads"] == 64
    assert (m["shortconv_kernel"], m["rope_theta"], m["rms_norm_eps"]) == (
        BODY["conv_L_cache"], BODY["rope_theta"], BODY["norm_eps"]) == (
        3, 1e6, 1e-5)
    assert (m["num_experts"], m["experts_per_token"],
            m["routed_scaling_factor"], m["norm_topk_prob"]) == (
        32, BODY["num_experts_per_tok"], BODY["routed_scaling_factor"],
        BODY["norm_topk_prob"]) == (32, 4, 1, True)
    assert (m["router_scoring"], m["expert_act"], m["shared_expert_width"],
            m["router_norm_eps"], m["qk_norm"], m["tie_embeddings"]) == (
        "sigmoid", "swiglu", 0, 1e-6, True, True)
    assert BODY["use_expert_bias"] is True and BODY["conv_bias"] is False
    # the cut, at the guide's floors for experts and above them for depth
    # and vocabulary: a whole period, five layers after the dense ones
    assert (m["experts_held"], m["vocab_size"]) == (
        BODY["num_experts"], BODY["vocab_size"]) == (8, 16384)
    assert m["vocab_size"] * 4 == 65536 and m["experts_held"] * 4 == 32
    assert list(BODY["assumed"])[0] == "tie_word_embeddings"
    for word in ("tie_word_embeddings", "expert_block", "expert_bias",
                 "dense_mlp", "operator", "aux_loss", "loss", "optimizer",
                 "init", "packing", "data_set"):
        assert BODY["assumed"][word], word
    for said in ("first loss", "rows"):
        assert said in BODY["assumed"]["init"]
    assert "4 chips share each layer" in BODY["deployment"]
    assert "experts 0..7" in BODY["deployment"]
    assert "rows 0..16383" in BODY["deployment"]
    assert "layers 0..6" in BODY["deployment"]
    shapes = spec.Bench(ROOT).reference(CONFIG).param_shapes(m)
    assert "head" not in shapes

    def layer(j):
        return sum(math.prod(v) for k, v in shapes.items()
                   if k.startswith(f"layers/{j}/"))

    # conv operator, dense MLP, attention, expert layer with 8 held
    assert (layer(0), layer(1), layer(4), layer(5)) == (
        16_785_408, 44_042_240, 10_487_936, 88_148_000)
    assert sum(math.prod(v) for v in shapes.values()) == BODY["parameters"] \
        == 5 * 16_785_408 + 2 * 10_487_936 + 2 * 44_042_240 \
        + 5 * 88_148_000 + 33_554_432 + 2_048 == 667_283_872


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


def test_operation_and_byte_counts_against_a_hand_count():
    assert [flops.layers_of(MODEL, k) for k in (
        flops.SHORTCONV, flops.MLP, flops.ATTENTION, flops.EXPERTS)] == [
        5, 2, 2, 5]
    # one operator, one sequence, forward: 2048 -> 6144 and 2048 -> 2048
    assert flops.shortconv_projection_flops(MODEL) == 2 * 8192 * 2048 * 8192
    assert flops.mlp_flops(MODEL) == 3 * 2 * 8192 * 2048 * 7168
    assert flops.attention_projection_flops(MODEL) == 2 * 8192 * 2048 * (
        2 * 2048 + 2 * 512)
    assert flops.attention_flops(MODEL) == 4 * 64 * 32 * (8192 * 8193 // 2)
    assert flops.router_flops(MODEL) == 2 * 8192 * 2048 * 32
    assert flops.routed_flops(MODEL, 2048) == 2048 * 3 * 2 * 2048 * 1792
    assert flops.head_flops(MODEL) == 2 * 8192 * 2048 * 16384
    dense = 5 * flops.shortconv_projection_flops(MODEL) \
        + 2 * flops.mlp_flops(MODEL) \
        + 2 * (flops.attention_projection_flops(MODEL)
               + flops.attention_flops(MODEL)) \
        + 5 * flops.router_flops(MODEL) + flops.head_flops(MODEL)
    assert flops.dense_forward_flops(MODEL) == dense
    # the issue's count a token, forward: the operators 168 M, the dense
    # MLPs 176, the experts 110 at a quarter of their load (4 x 8 / 32 rows
    # a token and expert layer), attention 109, the head 67
    per_token = {
        "conv": 5 * flops.shortconv_projection_flops(MODEL) / 8192,
        "mlp": 2 * flops.mlp_flops(MODEL) / 8192,
        "experts": 5 * flops.routed_flops(MODEL, 1.0),
        "attention": 2 * (flops.attention_projection_flops(MODEL)
                          + flops.attention_flops(MODEL)) / 8192,
        "head": flops.head_flops(MODEL) / 8192}
    assert {k: round(v / 1e6) for k, v in per_token.items()} == {
        "conv": 168, "mlp": 176, "experts": 110, "attention": 109,
        "head": 67}
    rows = 5 * 16384 * 4 * 8 / 32
    step = flops.window_flops(MODEL, 2, 1, 0, rows)
    assert step == 2 * 3 * dense + 3 * flops.routed_flops(MODEL, rows)
    assert step == pytest.approx(3.1e13, rel=0.01)
    # the two new layer kinds are a little over half the step
    new = 16384 * 3 * (per_token["conv"] + per_token["mlp"])
    assert 0.5 < new / step < 0.6
    # validation batches are forward passes, their rows at the steps' mean
    assert flops.window_flops(MODEL, 2, 4, 2, 4000.0) == \
        2 * (3 * 4 + 2) * dense + flops.routed_flops(
            MODEL, 4000.0 * (3 + 2 / 4))
    # the operator's roof is its projections' products: five layers, two
    # sequences, three passes a step and one a validation batch
    peaks = spec.Bench(ROOT).peaks("TPU v5 lite")
    assert flops.shortconv_least_seconds(MODEL, 2, 4, 2, peaks) == \
        5 * 2 * (3 * 4 + 2) * 2 * 8192 * 2048 * 8192 / 197e12
    # gate, taps and gate as a pass of their own over HBM: B, C, x' read
    # and y written, 8192 x 2048 x 2 bytes each; backward reads those and
    # dy and writes three gradients. At the chip's peak they take a tenth
    # of the products' time, so they set no roof
    one = 8192 * 2048 * 2
    mix = 5 * 2 * (4 * 11 * one + 2 * 4 * one) / 819e9
    assert 0.08 < mix / flops.shortconv_least_seconds(
        MODEL, 2, 4, 2, peaks) < 0.12


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
            + "rdp.shortconv.proj/dot_general"),
        _op("fusion.2 fusion", 13_000, 500, STEP + LAYER
            + "rdp.shortconv.mix/mul"),
        _op("fusion.3 fusion", 14_000, 1_500, STEP + BACK
            + "rdp.shortconv.mix/mul"),
        _op("fusion.4 fusion", 16_000, 4_000, STEP + BACK
            + "rdp.shortconv.proj/dot_general"),
        _op("fusion.5 fusion", 21_000, 3_000, STEP + LAYER
            + "rdp.mlp/dot_general"),
        _op("fusion.6 fusion", 25_000, 6_000, STEP + BACK
            + "rdp.mlp/dot_general"),
        _op("custom-call.7 custom-call", 32_000, 2_000, STEP + LAYER
            + "rdp.attn.causal/pallas_call"),
        _op("custom-call.8 custom-call", 35_000, 5_000, STEP + BACK
            + "rdp.attn.causal/pallas_call"),
        _op("fusion.9 fusion", 41_000, 1_000, STEP + LAYER
            + "rdp.attn.proj/dot_general"),
        _op("fusion.10 fusion", 43_000, 1_500, STEP + LAYER
            + "rdp.moe.route/top_k"),
        _op("fusion.11 fusion", 51_000, 3_000, STEP + "rdp.optimizer/mul"),
        _op("fusion.12 fusion", 60_000, 500,
            EVAL + "rdp.lm.layer/rdp.shortconv.mix/mul"),
        _op("fusion.13 fusion", 61_000, 1_000,
            EVAL + "rdp.lm.layer/rdp.mlp/dot_general"),
        _op("custom-call.14 custom-call", 63_000, 1_000,
            EVAL + "rdp.lm.layer/rdp.attn.causal/pallas_call"),
    ]}]},
    {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [trace.WINDOW_SPAN, 0, 100_000 * US, {}],
        ["rdp.train.job", 1_000 * US, 98_000 * US, {}],
    ]}]},
]}
COUNTERS = {"optimizer_steps": 4, "eval_batches": 2, "batch": 2,
            "routed_rows": 4 * 5 * 2048.0, "window_s": 0.1}
NEW = ("shortconv_mixer_ms", "shortconv_mixer_roofline", "dense_mlp_ms")


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


def test_the_operators_time_is_both_scopes_a_step(ctx):
    # proj 2 + 4, mix 0.5 + 1.5 + 0.5: 8.5 ms, 4 steps
    assert _reader("shortconv_mixer_ms").read(ctx) == pytest.approx(8.5 / 4)


def test_the_operators_roofline_is_its_products_over_both_scopes(
        ctx, monkeypatch):
    # the 8.5 ms that hold the whole operator, wherever a fused part is
    # booked: five layers, two sequences, 3 x 4 + 2 passes of the two
    # projections at peak FLOP/s
    least = 5 * 2 * 14 * 2 * 8192 * 2048 * 8192 / 197e12
    got = _reader("shortconv_mixer_roofline").read(ctx)
    assert got == pytest.approx(100.0 * least / 0.0085)
    # time moved from one scope to the other leaves the share as it is
    mix = DOC["planes"][0]["lines"][0]["events"][1]
    moved = [mix[0], mix[1], mix[2], {"scope": mix[3]["scope"].replace(
        "rdp.shortconv.mix", "rdp.shortconv.proj")}]
    doc = json.loads(json.dumps(DOC))
    doc["planes"][0]["lines"][0]["events"][1] = moved
    spans_lib._load.cache_clear()
    monkeypatch.setattr(spans_lib, "load_xplane", lambda path: doc)
    assert _reader("shortconv_mixer_roofline").read(ctx) == pytest.approx(
        got)


def test_the_dense_mlps_time_is_its_scope_a_step(ctx):
    # 3 + 6 + 1 ms, 4 steps
    assert _reader("dense_mlp_ms").read(ctx) == pytest.approx(10.0 / 4)


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


@pytest.mark.parametrize("other", ["sdar-30b-a3b", "mellum2-12b-a2.5b",
                                   "nemotron-twotower-30b-a3b"])
def test_another_familys_program_reads_none_of_the_three(ctx, monkeypatch,
                                                         other):
    """The other language models' traces hold no short convolution and no
    dense MLP: the three read nothing there."""
    theirs = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            _op("custom-call.1 custom-call", 10_000, 2_000, STEP + LAYER
                + "rdp.attn.causal/pallas_call"),
            _op("fusion.2 fusion", 13_000, 2_000, STEP + LAYER
                + "rdp.moe.shared/dot_general"),
            _op("fusion.3 fusion", 16_000, 2_000, STEP + LAYER
                + "rdp.ssm.conv/mul")]}]},
        DOC["planes"][1]]}
    monkeypatch.setattr(spans_lib, "load_xplane", lambda path: theirs)
    spans_lib._load.cache_clear()
    ctx.cell.config = {"model": spec.Bench(ROOT).config(other)["model"]}
    for metric in NEW:
        assert _reader(metric).read(ctx) is None, metric


def test_the_new_readers_list_the_new_cell_alone():
    bench = spec.Bench(ROOT)
    for name in NEW:
        entry = next(m for m in bench.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_img_per_s"
        assert entry["source"] == "device_trace"
        assert entry["unit"] == ("%" if name.endswith("_roofline") else "ms")
    owed = {m["name"] for m in bench.doc["per_layer"]
            if bench.reports(m, CELL)}
    # the five without a list, the six generic ones of the job, compile,
    # device and optimiser layers that took the cell onto theirs, and its
    # own three; at least these, so that a later PR that puts the cell on a
    # shared reader's list needs no edit here
    assert owed >= {"outside_steps_share", "step_device_ms",
                    "device_idle_share", "peak_hbm_gib", "step_mfu",
                    "job_fixed_s", "recompile_s", "checkpoint_stall_s",
                    "validation_share", "unattributed_idle_share",
                    "optimizer_ms", *NEW}
    entry = bench.workload(CELL)
    assert (entry["chips"], entry["config"], entry["traffic"]) == (
        1, CONFIG, TRAFFIC)
    assert len(entry["why"]) <= 200
    config = next(c for c in bench.doc["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == BODY["reduced"]
    assert config["source"] in BODY["source"]
    traffic = bench.traffic(TRAFFIC)
    assert traffic["driver"] == "retrain_shortconv"
    assert traffic["dataset"] == {"kind": "tokens", "sequences": 60,
                                  "seq_len": 8192}
    assert traffic["train"]["batch_size"] == 2
    assert (traffic["window"]["epochs"], traffic["window"]["at_seconds"]) \
        == (4, bench.doc["run_seconds"])
    assert set(bench.limits(CELL)) == set(LIMITS)


# -- what set-up compiles ahead, from shapes alone ------------------------------
TINY = {**MODEL, "vocab_size": 64, "hidden_size": 64, "num_layers": 8,
        "layer_pattern": "cm*EcEcE", "seq_len": 32, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "rope_theta": 100.0,
        "mlp_width": 128, "num_experts": 8, "experts_per_token": 2,
        "experts_held": 2, "expert_width": 32}


def test_the_epoch_programs_are_the_jobs_two_scans_at_its_shapes():
    bench = spec.Bench(ROOT)
    driver = bench.driver("retrain_shortconv")
    cell = types.SimpleNamespace(config=bench.config(CONFIG),
                                 traffic=bench.traffic(TRAFFIC))
    model_cfg, cfg, sequences = driver._abstract(cell)
    assert model_cfg.kernel_impl == "pallas" and sequences == 60
    assert model_cfg.layer_pattern == tuple(
        {"c": "shortconv", "m": "mlp", "E": "experts", "*": "attention"}[c]
        for c in "cmcm*EcEcEcE*E")
    assert model_cfg.tie_embeddings and model_cfg.qk_norm
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
    # one leaf for the embedding and the head, its moments with it
    assert set(state.params) == {"embed", "layers", "final_norm"}
    assert state.params["embed"].shape == (16384, 2048)
    assert set(state.opt_state[0].mu) == set(state.params)
    import jax

    assert sum(math.prod(a.shape) for a in jax.tree.leaves(state.params)) \
        == BODY["parameters"]
    for name in ("setup", "window", "end_to_end", "counters", "check",
                 "follow", "readings", "controls", "abstract_step"):
        assert callable(getattr(driver, name)), name


# -- the cell through the harness, at the unit tests' size ---------------------
# val_loss_gap: the program reads 1e-7, a tap ahead 7e-5. update_gap and
# grad_worst_gap: the program reads 4e-7 and 9e-8, the convolution's sums in
# bfloat16 1.9e-4 and 1.2e-4, the head's gradient kept from the embedding
# 0.18 and 0.23. shortconv_gap, the operator alone by the gradient of its
# taps as a vector: the program in float32 reads 0 (the same sums in the
# same order), the sums in bfloat16 2e-3
LIMITS = {"loss_gap": 1e-4, "val_loss_gap": 1e-5, "grad_gap": 1e-3,
          "grad_worst_gap": 1e-5, "update_gap": 1e-5, "routed_rows_gap": 1e-3,
          "epoch_loss_gap": 1e-4, "shortconv_gap": 1e-5,
          "epochs_missing": 0, "window_epochs_missing": 0}
CONTROLS = ("int8", "conv_bf16", "tap_ahead", "no_c_gate", "no_qk_norm",
            "untied_grad", "stale_eval", "epoch_fewer")


def tiny_bench() -> spec.Bench:
    """The cell's files with the unit tests' sizes in the configuration's
    and the traffic's place: float32 compute, so that the limits can be
    tight enough for every control to fail them."""
    bench = spec.Bench(ROOT)
    config = {"model": {**TINY, "compute_dtype": "float32",
                        "moe_chunk_rows": 64},
              "train": {"learning_rate": 1e-4}}
    traffic = {**bench.traffic(TRAFFIC),
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
            tiny_bench(), CELL, 4_200_000_019, 0.2, True,
            require_chip=False)))


def test_the_harness_runs_the_cell_and_finds_it_correct(traced_line):
    line = traced_line
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 3 * 8 and line["failed"] == 0
    assert set(line["compared"]) == set(LIMITS)
    window = line["window"]
    assert window["routed_rows"] > 0 and window["model_flops"] > 0
    assert window["eval_batches"] == 3 * 2
    assert window["batch"] == 2 and window["window_s"] > 0
    assert window["train_phase_s"] > 0
    # three expert layers of two held experts: 64 tokens x 2 picks x 2 / 8
    # rows an expert and step if the router were even
    assert 4 < window["rows_per_expert_start"] < 64
    assert window["rows_per_expert_window"] == pytest.approx(
        window["routed_rows"] / (24 * 6))
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
    # the control follows the first epoch's steps too
    assert {"grad_gap", "update_gap", "epoch_loss_gap"} <= caught["int8"]
    # sums kept in bfloat16 reach the operator alone, and little else
    assert "shortconv_gap" in caught["conv_bf16"]
    assert "loss_gap" not in caught["conv_bf16"]
    assert {"shortconv_gap", "loss_gap"} <= caught["tap_ahead"]
    assert {"shortconv_gap", "loss_gap"} <= caught["no_c_gate"]
    assert {"loss_gap", "grad_worst_gap"} <= caught["no_qk_norm"]
    assert "shortconv_gap" not in caught["no_qk_norm"]
    # the tied leaf with half its gradient: its moment and its change
    assert {"grad_worst_gap", "update_gap"} <= caught["untied_grad"]
    # validation on the starting parameters, on a row the probe trains on
    assert caught["stale_eval"] == {"val_loss_gap"}
    assert caught["epoch_fewer"] == {"epochs_missing"}


def test_no_reading_sees_the_routers_constant(tmp_path, monkeypatch):
    """1e-20 in the place of 1e-6 moves a routed weight by under 5e-7 of
    itself: even in float32, at limits a hundred times tighter than the
    cell's, every number stays inside. It is no control for that reason."""
    _streamed(monkeypatch)
    bench = tiny_bench()
    cell = bench.cell(CELL, 13, 0.2, tmp_path / "work")
    (tmp_path / "work").mkdir()
    driver = bench.driver("retrain_shortconv")
    job = driver.setup(cell)
    want = driver.follow(job)
    planted = {**want, **driver.follow(job, probe_only=True,
                                       fault="norm_eps_tiny")}
    ok, table = compare.judge(
        driver.readings(job, planted, want),
        {k: v for k, v in LIMITS.items() if not k.startswith("window_")})
    assert ok, table
    assert "norm_eps_tiny" not in driver.FAULTS


@pytest.mark.parametrize("seed", [1, 2, 4_200_000_019])
def test_the_operator_alone_is_held_to_the_shifted_sums_alone(seed):
    """``shortconv_gap``'s two sides: the program's gate, convolution and
    gate on what the reference's first branch is handed for a batch of the
    seed's rows give the taps the gradient that the three shifted sums give
    them, to the order of the sums in float32 and in bfloat16 alike (every
    input is a bfloat16 number and the sums are float32 either way); sums
    kept in bfloat16, a tap ahead or a dropped gate are further off, and
    the inputs are the seed's."""
    import ml_dtypes
    import numpy as np

    from perfbench.drivers import retrain_shortconv
    from robotic_discovery_platform_tpu.utils.config import (
        HybridLMConfig, from_dict)

    ref = spec.Bench(ROOT).reference(CONFIG)
    start = ref.init(TINY, seed)
    rows = ref.tokens(TINY, seed, 2)
    drawn = ref.shortconv_check_inputs(TINY, seed, start, rows)
    assert {k: v.shape for k, v in drawn.items()} == {
        "b": (2, 32, 64), "c": (2, 32, 64), "xs": (2, 32, 64),
        "taps": (64, 3), "readout": (2, 32, 64)}
    # the first branch's own taps, and what it is handed: the embedded rows
    # normed and projected, in bfloat16's numbers
    np.testing.assert_array_equal(drawn["taps"],
                                  np.asarray(start["layers/0/conv_taps"][0]))
    x = np.asarray(start["embed"])[rows]
    u = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + TINY["rms_norm_eps"])
    np.testing.assert_allclose(
        np.concatenate([drawn[k] for k in ("b", "c", "xs")], -1),
        u @ np.asarray(start["layers/0/w_in"][0]), rtol=1e-2, atol=1e-4)
    for name in ("b", "c", "xs", "readout"):    # bfloat16 holds them
        np.testing.assert_array_equal(
            drawn[name], drawn[name].astype(ml_dtypes.bfloat16).astype(
                np.float32))
    want = ref.shortconv_taps_grad(drawn)
    assert want.shape == (64, 3)

    def apart(got):
        return np.linalg.norm(got - want) / np.linalg.norm(want)

    program = {dtype: apart(retrain_shortconv.shortconv_alone(
        drawn, from_dict(HybridLMConfig, {**TINY, "compute_dtype": dtype})))
        for dtype in ("float32", "bfloat16")}
    assert max(program.values()) < 1e-6
    assert 1e-4 < apart(ref.shortconv_taps_grad(drawn, "conv_bf16")) < 0.05
    for fault in ("tap_ahead", "no_c_gate"):
        assert apart(ref.shortconv_taps_grad(drawn, fault)) > 0.5
    other = ref.shortconv_check_inputs(TINY, seed + 1, start, rows)
    assert apart(ref.shortconv_taps_grad(other)) > 0.5
    with pytest.raises(ValueError, match="first branch"):
        ref.shortconv_check_inputs({**TINY, "layer_pattern": "*EcEcEcm"},
                                   seed, ref.init({
                                       **TINY, "layer_pattern": "*EcEcEcm"},
                                       seed), rows)


def test_the_probe_trains_on_rows_the_data_set_does_not_hold(tmp_path,
                                                             monkeypatch):
    import numpy as np

    from perfbench.lib import order

    _streamed(monkeypatch)
    bench = tiny_bench()
    cell = bench.cell(CELL, 13, 0.2, tmp_path / "work")
    (tmp_path / "work").mkdir()
    job = bench.driver("retrain_shortconv").setup(cell)
    assert job.probe_tokens.shape == (job.n_probe, 32) == (3, 32)
    assert job.tokens.shape == (20, 32)
    drawn = cell.reference.tokens(cell.config["model"], 13, 23)
    np.testing.assert_array_equal(job.tokens, drawn[3:])
    # the probe's training rows are the draw's; the row it validates on is
    # a copy of one of them
    tr, va = order.train_val_split(3, job.base_cfg.validation_split,
                                   job.base_cfg.seed)
    assert len(tr) == 2 and len(va) == 1
    np.testing.assert_array_equal(job.probe_tokens[tr], drawn[:3][tr])
    np.testing.assert_array_equal(job.probe_tokens[va[0]],
                                  job.probe_tokens[tr[0]])
    assert len(job.produced["probe"]["loss"]) == 3
    assert len(job.produced["epoch"]["step_loss"]) == 2
    assert job.produced["shortconv"].shape == (64, 3)
    # the operator alone is compared on the probe's training batch, whole
    assert job.conv_inputs["xs"].shape == (2, 32, 64)
