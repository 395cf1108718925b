"""The causal language-model task at a small size on the CPU (two periods of
the pattern (sliding, full), a window of 8 in sequences of 32, hidden 64, 8
experts of which 2 held, top-2, vocabulary 64), on seeded random weights:
the program against the benchmark's plain reference
(``perfbench/reference/mellum2-12b-a2.5b.py``, which imports nothing of the
program), the causal and the window rule of the attention wrapper against
their definitions and the kernel in interpret mode against the dense form,
the YaRN table against its formula, the prefix property, the chip's share
against the uncut layer, the scopes and counters, and the task through
``train_model``.

Tolerances: the program in float32 differs from the reference by the order
of its sums alone (1e-5 relative on a leaf's gradient); in bfloat16, the
configuration's compute type, by bfloat16's 8 bits of mantissa through four
layers."""

import dataclasses
import importlib.util
import math
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from robotic_discovery_platform_tpu.models import blockdiff_lm, causal_lm as lm
from robotic_discovery_platform_tpu.models import moe
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.ops.pallas import (
    blockdiff_attention, masked_attention as attn)
from robotic_discovery_platform_tpu.training import tasks, trainer
from robotic_discovery_platform_tpu.utils.config import (
    CausalLMConfig, RotaryConfig, TrainConfig, from_dict)

ROOT = Path(__file__).resolve().parents[1]
REFERENCE = ROOT / "perfbench" / "reference" / "mellum2-12b-a2.5b.py"
SLIDING, FULL = "sliding_attention", "full_attention"


def _own_copy(path: Path):
    """The reference as a module of this file's own (what is compiled here
    must not be found compiled by ``tests/perfbench``'s tests)."""
    found = importlib.util.spec_from_file_location(
        "test_causal_lm_reference", path)
    module = sys.modules[found.name] = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


ref = _own_copy(REFERENCE)
LEAVES = sorted(lm.param_shapes(CausalLMConfig()))
SEED = 5
# the published tables (Mellum2's rope_parameters)
PUBLISHED_YARN = RotaryConfig(
    theta=500000.0, factor=16.0, original_max_position=8192, beta_fast=32.0,
    beta_slow=1.0, attention_factor=1.2772588722239782)


def small(**kw) -> CausalLMConfig:
    return CausalLMConfig(**{"compute_dtype": "float32",
                             "kernel_impl": "xla", **kw})


def seeded(cfg: CausalLMConfig, batch: int = 2):
    """(reference's model dict, flat weights, nested weights, tokens)."""
    model = dataclasses.asdict(cfg)
    flat = {k: jnp.asarray(v) for k, v in ref.init(model, SEED).items()}
    nested = unflatten_dict({tuple(k.split("/")): v
                             for k, v in flat.items()})
    return model, flat, nested, ref.tokens(model, SEED, batch)


@pytest.fixture(scope="module")
def f32_pair():
    """Loss, validation loss, logits, rows and gradients of program and
    reference in float32."""
    cfg = small()
    model, flat, nested, tokens = seeded(cfg)
    net = lm.build_causal_lm(cfg)

    def loss(p):
        value, _, rows = net.loss(p, jnp.asarray(tokens))
        return value, rows

    (got, rows), grads = jax.value_and_grad(loss, has_aux=True)(nested)
    want, want_grads, want_rows = ref.loss_and_grads(model, flat, tokens)
    held_out = ref.tokens(model, SEED + 1, 2)
    state = trainer.TrainState(params=nested, opt_state=None, batch_stats={},
                               epoch=None, best_val_loss=None)
    evaluated = tasks.CAUSAL_LM.evaluate(net, None, state,
                                         jnp.asarray(held_out), None)
    logits = net.apply(nested, jnp.asarray(held_out))[0]
    hits = (np.argmax(logits[:, :-1], -1) == held_out[:, 1:]).mean()
    return {"loss": (float(got), want),
            "val_loss": (float(evaluated["loss"]),
                         ref.eval_loss(model, 0, flat, held_out)),
            "accuracy": (float(evaluated["token_accuracy"]), hits),
            "rows": (np.asarray(rows), want_rows),
            "logits": (logits, ref.forward(model, flat, held_out)),
            "grads": (flatten_dict(grads, sep="/"), want_grads)}


def _samples():
    return {form: obs.ATTN_QK_PREP.labels(form=form).value
            for form in ("fused", "xla")}


@pytest.fixture(scope="module")
def fused_pair():
    """Loss and gradients of a model whose heads fill a 128-lane tile
    (YaRN's ``attention_factor`` in the full layers' table), under the
    interpreted kernels and under the dense forms, in float32; and the
    samples each trace added to ``rdp_attn_qk_prep_total``."""
    got = {}
    for impl in ("interpret", "xla"):
        cfg = small(num_heads=2, num_kv_heads=1, head_dim=128,
                    full_rope=PUBLISHED_YARN, kernel_impl=impl)
        _, _, nested, tokens = seeded(cfg)
        net = lm.build_causal_lm(cfg)
        before = _samples()
        value, grads = jax.value_and_grad(
            lambda p: net.loss(p, jnp.asarray(tokens))[0])(nested)
        got[impl] = ({"loss": value, **flatten_dict(grads, sep="/")},
                     {k: v - before[k] for k, v in _samples().items()})
    return got


@pytest.mark.parametrize("leaf", ["loss"] + LEAVES)
def test_q_and_k_prepared_in_one_pass_are_the_dense_chains(fused_pair, leaf):
    """``ops/pallas/qk_prep``: the model under ``impl="interpret"`` (q and k
    of every layer rotated and scaled by the fused pass, no norm) against
    ``impl="xla"``, by the order of float32's sums."""
    (got, fused), (want, dense) = fused_pair["interpret"], fused_pair["xla"]
    assert fused["fused"] > 0 and fused["fused"] % 2 == 0
    assert fused["xla"] == 0 and dense["fused"] == 0 and dense["xla"] > 0
    mine, its = np.asarray(got[leaf]), np.asarray(want[leaf])
    assert np.linalg.norm(mine - its) <= 1e-5 * np.linalg.norm(its)


def test_the_reference_imports_nothing_of_the_program():
    source = REFERENCE.read_text()
    assert "robotic_discovery_platform_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert lm.param_shapes(CausalLMConfig()) == ref.param_shapes(
        dataclasses.asdict(CausalLMConfig()))
    assert list(lm.param_shapes(CausalLMConfig())) == list(ref.param_shapes(
        dataclasses.asdict(CausalLMConfig())))


def test_logits_losses_and_rows_against_the_reference(f32_pair):
    got, want = f32_pair["logits"]
    assert got.shape == (2, 32, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-6)
    for name in ("loss", "val_loss", "accuracy"):
        assert f32_pair[name][0] == pytest.approx(f32_pair[name][1],
                                                  rel=1e-6), name
    np.testing.assert_array_equal(*f32_pair["rows"])
    assert f32_pair["rows"][0].shape == (4, 2)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_against_the_reference(f32_pair, leaf):
    got, want = (np.asarray(g[leaf]) for g in f32_pair["grads"])
    assert np.linalg.norm(want) > 0
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_configurations_bfloat16_stays_near_the_reference(impl):
    cfg = small(compute_dtype="bfloat16", kernel_impl=impl)
    model, flat, nested, tokens = seeded(cfg)
    got, _ = lm.forward(cfg, nested, jnp.asarray(tokens))
    want = ref.forward(model, flat, tokens)
    assert float(jnp.abs(got - want).max()) < 0.04 * float(
        jnp.abs(want).max())


def test_the_head_in_chunks_is_the_head_whole(monkeypatch):
    cfg = small()
    _, _, nested, tokens = seeded(cfg)
    net = lm.build_causal_lm(cfg)
    whole = jax.value_and_grad(lambda p: net.loss(p, jnp.asarray(tokens))[0])(
        nested)
    monkeypatch.setattr(lm, "HEAD_CHUNK", 8)
    parts = jax.value_and_grad(lambda p: net.loss(p, jnp.asarray(tokens))[0])(
        nested)
    assert float(parts[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    np.testing.assert_allclose(parts[1]["head"], whole[1]["head"], atol=1e-7)
    assert net.loss(nested, jnp.asarray(tokens), with_hits=True)[1] >= 0


def test_three_adam_steps_through_the_trainers_step_against_the_reference():
    cfg, tcfg = small(), TrainConfig(seed=11, learning_rate=1e-3)
    model, flat, nested, tokens = seeded(cfg)
    task, tx = tasks.CAUSAL_LM, optax.adam(tcfg.learning_rate)
    state = trainer.TrainState(
        params=nested, opt_state=tx.init(nested), batch_stats={},
        epoch=jnp.asarray(0, jnp.int32),
        best_val_loss=jnp.asarray(jnp.inf, jnp.float32))
    step = jax.jit(trainer.core_train_step(
        task.build(cfg), tx, task.make_loss(tcfg), task=task))
    params = {k: jnp.array(v) for k, v in flat.items()}
    opt = ref.adam_init(params)
    for _ in range(3):
        state, out = step(state, jnp.asarray(tokens),
                          jnp.zeros(len(tokens), jnp.int32))
        params, opt, want, _, rows = ref.train_step(
            model, tcfg.learning_rate, tcfg.seed, params, opt, tokens)
        assert float(out["loss"]) == pytest.approx(want, rel=1e-5)
        assert float(out["routed_rows"]) == rows.sum()
        np.testing.assert_array_equal(out["expert_load"], rows.sum(0))
    got = flatten_dict(state.params, sep="/")
    for leaf in LEAVES:
        moved = np.linalg.norm(np.asarray(params[leaf] - flat[leaf]))
        assert moved > 0
        assert np.linalg.norm(np.asarray(got[leaf] - params[leaf])) \
            <= 2e-3 * moved, leaf


# -- the layer pattern --------------------------------------------------------

@pytest.mark.parametrize("kinds,period", [
    ((SLIDING, FULL) * 2, 2), ((SLIDING,) * 3 + (FULL,), 4),
    ((FULL,) * 3, 1), ((SLIDING, SLIDING, FULL) * 2, 3),
    ((SLIDING, FULL, FULL, SLIDING), 4)])
def test_any_pattern_of_the_two_kinds_runs_by_its_shortest_period(
        kinds, period):
    cfg = small(layer_types=kinds, num_layers=len(kinds))
    assert lm.period(cfg.layer_types) == period
    shapes = lm.param_shapes(cfg)
    assert shapes[f"layers/{period - 1}/wq"] == (len(kinds) // period, 64, 64)
    assert f"layers/{period}/wq" not in shapes
    model, flat, nested, tokens = seeded(cfg, batch=1)
    got, rows = lm.forward(cfg, nested, jnp.asarray(tokens))
    np.testing.assert_allclose(got, ref.forward(model, flat, tokens),
                               atol=2e-6)
    assert rows.shape == (len(kinds), 2)


def test_a_pattern_has_to_name_every_layer_by_a_known_kind():
    with pytest.raises(ValueError, match="layer_types"):
        small(layer_types=(SLIDING, FULL))
    with pytest.raises(ValueError, match="layer_types"):
        small(layer_types=(SLIDING, "linear_attention") * 2)
    # from a JSON document: lists and dicts
    doc = dataclasses.asdict(small())
    doc["layer_types"] = list(doc["layer_types"])
    assert from_dict(CausalLMConfig, doc) == small() == CausalLMConfig(**doc)
    hash(small())


def test_logits_depend_on_the_tokens_up_to_their_position_alone():
    cfg = small()
    _, _, nested, tokens = seeded(cfg)
    cut = 20
    other = np.array(tokens)
    other[:, cut + 1:] = (other[:, cut + 1:] + 7) % cfg.vocab_size
    a = lm.forward(cfg, nested, jnp.asarray(tokens))[0]
    b = lm.forward(cfg, nested, jnp.asarray(other))[0]
    np.testing.assert_array_equal(a[:, :cut + 1], b[:, :cut + 1])
    assert float(jnp.abs(a[:, cut + 1:] - b[:, cut + 1:]).max()) > 1e-3


def test_a_sliding_layer_alone_sees_its_window_and_no_further():
    cfg = small(layer_types=(SLIDING,), num_layers=1)
    _, _, nested, tokens = seeded(cfg)
    i, w = 20, cfg.sliding_window
    other = np.array(tokens)
    other[:, :i - w + 1] = (other[:, :i - w + 1] + 7) % cfg.vocab_size
    other[:, i + 1:] = (other[:, i + 1:] + 3) % cfg.vocab_size
    a = lm.forward(cfg, nested, jnp.asarray(tokens))[0]
    b = lm.forward(cfg, nested, jnp.asarray(other))[0]
    np.testing.assert_allclose(a[:, i], b[:, i], atol=1e-6)
    # one key further back is inside the window of position i - 1
    assert float(jnp.abs(a[:, i - 1] - b[:, i - 1]).max()) > 1e-4
    # and a full layer in its place sees the whole prefix
    full = dataclasses.replace(cfg, layer_types=(FULL,))
    a = lm.forward(full, nested, jnp.asarray(tokens))[0]
    b = lm.forward(full, nested, jnp.asarray(other))[0]
    assert float(jnp.abs(a[:, i] - b[:, i]).max()) > 1e-4


# -- the rotary tables --------------------------------------------------------

def _yarn_by_the_formula(rope: RotaryConfig, d: int, length: int):
    """float64 numpy, as the issue writes it down."""
    def corr(n):
        return d * math.log(rope.original_max_position / (
            2 * math.pi * n)) / (2 * math.log(rope.theta))

    low = max(math.floor(corr(rope.beta_fast)), 0)
    high = min(math.ceil(corr(rope.beta_slow)), d - 1)
    i = np.arange(d // 2)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv_freq = rope.theta ** (-2.0 * i / d) * ((1 - ramp)
                                               + ramp / rope.factor)
    angles = np.arange(length)[:, None] * inv_freq[None, :]
    return (low, high, np.concatenate([np.cos(angles)] * 2, -1)
            * rope.attention_factor, np.concatenate([np.sin(angles)] * 2, -1)
            * rope.attention_factor)


@pytest.mark.parametrize("rope,d,low_high", [
    (PUBLISHED_YARN, 128, (18, 35)),
    (CausalLMConfig().full_rope, 16, (1, 7))], ids=["published", "small"])
def test_the_yarn_table_is_the_formulas(rope, d, low_high):
    low, high, cos, sin = _yarn_by_the_formula(rope, d, 64)
    assert (low, high) == low_high == lm.yarn_range(rope, d) \
        == ref.yarn_range(dataclasses.asdict(rope), d)
    got = lm.rope_table(rope, d, jnp.arange(64))
    np.testing.assert_allclose(got[0], cos, atol=2e-5)
    np.testing.assert_allclose(got[1], sin, atol=2e-5)
    np.testing.assert_allclose(
        ref.rope_table(dataclasses.asdict(rope), d, 64), [cos, sin],
        atol=1e-6)
    # position 0: cos is the attention factor, which is 0.1 ln(factor) + 1
    assert float(got[0][0, 0]) == pytest.approx(rope.attention_factor)
    assert rope.attention_factor == pytest.approx(
        0.1 * math.log(rope.factor) + 1)
    # the fastest frequencies are left alone, the slowest divided by factor
    plain = lm.rope_table(RotaryConfig(theta=rope.theta), d, jnp.arange(64))
    np.testing.assert_allclose(got[1][:, 0] / rope.attention_factor,
                               plain[1][:, 0], atol=1e-6)
    slow = rope.theta ** (-2.0 * (d // 2 - 1) / d) / rope.factor
    assert float(got[1][1, d // 2 - 1]) == pytest.approx(
        math.sin(slow) * rope.attention_factor, rel=1e-4)


def test_the_two_kinds_of_layer_get_a_table_each():
    cfg = small()
    sliding, full = (lm.rope_table(lm.rope_of(cfg, kind), cfg.head_dim,
                                   jnp.arange(32))
                     for kind in (SLIDING, FULL))
    assert float(jnp.abs(sliding[0]).max()) == pytest.approx(1.0)
    assert float(jnp.abs(full[0]).max()) == pytest.approx(
        cfg.full_rope.attention_factor)
    x = jax.random.normal(jax.random.key(0), (3, 32, 16))
    # rotate-half by cos and sin, scaled in the same pass
    d = 16
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    np.testing.assert_allclose(
        lm.apply_rotary(x, sliding, 0.25),
        0.25 * (x * sliding[0] + rotated * sliding[1]), atol=1e-6)


# -- the two rules of the attention wrapper -----------------------------------

RULES = {"causal": attn.Causal(tile=128), "window": attn.Window(40, tile=128)}


@pytest.mark.parametrize("name", list(RULES))
def test_a_rule_is_its_definition_and_has_its_live_pairs(name):
    rule = RULES[name]
    for length in (32, 100, 256):
        i, j = np.arange(length)[:, None], np.arange(length)[None, :]
        want = (j <= i) & ((i - j < 40) if name == "window" else True)
        np.testing.assert_array_equal(rule.definition(i, j), want)
        padded = -(-length // 128) * 128
        rows = rule.rows(padded)
        got = np.asarray(rule.live(rows[:, None],
                                   np.arange(padded, dtype=np.int32)[None]))
        np.testing.assert_array_equal(got[:length, :length], want)
        assert not got[:length, length:].any()      # no padding key
        assert got[length:].any(axis=1).all()       # no empty padding row
        assert want.sum() == rule.live_pairs(length)
        kind = SLIDING if name == "window" else FULL
        np.testing.assert_array_equal(
            ref.mask_matrix(kind, length, 40), want)


def test_live_pairs_and_tiles_at_the_cells_size():
    """The issue's counts at 8,192 positions and a window of 1,024."""
    assert attn.Causal().live_pairs(8192) == 33_558_528
    assert attn.Window(1024).live_pairs(8192) == 7_864_832
    i = np.arange(8192)

    def visited(rule, tile):
        edges = i.reshape(-1, tile)[:, [0, -1]]     # a tile's first and last
        q_lo, q_hi = edges[:, None, 0], edges[:, None, 1]
        k_lo, k_hi = edges[None, :, 0], edges[None, :, 1]
        live = k_lo <= q_hi
        if isinstance(rule, attn.Window):
            live &= q_lo - k_hi < rule.window
        return int(live.sum()), live.size

    assert visited(attn.Causal(), 1024) == (36, 64)
    assert visited(attn.Window(1024), 1024) == (15, 64)
    assert visited(attn.Window(1024), 512) == (45, 256)
    assert visited(attn.Window(1024), 256) == (150, 1024)


def test_the_kernel_is_written_against_this_jax():
    """``splash_mask`` leans on internals of the splash kernels (a private
    base class, ``q_sequence`` handed on unchanged): after an upgrade read
    the test below before this line is moved."""
    assert jax.__version__ == "0.9.0"


@pytest.mark.parametrize("is_dkv", [False, True], ids=["fwd_dq", "dkv"])
@pytest.mark.parametrize("name,length,tile,padded,visited", [
    ("causal", 1000, 128, 1024, 36), ("window", 1000, 128, 1024, 15),
    ("window", 512, 128, 512, 7)])
def test_the_kernels_block_map_visits_the_live_tiles_alone(
        name, length, tile, padded, visited, is_dkv):
    """What splash builds from a rule, tile by tile against its definition
    (at 8 x 8 tiles and a window of a tile the counts are the cell's at
    1024: 36 and 15 of 64)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm, splash_attention_mask_info as mi)

    rule = attn.Causal(tile) if name == "causal" else attn.Window(tile, tile)
    heads = sm.MultiHeadMask([attn.splash_mask(padded, rule)] * 2)
    ids = np.arange(padded)
    full = rule.definition(ids[:, None], ids[None, :])
    tiles = full.reshape(padded // tile, tile, padded // tile, tile)
    info, fn = mi.process_mask(heads, (tile, tile), is_dkv=is_dkv,
                               shrink_grid=False)
    got = np.asarray(info.block_mask[0])
    np.testing.assert_array_equal(got > 0, tiles.any(axis=(1, 3)))
    np.testing.assert_array_equal(got == 2, tiles.all(axis=(1, 3)))
    assert (got > 0).sum() == visited < got.size
    # the mask is computed in the kernel (no tile of it is stored), from
    # the rows the rule handed over
    assert info.partial_mask_blocks is None and fn is not None
    np.testing.assert_array_equal(info.q_sequence, rule.rows(padded))
    shrunk = mi.process_mask(heads, (tile, tile), is_dkv=is_dkv)[0]
    assert (np.asarray(shrunk.block_mask[0]) > 0).sum() == visited


@pytest.fixture(scope="module", params=list(RULES))
def attention_pair(request):
    """Forward and the three gradients, kernel (interpreter) against the
    dense form: 8 query heads on 2 key/value heads, 200 positions, which is
    no multiple of the 128-wide tile."""
    rule = RULES[request.param]
    s, b, h, g, d = 200, 2, 8, 2, 64
    keys = jax.random.split(jax.random.key(0), 4)
    q = 0.3 * jax.random.normal(keys[0], (b, h, s, d))
    k = jax.random.normal(keys[1], (b, g, s, d))
    v = jax.random.normal(keys[2], (b, g, s, d))
    w = jax.random.normal(keys[3], (b, h, s, d))

    def run(impl):
        def f(q, k, v):
            out = attn.masked_attention(q, k, v, rule, impl=impl)
            return jnp.sum(out * w), out

        (_, out), grads = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(
            q, k, v)
        return {"out": out, "dq": grads[0], "dk": grads[1], "dv": grads[2]}

    return run("interpret"), run("xla")


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_the_attention_kernel_against_the_dense_form(attention_pair, what):
    got, want = (np.asarray(r[what]) for r in attention_pair)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def _tiles(kind):
    return {state: obs.ATTN_MASK_TILES.labels(kind=kind, state=state).value
            for state in ("visited", "skipped")}


def test_the_tile_counter_is_sampled_where_the_grid_is_built():
    q = jnp.zeros((1, 2, 1024, 16))
    before = {kind: _tiles(kind) for kind in ("causal", "window", "blockdiff")}
    for rule in (attn.Causal(128), attn.Window(128, 128)):
        jax.eval_shape(lambda q: attn.masked_attention(
            q, q, q, rule, impl="interpret"), q)
    jax.eval_shape(lambda q: blockdiff_attention.blockdiff_attention(
        q, q, q, seq_len=512, block=4, impl="interpret", tile=128), q)
    # the dense form builds no grid
    jax.eval_shape(lambda q: attn.masked_attention(
        q, q, q, attn.Causal(128), impl="xla"), q)
    moved = {kind: {s: _tiles(kind)[s] - before[kind][s]
                    for s in ("visited", "skipped")} for kind in before}
    assert moved["causal"] == {"visited": 36, "skipped": 28}
    assert moved["window"] == {"visited": 15, "skipped": 49}
    # the block-diffusion rule: the noisy copy's diagonal, the clean copy's
    # lower triangle, and the same triangle of clean keys for noisy queries
    assert moved["blockdiff"] == {"visited": 4 + 10 + 10, "skipped": 40}


# -- the shared expert layer and the chip's share -----------------------------

def test_both_language_models_run_one_expert_layer():
    for name in ("route", "routed_experts", "expert_layer", "rms_norm"):
        assert getattr(blockdiff_lm, name) is getattr(moe, name)
    assert lm.expert_layer is moe.expert_layer
    assert lm.rms_norm is moe.rms_norm


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's share test: 4 chips hold 4 of 16 experts each; what the
    4 shares add, with attention (which every chip computes alike) counted
    once, is the uncut reference's layer; for either kind."""
    for kind in (SLIDING, FULL):
        uncut = small(num_experts=16, experts_held=16, num_layers=1,
                      layer_types=(kind,))
        model, flat, _, _ = seeded(uncut, batch=1)
        mask, table = (jnp.asarray(a) for a in ref.layer_inputs(model)[kind])
        x = 0.5 * jax.random.normal(jax.random.key(3),
                                    (uncut.seq_len, uncut.hidden_size))
        whole = {k: flat[f"layers/0/{k}"][0] for k in ref.LAYER_LEAVES}
        want, rows = ref.layer(model, whole, x, mask, table)
        rope = lm.rope_table(lm.rope_of(uncut, kind), uncut.head_dim,
                             jnp.arange(uncut.seq_len))
        experts = ("w_gate", "w_up", "w_down")
        no_experts = {**whole, **{k: jnp.zeros_like(whole[k][:4])
                                  for k in experts}}
        share0 = dataclasses.replace(uncut, experts_held=4)
        base, _ = lm.decoder_layer(share0, kind, no_experts, x[None], rope,
                                   "xla")
        total, taken = base, []
        for chip in range(4):
            share = dataclasses.replace(share0, first_expert=4 * chip)
            held = {**whole, **{k: whole[k][4 * chip:4 * chip + 4]
                                for k in experts}}
            out, sizes = lm.decoder_layer(share, kind, held, x[None], rope,
                                          "xla")
            total = total + (out - base)
            taken += sizes.tolist()
        np.testing.assert_array_equal(taken, rows)
        assert sum(taken) == uncut.seq_len * uncut.experts_per_token
        np.testing.assert_allclose(total[0], want, atol=2e-5)


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_init_rule_is_the_one_the_reference_re_derives(leaf):
    cfg = small(embed_init_std=4.0)
    params, stats = tasks.CAUSAL_LM.init_variables(
        lm.build_causal_lm(cfg), jax.random.key(21), TrainConfig(seed=21))
    got, want = flatten_dict(params, sep="/"), ref.init(
        dataclasses.asdict(cfg), 21)
    assert stats == {}
    assert list(got) == list(want)
    np.testing.assert_array_equal(got[leaf], want[leaf])
    if leaf.endswith("norm"):
        assert (np.asarray(got[leaf]) == 1).all()
    else:
        std = cfg.embed_init_std if leaf == "embed" else cfg.init_std
        assert np.std(np.asarray(got[leaf])) == pytest.approx(std, rel=0.2)


# -- scopes -------------------------------------------------------------------

@pytest.fixture(scope="module")
def compiled():
    """The ``op_name`` of every instruction of the compiled train and
    evaluation steps (kernels in interpret mode, so that the kernel's scope
    holds operations on a CPU)."""
    cfg = small(kernel_impl="interpret", seq_len=128, sliding_window=16)
    task, tx = tasks.CAUSAL_LM, optax.adam(1e-4)
    model = task.build(cfg)
    state = jax.eval_shape(lambda: trainer.task_state(
        task, model, tx, jax.random.key(0), TrainConfig()))
    x = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    y = jax.ShapeDtypeStruct((2,), jnp.int32)
    programs = {
        "train": jax.jit(trainer.core_train_step(model, tx, None, task=task)),
        "eval": jax.jit(trainer.core_eval_step(model, None, task=task))}
    return {name: set(re.findall(
        r'op_name="([^"]+)"', fn.lower(state, x, y).compile().as_text()))
        for name, fn in programs.items()}


@pytest.mark.parametrize("program,scopes,backward", [
    ("train", ("rdp.lm.embed",), True), ("train", ("rdp.attn.rope",), False),
    ("train", ("rdp.lm.layer", "rdp.attn.proj"), True),
    ("train", ("rdp.lm.layer", "rdp.attn.window"), True),
    ("train", ("rdp.lm.layer", "rdp.attn.causal"), True),
    ("train", ("rdp.lm.layer", "rdp.moe.route"), True),
    ("train", ("rdp.lm.layer", "rdp.moe.experts"), True),
    ("train", ("rdp.lm.head",), True), ("train", ("rdp.loss",), True),
    ("train", ("rdp.optimizer",), False),
    ("eval", ("rdp.eval", "rdp.lm.layer", "rdp.attn.window"), False),
    ("eval", ("rdp.eval", "rdp.lm.head"), False)])
def test_the_compiled_step_holds_the_named_scopes(compiled, program, scopes,
                                                  backward):
    from perfbench.lib import spans as spans_lib

    paths = [p for p in compiled[program]
             if all(spans_lib.under(scope, p) for scope in scopes)]
    assert paths, f"no operation of the {program} step is under {scopes}"
    if backward:
        # the backward pass keeps the scope under JAX's prefix
        assert any("transpose(" in p for p in paths)
    if scopes == ("rdp.optimizer",):
        assert not any(spans_lib.under("rdp.lm.layer", p) for p in paths)


# -- the task through train_model ---------------------------------------------

def _job(tmp_path, epochs, seed=3):
    cfg = TrainConfig(batch_size=2, epochs=epochs, seed=seed,
                      learning_rate=1e-3,
                      tracking_uri=f"file:{tmp_path / 'mlruns'}",
                      checkpoint_dir=str(tmp_path / "ckpt"))
    tokens = ref.tokens(dataclasses.asdict(small()), SEED, 20)
    return trainer.train_model(cfg, small(), arrays=(tokens, None),
                               resume=True)


@pytest.mark.parametrize("streamed", [True, False],
                         ids=["streamed", "device-snapshot"])
def test_train_model_trains_resumes_and_registers_the_task(
        tmp_path, streamed, monkeypatch):
    from robotic_discovery_platform_tpu import tracking
    from robotic_discovery_platform_tpu.tracking import api

    if streamed:    # and the registry takes the weights as leaf files
        monkeypatch.setattr(api, "_LEAF_FILES_ABOVE", 1000)
        monkeypatch.setattr(trainer, "_DEVICE_SNAPSHOT_MAX_BYTES", 1000)
    rows = obs.MOE_ROUTED_ROWS.value
    first = _job(tmp_path, 2)
    second = _job(tmp_path, 4)
    assert (first.epochs_run, second.epochs_run) == (2, 2)
    assert second.registry_version == first.registry_version + 1
    assert set(second.final_metrics) == {"loss", "token_accuracy"}
    assert second.best_val_loss <= first.best_val_loss
    assert (tmp_path / "ckpt" / "streamed").is_dir() == streamed
    # the counters: 4 epochs of 8 steps on 2 x 32 positions, 4 layers
    assert 0 < obs.MOE_ROUTED_ROWS.value - rows <= 32 * 2 * 32 * 4 * 2
    assert obs.MOE_LOAD_RATIO.value >= 1.0
    assert obs.TRAIN_TOKENS_RATE.value > 0
    history = tracking.get_metric_history(second.run_id, "val_token_accuracy")
    assert len(history) == 2
    losses = [m["value"] for m in tracking.get_metric_history(
        second.run_id, "train_loss")]
    assert losses[-1] < math.log(64)
    # what was registered loads back as the task's model
    path = tracking.resolve_model_uri("models:/Actuator-Segmenter/latest")
    model, variables = tracking.load_model(path.as_posix())
    assert isinstance(model, lm.CausalLM) and model.cfg == small()
    assert set(variables["params"]) == {"embed", "layers", "final_norm",
                                        "head"}
    assert set(variables["params"]["layers"]) == {"0", "1"}


def test_the_task_is_found_by_configuration_and_by_name():
    assert tasks.task_for(CausalLMConfig()) is tasks.CAUSAL_LM
    assert tasks.task_named("causal_lm") is tasks.CAUSAL_LM
    with pytest.raises(ValueError, match="one device"):
        tasks.CAUSAL_LM.for_mesh(CausalLMConfig())
    with pytest.raises(ValueError, match="in-memory"):
        tasks.CAUSAL_LM.file_data(TrainConfig())
    with pytest.raises(ValueError, match="seq_len"):
        tasks.CAUSAL_LM.train_loss(
            lm.build_causal_lm(small()), None, None, None,
            jnp.zeros((2, 16), jnp.int32), None)
