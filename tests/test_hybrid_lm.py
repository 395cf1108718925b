"""The hybrid language-model task at a small size on the CPU (two periods of
the pattern (mamba, experts, attention, experts), sequences of 32 in chunks
of 8, hidden 64, 4 mixer heads of 16 in 2 groups with a state of 16, 8
experts of which 2 held, top-2, a shared expert, vocabulary 64), on seeded
random weights: the chunked scan against the recurrence position by
position, the program against the benchmark's plain reference
(``perfbench/reference/nemotron-twotower-30b-a3b.py``, which imports nothing
of the program and runs the recurrence as the recurrence), causality through
the whole model, the chip's share against the uncut layer, the sigmoid
router, the other families' expert layer against the parent commit's, the
scopes and counters, and the task through ``train_model``.

Tolerances: the program in float32 differs from the reference by the order
of its sums alone (1e-5 relative on a leaf's gradient; the chunked scan
against the recurrence 2e-5 of the largest value, since the chunked form
multiplies exponentials of differences where the recurrence multiplies one
decay a step); in bfloat16, the configuration's compute type, by bfloat16's
8 bits of mantissa through eight layers. The steps are drawn on [0.05, 0.5]
here (the published 0.001 to 0.1 are sized for chunks of 128, not 8), so
that a chunk's decays sum to the order of 10 as they do at the cell's size
and a sum kept in bfloat16 is off by a few percent in its exponential."""

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

import _parent_moe as parent_moe
from robotic_discovery_platform_tpu.models import (
    blockdiff_lm, causal_lm, hybrid_lm as lm, moe)
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.ops import ssm_scan as scan_lib
from robotic_discovery_platform_tpu.training import tasks, trainer
from robotic_discovery_platform_tpu.utils.config import (
    BlockDiffLMConfig, CausalLMConfig, HybridLMConfig, TrainConfig, from_dict,
    to_dict)

ROOT = Path(__file__).resolve().parents[1]
REFERENCE = ROOT / "perfbench" / "reference" / "nemotron-twotower-30b-a3b.py"
SEED = 5


def _own_copy(path: Path):
    """The reference as a module of this file's own (what is compiled here
    must not be found compiled by ``tests/perfbench``'s tests)."""
    found = importlib.util.spec_from_file_location(
        "test_hybrid_lm_reference", path)
    module = sys.modules[found.name] = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


ref = _own_copy(REFERENCE)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**{"compute_dtype": "float32", "kernel_impl": "xla",
                             "time_step_min": 0.05, "time_step_max": 0.5,
                             **kw})


LEAVES = sorted(lm.param_shapes(small()))


def seeded(cfg: HybridLMConfig, batch: int = 2):
    """(reference's model dict, flat weights, nested weights, tokens)."""
    model = dataclasses.asdict(cfg)
    flat = {k: jnp.asarray(v) for k, v in ref.init(model, SEED).items()}
    nested = unflatten_dict({tuple(k.split("/")): v
                             for k, v in flat.items()})
    return model, flat, nested, ref.tokens(model, SEED, batch)


# -- q and k made ready for attention -----------------------------------------

def _samples():
    return {form: obs.ATTN_QK_PREP.labels(form=form).value
            for form in ("fused", "xla")}


#: what the attention layers' q and k need, and the form that gives it under
#: ``impl="interpret"``: LFM2's norm and table on heads of 128, the same on
#: its own heads of 64, and this family's first model (a scale alone)
PREPARED = {
    "norm+table-128": ({"qk_norm": True, "rope_theta": 100.0,
                        "head_dim": 128, "num_heads": 2,
                        "num_kv_heads": 1}, "fused"),
    "norm+table-64": ({"qk_norm": True, "rope_theta": 100.0, "head_dim": 64,
                       "num_heads": 2, "num_kv_heads": 1}, "xla"),
    "scale-alone": ({"head_dim": 128, "num_heads": 2, "num_kv_heads": 1},
                    "xla"),
}


@pytest.mark.parametrize("what", PREPARED)
def test_q_and_k_prepared_in_one_pass_are_the_dense_chains(what):
    """``ops/pallas/qk_prep`` through the attention layers: loss and every
    gradient leaf under ``impl="interpret"`` against ``impl="xla"`` in
    float32, and the form ``rdp_attn_qk_prep_total`` says each trace took."""
    fields, form = PREPARED[what]
    got = {}
    for impl in ("interpret", "xla"):
        cfg = small(num_layers=4, layer_pattern="ME*E", kernel_impl=impl,
                    **fields)
        tokens = ref.tokens(dataclasses.asdict(cfg), SEED, 2)
        net = lm.build_hybrid_lm(cfg)
        nested = net.init(jax.random.key(SEED))
        before = _samples()
        value, grads = jax.value_and_grad(
            lambda p: net.loss(p, jnp.asarray(tokens))[0])(nested)
        got[impl] = {"loss": value, **flatten_dict(grads, sep="/")}
        added = {k: v - before[k] for k, v in _samples().items()}
        other = "xla" if form == "fused" else "fused"
        assert added[other if impl == "interpret" else "fused"] == 0
        assert added[form if impl == "interpret" else "xla"] >= 2
    for leaf, its in got["xla"].items():
        mine, its = np.asarray(got["interpret"][leaf]), np.asarray(its)
        assert np.linalg.norm(mine - its) <= 1e-5 * np.linalg.norm(its), leaf


# -- the scan against the recurrence ------------------------------------------

SCAN_INPUTS = ("x", "dt", "a", "b", "c", "d")


def recurrence(x, dt, a, b, c, d):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t +
    d x_t``, one position at a time, written here."""
    batch, _, heads, p = x.shape
    per = heads // b.shape[2]
    b, c = jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2)

    def one(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (h * jnp.exp(dt_t * a)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return h, jnp.einsum("zhpn,zhn->zhp", h, c_t) + d[:, None] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((batch, heads, p, b.shape[-1])),
                        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def scan_inputs(length: int) -> tuple:
    """Two sequences, 4 heads of 16 in 2 groups, a state of 16; a chunk's
    decays sum to the order of 10."""
    keys = jax.random.split(jax.random.key(7), 6)
    return (jax.random.normal(keys[0], (2, length, 4, 16)),
            jax.random.uniform(keys[1], (2, length, 4), minval=0.05,
                               maxval=0.5),
            -jax.random.uniform(keys[2], (4,), minval=1.0, maxval=16.0),
            jax.random.normal(keys[3], (2, length, 2, 16)),
            jax.random.normal(keys[4], (2, length, 2, 16)),
            jax.random.normal(keys[5], (4,)))


def _readout(y):
    return jnp.sum(jnp.sin(y))


@pytest.fixture(scope="module", params=[32, 27], ids=["whole-chunks",
                                                       "padded"])
def scan_pair(request):
    """Values and gradients of the chunked scan and of the recurrence at a
    length that is (32) and is not (27) a multiple of the chunk of 8."""
    inputs = scan_inputs(request.param)
    chunked = lambda *v: scan_lib.ssm_scan(*v, chunk=8)
    return {
        "y": (chunked(*inputs), recurrence(*inputs)),
        **{name: (got, want) for name, got, want in zip(
            SCAN_INPUTS,
            jax.grad(lambda *v: _readout(chunked(*v)), range(6))(*inputs),
            jax.grad(lambda *v: _readout(recurrence(*v)), range(6))(
                *inputs))}}


@pytest.mark.parametrize("what", ("y",) + SCAN_INPUTS)
def test_the_chunked_scan_is_the_recurrence(scan_pair, what):
    got, want = scan_pair[what]
    assert got.shape == want.shape and float(jnp.max(jnp.abs(want))) > 0
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(
        jnp.max(jnp.abs(want)))


def _rounded(x):
    """To bfloat16's precision, by the operation no compiler simplifies
    away."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@pytest.mark.parametrize("planted", ["decays", "state"])
def test_a_scan_with_bfloat16_sums_or_state_is_not_the_recurrence(
        planted, monkeypatch):
    """The decays' running sums, or the state carried between chunks, kept
    in bfloat16 fail the tolerance the scan is held to."""
    inputs = scan_inputs(32)
    want = recurrence(*inputs)
    if planted == "decays":
        cumsum = jnp.cumsum
        monkeypatch.setattr(scan_lib.jnp, "cumsum",
                            lambda *a, **kw: _rounded(cumsum(*a, **kw)))
    else:
        scan = jax.lax.scan

        def rounding(f, init, xs):
            def step(carry, x):
                carry, out = f(carry, x)
                return _rounded(carry), out
            return scan(step, init, xs)

        monkeypatch.setattr(scan_lib.jax.lax, "scan", rounding)
    got = scan_lib.ssm_scan(*inputs, chunk=8)
    monkeypatch.undo()
    assert float(jnp.max(jnp.abs(got - want))) > 10 * 2e-5 * float(
        jnp.max(jnp.abs(want)))


def test_the_scans_sums_and_state_are_float32_under_bfloat16_inputs():
    x, dt, a, b, c, d = scan_inputs(32)
    half = lambda v: v.astype(jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *v: scan_lib.ssm_scan(*v, chunk=8))(
        half(x), dt, a, half(b), half(c), d)
    by_name, eqns = {}, []

    def walk(inner):        # through the jitted helpers (cumsum, tril)
        for eqn in inner.eqns:
            eqns.append(eqn)
            by_name.setdefault(eqn.primitive.name, []).append(eqn)
            if eqn.primitive.name == "jit":
                walk(eqn.params["jaxpr"].jaxpr)

    walk(jaxpr.jaxpr)
    assert [e.outvars[0].aval.dtype for e in by_name["cumsum"]] == [
        jnp.float32]
    (loop,) = by_name["scan"]
    assert loop.outvars[0].aval.dtype == jnp.float32      # the carried state
    assert loop.outvars[0].aval.shape == (2, 2, 2, 16, 16)
    assert all(e.outvars[0].aval.dtype == jnp.float32
               for e in by_name["exp"])
    assert jaxpr.out_avals[0].dtype == jnp.bfloat16
    # no L x L matrix: nothing larger than batch x chunks x heads x 8 x 8
    # or the states of every chunk
    largest = max(math.prod(v.aval.shape) for e in eqns for v in e.outvars)
    assert largest <= 2 * 4 * 4 * 16 * 16


def test_the_chunk_counter_is_sampled_where_the_scan_is_traced():
    counter = obs.SSM_SCAN_CHUNKS.labels(kind="xla")    # chunk 8: no kernel
    fn = jax.jit(lambda *v: scan_lib.ssm_scan(*v, chunk=8))
    before = counter.value
    fn(*scan_inputs(27))
    assert counter.value - before == 4          # 27 positions in chunks of 8
    fn(*scan_inputs(27))
    assert counter.value - before == 4          # traced once


# -- the model against the reference ------------------------------------------

@pytest.fixture(scope="module")
def f32_pair():
    """Loss, validation loss, logits, rows and gradients of program and
    reference in float32."""
    cfg = small()
    model, flat, nested, tokens = seeded(cfg)
    net = lm.build_hybrid_lm(cfg)

    def loss(p):
        value, _, rows = net.loss(p, jnp.asarray(tokens))
        return value, rows

    (got, rows), grads = jax.value_and_grad(loss, has_aux=True)(nested)
    want, want_grads, want_rows = ref.loss_and_grads(model, flat, tokens)
    held_out = ref.tokens(model, SEED + 1, 2)
    state = trainer.TrainState(params=nested, opt_state=None, batch_stats={},
                               epoch=None, best_val_loss=None)
    evaluated = tasks.HYBRID_LM.evaluate(net, None, state,
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


def test_the_reference_imports_nothing_of_the_program():
    source = REFERENCE.read_text()
    assert "robotic_discovery_platform_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "cumsum" in source.split("def log_decays")[1].split("\ndef ")[0]
    assert source.count("cumsum(") == 1       # the planted fault's alone
    for cfg in (small(), small(layer_pattern="MEMEM*EME", num_layers=9)):
        mine, its = lm.param_shapes(cfg), ref.param_shapes(
            dataclasses.asdict(cfg))
        assert mine == its and list(mine) == list(its)


def test_logits_losses_and_rows_against_the_reference(f32_pair):
    got, want = f32_pair["logits"]
    assert got.shape == (2, 32, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=5e-6)
    for name in ("loss", "val_loss", "accuracy"):
        assert f32_pair[name][0] == pytest.approx(f32_pair[name][1],
                                                  rel=1e-6), name
    np.testing.assert_array_equal(*f32_pair["rows"])
    assert f32_pair["rows"][0].shape == (4, 2)      # the expert layers'


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_against_the_reference(f32_pair, leaf):
    got, want = (np.asarray(g[leaf]) for g in f32_pair["grads"])
    if leaf.endswith("router_bias"):    # picks, never weighs: no gradient
        assert not got.any() and not want.any()
        return
    assert np.linalg.norm(want) > 0
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)


def test_the_configurations_bfloat16_stays_near_the_reference(f32_pair):
    cfg = small(compute_dtype="bfloat16")
    _, _, nested, tokens = seeded(cfg)
    got, _, rows = lm.build_hybrid_lm(cfg).loss(nested, jnp.asarray(tokens))
    assert float(got) == pytest.approx(f32_pair["loss"][1], rel=2e-3)
    assert abs(int(rows.sum()) - int(f32_pair["rows"][1].sum())) <= 8


def test_three_adam_steps_through_the_trainers_step_against_the_reference():
    cfg, tcfg = small(), TrainConfig(seed=11, learning_rate=1e-3)
    model, flat, nested, tokens = seeded(cfg)
    task, tx = tasks.HYBRID_LM, optax.adam(tcfg.learning_rate)
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
        if leaf.endswith("router_bias"):
            assert moved == 0 and not np.asarray(got[leaf]).any()
            continue
        assert moved > 0
        assert np.linalg.norm(np.asarray(got[leaf] - params[leaf])) \
            <= 2e-3 * moved, leaf


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_init_rule_is_the_one_the_reference_re_derives(leaf):
    cfg = small(embed_init_std=4.0)
    params, stats = tasks.HYBRID_LM.init_variables(
        lm.build_hybrid_lm(cfg), jax.random.key(21), TrainConfig(seed=21))
    got, want = flatten_dict(params, sep="/"), ref.init(
        dataclasses.asdict(cfg), 21)
    assert stats == {} and list(got) == list(want)
    np.testing.assert_array_equal(got[leaf], want[leaf])
    value, own = np.asarray(got[leaf]), leaf.rsplit("/", 1)[-1]
    if own.endswith("norm") or own == "D":
        assert (value == 1).all()
    elif own == "router_bias":
        assert (value == 0).all()
    elif own == "A_log":
        assert (np.exp(value) >= 1).all() and (np.exp(value) <= 16).all()
    elif own == "dt_bias":      # softplus gives the drawn step back
        steps = np.log1p(np.exp(value))
        assert (steps >= 0.05 - 1e-6).all() and (steps <= 0.5 + 1e-6).all()
    elif own.startswith("conv"):
        assert (np.abs(value) <= 0.5).all() and np.abs(value).max() > 0.25
    else:
        std = cfg.embed_init_std if leaf == "embed" else cfg.init_std
        assert np.std(value) == pytest.approx(std, rel=0.2)


# -- the layer pattern, and causality -----------------------------------------

@pytest.mark.parametrize("pattern,period", [
    ("ME*E" * 2, 4), ("MEMEM*EME", 9), ("M" * 3, 1), ("*E" * 2, 2),
    ("MM*", 3)])
def test_any_pattern_of_the_three_kinds_runs_by_its_shortest_period(
        pattern, period):
    cfg = small(layer_pattern=pattern, num_layers=len(pattern))
    assert causal_lm.period(cfg.layer_pattern) == period
    shapes = lm.param_shapes(cfg)
    assert shapes[f"layers/{period - 1}/norm"] == (len(pattern) // period, 64)
    assert f"layers/{period}/norm" not in shapes
    model, flat, nested, tokens = seeded(cfg)
    got, _, rows = lm.build_hybrid_lm(cfg).loss(nested, jnp.asarray(tokens))
    assert float(got) == pytest.approx(ref.loss(model, flat, tokens),
                                       rel=1e-6)
    assert rows.shape == (pattern.count("E"), 2)


def test_a_pattern_has_to_name_every_layer_by_a_known_kind():
    assert small().layer_pattern == ("mamba", "experts", "attention",
                                     "experts") * 2
    assert small(layer_pattern=["mamba", "attention"], num_layers=2) \
        .layer_pattern == ("mamba", "attention")
    with pytest.raises(ValueError, match="letters"):
        small(layer_pattern="MX", num_layers=2)
    with pytest.raises(ValueError, match="names 3 layers"):
        small(layer_pattern="ME", num_layers=3)
    with pytest.raises(ValueError, match="names 1 layers"):
        small(layer_pattern=("conv",), num_layers=1)
    with pytest.raises(ValueError, match="groups"):
        small(mamba_heads=3)
    with pytest.raises(ValueError, match="router_scoring"):
        small(router_scoring="tanh")
    with pytest.raises(ValueError, match="expert_act"):
        CausalLMConfig(expert_act="gelu")
    back = from_dict(HybridLMConfig, to_dict(small()))
    assert back == small() and back.conv_dim == 64 + 2 * 2 * 16


def test_logits_depend_on_the_tokens_up_to_their_position_alone():
    """The convolution, the scan and attention are causal through the whole
    model: changing token ``t`` leaves every logit before ``t`` as it was,
    and moves the logits from ``t`` on (within four positions through the
    convolution alone, further through the state and attention)."""
    cfg = small()
    _, _, nested, tokens = seeded(cfg, batch=1)
    net = lm.build_hybrid_lm(cfg)
    base = net.apply(nested, jnp.asarray(tokens))[0]
    for t in (1, 9, 31):
        changed = tokens.copy()
        changed[0, t] = (changed[0, t] + 1) % cfg.vocab_size
        out = net.apply(nested, jnp.asarray(changed))[0]
        np.testing.assert_array_equal(out[0, :t], base[0, :t])
        assert float(jnp.abs(out[0, t] - base[0, t]).max()) > 1e-3
        assert float(jnp.abs(out[0, t:] - base[0, t:]).max(-1).min()) > 0


def test_the_convolution_sees_its_own_position_and_the_three_before():
    x = jnp.zeros((1, 12, 3)).at[0, 5].set(1.0)
    weight = jnp.asarray([[1.0, 2.0, 3.0, 4.0]] * 3)
    out = lm.causal_conv(x, weight, jnp.asarray([0.5, 0.0, -0.5]))
    # weight[:, 3] multiplies the position itself, weight[:, 0] the one
    # three before: an impulse at 5 reads 4, 3, 2, 1 at 5, 6, 7, 8
    np.testing.assert_array_equal(
        out[0, :, 1], [0, 0, 0, 0, 0, 4, 3, 2, 1, 0, 0, 0])
    np.testing.assert_array_equal(out[0, 0], [0.5, 0.0, -0.5])


def test_the_gate_norm_takes_its_statistics_group_by_group():
    y = jnp.concatenate([jnp.full((2, 8), 3.0), jnp.full((2, 8), 0.5)], -1)
    out = lm.grouped_rms_norm(y, jnp.ones(16), 2, 0.0)
    np.testing.assert_allclose(out, jnp.ones((2, 16)), rtol=1e-6)
    whole = lm.grouped_rms_norm(y, jnp.ones(16), 1, 0.0)
    assert float(whole[0, 0]) > 1.3 and float(whole[0, -1]) < 0.3


# -- the expert layer's forms, and the chip's share ---------------------------

def _expert_leaves(cfg, key=3):
    keys = jax.random.split(jax.random.key(key), 16)
    return {name: 0.2 * jax.random.normal(k, shape)
            for k, (name, shape) in zip(keys, moe.expert_shapes(cfg).items())}


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's share test: 4 chips hold 2 of 8 experts each (16 hold 8
    of 128 in the cell); what the shares' routed parts add, with the shared
    expert (which every chip computes alike) counted once, is the uncut
    layer, and is the uncut reference's layer."""
    uncut = small(experts_held=8)
    whole = _expert_leaves(uncut)
    whole["router_bias"] = 0.3 * jax.random.normal(jax.random.key(9), (8,))
    h = jax.random.normal(jax.random.key(4), (2 * 32, 64))
    want, rows = moe.expert_layer(uncut, whole, h, "xla")
    total = moe.shared_expert(moe.expert_matrices(whole, "shared_"), h)
    taken = []
    for chip in range(4):
        share = dataclasses.replace(uncut, experts_held=2,
                                    first_expert=2 * chip,
                                    shared_expert_width=0)
        held = {**whole, **{k: whole[k][2 * chip:2 * chip + 2]
                            for k in ("w_up", "w_down")}}
        part, sizes = moe.expert_layer(share, held, h, "xla")
        total = total + part
        taken += sizes.tolist()
    np.testing.assert_array_equal(taken, rows)
    assert sum(taken) == 64 * uncut.experts_per_token
    np.testing.assert_allclose(total, want, atol=2e-5)
    model = dataclasses.asdict(uncut)
    layer = {"norm": jnp.ones(64), **whole}
    x = h[:32]
    ours, _ = lm.experts_layer(uncut, layer, x[None], "xla")
    theirs, their_rows = ref.experts_layer(model, layer, x)
    np.testing.assert_allclose(ours[0], theirs, atol=2e-5)
    assert int(their_rows.sum()) == 32 * uncut.experts_per_token


def test_the_sigmoid_router_picks_by_score_plus_bias_and_weighs_by_score():
    cfg = small(experts_held=8)
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -1.0, -2.0, -3.0]])
    s = jax.nn.sigmoid(logits[0])
    bias = jnp.zeros(8).at[3].set(1.0)      # lifts expert 3 over 1 and 2
    scores, picked_by = moe.router_scores(cfg, {"router_bias": bias}, logits)
    np.testing.assert_allclose(scores[0], s, rtol=1e-6)
    np.testing.assert_allclose(picked_by[0], s + bias, rtol=1e-6)
    plan = moe.route(cfg, scores, picked_by)
    assert plan["group_sizes"].tolist() == [1, 0, 0, 1, 0, 0, 0, 0]
    # weights: the picked experts' own scores (without the bias),
    # renormalised, times the scaling factor; sorted by expert
    want = 2.5 * jnp.asarray([s[0], s[3]]) / (s[0] + s[3] + 1e-20)
    np.testing.assert_allclose(plan["weight"], want, rtol=1e-6)
    unbiased = moe.route(cfg, *moe.router_scores(
        cfg, {"router_bias": jnp.zeros(8)}, logits))
    assert unbiased["group_sizes"].tolist() == [1, 1, 0, 0, 0, 0, 0, 0]
    # the bias takes no gradient; the scores do
    grad = jax.grad(lambda b: jnp.sum(moe.route(cfg, *moe.router_scores(
        cfg, {"router_bias": b}, logits))["weight"] ** 2))(bias)
    assert not np.asarray(grad).any()


def test_a_squared_relu_expert_has_two_matrices_and_no_gate():
    cfg = small()
    assert list(moe.expert_shapes(cfg)) == [
        "router", "router_bias", "w_up", "w_down", "shared_up", "shared_down"]
    assert list(moe.expert_shapes(CausalLMConfig())) == [
        "router", "w_gate", "w_up", "w_down"]
    gated = CausalLMConfig(shared_expert_width=16, router_scoring="sigmoid")
    assert list(moe.expert_shapes(gated)) == [
        "router", "router_bias", "w_gate", "w_up", "w_down", "shared_gate",
        "shared_up", "shared_down"]
    h = jax.random.normal(jax.random.key(1), (5, 64))
    up, down = (0.2 * jax.random.normal(jax.random.key(k), s)
                for k, s in ((2, (64, 48)), (3, (48, 64))))
    np.testing.assert_allclose(
        moe.shared_expert((up, down), h),
        jnp.square(jnp.maximum(h @ up, 0)) @ down, rtol=1e-5, atol=1e-6)


def test_the_other_families_take_the_new_forms_from_their_configuration():
    """A window-attention model asked for a sigmoid router and a shared
    (gated) expert gets the leaves and runs them."""
    cfg = CausalLMConfig(compute_dtype="float32", kernel_impl="xla",
                         router_scoring="sigmoid", routed_scaling_factor=2.0,
                         shared_expert_width=16)
    net = causal_lm.build_causal_lm(cfg)
    params = net.init(jax.random.key(2))
    assert params["layers"]["0"]["shared_gate"].shape == (2, 64, 16)
    assert not np.asarray(params["layers"]["1"]["router_bias"]).any()
    tokens = jnp.asarray(ref.tokens({"vocab_size": 64, "seq_len": 32}, 3, 2))
    value, grads = jax.value_and_grad(
        lambda p: net.loss(p, tokens)[0])(params)
    assert np.isfinite(float(value))
    assert np.asarray(grads["layers"]["0"]["shared_down"]).any()
    assert not np.asarray(grads["layers"]["0"]["router_bias"]).any()


@pytest.mark.parametrize("family", ["blockdiff", "causal"])
def test_the_existing_families_expert_layer_is_the_parents_bit_for_bit(
        family):
    """``sdar``'s and ``mellum``'s layer at their tiny configurations:
    outputs, rows and every gradient equal to the parent commit's
    ``expert_layer``, bit for bit, in float32 and in bfloat16."""
    cfg = (BlockDiffLMConfig(kernel_impl="xla") if family == "blockdiff"
           else CausalLMConfig(kernel_impl="xla"))
    layer = _expert_leaves(cfg)
    assert list(layer) == ["router", "w_gate", "w_up", "w_down"]
    for dtype in (jnp.float32, jnp.bfloat16):
        h = jax.random.normal(jax.random.key(6), (64, 64)).astype(dtype)

        def run(fn):
            def readout(layer, h):
                mixed, sizes = fn(cfg, layer, h, "xla")
                return jnp.sum(jnp.sin(mixed.astype(jnp.float32))), (
                    mixed, sizes)
            (_, (mixed, sizes)), grads = jax.value_and_grad(
                readout, argnums=(0, 1), has_aux=True)(layer, h)
            return mixed, sizes, grads

        ours, theirs = run(moe.expert_layer), run(parent_moe.expert_layer)
        for got, want in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))


def test_the_three_language_models_run_one_expert_layer():
    for module in (blockdiff_lm, causal_lm, lm):
        assert module.expert_layer is moe.expert_layer
        assert module.rms_norm is moe.rms_norm
    assert lm.next_token_loss is causal_lm.next_token_loss


# -- scopes -------------------------------------------------------------------

@pytest.fixture(scope="module")
def compiled():
    """The ``op_name`` of every instruction of the compiled train and
    evaluation steps (kernels in interpret mode, so that the kernel's scope
    holds operations on a CPU)."""
    cfg = small(kernel_impl="interpret", seq_len=128, ssm_chunk=32)
    task, tx = tasks.HYBRID_LM, optax.adam(1e-4)
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
    ("train", ("rdp.lm.embed",), True),
    ("train", ("rdp.lm.layer", "rdp.ssm.proj"), True),
    ("train", ("rdp.lm.layer", "rdp.ssm.conv"), True),
    ("train", ("rdp.lm.layer", "rdp.ssm.scan"), True),
    ("train", ("rdp.lm.layer", "rdp.ssm.gate"), True),
    ("train", ("rdp.lm.layer", "rdp.attn.proj"), True),
    ("train", ("rdp.lm.layer", "rdp.attn.causal"), True),
    ("train", ("rdp.lm.layer", "rdp.moe.route"), True),
    ("train", ("rdp.lm.layer", "rdp.moe.experts"), True),
    ("train", ("rdp.lm.layer", "rdp.moe.shared"), True),
    ("train", ("rdp.lm.head",), True), ("train", ("rdp.loss",), True),
    ("train", ("rdp.optimizer",), False),
    ("eval", ("rdp.eval", "rdp.lm.layer", "rdp.ssm.scan"), False),
    ("eval", ("rdp.eval", "rdp.lm.layer", "rdp.moe.shared"), False),
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

def _job(tmp_path, epochs, seed=3, **kw):
    cfg = TrainConfig(batch_size=2, epochs=epochs, seed=seed,
                      learning_rate=1e-3,
                      tracking_uri=f"file:{tmp_path / 'mlruns'}",
                      checkpoint_dir=str(tmp_path / "ckpt"), **kw)
    tokens = ref.tokens(dataclasses.asdict(small()), SEED, 20)
    return trainer.train_model(cfg, small(), arrays=(tokens, None),
                               resume=True)


@pytest.mark.parametrize("mode", ["scan", "stream"])
def test_train_model_trains_resumes_and_registers_the_task(
        tmp_path, mode, monkeypatch):
    from robotic_discovery_platform_tpu import tracking
    from robotic_discovery_platform_tpu.tracking import api

    if mode == "scan":  # and the state streamed, the weights as leaf files
        monkeypatch.setattr(api, "_LEAF_FILES_ABOVE", 1000)
        monkeypatch.setattr(trainer, "_DEVICE_SNAPSHOT_MAX_BYTES", 1000)
    rows = obs.MOE_ROUTED_ROWS.value
    first = _job(tmp_path, 2, epoch_mode=mode)
    second = _job(tmp_path, 4, epoch_mode=mode)
    assert (first.epochs_run, second.epochs_run) == (2, 2)
    assert second.registry_version == first.registry_version + 1
    assert set(second.final_metrics) == {"loss", "token_accuracy"}
    assert second.best_val_loss <= first.best_val_loss
    assert (tmp_path / "ckpt" / "streamed").is_dir() == (mode == "scan")
    # the counters: 4 epochs of 8 steps on 2 x 32 positions, 4 expert layers
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
    assert isinstance(model, lm.HybridLM) and model.cfg == small()
    assert set(variables["params"]) == {"embed", "layers", "final_norm",
                                        "head"}
    assert set(variables["params"]["layers"]) == {"0", "1", "2", "3"}
    assert set(variables["params"]["layers"]["0"]) == {
        "norm", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
        "gate_norm", "w_out"}


def test_the_task_is_found_by_configuration_and_by_name():
    assert tasks.task_for(HybridLMConfig()) is tasks.HYBRID_LM
    assert tasks.task_for(CausalLMConfig()) is tasks.CAUSAL_LM
    assert tasks.task_named("hybrid_lm") is tasks.HYBRID_LM
    assert tasks.HYBRID_LM.run_params(TrainConfig(), small())[
        "layer_pattern"] == "ME*EME*E"
    with pytest.raises(ValueError, match="one device"):
        tasks.HYBRID_LM.for_mesh(HybridLMConfig())
    with pytest.raises(ValueError, match="in-memory"):
        tasks.HYBRID_LM.file_data(TrainConfig())
    with pytest.raises(ValueError, match="seq_len"):
        tasks.HYBRID_LM.train_loss(
            lm.build_hybrid_lm(small()), None, None, None,
            jnp.zeros((2, 16), jnp.int32), None)
