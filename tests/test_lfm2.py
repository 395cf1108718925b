"""LFM2's layer kinds in ``models/hybrid_lm`` at a small size on the CPU
(the pattern ``cm*EcEcE``: a short convolution and a dense MLP, attention
and experts, two more convolutions with experts; hidden 64, 4 heads of 16 on
2 key/value heads with a q/k norm and a rotary table, 8 sigmoid-routed
SwiGLU experts of which 2 held, top-2, a tied head over a vocabulary of 64),
on seeded random weights: the program against the benchmark's plain
reference (``perfbench/reference/lfm2-8b-a1b.py``, which imports nothing of
the program) for logits, loss, every gradient and three Adam steps through
the task; the operator and attention against the installed ``transformers``
``lfm2`` modules with the same weights; the convolution's causality; the
chip's share against the uncut layer; the tied leaf; the router's constant.

Tolerances: the program in float32 differs from the reference by the order
of its sums alone (1e-5 relative on a leaf's gradient); against torch's
float32 modules by the same."""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

from robotic_discovery_platform_tpu.models import causal_lm, hybrid_lm as lm
from robotic_discovery_platform_tpu.models import moe
from robotic_discovery_platform_tpu.training import tasks, trainer
from robotic_discovery_platform_tpu.utils.config import (
    BlockDiffLMConfig, CausalLMConfig, HybridLMConfig, TrainConfig)

ROOT = Path(__file__).resolve().parents[1]
REFERENCE = ROOT / "perfbench" / "reference" / "lfm2-8b-a1b.py"
SEED = 5
PATTERN = "cm*EcEcE"


def _own_copy(path: Path):
    """The reference as a module of this file's own (what is compiled here
    must not be found compiled by ``tests/perfbench``'s tests)."""
    found = importlib.util.spec_from_file_location(
        "test_lfm2_reference", path)
    module = sys.modules[found.name] = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


ref = _own_copy(REFERENCE)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**{
        "num_layers": len(PATTERN), "layer_pattern": PATTERN,
        "qk_norm": True, "rope_theta": 100.0, "tie_embeddings": True,
        "router_norm_eps": 1e-6, "routed_scaling_factor": 1.0,
        "expert_act": "swiglu", "shared_expert_width": 0,
        "compute_dtype": "float32", "kernel_impl": "xla", **kw})


LEAVES = sorted(lm.param_shapes(small()))


def seeded(cfg: HybridLMConfig, batch: int = 2):
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
    return {"loss": (float(got), want),
            "val_loss": (float(evaluated["loss"]),
                         ref.eval_loss(model, 0, flat, held_out)),
            "rows": (np.asarray(rows), want_rows),
            "logits": (net.apply(nested, jnp.asarray(held_out))[0],
                       ref.forward(model, flat, held_out)),
            "grads": (flatten_dict(grads, sep="/"), want_grads)}


def test_the_reference_imports_nothing_of_the_program():
    source = REFERENCE.read_text()
    assert "robotic_discovery_platform_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    for cfg in (small(), small(layer_pattern="cmcm*EcEcEcE*E",
                               num_layers=14)):
        mine, its = lm.param_shapes(cfg), ref.param_shapes(
            dataclasses.asdict(cfg))
        assert mine == its and list(mine) == list(its)


def test_logits_losses_and_rows_against_the_reference(f32_pair):
    got, want = f32_pair["logits"]
    assert got.shape == (2, 32, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=5e-6)
    for name in ("loss", "val_loss"):
        assert f32_pair[name][0] == pytest.approx(f32_pair[name][1],
                                                  rel=1e-6), name
    np.testing.assert_array_equal(*f32_pair["rows"])
    assert f32_pair["rows"][0].shape == (3, 2)      # the expert layers'


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
    task, tx = tasks.task_for(cfg), optax.adam(tcfg.learning_rate)
    assert task is tasks.HYBRID_LM
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
    cfg = small(embed_init_std=0.05)
    params, stats = tasks.HYBRID_LM.init_variables(
        lm.build_hybrid_lm(cfg), jax.random.key(21), TrainConfig(seed=21))
    got, want = flatten_dict(params, sep="/"), ref.init(
        dataclasses.asdict(cfg), 21)
    assert stats == {} and list(got) == list(want)
    np.testing.assert_array_equal(got[leaf], want[leaf])
    value, own = np.asarray(got[leaf]), leaf.rsplit("/", 1)[-1]
    if own.endswith("norm"):
        assert (value == 1).all()
    elif own == "router_bias":
        assert (value == 0).all()
    elif own == "conv_taps":    # uniform on +- 1 / sqrt(3)
        assert (np.abs(value) <= 3 ** -0.5).all()
        assert np.abs(value).max() > 0.5
    else:
        std = cfg.embed_init_std if leaf == "embed" else cfg.init_std
        assert np.std(value) == pytest.approx(std, rel=0.2)


# -- the tied head -------------------------------------------------------------

def test_the_tied_leaf_is_one_leaf_and_its_gradient_the_sum_of_both_uses():
    """No ``head`` leaf (so no second gradient, pair of moments or file of
    the streamed checkpoint); the embedding's gradient is what an untied
    model with ``head = embed^T`` gives its embedding plus, transposed,
    what it gives its head."""
    cfg = small()
    _, flat, nested, tokens = seeded(cfg)
    assert "head" not in lm.param_shapes(cfg) and "head" not in nested
    state = trainer.task_state(tasks.HYBRID_LM, lm.build_hybrid_lm(cfg),
                               optax.adam(1e-4), jax.random.key(0),
                               TrainConfig(seed=3))
    assert set(state.params) == set(state.opt_state[0].mu) == {
        "embed", "layers", "final_norm"}
    tied = jax.grad(lambda p: lm.build_hybrid_lm(cfg).loss(
        p, jnp.asarray(tokens))[0])(nested)
    loose_cfg = small(tie_embeddings=False)
    loose = jax.grad(lambda p: lm.build_hybrid_lm(loose_cfg).loss(
        p, jnp.asarray(tokens))[0])({**nested, "head": flat["embed"].T})
    # both uses weigh: neither part is noise beside the other
    parts = [float(jnp.linalg.norm(loose[k])) for k in ("head", "embed")]
    assert 0.1 < parts[0] / parts[1] < 10
    np.testing.assert_allclose(tied["embed"],
                               loose["embed"] + loose["head"].T, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(tied["final_norm"], loose["final_norm"],
                               rtol=1e-5)


# -- the operator, against the reference and the installed transformers --------

def _operator_inputs(cfg, length=24):
    keys = jax.random.split(jax.random.key(17), 8)
    h = cfg.hidden_size
    conv = {"norm": jnp.ones(h),
            "w_in": 0.2 * jax.random.normal(keys[0], (h, 3 * h)),
            "conv_taps": jax.random.normal(keys[1],
                                           (h, cfg.shortconv_kernel)),
            "w_out": 0.2 * jax.random.normal(keys[2], (h, h))}
    d = cfg.head_dim
    q, kv = cfg.num_heads * d, cfg.num_kv_heads * d
    attn = {"norm": jnp.ones(h),
            "wq": 0.2 * jax.random.normal(keys[3], (h, q)),
            "wk": 0.2 * jax.random.normal(keys[4], (h, kv)),
            "wv": 0.2 * jax.random.normal(keys[5], (h, kv)),
            "q_norm": 1 + 0.1 * jax.random.normal(keys[6], (d,)),
            "k_norm": 1 + 0.1 * jax.random.normal(keys[6], (d,))[::-1],
            "wo": 0.2 * jax.random.normal(keys[7], (q, h))}
    x = jax.random.normal(jax.random.key(18), (2, length, h))
    return conv, attn, x


def test_the_operator_alone_is_the_references():
    cfg = small()
    conv, _, x = _operator_inputs(cfg)
    ours, none = lm.shortconv_layer(cfg, conv, x, "xla")
    assert none is None
    for b in range(2):
        theirs = ref.shortconv_layer(dataclasses.asdict(cfg), conv, x[b])
        np.testing.assert_allclose(ours[b], theirs, rtol=1e-5, atol=1e-5)


def _hf_config(cfg):
    lfm2 = pytest.importorskip("transformers.models.lfm2.modeling_lfm2")
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config

    hf = Lfm2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.mlp_width, num_hidden_layers=2,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, conv_bias=False,
        conv_L_cache=cfg.shortconv_kernel, block_auto_adjust_ff_dim=False,
        layer_types=["conv", "full_attention"])
    hf._attn_implementation = "eager"
    return lfm2, hf


def test_the_operator_is_transformers_lfm2_short_conv_with_the_same_weights():
    """``Lfm2ShortConv.slow_forward`` (torch, float32) on ``RMSNorm(x)``:
    the branch the program adds to the stream."""
    torch = pytest.importorskip("torch")
    cfg = small()
    lfm2, hf = _hf_config(cfg)
    conv, _, x = _operator_inputs(cfg)
    module = lfm2.Lfm2ShortConv(hf, 0)
    with torch.no_grad():
        module.in_proj.weight.copy_(torch.tensor(np.asarray(conv["w_in"]).T))
        module.out_proj.weight.copy_(torch.tensor(
            np.asarray(conv["w_out"]).T))
        module.conv.weight.copy_(torch.tensor(
            np.asarray(conv["conv_taps"])[:, None, :]))
        u = moe.rms_norm(x, conv["norm"], cfg.rms_norm_eps)
        want = module.slow_forward(torch.tensor(np.asarray(u))).numpy()
    ours, _ = lm.shortconv_layer(cfg, conv, x, "xla")
    np.testing.assert_allclose(ours - x, want, rtol=1e-5, atol=1e-5)


def test_attention_is_transformers_lfm2_attention_with_the_same_weights():
    """``Lfm2Attention`` (eager, torch, float32): q and k normed head by
    head, rotated by the rotary table of theta, causal."""
    torch = pytest.importorskip("torch")
    cfg = small()
    lfm2, hf = _hf_config(cfg)
    _, attn, x = _operator_inputs(cfg)
    length = x.shape[1]
    module = lfm2.Lfm2Attention(hf, 1)
    module.eval()
    with torch.no_grad():
        for ours, theirs in (("wq", module.q_proj), ("wk", module.k_proj),
                             ("wv", module.v_proj), ("wo", module.out_proj)):
            theirs.weight.copy_(torch.tensor(np.asarray(attn[ours]).T))
        module.q_layernorm.weight.copy_(torch.tensor(
            np.asarray(attn["q_norm"])))
        module.k_layernorm.weight.copy_(torch.tensor(
            np.asarray(attn["k_norm"])))
        u = torch.tensor(np.asarray(moe.rms_norm(x, attn["norm"],
                                                 cfg.rms_norm_eps)))
        positions = torch.arange(length)[None].expand(2, -1)
        cos, sin = lfm2.Lfm2RotaryEmbedding(hf)(u, positions)
        mask = torch.full((length, length), float("-inf")).triu(1)[None, None]
        want, _ = module(u, (cos, sin), mask)
    table = causal_lm.rope_table(
        lm.RotaryConfig(theta=cfg.rope_theta), cfg.head_dim,
        jnp.arange(length))
    ours, _ = lm.attention_layer(cfg, attn, x, "xla", table)
    np.testing.assert_allclose(ours - x, want.numpy(), rtol=2e-5, atol=2e-5)
    theirs = ref.attention_layer(dataclasses.asdict(cfg), attn, x[0])
    np.testing.assert_allclose(ours[0], theirs, rtol=2e-5, atol=2e-5)
    # without the norm or the table it is another layer
    for other in (dataclasses.replace(cfg, qk_norm=False), cfg):
        off, _ = lm.attention_layer(
            other, attn, x, "xla", table if other is not cfg else None)
        assert float(jnp.abs(off - ours).max()) > 1e-2


def test_the_convolution_does_not_see_ahead():
    """Position ``t``'s output does not move when ``t + 1``'s input does,
    and moves with its own and the two before it (three taps, no bias)."""
    cfg = small()
    conv, _, x = _operator_inputs(cfg)
    base, _ = lm.shortconv_layer(cfg, conv, x, "xla")
    for t in (0, 7, 22):
        moved, _ = lm.shortconv_layer(cfg, conv, x.at[:, t + 1].add(1.0),
                                      "xla")
        np.testing.assert_array_equal(moved[:, :t + 1], base[:, :t + 1])
        # t + 1 .. t + 3 see the change, t + 4 no longer
        assert float(jnp.abs(moved[:, t + 1:t + 4] - base[:, t + 1:t + 4])
                     .max(-1).min()) > 1e-4
        np.testing.assert_array_equal(moved[:, t + 4:], base[:, t + 4:])
    impulse = jnp.zeros((1, 8, 2)).at[0, 3].set(1.0)
    out = lm.causal_conv(impulse, jnp.asarray([[1.0, 2.0, 3.0]] * 2))
    np.testing.assert_array_equal(out[0, :, 0], [0, 0, 0, 3, 2, 1, 0, 0])
    ones = jnp.ones_like(impulse)
    gated = lm.shortconv_mix(impulse, 2 * ones, ones,
                             jnp.asarray([[1.0, 2.0, 3.0]] * 2))
    np.testing.assert_array_equal(gated[0, :, 1], [0, 0, 0, 6, 4, 2, 0, 0])


def test_one_convolution_serves_both_operators():
    """``causal_conv`` with a bias is the Mamba-2 mixer's, bit for bit what
    it was; without one it is the short convolution's."""
    x = jax.random.normal(jax.random.key(2), (2, 16, 6))
    w = jax.random.normal(jax.random.key(3), (6, 4))
    b = jax.random.normal(jax.random.key(4), (6,))
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    was = b.astype(jnp.float32)
    for k in range(4):
        was = was + padded[:, k:k + 16].astype(jnp.float32) * w[:, k]
    np.testing.assert_array_equal(lm.causal_conv(x, w, b), was)
    np.testing.assert_allclose(lm.causal_conv(x, w), was - b, atol=1e-6)
    assert lm.causal_conv(x.astype(jnp.bfloat16), w).dtype == jnp.float32


# -- the chip's share, and the router's constant -------------------------------

def _expert_leaves(cfg, key=3):
    keys = jax.random.split(jax.random.key(key), 16)
    return {name: 0.2 * jax.random.normal(k, shape)
            for k, (name, shape) in zip(keys, moe.expert_shapes(cfg).items())}


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """4 chips hold 2 of 8 experts each (4 hold 8 of 32 in the cell); what
    the shares add is the uncut layer (there is no shared expert to count
    once), and is the uncut reference's layer."""
    uncut = small(experts_held=8)
    whole = _expert_leaves(uncut)
    assert list(whole) == ["router", "router_bias", "w_gate", "w_up",
                           "w_down"]
    whole["router_bias"] = 0.3 * jax.random.normal(jax.random.key(9), (8,))
    h = jax.random.normal(jax.random.key(4), (2 * 32, 64))
    want, rows = moe.expert_layer(uncut, whole, h, "xla")
    total, taken = 0.0, []
    for chip in range(4):
        share = dataclasses.replace(uncut, experts_held=2,
                                    first_expert=2 * chip)
        held = {**whole, **{k: whole[k][2 * chip:2 * chip + 2]
                            for k in ("w_gate", "w_up", "w_down")}}
        part, sizes = moe.expert_layer(share, held, h, "xla")
        total = total + part
        taken += sizes.tolist()
    np.testing.assert_array_equal(taken, rows)
    assert sum(taken) == 64 * uncut.experts_per_token
    np.testing.assert_allclose(total, want, atol=2e-5)
    layer = {"norm": jnp.ones(64), **whole}
    ours, _ = lm.experts_layer(uncut, layer, h[None, :32], "xla")
    theirs, their_rows = ref.experts_layer(dataclasses.asdict(uncut), layer,
                                           h[:32])
    np.testing.assert_allclose(ours[0], theirs, atol=2e-5)
    assert int(their_rows.sum()) == 32 * uncut.experts_per_token


def test_the_routers_constant_is_the_configurations_and_1e_20_by_default():
    """``nemotron``'s routing is what it was: every family's configuration
    carries the constant as a plain field that is 1e-20 unless given, and
    the default divides by ``sum + 1e-20`` bit for bit; this model's 1e-6 is
    another number."""
    probs = jax.nn.sigmoid(3 * jax.random.normal(jax.random.key(1), (64, 8)))
    picked = probs + 0.1 * jax.random.normal(jax.random.key(2), (8,))
    default = HybridLMConfig()
    for family in (HybridLMConfig, CausalLMConfig, BlockDiffLMConfig):
        assert family().router_norm_eps == 1e-20
    top = jnp.take_along_axis(probs, jax.lax.top_k(picked, 2)[1], -1)
    was = (top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
           * default.routed_scaling_factor)
    plan = moe.route(default, probs, picked)
    # the plan's weights are ``was`` in the plan's order
    np.testing.assert_array_equal(
        np.sort(np.asarray(plan["weight"])), np.sort(
            np.asarray(was).reshape(-1)))
    ours = moe.route(dataclasses.replace(default, router_norm_eps=1e-6),
                     probs, picked)
    assert not np.array_equal(np.sort(np.asarray(ours["weight"])),
                              np.sort(np.asarray(was).reshape(-1)))
    np.testing.assert_allclose(np.sort(np.asarray(ours["weight"])),
                               np.sort(np.asarray(was).reshape(-1)),
                               rtol=2e-6)


# -- the pattern, the scopes and the task --------------------------------------

@pytest.mark.parametrize("pattern,period", [
    (PATTERN, 8), ("cmcm*EcEcEcE*E", 14), ("cE" * 3, 2), ("cm*m", 4),
    ("McE*", 4)])
def test_any_pattern_of_the_five_kinds_runs_by_its_shortest_period(
        pattern, period):
    cfg = small(layer_pattern=pattern, num_layers=len(pattern))
    assert causal_lm.period(cfg.layer_pattern) == period
    shapes = lm.param_shapes(cfg)
    assert f"layers/{period}/norm" not in shapes and "head" not in shapes
    if "M" in pattern:      # the reference of this file has no mixer
        net = lm.build_hybrid_lm(cfg)
        value, _, rows = net.loss(net.init(jax.random.key(0)),
                                  jnp.zeros((1, 32), jnp.int32))
        assert np.isfinite(float(value))
    else:
        model, flat, nested, tokens = seeded(cfg)
        got, _, rows = lm.build_hybrid_lm(cfg).loss(nested,
                                                    jnp.asarray(tokens))
        assert float(got) == pytest.approx(ref.loss(model, flat, tokens),
                                           rel=1e-6)
    assert rows.shape == (pattern.count("E"), 2)


def test_logits_depend_on_the_tokens_up_to_their_position_alone():
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


def test_the_compiled_step_holds_the_new_scopes():
    """The ``op_name`` of the compiled train step's instructions: every
    scope of this model, forward and backward, and none of the mixer's."""
    import re

    from perfbench.lib import spans as spans_lib

    cfg = small(kernel_impl="interpret", seq_len=128)
    task, tx = tasks.HYBRID_LM, optax.adam(1e-4)
    model = task.build(cfg)
    state = jax.eval_shape(lambda: trainer.task_state(
        task, model, tx, jax.random.key(0), TrainConfig()))
    step = jax.jit(trainer.core_train_step(model, tx, None, task=task))
    paths = set(re.findall(r'op_name="([^"]+)"', step.lower(
        state, jax.ShapeDtypeStruct((2, 128), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32)).compile().as_text()))
    for scope in ("rdp.shortconv.proj", "rdp.shortconv.mix", "rdp.mlp",
                  "rdp.attn.proj", "rdp.attn.causal", "rdp.moe.route",
                  "rdp.moe.experts", "rdp.lm.head", "rdp.loss"):
        found = [p for p in paths if spans_lib.under(scope, p)]
        assert found, scope
        assert any("transpose(" in p for p in found), scope
        if scope not in ("rdp.lm.head", "rdp.loss"):
            assert all(spans_lib.under("rdp.lm.layer", p) for p in found)
    assert any(spans_lib.under("rdp.attn.rope", p) for p in paths)
    for scope in ("rdp.ssm.proj", "rdp.ssm.scan", "rdp.moe.shared"):
        assert not any(spans_lib.under(scope, p) for p in paths), scope


def test_train_model_trains_resumes_and_registers_the_tied_model(
        tmp_path, monkeypatch):
    """The normal path: ``train_model`` through the task on resident token
    data, a streamed checkpoint with no file for a head, a resume, and a
    registered model that loads back as the task's, tied."""
    from robotic_discovery_platform_tpu import tracking
    from robotic_discovery_platform_tpu.tracking import api

    monkeypatch.setattr(api, "_LEAF_FILES_ABOVE", 1000)
    monkeypatch.setattr(trainer, "_DEVICE_SNAPSHOT_MAX_BYTES", 1000)
    tokens = ref.tokens(dataclasses.asdict(small()), SEED, 20)

    def job(epochs):
        cfg = TrainConfig(batch_size=2, epochs=epochs, seed=3,
                          learning_rate=1e-3, epoch_mode="scan",
                          tracking_uri=f"file:{tmp_path / 'mlruns'}",
                          checkpoint_dir=str(tmp_path / "ckpt"))
        return trainer.train_model(cfg, small(), arrays=(tokens, None),
                                   resume=True)

    first, second = job(2), job(4)
    assert (first.epochs_run, second.epochs_run) == (2, 2)
    assert second.registry_version == first.registry_version + 1
    assert second.best_val_loss <= first.best_val_loss
    import json

    manifests = list((tmp_path / "ckpt" / "streamed").rglob("manifest.json"))
    assert manifests
    for manifest in manifests:      # key path -> the leaf's one file
        keys = list(json.loads(manifest.read_text()))
        # parameters and Adam's two moments: one file each for the tied leaf
        assert sum("'embed'" in k for k in keys) == 3, keys
        assert not any("'head'" in k for k in keys)
    path = tracking.resolve_model_uri("models:/Actuator-Segmenter/latest")
    model, variables = tracking.load_model(path.as_posix())
    assert isinstance(model, lm.HybridLM) and model.cfg == small()
    assert set(variables["params"]) == {"embed", "layers", "final_norm"}
    assert set(variables["params"]["layers"]["0"]) == {
        "norm", "w_in", "conv_taps", "w_out"}
    assert set(variables["params"]["layers"]["2"]) == {
        "norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"}
    assert tasks.HYBRID_LM.run_params(TrainConfig(), small())[
        "layer_pattern"] == PATTERN
