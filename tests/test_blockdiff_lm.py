"""The block-diffusion language-model task at a small size on the CPU (2
layers, hidden 64, 8 experts of which 2 held, top-2, L = 32, block 4,
vocabulary 64), on seeded random weights: the program against the
benchmark's plain reference (``perfbench/reference/sdar-30b-a3b.py``, which
imports nothing of the program), the attention kernel in interpret mode
against the dense mask, the grouped product under lopsided routings, the
chip's share against the uncut layer, the noising rule, the streamed train
state, and the task through ``train_model``.

Tolerances: the program in float32 differs from the reference by the order
of its sums alone (1e-5 relative on a leaf's gradient); in bfloat16, the
configuration's compute type, by bfloat16's 8 bits of mantissa through two
layers (a few percent on a gradient leaf at this size, where a flipped
routing decision is a visible share of the rows)."""

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

from robotic_discovery_platform_tpu.models import blockdiff_lm as lm
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.ops.pallas import (
    blockdiff_attention as attn)
from robotic_discovery_platform_tpu.ops.pallas.grouped_matmul import (
    grouped_matmul)
from robotic_discovery_platform_tpu.training import (
    checkpoint as checkpoint_lib, data as data_lib, tasks, trainer)
from robotic_discovery_platform_tpu.utils.config import (
    BlockDiffLMConfig, ModelConfig, TrainConfig)

ROOT = Path(__file__).resolve().parents[1]


def _own_copy(path: Path):
    """The reference as a module of this file's own, not the one
    ``spec.load_module`` keeps for the process: what is jitted and compiled
    here must not be found compiled by ``tests/perfbench``'s tests, which
    count the reference's compiles and may run after these in one worker."""
    found = importlib.util.spec_from_file_location(
        "test_blockdiff_lm_reference", path)
    module = sys.modules[found.name] = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


ref = _own_copy(ROOT / "perfbench" / "reference" / "sdar-30b-a3b.py")
LEAVES = sorted(lm.param_shapes(BlockDiffLMConfig()))
SEED = 5


def small(**kw) -> BlockDiffLMConfig:
    return BlockDiffLMConfig(**{"compute_dtype": "float32",
                                "kernel_impl": "xla", **kw})


def seeded(cfg: BlockDiffLMConfig, batch: int = 2):
    """(reference's model dict, flat weights, nested weights, tokens,
    masked, t) from one seed."""
    model = dataclasses.asdict(cfg)
    flat = {k: jnp.asarray(v) for k, v in ref.init(model, SEED).items()}
    nested = unflatten_dict({tuple(k.split("/")): v
                             for k, v in flat.items()})
    tokens = ref.tokens(model, SEED, batch)
    masked, t = ref.noise(7, ref.TRAIN_NOISE, 0, batch, cfg.seq_len,
                          cfg.block_length)
    return model, flat, nested, tokens, masked, t


@pytest.fixture(scope="module")
def f32_pair():
    """Loss and gradients of program and reference in float32."""
    cfg = small()
    model, flat, nested, tokens, masked, t = seeded(cfg)

    def loss(p):
        logits, rows = lm.forward(cfg, p, jnp.asarray(tokens),
                                  jnp.asarray(masked))
        return lm.diffusion_loss(logits, jnp.asarray(tokens),
                                 jnp.asarray(masked), jnp.asarray(t)), (
            logits, rows)

    (got, (logits, rows)), grads = jax.value_and_grad(
        loss, has_aux=True)(nested)
    want, want_grads, want_rows = ref.loss_and_grads(
        model, flat, tokens, masked, t)
    return {"loss": (float(got), want), "rows": (np.asarray(rows),
                                                 want_rows),
            "logits": (logits, ref.forward(model, flat, tokens, masked)),
            "grads": ({"/".join(k): v
                       for k, v in flatten_dict(grads).items()},
                      want_grads)}


def test_the_reference_imports_nothing_of_the_program():
    source = (ROOT / "perfbench" / "reference"
              / "sdar-30b-a3b.py").read_text()
    assert "robotic_discovery_platform_tpu" not in source
    assert lm.param_shapes(BlockDiffLMConfig()) == ref.param_shapes(
        dataclasses.asdict(BlockDiffLMConfig()))


def test_logits_loss_and_rows_against_the_reference(f32_pair):
    got, want = f32_pair["logits"]
    assert got.shape == (2, 32, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert f32_pair["loss"][0] == pytest.approx(f32_pair["loss"][1],
                                                rel=1e-6)
    np.testing.assert_array_equal(*f32_pair["rows"])


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_against_the_reference(f32_pair, leaf):
    got, want = (np.asarray(g[leaf]) for g in f32_pair["grads"])
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_configurations_bfloat16_stays_near_the_reference(impl):
    cfg = small(compute_dtype="bfloat16", kernel_impl=impl)
    model, flat, nested, tokens, masked, _ = seeded(cfg)
    got, _ = lm.forward(cfg, nested, jnp.asarray(tokens),
                        jnp.asarray(masked))
    want = ref.forward(model, flat, tokens, masked)
    # the worst logit of 2 x 32 x 64 against the largest: bfloat16 rounds
    # every product's operands to 0.4%, through two layers and the head
    # (this seed reads 2.1% under the interpreted kernels)
    assert float(jnp.abs(got - want).max()) < 0.03 * float(
        jnp.abs(want).max())


def _samples():
    return {form: obs.ATTN_QK_PREP.labels(form=form).value
            for form in ("fused", "xla")}


@pytest.fixture(scope="module")
def fused_pair():
    """Loss and gradients of a model whose heads fill a 128-lane tile, under
    the interpreted kernels and under the dense forms, in float32; and the
    samples each trace added to ``rdp_attn_qk_prep_total``."""
    got = {}
    for impl in ("interpret", "xla"):
        cfg = small(num_heads=2, num_kv_heads=1, head_dim=128,
                    kernel_impl=impl)
        _, _, nested, tokens, masked, t = seeded(cfg)

        def loss(p):
            logits, _ = lm.forward(cfg, p, jnp.asarray(tokens),
                                   jnp.asarray(masked))
            return lm.diffusion_loss(logits, jnp.asarray(tokens),
                                     jnp.asarray(masked), jnp.asarray(t))

        before = _samples()
        value, grads = jax.value_and_grad(loss)(nested)
        got[impl] = ({"loss": value,
                      **{"/".join(k): v
                         for k, v in flatten_dict(grads).items()}},
                     {k: v - before[k] for k, v in _samples().items()})
    return got


@pytest.mark.parametrize("leaf", ["loss"] + LEAVES)
def test_q_and_k_prepared_in_one_pass_are_the_dense_chains(fused_pair, leaf):
    """``ops/pallas/qk_prep``: the model under ``impl="interpret"`` (q and k
    of every layer through the fused pass, forward and backward) against
    ``impl="xla"``, by the order of float32's sums."""
    (got, fused), (want, dense) = fused_pair["interpret"], fused_pair["xla"]
    assert fused["fused"] > 0 and fused["fused"] % 2 == 0
    assert fused["xla"] == 0 and dense["fused"] == 0 and dense["xla"] > 0
    mine, its = np.asarray(got[leaf]), np.asarray(want[leaf])
    assert np.linalg.norm(mine - its) <= 1e-5 * np.linalg.norm(its)


@pytest.mark.parametrize("length,d", [(32, 16), (50, 128)])
def test_the_table_built_once_is_what_rotary_computed_a_layer(length, d):
    """``rotary`` (angles rebuilt from the positions at every call) is what
    a layer did until the table left the layer scan."""
    from robotic_discovery_platform_tpu.ops.pallas import qk_prep

    cfg = small(head_dim=d, rope_theta=1e4)
    x = jax.random.normal(jax.random.key(2), (2, 4, 2 * length, d))
    positions = jnp.tile(jnp.arange(length), 2)
    table = lm.rotary_table(cfg, length)
    assert table[0].shape == table[1].shape == (2 * length, d)
    np.testing.assert_array_equal(
        qk_prep.apply_rotary(x, table),
        lm.rotary(x, positions, cfg.rope_theta))


def test_three_adam_steps_through_the_trainers_step_against_the_reference():
    cfg, tcfg = small(), TrainConfig(seed=11, learning_rate=1e-3)
    model, flat, nested, tokens, _, _ = seeded(cfg)
    task, tx = tasks.BLOCKDIFF_LM, optax.adam(tcfg.learning_rate)
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
                          jnp.full(len(tokens), tcfg.seed, jnp.int32))
        params, opt, want, _, rows = ref.train_step(
            model, tcfg.learning_rate, tcfg.seed, params, opt, tokens)
        assert float(out["loss"]) == pytest.approx(want, rel=1e-5)
        assert float(out["routed_rows"]) == rows.sum()
        np.testing.assert_array_equal(out["expert_load"], rows.sum(0))
    got = {"/".join(k): v for k, v in flatten_dict(state.params).items()}
    for leaf in LEAVES:
        moved = np.linalg.norm(np.asarray(params[leaf] - flat[leaf]))
        assert moved > 0
        assert np.linalg.norm(np.asarray(got[leaf] - params[leaf])) \
            <= 2e-3 * moved, leaf


# -- attention under the block-diffusion mask ---------------------------------

def test_the_mask_has_its_live_pairs_and_matches_the_references():
    for length, block in ((32, 4), (100, 4), (24, 8)):
        ids = np.arange(2 * length)
        m = attn.live(ids[:, None], ids[None, :], length, block)
        assert m.sum() == attn.live_pairs(length, block)
        np.testing.assert_array_equal(m, ref.mask_matrix(length, block))


@pytest.mark.parametrize("length,block,padded", [(32, 4, 64), (100, 4, 256),
                                                 (24, 8, 128)])
def test_the_kernels_packed_form_of_the_mask_is_the_mask(length, block,
                                                         padded):
    """What the kernel evaluates (the row's two intervals, packed by the
    host) against the definition; a padding row sees the first block alone
    and no row sees a padding key."""
    rows = attn.packed_rows(padded, length, block)
    keys = np.arange(padded, dtype=np.int32)
    got = np.asarray(attn.live_packed(rows[:, None], keys[None, :], length,
                                      block))
    ids = np.arange(2 * length)
    np.testing.assert_array_equal(
        got[:2 * length, :2 * length],
        attn.live(ids[:, None], ids[None, :], length, block))
    assert not got[:, 2 * length:].any()
    assert (got[2 * length:].sum(axis=1) == block).all()
    assert got[2 * length:, :block].all()


def test_the_kernel_is_written_against_this_jax():
    """``_splash_mask`` leans on internals of the splash kernels (a private
    base class, ``q_sequence`` handed on unchanged): after an upgrade read
    the two tests below before this line is moved."""
    assert jax.__version__ == "0.9.0"


@pytest.mark.parametrize("is_dkv", [False, True], ids=["fwd_dq", "dkv"])
@pytest.mark.parametrize("length,block,tile,padded", [(500, 4, 128, 1024),
                                                      (256, 4, 128, 512)])
def test_the_kernels_block_map_visits_the_live_tiles_alone(
        length, block, tile, padded, is_dkv):
    """What splash builds from the packed rows, tile by tile against
    ``live()``: a tile is visited if and only if ``M`` leaves a pair of it
    live (a padding row sees the first block), a tile marked whole is
    whole, and the rows reach the kernel as they were packed."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm, splash_attention_mask_info as mi)

    heads = sm.MultiHeadMask(
        [attn._splash_mask(padded, length, block, "blockdiff")] * 2)
    n, ids = 2 * length, np.arange(2 * length)
    full = np.zeros((padded, padded), bool)
    full[:n, :n] = attn.live(ids[:, None], ids[None, :], length, block)
    full[n:, :block] = True
    tiles = full.reshape(padded // tile, tile, padded // tile, tile)
    info, rule = mi.process_mask(heads, (tile, tile), is_dkv=is_dkv,
                                 shrink_grid=False)
    got = np.asarray(info.block_mask[0])
    np.testing.assert_array_equal(got > 0, tiles.any(axis=(1, 3)))
    np.testing.assert_array_equal(got == 2, tiles.all(axis=(1, 3)))
    assert 0 < (got > 0).sum() < got.size          # some tiles are skipped
    # the mask is computed in the kernel (no tile of it is stored), from
    # the rows as packed
    assert info.partial_mask_blocks is None and rule is not None
    np.testing.assert_array_equal(
        info.q_sequence, attn.packed_rows(padded, length, block))
    # the grid the kernel runs (shrunk) visits the same number of tiles
    shrunk = mi.process_mask(heads, (tile, tile), is_dkv=is_dkv)[0]
    assert (np.asarray(shrunk.block_mask[0]) > 0).sum() == (got > 0).sum()


@pytest.fixture(scope="module")
def attention_pair():
    """Forward and the three gradients, kernel (interpreter) against dense
    M: 8 query heads on 2 key/value heads, 2 x 100 positions, which is no
    multiple of the 128-wide tile."""
    length, block, b, h, g, d = 100, 4, 2, 8, 2, 64
    keys = jax.random.split(jax.random.key(0), 4)
    q = 0.3 * jax.random.normal(keys[0], (b, h, 2 * length, d))
    k = jax.random.normal(keys[1], (b, g, 2 * length, d))
    v = jax.random.normal(keys[2], (b, g, 2 * length, d))
    w = jax.random.normal(keys[3], (b, h, 2 * length, d))

    def run(impl):
        def f(q, k, v):
            out = attn.blockdiff_attention(q, k, v, seq_len=length,
                                           block=block, impl=impl)
            return jnp.sum(out * w), out

        (_, out), grads = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(
            q, k, v)
        return {"out": out, "dq": grads[0], "dk": grads[1], "dv": grads[2]}

    return run("interpret"), run("xla")


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_the_attention_kernel_against_the_dense_mask(attention_pair, what):
    got, want = (np.asarray(r[what]) for r in attention_pair)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_attention_refuses_positions_that_are_not_two_copies():
    q = jnp.zeros((1, 2, 60, 16))
    with pytest.raises(ValueError, match="two copies"):
        attn.blockdiff_attention(q, q, q, seq_len=32, block=4, impl="xla")


# -- the grouped product ------------------------------------------------------

@pytest.mark.parametrize("sizes", [[256, 0, 0], [0, 100, 50], [10, 0, 20],
                                   [0, 0, 0]],
                         ids=["all-to-one", "none-to-first", "none-to-middle",
                              "no-rows"])
def test_the_grouped_product_under_lopsided_routings(sizes):
    keys = jax.random.split(jax.random.key(1), 2)
    lhs = jax.random.normal(keys[0], (256, 32))
    rhs = jax.random.normal(keys[1], (3, 32, 128))
    sizes = jnp.asarray(sizes, jnp.int32)
    inside = (jnp.arange(256) < sizes.sum())[:, None]

    def loss(impl):
        def f(lhs, rhs):
            out = grouped_matmul(jnp.where(inside, lhs, 0), rhs, sizes,
                                 impl=impl, out_dtype=jnp.float32)
            return jnp.sum(jnp.where(inside, out, 0) ** 2)
        return jax.value_and_grad(f, (0, 1))(lhs, rhs)

    (got, got_grads), (want, want_grads) = loss("interpret"), loss("xla")
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-3 * (1 + float(
            jnp.abs(w).max())))


def test_routing_drops_nothing_whatever_the_counts():
    """Every token's rows for held experts are in the plan once, under a
    routing that sends everything to one held expert and under one that
    sends nothing here."""
    cfg = small(num_experts=8, experts_held=2, experts_per_token=2)
    for favoured, rows in ((0, 64), (5, 0)):
        probs = jnp.full((64, 8), 0.01).at[:, favoured].set(0.9)
        probs = probs.at[:, 7].set(0.05)
        plan = lm.route(cfg, probs / probs.sum(-1, keepdims=True))
        assert int(plan["rows"]) == rows
        assert plan["group_sizes"].tolist() == [rows, 0]
        assert sorted(plan["token"][:rows].tolist()) == list(range(rows))
    # through the layer, in chunks of 32 rows: a router that sends every
    # token to both held experts fills all four chunks (128 rows, none
    # dropped), one that sends none here runs no chunk and adds nothing
    layer = {k.split("/")[1]: v[0] for k, v in
             seeded(cfg)[1].items() if k.startswith("layers/")}
    h = 1.0 + jnp.abs(jax.random.normal(jax.random.key(2), (64, 64)))
    chunked = dataclasses.replace(cfg, moe_chunk_rows=32)
    for held_columns, rows in ((slice(0, 2), 128), (slice(6, 8), 0)):
        layer["router"] = jnp.zeros((64, 8)).at[:, held_columns].set(1.0)
        out, sizes = lm.expert_layer(chunked, layer, h, "xla")
        assert sizes.tolist() == [rows // 2, rows // 2]
        assert bool(jnp.any(out != 0)) == bool(rows)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's share test: 8 chips hold 2 of 16 experts each; what the
    8 shares add, with attention (which every chip computes alike) counted
    once, is the uncut reference's layer."""
    uncut = small(num_experts=16, experts_held=16, num_layers=1)
    model, flat, _, tokens, masked, _ = seeded(uncut, batch=1)
    mask = jnp.asarray(ref.mask_matrix(uncut.seq_len, uncut.block_length))
    x = 0.5 * jax.random.normal(jax.random.key(3),
                                (2 * uncut.seq_len, uncut.hidden_size))
    whole = {k[len("layers/"):]: v[0] for k, v in flat.items()
             if k.startswith("layers/")}
    want, rows = ref.layer(model, whole, x, mask)
    table = lm.rotary_table(uncut, uncut.seq_len)
    no_experts = {**whole, **{k: jnp.zeros_like(whole[k][:2])
                              for k in ("w_gate", "w_up", "w_down")}}
    share0 = dataclasses.replace(uncut, experts_held=2)
    base, _ = lm.decoder_layer(share0, no_experts, x[None], table, "xla")
    total, taken = base, []
    for chip in range(8):
        share = dataclasses.replace(share0, first_expert=2 * chip)
        held = {**whole, **{k: whole[k][2 * chip:2 * chip + 2]
                            for k in ("w_gate", "w_up", "w_down")}}
        out, sizes = lm.decoder_layer(share, held, x[None], table, "xla")
        total = total + (out - base)
        taken += sizes.tolist()
    np.testing.assert_array_equal(taken, rows)
    assert sum(taken) == 2 * uncut.seq_len * uncut.experts_per_token
    np.testing.assert_allclose(total[0], want, atol=2e-5)


# -- tokens and noise ---------------------------------------------------------

@pytest.mark.parametrize("stream,index", [(0, 0), (0, 5), (0, 26), (1, 0)])
def test_the_noising_rule_is_the_one_the_reference_re_derives(stream, index):
    got = data_lib.block_diffusion_noise(9, stream, jnp.asarray(index), 2,
                                         32, 4)
    want = ref.noise(9, stream, index, 2, 32, 4)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    t = np.asarray(got[1])
    assert (t > 0).all() and (t <= 1).all()
    assert (t.reshape(2, 8, 4) == t.reshape(2, 8, 4)[..., :1]).all()
    assert (data_lib.TRAIN_NOISE, data_lib.EVAL_NOISE) == (
        ref.TRAIN_NOISE, ref.EVAL_NOISE)


@pytest.mark.parametrize("seed", [0, 9, 2**31 - 2])
def test_a_traced_seed_draws_the_noise_of_its_number(seed):
    """The task hands the step its seed as data (``ys``): compiled once,
    the step draws every job's noise."""
    drawn = jax.jit(lambda seeds, index: data_lib.block_diffusion_noise(
        seeds[0], data_lib.TRAIN_NOISE, index, 2, 32, 4))
    got = drawn(np.full(2, seed, np.int32), jnp.asarray(3))
    want = ref.noise(seed, ref.TRAIN_NOISE, 3, 2, 32, 4)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert drawn._cache_size() == 1 or seed == 0


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_init_rule_is_the_one_the_reference_re_derives(leaf):
    cfg = small()
    params, stats = tasks.BLOCKDIFF_LM.init_variables(
        lm.build_blockdiff_lm(cfg), jax.random.key(21), TrainConfig(seed=21))
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


def test_token_data_sets_are_full_integer_sequences():
    tokens = data_lib.token_arrays([[1, 2, 3], [4, 5, 6]])
    assert tokens.dtype == np.int32 and tokens.shape == (2, 3)
    with pytest.raises(ValueError, match="integers"):
        data_lib.token_arrays(np.zeros((2, 3), np.float32))
    with pytest.raises(ValueError, match=r"\[n, L\]"):
        data_lib.token_arrays(np.zeros((2, 3, 1), np.int32))


# -- the streamed train state -------------------------------------------------

def _state(scale: float):
    params = {"a": float(scale) * jnp.arange(12.0).reshape(3, 4),
              "b": {"c": jnp.full((5,), float(scale))}}
    tx = optax.adam(1e-3)
    return trainer.TrainState(
        params=params, opt_state=tx.init(params), batch_stats={},
        epoch=jnp.asarray(int(scale), jnp.int32),
        best_val_loss=jnp.asarray(float(scale), jnp.float32))


def test_a_streamed_save_and_restore_round_trips_leaf_by_leaf(
        tmp_path, monkeypatch):
    # pieces of 16 bytes: the 48-byte leaf comes over in three slices
    monkeypatch.setattr(checkpoint_lib, "STREAM_PIECE_BYTES", 16)
    ckpt = checkpoint_lib.CheckpointManager(tmp_path, keep=1)
    for step, best in ((1, True), (2, False), (3, False)):
        ckpt.save_streamed(step, {"state": _state(step)}, best=best)
    assert ckpt.latest_step() == 3 and ckpt.best_step() == 1
    # keep=1 beside the best: step 2 is pruned
    assert sorted(p.name for p in (tmp_path / "streamed").iterdir()
                  if p.is_dir()) == ["1", "3"]
    abstract = jax.eval_shape(lambda: _state(0.0))
    placed = []

    def place(a):
        placed.append(a.shape)
        return jax.device_put(a)

    got = ckpt.restore_streamed({"state": abstract}, place=place)["state"]
    assert len(placed) == len(jax.tree.leaves(abstract))
    jax.tree.map(np.testing.assert_array_equal, got, _state(3))
    # the best step's parameters alone, from a template cut out of the state
    only = abstract.replace(opt_state=None, epoch=None, best_val_loss=None)
    best = ckpt.restore_streamed({"state": only}, step=1)["state"]
    np.testing.assert_array_equal(best.params["a"], _state(1).params["a"])
    assert isinstance(best.params["b"]["c"], np.ndarray)
    with pytest.raises(FileNotFoundError):
        ckpt.restore_streamed({"state": abstract}, step=2)
    with pytest.raises(ValueError, match="saved"):
        ckpt.restore_streamed({"state": abstract.replace(
            epoch=jax.ShapeDtypeStruct((2,), jnp.int32))})
    ckpt.close()
    assert checkpoint_lib.tree_bytes(abstract) == 4 * (3 * (12 + 5) + 3)


@pytest.mark.parametrize("linked", [True, False],
                         ids=["one file system", "another file system"])
def test_a_saved_candidate_becomes_an_artifact_without_a_second_copy(
        tmp_path, monkeypatch, linked):
    """``streamed_files`` names a saved sub-tree's leaves by their files
    and ``link_leaves`` makes of them what ``write_leaves`` would have
    written: hard links where the file system allows, copies where not,
    which outlive the checkpoint either way."""
    import os

    if not linked:
        def refuse(src, dst):
            raise OSError("cross-device link")
        monkeypatch.setattr(os, "link", refuse)
    ckpt = checkpoint_lib.CheckpointManager(tmp_path / "ckpt", keep=1)
    ckpt.save_streamed(1, {"state": _state(1)}, best=True)
    abstract = jax.eval_shape(lambda: _state(0.0))
    only = abstract.replace(opt_state=None, epoch=None, best_val_loss=None)
    # asked for while the save is in flight: it lands first
    files = ckpt.streamed_files({"state": only}, step=1)["state"]
    assert checkpoint_lib.are_leaf_files(files.params)
    assert not checkpoint_lib.are_leaf_files(_state(1).params)
    assert not checkpoint_lib.are_leaf_files({})
    variables = {"params": files.params}
    checkpoint_lib.link_leaves(tmp_path / "artifact", variables)
    made = tmp_path / "artifact" / "00000.npy"
    assert made.samefile(files.params["a"]) is linked
    with pytest.raises(KeyError, match="holds no leaf"):
        ckpt.streamed_files({"state": only.replace(
            params={"z": only.params["a"]})}, step=1)
    with pytest.raises(FileNotFoundError):
        ckpt.streamed_files({"state": only}, step=2)
    ckpt.close()
    # the checkpoint goes, the artifact stays whole
    import shutil
    shutil.rmtree(tmp_path / "ckpt")
    got = checkpoint_lib.read_leaves(
        tmp_path / "artifact", {"params": only.params})
    jax.tree.map(np.testing.assert_array_equal, got,
                 {"params": _state(1).params})


# -- the task through train_model ---------------------------------------------

def _job(tmp_path, epochs, seed=3):
    cfg = TrainConfig(batch_size=2, epochs=epochs, seed=seed,
                      learning_rate=1e-3,
                      tracking_uri=f"file:{tmp_path / 'mlruns'}",
                      checkpoint_dir=str(tmp_path / "ckpt"))
    tokens = ref.tokens(dataclasses.asdict(small()), SEED, 20)
    return trainer.train_model(cfg, small(), arrays=(tokens, None),
                               resume=True)


def _counts():
    return {r: obs.TRAIN_RUNNERS.labels(family="epoch", result=r).value
            for r in ("built", "reused")}


@pytest.mark.parametrize("streamed", [True, False],
                         ids=["streamed", "device-snapshot"])
def test_train_model_trains_resumes_and_registers_the_task(
        tmp_path, streamed, monkeypatch):
    from robotic_discovery_platform_tpu import tracking
    from robotic_discovery_platform_tpu.tracking import api

    if streamed:    # and the registry takes the weights as leaf files
        monkeypatch.setattr(api, "_LEAF_FILES_ABOVE", 1000)
        monkeypatch.setattr(trainer, "_DEVICE_SNAPSHOT_MAX_BYTES", 1000)
    trainer._kept_runners.cache_clear()
    before, rows = _counts(), obs.MOE_ROUTED_ROWS.value
    first = _job(tmp_path, 2)
    middle = _counts()
    second = _job(tmp_path, 4)
    after = _counts()
    assert middle["built"] - before["built"] == 1
    assert after["reused"] - middle["reused"] == 1
    assert after["built"] == middle["built"]
    assert (first.epochs_run, second.epochs_run) == (2, 2)
    assert second.registry_version == first.registry_version + 1
    assert set(second.final_metrics) == {"loss", "masked_accuracy"}
    assert second.best_val_loss <= first.best_val_loss
    assert (tmp_path / "ckpt" / "streamed").is_dir() == streamed
    # the counters: 4 epochs of 8 steps on 2 x 64 positions, 2 layers
    assert 0 < obs.MOE_ROUTED_ROWS.value - rows <= 32 * 2 * 64 * 2 * 2
    assert obs.MOE_LOAD_RATIO.value >= 1.0
    assert obs.TRAIN_TOKENS_RATE.value > 0
    history = tracking.get_metric_history(second.run_id,
                                          "val_masked_accuracy")
    assert len(history) == 2
    # what was registered loads back as the task's model
    path = tracking.resolve_model_uri("models:/Actuator-Segmenter/latest")
    assert (path / "variables").is_dir() == streamed
    assert (path / "variables.msgpack").is_file() != streamed
    model, variables = tracking.load_model(path.as_posix())
    assert isinstance(model, lm.BlockDiffLM) and model.cfg == small()
    assert variables["params"]["embed"].shape == (64, 64)
    assert set(variables["params"]) == {"embed", "layers", "final_norm",
                                        "head"}


def test_jobs_of_two_seeds_share_their_programs_and_log_every_step(
        tmp_path):
    """The seed is data, not a constant of the step: a job of another seed
    reuses the kept runners (and, in another process, the compile cache),
    and draws other noise. Every step's loss is logged beside the epoch's
    mean."""
    from robotic_discovery_platform_tpu import tracking

    def logged(result, key):    # under the job's own tracking directory
        return [m["value"]
                for m in tracking.get_metric_history(result.run_id, key)]

    trainer._kept_runners.cache_clear()
    steps, means = [], []
    for name, seed in (("a", 3), ("b", 4)):
        middle = _counts()
        result = _job(tmp_path / name, 2, seed=seed)
        steps.append(logged(result, "train_step_loss"))
        means.append(logged(result, "train_loss"))
    after = _counts()
    assert after["built"] == middle["built"]
    assert after["reused"] - middle["reused"] == 1
    assert len(steps[0]) == len(steps[1]) == 2 * 8
    for got, want in zip(steps, means):
        assert np.mean(got[:8]) == pytest.approx(want[0], rel=1e-5)
        assert np.mean(got[8:]) == pytest.approx(want[1], rel=1e-5)
    assert steps[0][0] != steps[1][0]


def test_tasks_are_found_by_configuration_and_by_name():
    assert tasks.task_for(ModelConfig()) is tasks.UNET
    assert tasks.task_for(BlockDiffLMConfig()) is tasks.BLOCKDIFF_LM
    assert tasks.task_named("unet") is tasks.UNET
    with pytest.raises(TypeError):
        tasks.task_for(TrainConfig())
    with pytest.raises(ValueError, match="one device"):
        tasks.BLOCKDIFF_LM.for_mesh(BlockDiffLMConfig())
