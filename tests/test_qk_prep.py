"""``ops/pallas/qk_prep``: the fused pass (Pallas interpreter) against the
dense definition, forward and every gradient, for the three decoder
families' variants; which form a call takes and what the counter says."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from robotic_discovery_platform_tpu.models.causal_lm import rope_table
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.ops.pallas import qk_prep
from robotic_discovery_platform_tpu.utils.config import RotaryConfig

EPS = 1e-6
#: one rounding to bfloat16 (8 significant bits, round to nearest)
BF16_ROUNDING = 2.0 ** -8

#: name -> (a norm weight, the table's attention_factor or None, scale as a
#: power of head_dim)
VARIANTS = {
    "norm+table+scale": (True, 1.0, -0.5),      # sdar's q
    "norm+table": (True, 1.0, 0.0),             # sdar's k
    "yarn-table+scale": (False, 1.2079, -0.5),  # mellum's q
    "yarn-table": (False, 1.2079, 0.0),         # mellum's k
    "norm+scale": (True, None, -0.5),           # a norm and no positions
}


def case(variant, s, heads, d, dtype, batch=2):
    """(y, cotangent, keywords of ``prepare_heads`` less ``impl``)."""
    norm, factor, power = VARIANTS[variant]
    keys = jax.random.split(jax.random.key(s * heads + d), 3)
    y = (3 * jax.random.normal(keys[0], (batch, s, heads * d))).astype(dtype)
    g = jax.random.normal(keys[1], (batch, heads, s, d)).astype(dtype)
    kw = {"eps": EPS, "scale": float(d) ** power}
    if norm:
        kw["norm_weight"] = 1 + 0.3 * jax.random.normal(keys[2], (d,))
    if factor is not None:
        # positions as the block-diffusion model has them: 0..s/2 twice
        positions = jnp.arange(s) % -(-s // 2)
        kw["table"] = rope_table(
            RotaryConfig(theta=1e4, attention_factor=factor), d, positions)
    return y, g, kw


def value_and_grads(fn, y, g, kw):
    """``fn(y, ...)``, and the cotangents of ``y`` and the norm weight under
    ``g``."""
    weight = kw.get("norm_weight")

    def call(y, weight):
        return fn(y, **({**kw, "norm_weight": weight}
                        if weight is not None else kw))

    out, vjp = jax.vjp(call, y, weight)
    return (out, *vjp(g.astype(out.dtype)))


def float32_definition(y, g, heads, d, kw):
    as32 = lambda a: a.astype(jnp.float32)
    return value_and_grads(
        lambda y, **kw: qk_prep.dense_heads(y, heads, d, **kw),
        as32(y), as32(g), kw)


@pytest.mark.parametrize("heads", [4, 8], ids=["kv-heads-4", "heads-8"])
@pytest.mark.parametrize("s", [48, 600], ids=["one-tile", "a-ragged-tile"])
@pytest.mark.parametrize("variant", VARIANTS)
def test_the_fused_pass_is_the_dense_definition_in_float32(variant, s, heads):
    """Float32 in and out: the kernel's arithmetic is the definition's to
    float32's own rounding, forward, ``dy`` and ``d norm_weight``; 600
    positions end in a tile of 88 whose other rows hold no data."""
    d = 128
    y, g, kw = case(variant, s, heads, d, jnp.float32)
    assert s <= qk_prep._TILE or s % qk_prep._TILE
    got = value_and_grads(
        lambda y, **kw: qk_prep.prepare_heads(y, heads, d, impl="interpret",
                                              **kw), y, g, kw)
    want = float32_definition(y, g, heads, d, kw)
    assert got[0].shape == (2, heads, s, d) and got[1].shape == y.shape
    for name, mine, its in zip(("out", "dy", "dw"), got, want):
        if its is None:
            assert mine is None
            continue
        np.testing.assert_allclose(
            mine, its, rtol=1e-5, atol=1e-5 * float(jnp.abs(its).max()),
            err_msg=name)


@pytest.mark.parametrize("d", [128, 256], ids=["head-128", "head-256"])
@pytest.mark.parametrize("variant", ["norm+table+scale", "yarn-table+scale"])
def test_in_bfloat16_the_fused_pass_rounds_once(variant, d):
    """Against the definition in float32 on the same bfloat16 inputs, every
    element of the output and of ``dy`` is within one bfloat16 rounding, and
    the pass is closer than the dense chain, which rounds after each step."""
    heads, s = 4, 200
    y, g, kw = case(variant, s, heads, d, jnp.bfloat16)
    fused = value_and_grads(
        lambda y, **kw: qk_prep.prepare_heads(y, heads, d, impl="interpret",
                                              **kw), y, g, kw)
    dense = value_and_grads(
        lambda y, **kw: qk_prep.prepare_heads(y, heads, d, impl="xla", **kw),
        y, g, kw)
    want = float32_definition(y, g, heads, d, kw)
    for name, mine, chain, its in zip(("out", "dy"), fused, dense, want):
        assert mine.dtype == jnp.bfloat16
        mine, chain = (np.asarray(a, np.float32) for a in (mine, chain))
        room = BF16_ROUNDING * np.abs(its) + 1e-6 * np.abs(its).max()
        assert (np.abs(mine - its) <= room).all(), name
        assert np.square(mine - its).sum() <= np.square(chain - its).sum()
    if want[2] is not None:
        assert fused[2].dtype == jnp.float32
        np.testing.assert_allclose(fused[2], want[2], rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(want[2]).max()))


def _samples():
    return {form: obs.ATTN_QK_PREP.labels(form=form).value
            for form in ("fused", "xla")}


def _traced(fn, *args):
    """The primitives of ``fn``'s jaxpr, and the counter's samples its
    trace added."""
    before = _samples()
    text = str(jax.make_jaxpr(fn)(*args))
    return text, {k: v - before[k] for k, v in _samples().items()}


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("impl,kernel", [
    ("interpret", True), ("pallas", True), ("xla", False), ("auto", False)])
def test_each_impl_traces_its_form_and_the_counter_says_which(
        variant, impl, kernel):
    """``"auto"`` off a TPU is the dense form; one sample a call."""
    assert jax.default_backend() != "tpu"
    y, _, kw = case(variant, 48, 4, 128, jnp.bfloat16)
    text, added = _traced(
        lambda y: qk_prep.prepare_heads(y, 4, 128, impl=impl, **kw), y)
    assert ("pallas_call" in text) is kernel
    assert added == {"fused": int(kernel), "xla": int(not kernel)}


@pytest.mark.parametrize("what", ["scale-only", "nothing", "heads-of-64"])
@pytest.mark.parametrize("impl", ["interpret", "pallas"])
def test_what_the_kernel_cannot_gain_on_runs_the_dense_form(what, impl):
    """A bare scale (``nemotron``'s q), a bare split (its k) and heads that
    fill half a 128-lane tile (``lfm2``) are the dense chain, bit for bit,
    whatever ``impl`` says."""
    if what == "heads-of-64":
        d, (y, _, kw) = 64, case("norm+table+scale", 48, 4, 64, jnp.bfloat16)
    else:
        d, (y, _, kw) = 128, case("norm+scale", 48, 4, 128, jnp.bfloat16)
        kw = {"scale": kw["scale"] if what == "scale-only" else 1.0}
    text, added = _traced(
        lambda y: qk_prep.prepare_heads(y, 4, d, impl=impl, **kw), y)
    assert "pallas_call" not in text
    assert added == {"fused": 0, "xla": 1}
    np.testing.assert_array_equal(
        qk_prep.prepare_heads(y, 4, d, impl=impl, **kw).astype(jnp.float32),
        qk_prep.dense_heads(y, 4, d, **kw).astype(jnp.float32))


def test_the_backward_pass_keeps_the_projections_output_alone():
    """The custom VJP's residuals are its inputs: ``y`` (which autodiff
    keeps today), the norm weight and the table, nothing the size of the
    output."""
    y, _, kw = case("norm+table+scale", 48, 4, 128, jnp.bfloat16)
    _, kept = qk_prep._fused_fwd(y, kw["norm_weight"], kw["table"], 4, EPS,
                                 kw["scale"], True)
    assert kept[0] is y and kept[1] is kw["norm_weight"]
    assert kept[2] is kw["table"] and len(kept) == 3


@pytest.mark.parametrize("s,heads,itemsize,blocks", [
    (8192, 32, 2, (512, 16)), (8192, 4, 2, (512, 4)), (8192, 48, 2, (512, 16)),
    (48, 4, 4, (48, 4)), (600, 6, 2, (512, 6))])
def test_a_grid_step_takes_a_tile_of_positions_and_a_block_of_heads(
        s, heads, itemsize, blocks):
    tile, block = qk_prep._blocks(s, heads, 128, itemsize)
    assert (tile, block) == blocks and heads % block == 0
    assert tile * block * 128 * itemsize <= qk_prep._BLOCK_BYTES
