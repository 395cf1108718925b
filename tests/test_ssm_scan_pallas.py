"""The state-space scan's Pallas kernels (``ops/pallas/ssm_scan``) and the
dispatch in front of them (``ops/ssm_scan.ssm_scan``), on the CPU through
the Pallas interpreter: the kernels against the XLA form and against the
recurrence position by position, values and the gradients of all six inputs,
at the cell's chunk, state and head width (128, 128, 64) with 2 groups of 2
heads, batch 2, 3 chunks and a length that is no multiple of the chunk, on
float32 and bfloat16 inputs; which shapes the kernels take; what each
``impl`` traces; the counter's label; the mixer handing ``kernel_impl``
through; and the kernels compiled for a described (not attached) ``v5e``
chip at the cell's widths, which is where Mosaic refuses what the
interpreter lets pass.

Tolerances. On float32 inputs the kernels, the XLA form and the recurrence
differ by the order of their sums: 5e-5 of the largest value (``a``'s
gradient, a sum over every position of terms of both signs, reads 2e-5
here for the kernels and 1e-5 for the XLA form; every other number 3e-6).
On bfloat16 inputs the recurrence is run in float32 on the same rounded inputs, so what is compared
is the rounding inside the two chunked forms: weights, scaled inputs, the
state and the cotangents rounded to bfloat16 before each product. The
kernels round where the XLA form's derivative does, so a gradient of theirs
lies as far from the recurrence's as the XLA form's does (as vectors, within
a factor of 1.5), and the two lie closer to each other than twice that."""

import os

import jax
import jax.numpy as jnp
import pytest

from robotic_discovery_platform_tpu.models import hybrid_lm as lm
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.ops import ssm_scan as scan_lib
from robotic_discovery_platform_tpu.ops.pallas import ssm_scan as kernels
from robotic_discovery_platform_tpu.utils.config import HybridLMConfig

INPUTS = ("x", "dt", "a", "b", "c", "d")
HEADS, HEAD_DIM, GROUPS, STATE, CHUNK = 4, 64, 2, 128, 128


def recurrence(x, dt, a, b, c, d):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t +
    d x_t``, one position at a time in float32, written here."""
    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
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


def scan_inputs(length: int, dtype, heads=HEADS, head_dim=HEAD_DIM,
                groups=GROUPS, state=STATE, steps=(0.001, 0.1)) -> tuple:
    """Two sequences; the steps on the published range, so that a chunk of
    128 decays by the order of ``exp(-10)`` under the fastest head."""
    keys = jax.random.split(jax.random.key(11), 6)
    return (jax.random.normal(keys[0], (2, length, heads, head_dim)).astype(
                dtype),
            jax.random.uniform(keys[1], (2, length, heads), minval=steps[0],
                               maxval=steps[1]),
            -jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=16.0),
            jax.random.normal(keys[3], (2, length, groups, state)).astype(
                dtype),
            jax.random.normal(keys[4], (2, length, groups, state)).astype(
                dtype),
            jax.random.normal(keys[5], (heads,)))


def _values_and_grads(fn, inputs, readout):
    def loss(*v):
        return jnp.sum(readout * fn(*v).astype(jnp.float32))

    return (fn(*inputs).astype(jnp.float32),) + tuple(
        g.astype(jnp.float32) for g in jax.grad(loss, range(6))(*inputs))


@pytest.fixture(scope="module", params=[
    (jnp.float32, 3 * CHUNK), (jnp.float32, 3 * CHUNK - 84),
    (jnp.bfloat16, 3 * CHUNK), (jnp.bfloat16, 3 * CHUNK - 84)],
    ids=["float32-whole-chunks", "float32-padded", "bfloat16-whole-chunks",
         "bfloat16-padded"])
def three_forms(request):
    """name -> (kernels, XLA form, recurrence) for ``y`` and each input's
    gradient under a drawn linear readout, and the inputs' dtype."""
    dtype, length = request.param
    inputs = scan_inputs(length, dtype)
    readout = jax.random.normal(jax.random.key(12), inputs[0].shape)
    forms = [_values_and_grads(fn, inputs, readout) for fn in (
        lambda *v: scan_lib.ssm_scan(*v, chunk=CHUNK, impl="interpret"),
        lambda *v: scan_lib.ssm_scan(*v, chunk=CHUNK, impl="xla"),
        recurrence)]
    return dict(zip(("y",) + INPUTS, zip(*forms))), dtype


def _apart(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("what", ("y",) + INPUTS)
def test_the_kernels_are_the_xla_form_and_the_recurrence(three_forms, what):
    forms, dtype = three_forms
    got, xla, want = forms[what]
    assert got.shape == xla.shape == want.shape
    largest = float(jnp.max(jnp.abs(want)))
    assert largest > 0
    if dtype == jnp.float32:
        assert float(jnp.max(jnp.abs(got - want))) <= 5e-5 * largest
        assert float(jnp.max(jnp.abs(got - xla))) <= 5e-5 * largest
    else:
        mine, its = _apart(got, want), _apart(xla, want)
        assert mine <= max(1.5 * its, 1e-3), (mine, its)
        assert _apart(got, xla) <= max(2 * its, 1e-3)


def test_a_float32_is_its_three_bfloat16_parts_and_the_spreaders_pick_them():
    """What makes the matrix unit's spreading exact: every part is a
    bfloat16, the parts sum to the number, and each column of a spreader
    picks the three parts of one quantity of one head."""
    per, p = 2, 64
    rows = jax.random.normal(jax.random.key(3), (4 * per, 128)) * 37.0
    parts = kernels.in_parts(rows)
    assert parts.shape == (12 * per, 128)
    assert jnp.array_equal(parts.astype(jnp.bfloat16).astype(jnp.float32),
                           parts)
    hi, mid, lo = jnp.split(parts, 3)
    assert jnp.array_equal((hi + mid) + lo, rows)
    tiles, channels = kernels.spreaders(per, p)
    turned = jnp.zeros((128, 128)).at[:, :12 * per].set(parts.T)
    spread = turned @ tiles
    for t in range(2):              # the running sum, the steps
        for r in range(per):
            at = (t * per + r) * 128
            assert jnp.array_equal(
                spread[:, at:at + 128],
                jnp.broadcast_to(rows[t * per + r][:, None], (128, 128)))
    spread = turned @ channels
    for t in range(2):              # exp(cum), exp(cum_last - cum) dt
        for r in range(per):
            at = t * per * p + r * p
            assert jnp.array_equal(
                spread[:, at:at + p],
                jnp.broadcast_to(rows[(2 + t) * per + r][:, None], (128, p)))


# -- the dispatch -------------------------------------------------------------

def _shapes(heads=64, head_dim=64, groups=8, state=128, length=8192):
    return (2, length, heads, head_dim), (2, length, groups, state)


@pytest.mark.parametrize("case,chunk,taken", [
    ({}, 128, True),                                    # the cell's widths
    ({"heads": HEADS, "groups": GROUPS}, 128, True),    # this file's
    ({"heads": 16, "head_dim": 128}, 128, True),        # a head a tile
    ({"state": 256}, 128, True),
    ({}, 8, False),                                     # the toy chunk
    ({}, 256, False),           # a chunk is turned as one 128 x 128 tile
    ({"state": 16}, 128, False),                        # the toy state
    ({"heads": 4, "head_dim": 16, "groups": 2}, 128, False),
    ({"heads": 2, "head_dim": 64, "groups": 2}, 128, False),    # half a tile
    ({"head_dim": 32}, 128, False),
    ({"heads": 128}, 128, False)])      # 16 heads a group: over 10
def test_the_predicate_takes_what_the_kernels_tile(case, chunk, taken):
    assert scan_lib.kernel_takes(*_shapes(**case), chunk) is taken


def _primitives(fn, *args) -> set:
    found = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            found.add(eqn.primitive.name)
            for inner in jax.core.jaxprs_in_params(eqn.params):
                walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("impl,kernel", [
    ("interpret", True), ("pallas", True), ("xla", False), ("auto", False)])
def test_each_impl_traces_its_form_and_the_counter_says_which(impl, kernel):
    """``"auto"`` off a TPU is the XLA form; the counter's ``kind`` is what
    was traced."""
    assert jax.default_backend() != "tpu"
    inputs = scan_inputs(2 * CHUNK, jnp.bfloat16)
    counters = {k: obs.SSM_SCAN_CHUNKS.labels(kind=k)
                for k in ("pallas", "xla")}
    before = {k: c.value for k, c in counters.items()}
    found = _primitives(
        lambda *v: scan_lib.ssm_scan(*v, chunk=CHUNK, impl=impl), *inputs)
    assert ("pallas_call" in found) is kernel
    assert ("cumsum" in found) is True          # the running sum stays in XLA
    assert {k: c.value - before[k] for k, c in counters.items()} == {
        "pallas": 2 * kernel, "xla": 2 * (not kernel)}


@pytest.mark.parametrize("refused", [
    {"chunk": 8}, {"chunk": CHUNK, "state": 16}], ids=["chunk-8", "state-16"])
def test_a_refused_shape_runs_the_xla_form_whatever_impl_says(refused):
    chunk = refused.pop("chunk")
    inputs = scan_inputs(32 if chunk == 8 else 2 * CHUNK, jnp.float32,
                         head_dim=16, steps=(0.05, 0.5), **refused)
    counter = obs.SSM_SCAN_CHUNKS.labels(kind="pallas")
    before = counter.value
    ask = lambda impl: (lambda *v: scan_lib.ssm_scan(*v, chunk=chunk,
                                                     impl=impl))
    assert "pallas_call" not in _primitives(ask("interpret"), *inputs)
    assert counter.value == before
    assert jnp.array_equal(ask("interpret")(*inputs), ask("xla")(*inputs))


def _wide(**kw) -> HybridLMConfig:
    return HybridLMConfig(**{
        "compute_dtype": "bfloat16", "hidden_size": 128, "seq_len": 256,
        "mamba_heads": HEADS, "mamba_head_dim": HEAD_DIM,
        "ssm_groups": GROUPS, "ssm_state": STATE, "ssm_chunk": CHUNK, **kw})


@pytest.mark.parametrize("impl,kernel", [("interpret", True), ("xla", False)])
def test_the_mixer_hands_kernel_impl_to_the_scan(impl, kernel):
    cfg = _wide(kernel_impl=impl)
    layer = {name: jax.ShapeDtypeStruct(shape, jnp.float32)
             for name, shape in lm.layer_shapes(cfg, "mamba").items()}
    x = jax.ShapeDtypeStruct((2, cfg.seq_len, cfg.hidden_size), jnp.bfloat16)
    found = _primitives(
        lambda layer, x: lm.mamba_layer(cfg, layer, x, cfg.kernel_impl)[0],
        layer, x)
    assert ("pallas_call" in found) is kernel


def test_the_whole_model_runs_the_kernels_under_its_kernel_impl():
    """Through ``hidden_states`` and its ``jax.checkpoint``, forward and
    backward: the loss and a mixer's gradient under ``"interpret"`` are the
    XLA form's to bfloat16's precision."""
    out = {}
    for impl in ("interpret", "xla"):
        cfg = _wide(kernel_impl=impl, num_layers=1, layer_pattern="M",
                    vocab_size=64)
        net = lm.build_hybrid_lm(cfg)
        params = net.init(jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, cfg.seq_len), 0,
                                    cfg.vocab_size)
        out[impl] = jax.value_and_grad(
            lambda p: net.loss(p, tokens)[0])(params)
    (got, got_grads), (want, want_grads) = out["interpret"], out["xla"]
    assert abs(float(got) - float(want)) <= 2e-3 * abs(float(want))
    for name in ("A_log", "dt_bias", "D", "w_in"):
        mine, its = (g["layers"]["0"][name] for g in (got_grads, want_grads))
        assert _apart(mine, its) <= 0.05, name


# -- for the chip's compiler --------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("heads,groups,head_dim", [
    (64, 8, 64), (HEADS, GROUPS, HEAD_DIM), (16, 8, 128)],
    ids=["the-cell", "this-file", "a-head-a-tile"])
def test_mosaic_compiles_both_kernels_at_shapes_the_predicate_takes(
        one_chip, no_compile_cache, heads, groups, head_dim):
    """1,024 positions of one sequence, as ``scan_alone`` runs them; forward
    alone (validation's program) and forward with backward."""
    chunk = CHUNK
    x, b = _shapes(heads=heads, groups=groups, head_dim=head_dim, length=1024)
    assert scan_lib.kernel_takes(x, b, chunk)

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct((1,) + shape[1:], dtype,
                                    sharding=one_chip)

    args = (abstract(x, jnp.bfloat16), abstract(x[:3], jnp.float32),
            jax.ShapeDtypeStruct((heads,), jnp.float32, sharding=one_chip),
            abstract(b, jnp.bfloat16), abstract(b, jnp.bfloat16),
            jax.ShapeDtypeStruct((heads,), jnp.float32, sharding=one_chip))
    scan = lambda *v: scan_lib.ssm_scan(*v, chunk=chunk, impl="pallas")
    loss = lambda *v: jnp.sum(scan(*v).astype(jnp.float32))
    forward = jax.jit(scan).lower(*args).compile().as_text()
    assert forward.count("tpu_custom_call") >= 1
    # the three bfloat16 parts are still made: as a cast to bfloat16 and
    # back the chip's compiler removed them (PERF.md section 6, PR 38)
    assert forward.count("reduce-precision") >= 3
    both = jax.jit(jax.grad(loss, range(6))).lower(*args).compile().as_text()
    assert "ssm_scan_forward" in both and "ssm_scan_backward" in both


@pytest.mark.parametrize("heads,norm,rope", [
    (32, True, True), (4, True, True), (48, False, True), (8, True, False)],
    ids=["sdar-q", "sdar-k", "mellum-q", "a-norm-alone"])
def test_mosaic_compiles_the_q_k_pass_at_the_cells_widths(
        one_chip, no_compile_cache, heads, norm, rope):
    """``ops/pallas/qk_prep`` (the chip's compiler is described in this file
    alone, so its kernels' compile lives here): two sequences of 8,192
    positions on heads of 128, forward alone and with the backward pass."""
    from robotic_discovery_platform_tpu.ops.pallas import qk_prep

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, s, d = 2, 8192, 128
    y = abstract((b, s, heads * d), jnp.bfloat16)
    weight = abstract((d,), jnp.float32) if norm else None
    table = (abstract((s, d), jnp.float32),) * 2 if rope else None
    prepare = lambda y, weight, table: qk_prep.prepare_heads(
        y, heads, d, norm_weight=weight, eps=1e-6, table=table,
        scale=d ** -0.5, impl="pallas")
    loss = lambda *v: jnp.sum(prepare(*v).astype(jnp.float32))
    forward = jax.jit(prepare).lower(y, weight, table).compile().as_text()
    assert "qk_prep_forward" in forward
    assert forward.count("tpu_custom_call") == 1
    both = jax.jit(jax.grad(loss, (0, 1) if norm else 0)).lower(
        y, weight, table).compile().as_text()
    assert "qk_prep_backward" in both
