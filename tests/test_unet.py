"""U-Net architecture tests: parameter-count parity with the reference
channel ladder, shape behavior, norm variants, and gradient flow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from robotic_discovery_platform_tpu.models import losses, unet
from robotic_discovery_platform_tpu.models.unet import (
    UNet, build_unet, init_unet, param_count, upsample_align_corners,
)
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.utils.config import ModelConfig


def expected_params_bilinear(f=64, in_ch=3, n_cls=1):
    """Analytic trainable-parameter count for the bilinear ladder
    (reference: pkg/segmentation_model.py:97-107): DoubleConv(in, out, mid) =
    9*in*mid + 2*mid + 9*mid*out + 2*out (convs are bias-free; norm has
    scale+bias)."""

    def dc(cin, cout, mid=None):
        mid = mid or cout
        return 9 * cin * mid + 2 * mid + 9 * mid * cout + 2 * cout

    total = dc(in_ch, f)  # inc
    total += dc(f, 2 * f) + dc(2 * f, 4 * f) + dc(4 * f, 8 * f)  # down1-3
    total += dc(8 * f, 8 * f)  # down4: 1024//2 = 512
    total += dc(16 * f, 4 * f, mid=8 * f)  # up1: cat(512,512)=1024 -> 256
    total += dc(8 * f, 2 * f, mid=4 * f)  # up2
    total += dc(4 * f, f, mid=2 * f)  # up3
    total += dc(2 * f, f, mid=f)  # up4: mid = (64+64)//2 = 64
    total += n_cls * f + n_cls  # 1x1 out conv (with bias)
    return total


def test_param_count_matches_reference_ladder():
    model = build_unet(ModelConfig())
    variables = init_unet(model, jax.random.key(0))
    assert param_count(variables) == expected_params_bilinear()


def test_forward_shape_and_dtype():
    model = build_unet(ModelConfig())
    variables = init_unet(model, jax.random.key(0))
    x = jnp.zeros((2, 256, 256, 3))
    y = model.apply(variables, x, train=False)
    assert y.shape == (2, 256, 256, 1)
    assert y.dtype == jnp.float32


def test_forward_odd_size():
    """Resize-to-skip fusion must handle non-power-of-two inputs (the
    reference pads to match, segmentation_model.py:67-76)."""
    model = build_unet(ModelConfig())
    variables = init_unet(model, jax.random.key(0))
    x = jnp.zeros((1, 250, 198, 3))
    y = model.apply(variables, x, train=False)
    assert y.shape == (1, 250, 198, 1)


def test_transpose_conv_variant():
    model = UNet(bilinear=False, dtype=jnp.float32)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 64, 64, 3)), train=False)
    y = model.apply(variables, jnp.zeros((1, 64, 64, 3)), train=False)
    assert y.shape == (1, 64, 64, 1)


def test_batchnorm_updates_stats():
    model = build_unet(ModelConfig())
    variables = init_unet(model, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 64, 64, 3))
    y, mutated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    before = jax.tree.leaves(variables["batch_stats"])
    after = jax.tree.leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_groupnorm_variant_has_no_batch_stats():
    model = build_unet(ModelConfig(norm="group"))
    variables = init_unet(model, jax.random.key(0))
    assert "batch_stats" not in variables


def test_gradients_flow():
    model = UNet(base_features=8, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 32, 32, 3))
    labels = (jax.random.uniform(jax.random.key(1), (2, 32, 32, 1)) > 0.5).astype(
        jnp.float32
    )
    variables = model.init(jax.random.key(2), x, train=False)

    def loss_fn(params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return losses.bce_with_logits(logits, labels)

    grads = jax.grad(loss_fn)(variables["params"])
    norms = [float(jnp.abs(g).max()) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert max(norms) > 0


def _taps(out: int, inp: int):
    """torch's ``align_corners=True`` grid from its definition: output
    index ``i`` samples the input at ``i * (inp - 1) / (out - 1)`` (at 0
    where either side has one sample), between the two nearest inputs."""
    pos = (np.zeros(out) if out == 1 or inp == 1
           else np.arange(out, dtype=np.float64) * (inp - 1) / (out - 1))
    lo = np.clip(np.floor(pos).astype(int), 0, inp - 1)
    return lo, np.minimum(lo + 1, inp - 1), pos - lo


def _resize_definition(x, h: int, w: int):
    """Bilinear align-corners resize of float64 ``[b, ih, iw, c]``: four
    taps and two blends an output pixel, no matrix."""
    r0, r1, rf = _taps(h, x.shape[1])
    c0, c1, cf = _taps(w, x.shape[2])
    rf, cf = rf[None, :, None, None], cf[None, None, :, None]
    rows = x[:, r0] * (1 - rf) + x[:, r1] * rf
    return rows[:, :, c0] * (1 - cf) + rows[:, :, c1] * cf


def _resize_definition_adjoint(g, ih: int, iw: int):
    """The adjoint of :func:`_resize_definition`: each output pixel's
    weight scattered back onto its four taps."""
    b, h, w, c = g.shape
    r0, r1, rf = _taps(h, ih)
    c0, c1, cf = _taps(w, iw)
    cf = cf[None, None, :, None]
    cols = np.zeros((b, h, iw, c))
    np.add.at(cols, (slice(None), slice(None), c0), g * (1 - cf))
    np.add.at(cols, (slice(None), slice(None), c1), g * cf)
    rf = rf[None, :, None, None]
    out = np.zeros((b, ih, iw, c))
    np.add.at(out, (slice(None), r0), cols * (1 - rf))
    np.add.at(out, (slice(None), r1), cols * rf)
    return out


def _dense_matrix_form(x, h: int, w: int):
    """``upsample_align_corners`` as it stood until PR 36 (and stands for
    a batch under 8), kept as the oracle of the form that replaced it: the
    same two matrices in ``x.dtype``, float32 products, the H pass first."""
    b, ih, iw, c = x.shape

    def interp_matrix(out: int, inp: int):
        lo, hi, frac = _taps(out, inp)
        frac = frac.astype(np.float32)
        m = np.zeros((out, inp), np.float32)
        np.add.at(m, (np.arange(out), lo), 1.0 - frac)
        np.add.at(m, (np.arange(out), hi), frac)
        return jnp.asarray(m, x.dtype)

    y = jnp.einsum("Hh,bhwc->bHwc", interp_matrix(h, ih), x,
                   preferred_element_type=jnp.float32)
    y = jnp.einsum("Ww,bhwc->bhWc", interp_matrix(w, iw), y,
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("batch", [2, 8], ids=["einsum", "batched"])
@pytest.mark.parametrize("inp,out", [
    ((128, 128), (256, 256)),   # the retraining cell's last block
    ((30, 40), (60, 80)),       # serving's first, 480x640 frames
    ((15, 20), (31, 41)),       # odd sizes: resize-to-skip
    ((1, 1), (2, 2)),           # one input sample
    ((4, 4), (4, 4)),           # the identity
], ids=lambda s: f"{s[0]}x{s[1]}")
def test_upsample_align_corners_is_the_bilinear_resize(inp, out, batch,
                                                       dtype):
    """Forward and the gradient of a weighted sum, in both forms the
    function writes its products in (by the batch), against the float64
    definition (to the dtype's rounding: a bfloat16 matrix entry and the
    bfloat16 result carry 2^-9 each) and against the dense-matrix form
    (the same arithmetic: float32 rounding before the result's cast)."""
    taken = obs.UNET_UPSAMPLE_FORM.labels(
        form="batched" if batch >= unet._BATCHED_FORM_MIN_BATCH else "einsum")
    rng = np.random.default_rng(inp[0] * 1000 + out[1])
    x = jnp.asarray(rng.standard_normal((batch, *inp, 3)), dtype)
    weights = rng.standard_normal((batch, *out, 3)).astype(np.float32)

    def weighted_sum(form):
        return lambda v: jnp.sum(form(v, *out).astype(jnp.float32) * weights)

    before = taken.value
    y = upsample_align_corners(x, *out)
    assert taken.value == before + 1
    dx = jax.grad(weighted_sum(upsample_align_corners))(x)
    assert y.shape == (batch, *out, 3) and y.dtype == x.dtype
    assert dx.shape == x.shape and dx.dtype == x.dtype

    exact = dtype == jnp.float32
    x64 = np.asarray(x, np.float64)
    for got, want, oracle in (
        (y, _resize_definition(x64, *out), _dense_matrix_form(x, *out)),
        (dx, _resize_definition_adjoint(weights.astype(np.float64), *inp),
         jax.grad(weighted_sum(_dense_matrix_form))(x)),
    ):
        got = np.asarray(got, np.float64)
        scale = np.abs(want).max()
        np.testing.assert_allclose(
            got, want, rtol=0, atol=scale * (1e-5 if exact else 2 ** -6))
        # one unit in the last place of the result's dtype where the two
        # float32 sums round to different sides of a bfloat16 tie
        np.testing.assert_allclose(
            got, np.asarray(oracle, np.float64),
            rtol=1e-6 if exact else 2 ** -7, atol=scale * 1e-6)
