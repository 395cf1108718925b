"""Device half of the split JPEG decode (ops/pallas/decode.py) and its
serving integration: the fused dequant+IDCT Pallas kernel vs the XLA
basis-matmul reference (co-traced in ONE jit, the
tests/test_pallas_geometry.py idiom -- integer ops have no
contraction-order freedom, so "bitwise" is well-defined and the gate is
exact equality), tuning-table dispatch for the ``jpeg_idct`` op key, the
64-byte-aligned pinned staging buffers, and the dispatcher's coefficient
lane (``submit_coef``) pinned bitwise against the pixel lane."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from robotic_discovery_platform_tpu.ops import pipeline as pipeline_lib
from robotic_discovery_platform_tpu.ops.pallas import (
    decode as pdecode,
    tuning,
)
from robotic_discovery_platform_tpu.serving import batching as batching_lib
from robotic_discovery_platform_tpu.serving import entropy

RNG = np.random.default_rng(17)


def _coef_batch(b, n, lo=-200, hi=200):
    coefs = jnp.asarray(RNG.integers(lo, hi, (b, n, 64)), jnp.int16)
    q = jnp.asarray(RNG.integers(1, 64, (b, 64)), jnp.uint16)
    return coefs, q


# -- dequant + IDCT kernel ---------------------------------------------------


def test_islow_basis_is_exact_integer_and_orthogonal_scaled():
    a = pdecode.islow_basis()
    assert a.dtype == np.int32 and a.shape == (8, 8)
    # the DC column is the flat basis vector: every entry identical
    assert len(set(a[:, 0].tolist())) == 1
    # A/2^CONST_BITS approximates the orthonormal IDCT-II basis (scaled
    # by sqrt(2) per islow's internal scaling)
    ref = np.zeros((8, 8))
    for j in range(8):
        c = np.sqrt(0.5) if j == 0 else 1.0
        ref[:, j] = c * np.cos((2 * np.arange(8) + 1) * j * np.pi / 16)
    np.testing.assert_allclose(a / 2**13, ref * np.sqrt(2), atol=2e-3)


@pytest.mark.parametrize("b,n", [(1, 48), (2, 300), (3, 512), (1, 4800)])
def test_dequant_idct_pallas_vs_xla_bitwise(b, n):
    """Both impls co-traced in one jit: exact equality, including block
    counts that don't divide the preferred tile."""
    coefs, q = _coef_batch(b, n)

    @jax.jit
    def both(c, q):
        return (pdecode.dequant_idct(c, q, impl="xla"),
                pdecode.dequant_idct(c, q, impl="interpret"))

    ref, got = both(coefs, q)
    assert ref.dtype == got.dtype == jnp.int32
    assert np.array_equal(np.asarray(ref), np.asarray(got))
    assert 0 <= int(np.asarray(ref).min()) and \
        int(np.asarray(ref).max()) <= 255


def test_dequant_idct_dc_only_block_is_flat():
    """A DC-only block IDCTs to a flat field: DESCALE(dc*q*basis) + 128,
    the quickest analytic cross-check of both constants and layout."""
    coefs = np.zeros((1, 1, 64), np.int16)
    coefs[0, 0, 0] = 16
    q = np.full((1, 64), 4, np.uint16)
    out = np.asarray(pdecode.dequant_idct(
        jnp.asarray(coefs), jnp.asarray(q), impl="xla"))[0, 0]
    assert len(np.unique(out)) == 1
    assert int(out[0]) == 136  # 128 + round(16*4 / 8)


def test_resolve_impl_routes_refused_op_to_xla(monkeypatch):
    """Mosaic refuses the int32 matmul (geometry.MOSAIC_REFUSES), so "auto"
    is XLA on every backend -- statically, whatever the tune table says;
    an explicit pin still reaches the kernel."""
    from robotic_discovery_platform_tpu.ops.pallas.geometry import (
        MOSAIC_REFUSES,
        resolve_impl,
    )

    assert "jpeg_idct" in MOSAIC_REFUSES
    key = tuning.op_key("jpeg_idct", b=8, n=4800)
    monkeypatch.setattr(tuning, "_cache", {key: {"impl": "pallas"}})
    assert resolve_impl("auto", "jpeg_idct", b=8, n=4800) == "xla"
    monkeypatch.setattr(tuning, "_cache", {})
    assert resolve_impl("auto", "jpeg_idct", b=8, n=4800) == "xla"
    assert resolve_impl("xla", "jpeg_idct", b=1, n=1) == "xla"
    assert resolve_impl("pallas", "jpeg_idct", b=1, n=1) == "pallas"
    assert resolve_impl("interpret", "jpeg_idct", b=1, n=1) == "interpret"


# -- whole decode stage ------------------------------------------------------


@pytest.mark.parametrize("subsampling", ["444", "420", "422"])
def test_decode_coef_batch_impl_paths_agree_bitwise(subsampling):
    h, w = 56, 72  # non-multiple-of-16: exercises the chroma crop
    (ybh, ybw), (cbh, cbw) = entropy.block_grids(h, w, subsampling)
    y, qy = _coef_batch(2, ybh * ybw)
    cb, qc = _coef_batch(2, cbh * cbw, -100, 100)
    cr, _ = _coef_batch(2, cbh * cbw, -100, 100)

    @jax.jit
    def both(y, cb, cr, qy, qc):
        args = dict(height=h, width=w, subsampling=subsampling)
        return (
            pipeline_lib.decode_coef_batch(y, cb, cr, qy, qc,
                                           impl="xla", **args),
            pipeline_lib.decode_coef_batch(y, cb, cr, qy, qc,
                                           impl="interpret", **args),
        )

    ref, got = both(y, cb, cr, qy, qc)
    assert ref.shape == (2, h, w, 3) and ref.dtype == jnp.uint8
    assert np.array_equal(np.asarray(ref), np.asarray(got))


def test_coef_analyzer_decodes_inside_one_graph():
    """make_coef_batch_analyzer == decode_coef_batch piped into the pixel
    batch analyzer: same mask, same curvature, coefficients in."""
    cv2 = pytest.importorskip("cv2")

    from robotic_discovery_platform_tpu.models.unet import (
        build_unet,
        init_unet,
    )
    from robotic_discovery_platform_tpu.utils.config import (
        GeometryConfig,
        ModelConfig,
    )

    model = build_unet(ModelConfig(base_features=8,
                                   compute_dtype="float32"))
    variables = init_unet(model, jax.random.key(0), img_size=64)
    rng = np.random.default_rng(3)
    bgr = cv2.GaussianBlur(
        rng.integers(0, 255, (64, 64, 3)).astype(np.uint8), (5, 5), 0)
    ok, jpg = cv2.imencode(".jpg", bgr)
    cf = entropy.parse_jpeg(jpg.tobytes())
    rgb = cv2.cvtColor(cv2.imdecode(jpg, cv2.IMREAD_COLOR),
                       cv2.COLOR_BGR2RGB)
    depth = rng.integers(200, 2000, (64, 64)).astype(np.uint16)
    intr = np.asarray([[60.0, 0, 32], [0, 60.0, 32], [0, 0, 1]],
                      np.float32)
    geom_cfg = GeometryConfig(kernel_impl="xla")
    an_pix = pipeline_lib.make_batch_analyzer(model, img_size=64,
                                              geom_cfg=geom_cfg)
    an_coef = pipeline_lib.make_coef_batch_analyzer(
        model, img_size=64, geom_cfg=geom_cfg, height=64, width=64,
        subsampling=cf.subsampling)
    ref = an_pix(variables, rgb[None], depth[None], intr[None],
                 np.asarray([0.001], np.float32))
    got = an_coef(variables, cf.y[None], cf.cb[None], cf.cr[None],
                  cf.qy[None], cf.qc[None], depth[None], intr[None],
                  np.asarray([0.001], np.float32))
    assert np.array_equal(np.asarray(got.mask), np.asarray(ref.mask))
    assert np.array_equal(
        np.asarray(got.profile.mean_curvature),
        np.asarray(ref.profile.mean_curvature))


# -- pinned staging buffers --------------------------------------------------


def test_aligned_empty_is_64b_aligned_and_correctly_shaped():
    for shape, dtype in [((3, 5, 7), np.uint8), ((4, 300, 64), np.int16),
                         ((2, 64), np.uint16), ((8, 3, 3), np.float32)]:
        arr = batching_lib._aligned_empty(shape, dtype)
        assert arr.shape == shape and arr.dtype == np.dtype(dtype)
        assert arr.ctypes.data % batching_lib._STAGE_ALIGN == 0
        arr[:] = 0  # writable, actually backed


def test_bucket_buffers_are_aligned():
    p = batching_lib._Pending(
        np.zeros((8, 8, 3), np.uint8), np.zeros((8, 8), np.uint16),
        np.eye(3, dtype=np.float32), 0.001)
    bufs = batching_lib._BucketBuffers((2,), p, 2)
    for arr in (bufs.frames, bufs.depths, bufs.intr, bufs.scales):
        assert arr.ctypes.data % batching_lib._STAGE_ALIGN == 0


def _coef_pending(h=48, w=64, seed=0):
    cv2 = pytest.importorskip("cv2")

    rng = np.random.default_rng(seed)
    bgr = cv2.GaussianBlur(
        rng.integers(0, 255, (h, w, 3)).astype(np.uint8), (5, 5), 0)
    ok, jpg = cv2.imencode(".jpg", bgr)
    cf = entropy.parse_jpeg(jpg.tobytes())
    depth = rng.integers(200, 2000, (h, w)).astype(np.uint16)
    return batching_lib._Pending(cf, depth, np.eye(3, dtype=np.float32),
                                 0.001)


def test_coef_bucket_buffers_fill_pad_aligned():
    p0, p1 = _coef_pending(seed=1), _coef_pending(seed=2)
    key = ("", "coef", "420", 48, 64)
    bufs = batching_lib._CoefBucketBuffers(key, p0, 3)
    for arr in (bufs.y, bufs.cb, bufs.cr, bufs.qy, bufs.qc, bufs.depths,
                bufs.intr, bufs.scales):
        assert arr.ctypes.data % batching_lib._STAGE_ALIGN == 0
    bufs.fill(0, p0)
    bufs.fill(1, p1)
    bufs.pad(2)
    assert np.array_equal(bufs.y[0], p0.frame_rgb.y)
    assert np.array_equal(bufs.y[1], p1.frame_rgb.y)
    assert np.array_equal(bufs.y[2], p0.frame_rgb.y)  # pad replicates 0
    assert np.array_equal(bufs.qc[1], p1.frame_rgb.qc)
    assert np.array_equal(bufs.depths[1], p1.depth)


# -- dispatcher coefficient lane ---------------------------------------------


def _coef_factory_for(model, variables, img_size=64):
    from robotic_discovery_platform_tpu.utils.config import GeometryConfig

    def factory(model_key, height, width, subsampling):
        an = pipeline_lib.make_coef_batch_analyzer(
            model, img_size=img_size, geom_cfg=GeometryConfig(
                kernel_impl="xla"),
            height=height, width=width, subsampling=subsampling)
        return (lambda y, cb, cr, qy, qc, d, k, s:
                an(variables, y, cb, cr, qy, qc, d, k, s))

    return factory


def test_submit_coef_bitwise_matches_pixel_lane():
    """The acceptance pin: the SAME JPEG submitted as decoded pixels and
    as coefficients yields a bitwise-identical mask through the real
    dispatcher (coef lane groups by (model, 'coef', subsampling, h, w)
    and decodes on 'device')."""
    cv2 = pytest.importorskip("cv2")
    jax.config.update("jax_platforms", "cpu")

    from robotic_discovery_platform_tpu.models.unet import (
        build_unet,
        init_unet,
    )
    from robotic_discovery_platform_tpu.utils.config import (
        GeometryConfig,
        ModelConfig,
    )

    model = build_unet(ModelConfig(base_features=8,
                                   compute_dtype="float32"))
    variables = init_unet(model, jax.random.key(0), img_size=64)
    geom_cfg = GeometryConfig(kernel_impl="xla")
    an_pix = pipeline_lib.make_batch_analyzer(model, img_size=64,
                                              geom_cfg=geom_cfg)

    def analyze(frames, depths, intr, scales):
        return an_pix(variables, frames, depths, intr, scales)

    disp = batching_lib.BatchDispatcher(
        analyze, window_ms=1.0, max_batch=4, watchdog_interval_s=0.0,
        coef_analyzer_factory=_coef_factory_for(model, variables))
    try:
        rng = np.random.default_rng(9)
        bgr = cv2.GaussianBlur(
            rng.integers(0, 255, (64, 64, 3)).astype(np.uint8), (5, 5), 0)
        ok, jpg = cv2.imencode(".jpg", bgr)
        cf = entropy.parse_jpeg(jpg.tobytes())
        rgb = cv2.cvtColor(cv2.imdecode(jpg, cv2.IMREAD_COLOR),
                           cv2.COLOR_BGR2RGB)
        depth = rng.integers(200, 2000, (64, 64)).astype(np.uint16)
        k = np.asarray([[60.0, 0, 32], [0, 60.0, 32], [0, 0, 1]],
                       np.float32)
        ref = disp.submit(rgb, depth, k, 0.001, timeout_s=60.0)
        got = disp.submit_coef(cf, depth, k, 0.001, timeout_s=60.0)
        assert np.array_equal(np.asarray(got.mask), np.asarray(ref.mask))
        assert np.array_equal(
            np.asarray(got.profile.mean_curvature),
            np.asarray(ref.profile.mean_curvature))
    finally:
        disp.stop()


def test_submit_coef_rejects_wrong_types():
    disp = batching_lib.BatchDispatcher(
        lambda *a: None, window_ms=1.0, max_batch=2,
        watchdog_interval_s=0.0)
    try:
        with pytest.raises(TypeError, match="CoefficientFrame"):
            disp.submit_coef(np.zeros((8, 8, 3), np.uint8),
                             np.zeros((8, 8), np.uint16),
                             np.eye(3, dtype=np.float32), 0.001)
        p = _coef_pending()
        with pytest.raises(ValueError, match="depth"):
            disp.submit_coef(p.frame_rgb, np.zeros((4, 4), np.uint16),
                             np.eye(3, dtype=np.float32), 0.001)
    finally:
        disp.stop()


def test_coef_frame_without_factory_errors_frame():
    disp = batching_lib.BatchDispatcher(
        lambda *a: {"x": np.zeros(1)}, window_ms=1.0, max_batch=2,
        watchdog_interval_s=0.0)
    try:
        p = _coef_pending()
        with pytest.raises(Exception, match="coef_analyzer_factory"):
            disp.submit_coef(p.frame_rgb, p.depth, p.intrinsics, 0.001,
                             timeout_s=10.0)
    finally:
        disp.stop()
