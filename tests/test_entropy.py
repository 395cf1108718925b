"""Split-JPEG-decode host half (serving/entropy.py) and its wire format.

Golden parity is generated in-test: cv2 encodes a structured frame,
entropy.parse_jpeg recovers the quantized coefficient blocks, and the
device half (ops/pipeline.decode_coef_batch, XLA reference path) must
reproduce ``cv2.imdecode`` of the SAME bytes bitwise -- libjpeg's islow
IDCT, fancy upsample, and fixed-point color convert are all exact
integer arithmetic, so the acceptance tolerance (+-1 LSB) is met with
margin: zero. Also covers the format=2 pack/unpack roundtrip, the
client's fmt="coef" leg, corrupt/truncated-stream error completion
through the decode pool (frame errors, worker survives), and the
RDP_ONCHIP_DECODE reference mode.

Runs clean under RDP_LOCKCHECK=strict / RDP_TRANSFER_GUARD=strict (the
CI decode-smoke job does exactly that)."""

import dataclasses

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

from robotic_discovery_platform_tpu.ops import pipeline as pipeline_lib
from robotic_discovery_platform_tpu.resilience import configure_faults
from robotic_discovery_platform_tpu.serving import client as client_lib
from robotic_discovery_platform_tpu.serving import entropy, ingest
from robotic_discovery_platform_tpu.serving.proto import vision_pb2

_SF = {
    "444": cv2.IMWRITE_JPEG_SAMPLING_FACTOR_444,
    "420": cv2.IMWRITE_JPEG_SAMPLING_FACTOR_420,
    "422": cv2.IMWRITE_JPEG_SAMPLING_FACTOR_422,
}


@pytest.fixture(autouse=True)
def _clean_faults():
    configure_faults(None)
    yield
    configure_faults(None)


def _scene(h, w, seed=0):
    """A structured frame (gradients + a disc), not pure noise: JPEG's
    entropy stream should look like a camera's, not its pathological
    case."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    img = np.stack(
        [(xx * 3) % 256, (yy * 2 + xx) % 256, ((xx + yy) * 2) % 256],
        axis=-1,
    ).astype(np.uint8)
    cy, cx, r = h // 2, w // 2, min(h, w) // 3
    disc = (yy - cy) ** 2 + (xx - cx) ** 2 < r * r
    img[disc] = (200, 64, 32)
    noise = rng.integers(-8, 8, img.shape)
    return np.clip(img.astype(np.int16) + noise, 0, 255).astype(np.uint8)


def _encode(img_bgr, subsampling="420", extra=()):
    flags = [cv2.IMWRITE_JPEG_SAMPLING_FACTOR, _SF[subsampling],
             *extra]
    ok, jpg = cv2.imencode(".jpg", img_bgr, flags)
    assert ok
    return jpg.tobytes()


def _device_decode(cf: entropy.CoefficientFrame) -> np.ndarray:
    out = pipeline_lib.decode_coef_batch(
        cf.y[None], cf.cb[None], cf.cr[None], cf.qy[None], cf.qc[None],
        height=cf.height, width=cf.width, subsampling=cf.subsampling,
        impl="xla",
    )
    return np.asarray(out[0])


# -- golden parity vs cv2 ----------------------------------------------------


@pytest.mark.parametrize("subsampling", ["444", "420", "422"])
@pytest.mark.parametrize("hw", [(64, 64), (120, 160), (119, 157),
                                (33, 47)])
def test_split_decode_bitwise_matches_cv2(subsampling, hw):
    """parse_jpeg + decode_coef_batch == cv2.imdecode, bitwise, including
    non-multiple-of-16 dims (MCU padding must never leak into the fancy
    upsamplers' edge taps)."""
    h, w = hw
    jpg = _encode(_scene(h, w), subsampling)
    cf = entropy.parse_jpeg(jpg)
    assert (cf.height, cf.width, cf.subsampling) == (h, w, subsampling)
    ref = cv2.cvtColor(cv2.imdecode(np.frombuffer(jpg, np.uint8),
                                    cv2.IMREAD_COLOR), cv2.COLOR_BGR2RGB)
    got = _device_decode(cf)
    assert np.array_equal(got, ref), (
        f"max |diff| = "
        f"{int(np.abs(got.astype(int) - ref.astype(int)).max())}"
    )


def test_split_decode_with_restart_markers():
    """DRI/RSTn streams: the bit reader must resync and reset DC
    predictors at every restart interval."""
    jpg = _encode(_scene(96, 128), "420",
                  extra=(cv2.IMWRITE_JPEG_RST_INTERVAL, 2))
    assert b"\xff\xdd" in jpg  # the DRI segment actually landed
    cf = entropy.parse_jpeg(jpg)
    ref = cv2.cvtColor(cv2.imdecode(np.frombuffer(jpg, np.uint8),
                                    cv2.IMREAD_COLOR), cv2.COLOR_BGR2RGB)
    assert np.array_equal(_device_decode(cf), ref)


def test_split_decode_across_qualities():
    for quality in (30, 75, 95):
        jpg = _encode(_scene(48, 64), "420",
                      extra=(cv2.IMWRITE_JPEG_QUALITY, quality))
        cf = entropy.parse_jpeg(jpg)
        ref = cv2.cvtColor(
            cv2.imdecode(np.frombuffer(jpg, np.uint8), cv2.IMREAD_COLOR),
            cv2.COLOR_BGR2RGB)
        assert np.array_equal(_device_decode(cf), ref), quality


# -- malformed streams -------------------------------------------------------


def test_truncated_entropy_stream_raises():
    jpg = _encode(_scene(64, 64), "420")
    with pytest.raises(ValueError, match="truncated"):
        entropy.parse_jpeg(jpg[: len(jpg) // 2])


def test_corrupt_entropy_stream_raises_not_hangs():
    jpg = bytearray(_encode(_scene(64, 64), "420"))
    # stomp a run of scan bytes: decode must fail loudly, not wedge
    jpg[-200:-150] = b"\xff" * 50
    with pytest.raises(ValueError):
        entropy.parse_jpeg(bytes(jpg))


def test_not_a_jpeg_raises():
    with pytest.raises(ValueError, match="SOI"):
        entropy.parse_jpeg(b"\x89PNG\r\n\x1a\n" + b"\x00" * 32)


def test_progressive_jpeg_rejected_as_unsupported():
    """Progressive (SOF2) is exotic-but-valid: the error prefix is
    'unsupported', the contract ingest's onchip fallback keys on."""
    jpg = _encode(_scene(64, 64), "420",
                  extra=(cv2.IMWRITE_JPEG_PROGRESSIVE, 1))
    with pytest.raises(ValueError, match="unsupported"):
        entropy.parse_jpeg(jpg)


# -- format=2 wire -----------------------------------------------------------


def test_pack_unpack_roundtrip_exact():
    cf = entropy.parse_jpeg(_encode(_scene(119, 157), "420"))
    cf2 = entropy.unpack_coefficients(entropy.pack_coefficients(cf))
    assert (cf2.height, cf2.width, cf2.subsampling) == (
        cf.height, cf.width, cf.subsampling)
    for name in ("y", "cb", "cr", "qy", "qc"):
        assert np.array_equal(getattr(cf2, name), getattr(cf, name)), name
    # the unpack side is zero-copy views of the payload bytes
    assert cf2.y.base is not None and not cf2.y.flags.writeable


def test_unpack_rejects_corrupt_payloads():
    payload = entropy.pack_coefficients(
        entropy.parse_jpeg(_encode(_scene(48, 64), "420")))
    with pytest.raises(ValueError, match="too short"):
        entropy.unpack_coefficients(payload[:8])
    with pytest.raises(ValueError, match="bad magic"):
        entropy.unpack_coefficients(b"XXXX" + payload[4:])
    with pytest.raises(ValueError, match="expected"):
        entropy.unpack_coefficients(payload[:-10])


# -- client fmt="coef" -------------------------------------------------------


def test_client_coef_request_roundtrip():
    color_bgr = _scene(48, 64, seed=5)
    depth = np.random.default_rng(5).integers(
        0, 4000, (48, 64)).astype(np.uint16)
    req = client_lib.encode_request(color_bgr, depth, fmt="coef")
    assert req.color_image.format == ingest.FORMAT_COEF
    assert ingest.request_format(req) == "coef"
    rgb, d, fmt = ingest.decode_request(req)
    assert fmt == "coef"
    assert isinstance(rgb, entropy.CoefficientFrame)
    assert np.array_equal(d, depth)  # depth rides raw z16, lossless
    # the coefficients decode to EXACTLY what the server's encoded leg
    # would have seen for the same frame (same cv2 default quality)
    jpg_req = client_lib.encode_request(color_bgr, depth)
    ref, _, _ = ingest.decode_request(jpg_req)
    assert np.array_equal(_device_decode(rgb), ref)


def test_client_unknown_format_mentions_coef():
    with pytest.raises(ValueError, match="coef"):
        client_lib.encode_request(_scene(16, 16), np.zeros((16, 16),
                                  np.uint16), fmt="bogus")


# -- ingest integration ------------------------------------------------------


def test_coef_dims_mismatch_rejected():
    cf = entropy.parse_jpeg(_encode(_scene(48, 64), "420"))
    img = vision_pb2.Image(data=entropy.pack_coefficients(cf),
                           width=999, height=48,
                           format=ingest.FORMAT_COEF)
    with pytest.raises(ValueError, match="999"):
        ingest.decode_color(img)


def test_corrupt_coef_payload_errors_frame_not_worker():
    """A stomped coefficient payload error-completes ITS frame through
    the serving.ingest.decode fault site's guard; the worker survives and
    later frames decode."""
    color_bgr = _scene(48, 64, seed=6)
    depth = np.zeros((48, 64), np.uint16)
    good = client_lib.encode_request(color_bgr, depth, fmt="coef")
    bad = vision_pb2.AnalysisRequest()
    bad.CopyFrom(good)
    bad.color_image.data = b"XXXX" + bad.color_image.data[4:]
    pool = ingest.DecodePool(1)
    try:
        frames = list(pool.iter_decoded(iter([bad, good, good])))
        assert len(frames) == 3
        assert frames[0].error is not None
        assert isinstance(frames[0].error, ValueError)
        for f in frames[1:]:
            assert f.error is None
            assert isinstance(f.rgb, entropy.CoefficientFrame)
        assert all(t.is_alive() for t in pool._threads)
    finally:
        pool.stop()


def test_truncated_coef_payload_through_pool():
    good = client_lib.encode_request(_scene(48, 64),
                                     np.zeros((48, 64), np.uint16),
                                     fmt="coef")
    bad = vision_pb2.AnalysisRequest()
    bad.CopyFrom(good)
    bad.color_image.data = bad.color_image.data[:100]
    pool = ingest.DecodePool(0)
    try:
        frames = list(pool.iter_decoded(iter([bad])))
        assert frames[0].error is not None
    finally:
        pool.stop()


# -- RDP_ONCHIP_DECODE reference mode ----------------------------------------


def test_resolve_onchip_decode(monkeypatch):
    monkeypatch.delenv(ingest._ONCHIP_ENV_VAR, raising=False)
    assert ingest.resolve_onchip_decode(False) is False
    assert ingest.resolve_onchip_decode(True) is True
    monkeypatch.setenv(ingest._ONCHIP_ENV_VAR, "1")
    assert ingest.resolve_onchip_decode(False) is True
    monkeypatch.setenv(ingest._ONCHIP_ENV_VAR, "0")
    assert ingest.resolve_onchip_decode(True) is False


def test_onchip_decode_returns_coefficients_for_jpeg_wire():
    """RDP_ONCHIP_DECODE on a legacy format=0 JPEG request: the host half
    entropy-decodes and hands the device half coefficients whose decode
    is bitwise what cv2 would have produced."""
    color_bgr = _scene(48, 64, seed=7)
    depth = np.zeros((48, 64), np.uint16)
    req = client_lib.encode_request(color_bgr, depth)  # format=0 JPEG
    rgb, _, _ = ingest.decode_request(req, onchip=True)
    assert isinstance(rgb, entropy.CoefficientFrame)
    ref, _, _ = ingest.decode_request(req)  # cv2 path
    assert np.array_equal(_device_decode(rgb), ref)


def test_onchip_falls_back_to_cv2_for_unsupported_streams():
    """Progressive JPEG under onchip: 'unsupported' streams fall back to
    cv2.imdecode instead of erroring the frame."""
    jpg = _encode(_scene(48, 64), "420",
                  extra=(cv2.IMWRITE_JPEG_PROGRESSIVE, 1))
    img = vision_pb2.Image(data=jpg, width=64, height=48)
    rgb = ingest.decode_color(img, onchip=True)
    assert isinstance(rgb, np.ndarray) and rgb.shape == (48, 64, 3)


def test_onchip_leaves_png_untouched():
    ok, png = cv2.imencode(".png", _scene(32, 32))
    img = vision_pb2.Image(data=png.tobytes(), width=32, height=32)
    rgb = ingest.decode_color(img, onchip=True)
    assert isinstance(rgb, np.ndarray)


def test_onchip_split_frame_observes_entropy_stage():
    from robotic_discovery_platform_tpu.observability import (
        instruments as obs,
    )

    req = client_lib.encode_request(_scene(48, 64),
                                    np.zeros((48, 64), np.uint16),
                                    fmt="coef")
    pool = ingest.DecodePool(0)
    try:
        before_e = obs.HOST_STAGE_SPLIT.labels(stage="entropy").count
        before_c = obs.DECODE_SECONDS.labels(format="coef").count
        pool.decode(req)
        assert obs.HOST_STAGE_SPLIT.labels(stage="entropy").count == \
            before_e + 1
        assert obs.DECODE_SECONDS.labels(format="coef").count == \
            before_c + 1
    finally:
        pool.stop()


# -- flops satellites --------------------------------------------------------


def test_decode_rooflines_are_bandwidth_bound_at_serving_shapes():
    """The bench_pallas gate's analytic half: the whole on-chip decode
    stage classifies bandwidth-bound at the serving frame shape -- it
    rides the analyzer's HBM streams rather than competing for MXU."""
    from robotic_discovery_platform_tpu.utils import flops as flops_lib

    peaks = flops_lib.chip_peaks("TPU v5 lite")  # the serving chip
    for b in (1, 8):
        roof = flops_lib.jpeg_decode_roofline_ms(480, 640, batch=b,
                                                 subsampling="420",
                                                 peaks=peaks)
        assert roof["bound_by"] == "memory", roof
        assert roof["flops"] > 0 and roof["bytes"] > 0
    idct = flops_lib.jpeg_idct_roofline_ms(4800, batch=8, peaks=peaks)
    assert idct["bound_by"] == "memory", idct
