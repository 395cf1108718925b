"""Pallas-vs-XLA micro-benchmark at the deployed U-Net's layer shapes.

The claim behind ops/pallas (SURVEY.md Phase 2: kernels for the reference's
hot blocks, pkg/segmentation_model.py:24-40,54-65) is checked empirically
here: for every 3x3 conv+BN+ReLU shape in the 256x256 inference forward,
plus the 1x1 head and the 2x2 stride-2 transpose conv, time the fused
Pallas kernel against the plain-XLA equivalent on the real chip, then time
the whole-net forward (auto-dispatched Pallas net vs Flax/XLA). Writes
PALLASBENCH.json -- the in-repo evidence for the per-shape dispatch
threshold in ops/pallas/unet_infer.py (PALLAS_MAX_ELEMS).

Same chained-scan timing as bench.py (see its docstring): K data-dependent
kernel applications inside one compiled ``lax.scan``, one host fetch, minus
the independently measured fetch round-trip. bf16 inputs / f32 accumulation,
matching serving.

Needs the TPU and touches JAX in this one process only: with no TPU it
exits non-zero naming the platform JAX found, and a section that raises
(a Mosaic refusal included) ends the run with its traceback.

Caveat: the per-shape chains need a shape-preserving feedback transform
(tile/slice) whose overhead rides on both sides of each comparison; at
sub-millisecond scales the per-shape ratios vary noticeably between runs.
Treat individual rows as indicative, the aggregate picture and the
``full_forward_b1_256`` row (the real dispatch-policy evidence, stable
across runs) as the conclusions.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

CHAIN = 100

# Every distinct (H, W, Cin, Cout) the deployed bilinear-variant forward
# runs through conv3x3_bn_relu at batch 1, 256x256 input
# (models/unet.py channel ladder 64..512, halved decoder mids).
CONV3X3_SHAPES = [
    (256, 256, 3, 64), (256, 256, 64, 64),
    (128, 128, 64, 128), (128, 128, 128, 128),
    (64, 64, 128, 256), (64, 64, 256, 256),
    (32, 32, 256, 512), (32, 32, 512, 512),
    (16, 16, 512, 512),
    (32, 32, 1024, 512), (32, 32, 512, 256),
    (64, 64, 512, 256), (64, 64, 256, 128),
    (128, 128, 256, 128), (128, 128, 128, 64),
    (256, 256, 128, 64),
]


def _roundtrip_ms() -> float:
    @jax.jit
    def trivial(x):
        return x + 1.0

    x = jnp.ones((8,))
    float(trivial(x)[0])
    ts = []
    for _ in range(10):
        t0 = time.perf_counter()
        float(trivial(x)[0])
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e3)


def _roofline_fields(roof: dict, pallas_ms: float | None,
                     xla_ms: float | None) -> dict:
    """The per-shape roofline block every PALLASBENCH row carries:
    analytic flops + minimal HBM traffic (utils/flops.py), the
    compute-vs-bandwidth classification, and -- when a measurement is
    present -- percent of the roofline bound achieved (the chain's
    feedback-transform overhead rides on the measured time, so the
    percentages are conservative)."""
    out = {
        "flops": roof["flops"],
        "hbm_bytes": roof["bytes"],
        "roofline_ms": round(roof["bound_ms"], 4),
        "bound_by": roof["bound_by"],
    }
    if pallas_ms:
        out["pallas_pct_of_bound"] = round(
            100 * roof["bound_ms"] / pallas_ms, 1)
    if pallas_ms and xla_ms:
        out["best_pct_of_bound"] = round(
            100 * roof["bound_ms"] / min(pallas_ms, xla_ms), 1)
    return out


def _time_chain(fn, x0, rt_ms: float, reps: int = 3) -> float:
    """Per-application ms of ``fn`` chained CHAIN times (x must map to an
    output that can be fed back; callers wrap to keep shapes fixed)."""

    @jax.jit
    def chained(x):
        final, _ = lax.scan(lambda c, _: (fn(c), None), x, None, length=CHAIN)
        return final

    np.asarray(jax.block_until_ready(chained(x0)))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(chained(x0))
        best = min(best, time.perf_counter() - t0)
    if not np.isfinite(best) or best <= 0.0:
        raise RuntimeError(f"non-positive chain time {best!r}s")
    return max((best * 1e3 - rt_ms) / CHAIN, 1e-6)


def bench_conv3x3(rt_ms: float, peaks) -> list[dict]:
    from robotic_discovery_platform_tpu.ops.pallas import (
        conv3x3_bn_relu, conv3x3_bn_relu_xla)
    from robotic_discovery_platform_tpu.utils import flops as flops_lib

    rng = np.random.default_rng(0)
    rows = []
    for h, w, ci, co in CONV3X3_SHAPES:
        x = jnp.asarray(rng.normal(size=(1, h, w, ci)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(3, 3, ci, co)) * 0.1, jnp.float32)
        scale = jnp.ones((co,), jnp.float32)
        bias = jnp.zeros((co,), jnp.float32)
        # feed a Cin-slice of the output back in so the chain is
        # data-dependent but shape-stable
        reps_in = -(-ci // co)  # ceil

        def step(c, kernel=k, s=scale, b=bias, cin=ci, r=reps_in):
            y = conv3x3_bn_relu(c, kernel, s, b, relu=True)
            return jnp.tile(y, (1, 1, 1, r))[..., :cin].astype(jnp.bfloat16)

        def step_xla(c, kernel=k, s=scale, b=bias, cin=ci, r=reps_in):
            y = conv3x3_bn_relu_xla(c, kernel, s, b, relu=True)
            return jnp.tile(y, (1, 1, 1, r))[..., :cin].astype(jnp.bfloat16)

        t_pallas = _time_chain(step, x, rt_ms)
        t_xla = _time_chain(step_xla, x, rt_ms)
        # roofline: how close the better implementation runs to the chip's
        # compute/bandwidth bound for this shape (utils/flops.py; the
        # chain's feedback tile/slice overhead rides on the measured time,
        # so pct_of_bound is understated -- a conservative bound)
        roof = flops_lib.conv3x3_roofline_ms(h, w, ci, co, peaks=peaks)
        rows.append({
            "op": "conv3x3_bn_relu", "h": h, "w": w, "cin": ci, "cout": co,
            "pallas_ms": round(t_pallas, 4), "xla_ms": round(t_xla, 4),
            "speedup": round(t_xla / t_pallas, 3),
            **_roofline_fields(roof, t_pallas, t_xla),
        })
        print(f"# 3x3 {h}x{w} {ci}->{co}: pallas={t_pallas:.3f}ms "
              f"xla={t_xla:.3f}ms x{t_xla / t_pallas:.2f} "
              f"roof={roof['bound_ms']:.3f}ms ({roof['bound_by']})",
              file=sys.stderr)
    return rows


def bench_heads(rt_ms: float, peaks) -> list[dict]:
    from robotic_discovery_platform_tpu.ops.pallas import (
        conv1x1, conv1x1_xla, conv_transpose2x2, conv_transpose2x2_xla)
    from robotic_discovery_platform_tpu.utils import flops as flops_lib

    rng = np.random.default_rng(1)
    rows = []

    # 1x1 head at full resolution: 256x256, 64 -> 1 (OutConv). conv1x1
    # takes the [Cin, Cout] kernel (the [0, 0] slice of the HWIO tree, same
    # as unet_infer's call site).
    x = jnp.asarray(rng.normal(size=(1, 256, 256, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(64, 1)) * 0.1, jnp.float32)
    s, b = jnp.ones((1,), jnp.float32), jnp.zeros((1,), jnp.float32)

    def head(c):
        y = conv1x1(c, k, s, b)
        return (c + y.astype(jnp.bfloat16))  # broadcast dependency

    def head_xla(c):
        y = conv1x1_xla(c, k, s, b)
        return (c + y.astype(jnp.bfloat16))

    t_p, t_x = _time_chain(head, x, rt_ms), _time_chain(head_xla, x, rt_ms)
    rows.append({"op": "conv1x1", "h": 256, "w": 256, "cin": 64, "cout": 1,
                 "pallas_ms": round(t_p, 4), "xla_ms": round(t_x, 4),
                 "speedup": round(t_x / t_p, 3),
                 **_roofline_fields(
                     flops_lib.conv1x1_roofline_ms(256, 256, 64, 1,
                                                   peaks=peaks),
                     t_p, t_x)})
    print(f"# 1x1 head: pallas={t_p:.3f}ms xla={t_x:.3f}ms", file=sys.stderr)

    # transpose-conv decoder step (non-bilinear variant): 32x32 512 -> 256
    x = jnp.asarray(rng.normal(size=(1, 32, 32, 512)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 2, 512, 256)) * 0.1, jnp.float32)
    bias = jnp.zeros((256,), jnp.float32)

    def tc(c):
        y = conv_transpose2x2(c, k, bias)  # [1,64,64,256]
        y = y.reshape(1, 32, 2, 32, 2, 256).mean((2, 4))  # back to 32x32
        return jnp.tile(y, (1, 1, 1, 2)).astype(jnp.bfloat16)

    def tc_xla(c):
        y = conv_transpose2x2_xla(c, k, bias)
        y = y.reshape(1, 32, 2, 32, 2, 256).mean((2, 4))
        return jnp.tile(y, (1, 1, 1, 2)).astype(jnp.bfloat16)

    t_p, t_x = _time_chain(tc, x, rt_ms), _time_chain(tc_xla, x, rt_ms)
    rows.append({"op": "conv_transpose2x2", "h": 32, "w": 32, "cin": 512,
                 "cout": 256, "pallas_ms": round(t_p, 4),
                 "xla_ms": round(t_x, 4), "speedup": round(t_x / t_p, 3),
                 **_roofline_fields(
                     flops_lib.conv_transpose2x2_roofline_ms(
                         32, 32, 512, 256, peaks=peaks),
                     t_p, t_x)})
    print(f"# 2x2^T: pallas={t_p:.3f}ms xla={t_x:.3f}ms", file=sys.stderr)
    return rows


def bench_geometry(rt_ms: float, peaks) -> list[dict]:
    """Fused geometry/B-spline kernels (ops/pallas/geometry.py) vs their
    XLA reference chains, at the deployed analyzer shapes: the 480x640
    deproject+edge-stats pass (stride 1 and the pooled stride-2 view) and
    the B-spline design/curvature stages (N = num_bins * max_per_bin =
    6400 edge budget, C = 16 control points, 100 curvature samples)."""
    import jax.numpy as jnp

    from robotic_discovery_platform_tpu.ops import bspline, geometry
    from robotic_discovery_platform_tpu.ops.pallas import (
        geometry as pgeom,
    )
    from robotic_discovery_platform_tpu.utils import flops as flops_lib
    from robotic_discovery_platform_tpu.utils.config import GeometryConfig

    rng = np.random.default_rng(3)
    rows = []
    cfg = GeometryConfig()
    big = jnp.float32(1e30)

    # deproject + edge stats: feed the z map back as depth (z = depth *
    # scale, so the chain is data-dependent and shape-stable); the tiny
    # tanh(stat) term keeps the reductions live on both sides.
    for stride in (1, 2):
        h, w = 480 // stride, 640 // stride
        mask = jnp.asarray(rng.random((h, w)) > 0.4, jnp.uint8)
        d0 = jnp.asarray(rng.random((h, w)) * 800 + 200, jnp.float32)
        fx = fy = jnp.float32(600.0)
        cx, cy = jnp.float32(w / 2), jnp.float32(h / 2)

        def step_pallas(d, stride=stride, mask=mask, fx=fx, fy=fy,
                        cx=cx, cy=cy):
            _, _, z, _, st = pgeom.deproject_edge_stats(
                mask, d, fx, fy, cx, cy, 0.001, stride=stride
            )
            return z * 1000.0 + jnp.tanh(st[0])

        def step_xla(d, stride=stride, mask=mask, fx=fx, fy=fy,
                     cx=cx, cy=cy):
            x, y, z, v = geometry.deproject(
                mask, d, fx, fy, cx, cy, 0.001, stride=stride
            )
            xs, ys, vf = x.reshape(-1), y.reshape(-1), v.reshape(-1)
            x_min = jnp.min(jnp.where(vf, xs, big))
            jnp.max(jnp.where(vf, xs, -big))
            return z * 1000.0 + jnp.tanh(x_min)

        t_p = _time_chain(step_pallas, d0, rt_ms)
        t_x = _time_chain(step_xla, d0, rt_ms)
        roof = flops_lib.deproject_roofline_ms(h, w, peaks=peaks)
        rows.append({
            "op": "deproject_edge_stats", "h": h, "w": w, "stride": stride,
            "pallas_ms": round(t_p, 4), "xla_ms": round(t_x, 4),
            "speedup": round(t_x / t_p, 3),
            **_roofline_fields(roof, t_p, t_x),
        })
        print(f"# deproject {h}x{w} s{stride}: pallas={t_p:.3f}ms "
              f"xla={t_x:.3f}ms x{t_x / t_p:.2f}", file=sys.stderr)

    # B-spline design + curvature at the deployed fit shapes
    n, c = cfg.num_bins * cfg.max_per_bin, cfg.num_ctrl
    knots = bspline.clamped_uniform_knots(c, cfg.spline_degree)
    pts0 = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    wts = jnp.asarray(rng.random(n) > 0.3, jnp.float32)

    def design_pallas(pts, wts=wts):
        u = bspline.chord_length_params(pts, wts)
        _, rhs = pgeom.bspline_design(
            pts, wts, u, pgeom.static_knots(knots), cfg.spline_degree
        )
        reps = -(-n // rhs.shape[0])
        return pts + 1e-3 * jnp.tanh(jnp.tile(rhs, (reps, 1))[:n])

    def design_xla(pts, wts=wts):
        u = bspline.chord_length_params(pts, wts)
        b = bspline.bspline_basis(u, knots, cfg.spline_degree)
        bw = b * wts[:, None]
        rhs = bspline._mm(bw.T, pts)
        bspline._mm(bw.T, b)
        reps = -(-n // rhs.shape[0])
        return pts + 1e-3 * jnp.tanh(jnp.tile(rhs, (reps, 1))[:n])

    t_p = _time_chain(design_pallas, pts0, rt_ms)
    t_x = _time_chain(design_xla, pts0, rt_ms)
    roof = flops_lib.bspline_design_roofline_ms(n, c, peaks=peaks)
    rows.append({
        "op": "bspline_design", "n": n, "c": c,
        "pallas_ms": round(t_p, 4), "xla_ms": round(t_x, 4),
        "speedup": round(t_x / t_p, 3),
        **_roofline_fields(roof, t_p, t_x),
    })
    print(f"# bspline_design n{n} c{c}: pallas={t_p:.3f}ms "
          f"xla={t_x:.3f}ms x{t_x / t_p:.2f}", file=sys.stderr)

    ns = cfg.num_samples
    u_fine = jnp.linspace(0.0, 1.0, ns)
    ctrl0 = jnp.asarray(rng.normal(size=(c, 3)), jnp.float32)

    def curv_pallas(ctrl):
        kappa, _, r = pgeom.bspline_curvature(
            ctrl, u_fine, pgeom.static_knots(knots), cfg.spline_degree
        )
        return ctrl + 1e-3 * jnp.tanh(r[:c] + kappa[:c, None])

    def curv_xla(ctrl):
        kappa, _, r = bspline.curvature_profile(
            ctrl, knots, u_fine, cfg.spline_degree
        )
        return ctrl + 1e-3 * jnp.tanh(r[:c] + kappa[:c, None])

    t_p = _time_chain(curv_pallas, ctrl0, rt_ms)
    t_x = _time_chain(curv_xla, ctrl0, rt_ms)
    roof = flops_lib.bspline_curvature_roofline_ms(ns, c, peaks=peaks)
    rows.append({
        "op": "bspline_curvature", "n": ns, "c": c,
        "pallas_ms": round(t_p, 4), "xla_ms": round(t_x, 4),
        "speedup": round(t_x / t_p, 3),
        **_roofline_fields(roof, t_p, t_x),
    })
    print(f"# bspline_curvature n{ns} c{c}: pallas={t_p:.3f}ms "
          f"xla={t_x:.3f}ms x{t_x / t_p:.2f}", file=sys.stderr)
    return rows


def bench_decode(rt_ms: float, peaks) -> list[dict]:
    """Split-JPEG decode stage (ops/pallas/decode.py + ops/pipeline.py)
    vs the XLA reference, at the serving frame shape (480x640 4:2:0).

    Two row families: the fused dequant+IDCT launch alone (Pallas kernel
    vs the XLA basis-matmul reference, both bitwise-identical so the race
    is pure schedule), and the whole ``decode_coef_batch`` stage
    (dequant+IDCT x3 planes + fancy upsample + color convert). The gate:
    the whole-stage roofline must classify as bandwidth-bound (``bound_by
    == "memory"``) -- on-chip decode rides the analyzer's HBM streams, it
    must not compete for MXU time -- and this section asserts that, so a
    flops.py regression that flips the classification fails the bench."""
    from robotic_discovery_platform_tpu.ops import pipeline as pipeline_lib
    from robotic_discovery_platform_tpu.ops.pallas import decode as pdecode
    from robotic_discovery_platform_tpu.ops.pallas.geometry import (
        MOSAIC_REFUSES,
    )
    from robotic_discovery_platform_tpu.utils import flops as flops_lib

    rng = np.random.default_rng(4)
    rows = []
    # Mosaic refuses the IDCT kernel on this installation (a static table
    # serving reads too): its rows carry the XLA time, pallas_ms null and
    # the compiler's reason
    refused = MOSAIC_REFUSES.get("jpeg_idct")

    def pallas_side(step, x0):
        if refused is not None:
            return None, {"pallas_refused": refused}
        return _time_chain(step, x0, rt_ms), {}

    def ratio(t_x, t_p):
        return round(t_x / t_p, 3) if t_p else None
    h, w = 480, 640
    ybh, ybw = h // 8, w // 8          # 60 x 80 luma blocks
    cbh, cbw = h // 16, w // 16        # 4:2:0 chroma grid

    # fused dequant+IDCT alone: [B, N, 64] coefficients through the two
    # basis matmuls; the output samples (0..255) level-shift back to a
    # coefficient-shaped int16 feed, so the chain is data-dependent and
    # shape-stable on both sides.
    for b in (1, 8):
        n = ybh * ybw
        coefs = jnp.asarray(
            rng.integers(-64, 64, (b, n, 64)), jnp.int16)
        q = jnp.asarray(rng.integers(2, 24, (b, 64)), jnp.uint16)

        def step_pallas(c, q=q):
            y = pdecode.dequant_idct(c, q, impl="pallas")
            return (y - 128).astype(jnp.int16)

        def step_xla(c, q=q):
            y = pdecode.dequant_idct(c, q, impl="xla")
            return (y - 128).astype(jnp.int16)

        t_p, note = pallas_side(step_pallas, coefs)
        t_x = _time_chain(step_xla, coefs, rt_ms)
        roof = flops_lib.jpeg_idct_roofline_ms(n, batch=b, peaks=peaks)
        rows.append({
            "op": "jpeg_dequant_idct", "b": b, "n_blocks": n,
            "pallas_ms": t_p and round(t_p, 4), "xla_ms": round(t_x, 4),
            "speedup": ratio(t_x, t_p), **note,
            **_roofline_fields(roof, t_p, t_x),
        })
        print(f"# dequant_idct b{b} n{n}: pallas={t_p}ms "
              f"xla={t_x:.3f}ms "
              f"roof={roof['bound_ms']:.3f}ms ({roof['bound_by']})",
              file=sys.stderr)

    # whole decode stage: coefficients -> RGB. Feed the decoded luma
    # channel back through the inverse block assembly as the next luma
    # coefficient plane (chroma/quant ride as closed-over constants).
    b = 8
    ny, nc = ybh * ybw, cbh * cbw
    y0 = jnp.asarray(rng.integers(-64, 64, (b, ny, 64)), jnp.int16)
    cb0 = jnp.asarray(rng.integers(-32, 32, (b, nc, 64)), jnp.int16)
    cr0 = jnp.asarray(rng.integers(-32, 32, (b, nc, 64)), jnp.int16)
    qy = jnp.asarray(rng.integers(2, 24, (b, 64)), jnp.uint16)
    qc = jnp.asarray(rng.integers(2, 32, (b, 64)), jnp.uint16)

    def _decode_step(impl):
        def step(y):
            rgb = pipeline_lib.decode_coef_batch(
                y, cb0, cr0, qy, qc, height=h, width=w,
                subsampling="420", impl=impl)
            lum = rgb[..., 0].astype(jnp.int32) - 128
            blocks = lum.reshape(b, ybh, 8, ybw, 8).transpose(
                0, 1, 3, 2, 4).reshape(b, ny, 64)
            return blocks.astype(jnp.int16)
        return step

    t_p, note = pallas_side(_decode_step("pallas"), y0)
    t_x = _time_chain(_decode_step("xla"), y0, rt_ms)
    roof = flops_lib.jpeg_decode_roofline_ms(h, w, batch=b,
                                             subsampling="420", peaks=peaks)
    # the gate: on-chip decode must be bandwidth-bound at serving shapes
    assert roof["bound_by"] == "memory", (
        f"decode stage classified {roof['bound_by']!r}-bound at "
        f"{h}x{w} b{b}; the split-decode design requires it to ride "
        "the HBM streams (see utils/flops.jpeg_decode_roofline_ms)"
    )
    rows.append({
        "op": "decode_coef_batch", "b": b, "h": h, "w": w,
        "subsampling": "420",
        "pallas_ms": t_p and round(t_p, 4), "xla_ms": round(t_x, 4),
        "speedup": ratio(t_x, t_p), **note,
        **_roofline_fields(roof, t_p, t_x),
    })
    print(f"# decode b{b} {h}x{w}: pallas={t_p}ms xla={t_x:.3f}ms "
          f"roof={roof['bound_ms']:.3f}ms ({roof['bound_by']})",
          file=sys.stderr)
    return rows


def bench_egress(rt_ms: float, peaks) -> list[dict]:
    """Egress mask bitpack (ops/pallas/pack.bitpack_mask) vs the XLA
    fallback at the serving mask shape (480x640) -- the device half of
    the one-fetch egress wire (serving/egress.py).

    Both backends run the same _pack_math arithmetic (results bitwise
    identical; tests/test_egress.py), so the race is pure schedule. The
    gate: the roofline must classify as bandwidth-bound (``bound_by ==
    "memory"``) -- packing is one HBM pass over the mask and must ride
    free under the analyzer's compute, the same contract the decode
    stage pins on the way in."""
    from robotic_discovery_platform_tpu.ops.pallas import pack as pack_lib
    from robotic_discovery_platform_tpu.utils import flops as flops_lib

    rng = np.random.default_rng(5)
    rows = []
    h, w = 480, 640
    wb = pack_lib.packed_row_bytes(w)
    for b in (1, 8):
        mask0 = jnp.asarray(rng.integers(0, 2, (b, h, w)), jnp.uint8)

        def step_for(impl, b=b):
            def step(m):
                p = pack_lib.bitpack_mask(m, impl=impl)
                # unpack in-graph back to a mask-shaped feed, so the
                # chain is data-dependent and shape-stable
                bits = (p[..., None] >> jnp.arange(7, -1, -1,
                                                   dtype=jnp.uint8)) & 1
                return bits.reshape(b, h, wb * 8)[..., :w]
            return step

        t_p = _time_chain(step_for("pallas"), mask0, rt_ms)
        t_x = _time_chain(step_for("xla"), mask0, rt_ms)
        roof = flops_lib.mask_bitpack_roofline_ms(h, w, batch=b,
                                                 peaks=peaks)
        # the gate: packing must be bandwidth-bound at serving shapes
        assert roof["bound_by"] == "memory", (
            f"mask bitpack classified {roof['bound_by']!r}-bound at "
            f"{h}x{w} b{b}; the egress design requires one bandwidth-"
            "bound HBM pass (see utils/flops.mask_bitpack_roofline_ms)"
        )
        rows.append({
            "op": "mask_bitpack", "b": b, "h": h, "w": w,
            "pallas_ms": round(t_p, 4), "xla_ms": round(t_x, 4),
            "speedup": round(t_x / t_p, 3),
            **_roofline_fields(roof, t_p, t_x),
        })
        print(f"# mask_bitpack b{b} {h}x{w}: pallas={t_p:.3f}ms "
              f"xla={t_x:.3f}ms x{t_x / t_p:.2f} "
              f"roof={roof['bound_ms']:.3f}ms ({roof['bound_by']})",
              file=sys.stderr)
    return rows


def bench_full_forward(rt_ms: float) -> dict:
    from robotic_discovery_platform_tpu.models.unet import build_unet, init_unet
    from robotic_discovery_platform_tpu.ops.pallas import make_pallas_unet
    from robotic_discovery_platform_tpu.utils.config import ModelConfig

    model = build_unet(ModelConfig())
    variables = init_unet(model, jax.random.key(0))
    pnet = make_pallas_unet(model, variables)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.uniform(size=(1, 256, 256, 3)), jnp.bfloat16)

    def flax_fwd(c):
        y = model.apply(variables, c, train=False)  # [1,256,256,1]
        return jnp.concatenate([c[..., :2], y.astype(jnp.bfloat16)], -1)

    def pallas_fwd(c):
        y = pnet(c)
        return jnp.concatenate([c[..., :2], y.astype(jnp.bfloat16)], -1)

    t_flax = _time_chain(flax_fwd, x, rt_ms)
    t_pallas = _time_chain(pallas_fwd, x, rt_ms)
    print(f"# full forward 256x256: pallas-auto={t_pallas:.3f}ms "
          f"flax/xla={t_flax:.3f}ms", file=sys.stderr)
    return {"flax_xla_ms": round(t_flax, 4),
            "pallas_auto_ms": round(t_pallas, 4),
            "speedup": round(t_flax / t_pallas, 3)}


def autotune(rt_ms: float, focus=None) -> dict:
    """Sweep every budget-feasible (tile_h, tile_co, dx_major) per conv
    shape (ops/pallas/tuning.candidates) with the chained-scan timing; a
    config is recorded as an override only when it beats BOTH the analytic
    heuristic and a re-measured XLA anchor by >3% (otherwise the entry is
    dropped so the uniform-dispatch decision stays evidence-based). Writes
    PALLAS_TUNE.json, which unet_infer's dispatch consults per launch."""
    from robotic_discovery_platform_tpu.ops.pallas import (
        conv3x3_bn_relu, conv3x3_bn_relu_xla, tuning)

    rng = np.random.default_rng(0)
    entries, report = {}, []
    shapes = focus or CONV3X3_SHAPES
    for h, w, ci, co in shapes:
        x = jnp.asarray(rng.normal(size=(1, h, w, ci)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(3, 3, ci, co)) * 0.1, jnp.float32)
        scale = jnp.ones((co,), jnp.float32)
        bias = jnp.zeros((co,), jnp.float32)
        reps_in = -(-ci // co)

        def step_for(tiling, kernel=k, s=scale, b=bias, cin=ci, r=reps_in):
            def step(c):
                y = conv3x3_bn_relu(c, kernel, s, b, relu=True,
                                    tiling=tiling)
                return jnp.tile(y, (1, 1, 1, r))[..., :cin].astype(
                    jnp.bfloat16)
            return step

        def step_xla(c, kernel=k, s=scale, b=bias, cin=ci, r=reps_in):
            y = conv3x3_bn_relu_xla(c, kernel, s, b, relu=True)
            return jnp.tile(y, (1, 1, 1, r))[..., :cin].astype(jnp.bfloat16)

        cands = tuning.candidates(h, w, ci, co)
        t_heur = _time_chain(step_for(None), x, rt_ms)
        t_xla = _time_chain(step_xla, x, rt_ms)
        best_t, best_cfg = t_heur, cands[0]
        for cand in cands[1:]:
            try:
                t = _time_chain(step_for(cand), x, rt_ms)
            except Exception as exc:  # infeasible config (compile/VMEM)
                print(f"#   {h}x{w} {ci}->{co} {cand}: {type(exc).__name__}",
                      file=sys.stderr)
                continue
            if t < best_t:
                best_t, best_cfg = t, cand
        improved = best_t < t_heur * 0.97 and best_t < t_xla * 0.97
        row = {
            "h": h, "w": w, "cin": ci, "cout": co,
            "heuristic": {"cfg": list(cands[0]),
                          "ms": round(t_heur, 4)},
            "best": {"cfg": list(best_cfg), "ms": round(best_t, 4)},
            "xla_ms": round(t_xla, 4),
            "tuned": bool(improved),
            "n_candidates": len(cands),
        }
        report.append(row)
        print(f"# tune {h}x{w} {ci}->{co}: heur={t_heur:.3f}ms "
              f"best={best_t:.3f}ms ({best_cfg}) xla={t_xla:.3f}ms "
              f"{'TUNED' if improved else 'keep-heuristic'}",
              file=sys.stderr)
        if improved:
            th, tc, dxm = best_cfg
            entries[tuning.key(h, w, ci, co)] = {
                "tile_h": th, "tile_co": tc, "dx_major": dxm,
                "ms": round(best_t, 4),
                "heuristic_ms": round(t_heur, 4),
                "xla_ms": round(t_xla, 4),
            }
    meta = {
        "device": jax.devices()[0].device_kind,
        "chain": CHAIN,
        "roundtrip_ms": round(rt_ms, 1),
        "criterion": ">3% faster than heuristic AND xla",
        "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if focus:
        # focused re-tune: merge over the existing table -- replace every
        # swept shape's entry (tuned or dropped), keep the rest
        prev = dict(tuning._table())
        for h, w, ci, co in shapes:
            prev.pop(tuning.key(h, w, ci, co), None)
        prev.update(entries)
        entries = prev
    path = tuning.save_entries(entries, meta)
    print(f"# wrote {path} with {len(entries)} overrides", file=sys.stderr)
    return {"entries": len(entries), "report": report}


def main() -> None:
    from robotic_discovery_platform_tpu.utils import flops as flops_lib
    from robotic_discovery_platform_tpu.utils.platforms import (
        enable_compile_cache,
        require_accelerator,
    )

    enable_compile_cache()
    device = require_accelerator("bench_pallas.py")
    peaks = flops_lib.chip_peaks(device.device_kind)
    rt_ms = _roundtrip_ms()
    if len(sys.argv) > 1 and sys.argv[1] == "autotune":
        # optional shape filter: "autotune 32" tunes only 32x32 layers
        focus = None
        if len(sys.argv) > 2:
            want = int(sys.argv[2])
            focus = [s for s in CONV3X3_SHAPES if s[0] == want]
            if not focus:
                sys.exit(f"no conv shape with H={want} "
                         f"(have {sorted({s[0] for s in CONV3X3_SHAPES})})")
        out = autotune(rt_ms, focus)
        print(json.dumps({"autotuned_overrides": out["entries"]}))
        return
    result = {
        "backend": jax.default_backend(),
        "device": device.device_kind,
        "chain": CHAIN,
        "roundtrip_ms": round(rt_ms, 1),
        "dtype": "bfloat16 in / f32 accumulate",
        "conv3x3": bench_conv3x3(rt_ms, peaks),
        "heads": bench_heads(rt_ms, peaks),
        "geometry": bench_geometry(rt_ms, peaks),
        "decode": bench_decode(rt_ms, peaks),
        "egress": bench_egress(rt_ms, peaks),
        "full_forward_b1_256": bench_full_forward(rt_ms),
        "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    out = REPO / "PALLASBENCH.json"
    out.write_text(json.dumps(result, indent=2) + "\n")
    print(json.dumps({"wrote": str(out),
                      "full_forward": result["full_forward_b1_256"]}))


if __name__ == "__main__":
    main()
