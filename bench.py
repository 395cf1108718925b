"""Headline benchmark: fused segmentation + curvature throughput at 640x480
on one chip, vs the MEASURED reference CPU pipeline (BASELINE_MEASURED.json,
produced by bench_reference.py) and the 30 FPS design target (BASELINE.json;
the reference itself publishes no numbers -- BASELINE.md).

Timing method: K data-dependent fused iterations are chained inside one
compiled `lax.scan` (each frame is a function of the previous mask, so no
iteration can be elided or overlapped), timed around exactly one host
fetch, and the independently measured fetch round-trip is subtracted. That
is the steady-state streaming throughput of the chip itself, with the
per-call dispatch cost taken out.

The headline bench and the non-smoke serving bench need the accelerator:
with no TPU they exit non-zero naming the platform JAX found, and any
exception leaves a traceback and a non-zero exit code. Everything runs in
this one process (a chip belongs to one process at a time).

The model forward runs through the Pallas-fused kernels (ops/pallas) on TPU
and plain Flax/XLA elsewhere -- the same auto policy the server uses; both
paths are timed and reported on stderr, with batched (cross-stream
micro-batching) throughput at B=4 and B=8.

Prints ONE JSON line on success:
  {"metric": ..., "value": N, "unit": "frames/sec", "vs_baseline": N,
   "vs_target": N}
where vs_baseline is vs the measured reference CPU FPS when
BASELINE_MEASURED.json exists (falling back to the 30 FPS target), and
vs_target is always vs the 30 FPS north star.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

TARGET_FPS = 30.0  # BASELINE.json north star for serving on v5e-1
CHAIN = 200


def _roundtrip_ms() -> float:
    """Median host->device->host latency for a trivial fetch."""

    @jax.jit
    def trivial(x):
        return x + 1.0

    x = jnp.ones((8,))
    float(trivial(x)[0])  # compile
    ts = []
    for _ in range(10):
        t0 = time.perf_counter()
        float(trivial(x)[0])
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e3)


def _measure_chain(chained, f0, chain: int, rt_ms: float, reps: int = 3):
    """Best-of-reps per-iteration ms for one compiled chain + one fetch
    (the first call pays compilation and is timed separately)."""
    t0 = time.perf_counter()
    np.asarray(chained(f0))
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(chained(f0))
        best = min(best, time.perf_counter() - t0)
    return max((best * 1e3 - rt_ms) / chain, 1e-6), compile_s


def main() -> None:
    from robotic_discovery_platform_tpu.models.unet import build_unet, init_unet
    from robotic_discovery_platform_tpu.ops import geometry, pipeline
    from robotic_discovery_platform_tpu.ops import pallas as pallas_ops
    from robotic_discovery_platform_tpu.utils import flops as flops_lib
    from robotic_discovery_platform_tpu.utils.config import (
        GeometryConfig,
        ModelConfig,
    )
    from robotic_discovery_platform_tpu.utils.platforms import (
        require_accelerator,
    )

    device = require_accelerator("bench.py (the headline fused-graph bench)")
    peaks = flops_lib.chip_peaks(device.device_kind)
    model = build_unet(ModelConfig())
    variables = init_unet(model, jax.random.key(0))
    # Headline profile = the SERVING DEFAULT (ServerConfig.geometry_stride=1,
    # reference-exact dense geometry). The stride-2 decimated profile is the
    # documented opt-in fast path (fast_stride2_b1; accuracy quantified in
    # GEOMETRY_PARITY.json).
    geom_cfg = GeometryConfig(stride=1)
    geom_cfg_fast = GeometryConfig(stride=2)
    pnet = pallas_ops.make_pallas_unet(model, variables)

    h, w = 480, 640
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 255, size=(h, w, 3), dtype=np.uint8)
    frame[h // 3: 2 * h // 3] = (200, 60, 60)
    depth = jnp.asarray(np.full((h, w), 500, np.uint16))
    intrinsics = jnp.asarray(
        [[600.0, 0, w / 2], [0, 600.0, h / 2], [0, 0, 1]], jnp.float32
    )
    scale = jnp.float32(0.001)

    def make_fused_step(forward, batch: int, gcfg, impl: str = "dense"):
        depth_b = jnp.broadcast_to(depth, (batch, h, w))
        intr_b = jnp.broadcast_to(intrinsics, (batch, 3, 3))
        scale_b = jnp.broadcast_to(scale, (batch,))

        def per_frame(mm, dd, kk, ss, cfg=gcfg):
            return geometry.compute_curvature_profile(mm, dd, kk, ss, cfg)

        # the vmapped leg pins the fused geometry kernels to XLA, exactly as
        # ops/pipeline._analyze_batch does
        gcfg_vmap = dataclasses.replace(gcfg, kernel_impl="xla")

        def one_frame(fi, dd, kk, ss):
            x = pipeline.preprocess(fi[None], 256)
            logits = (forward(x) if forward is not None
                      else model.apply(variables, x, train=False))
            m = pipeline.logits_to_native_masks(logits, h, w)[0]
            prof = per_frame(m, dd, kk, ss)
            dep = (m & jnp.uint8(1)) ^ (
                prof.mean_curvature > 1e30
            ).astype(jnp.uint8)
            return fi ^ dep[..., None]

        def fused_step(f):  # f: [B, H, W, 3] uint8
            if impl == "scan" and batch > 1:
                # scan-over-frames inside ONE dispatch: B=1 VMEM residency,
                # amortized launch (ServerConfig.batch_impl="scan")
                _, out = lax.scan(
                    lambda c, inp: (c, one_frame(*inp)), 0,
                    (f, depth_b, intr_b, scale_b),
                )
                return out
            x = pipeline.preprocess(f, 256)
            logits = (forward(x) if forward is not None
                      else model.apply(variables, x, train=False))
            m = pipeline.logits_to_native_masks(logits, h, w)
            # same batching policy as ops/pipeline._analyze_batch: vmap --
            # the packed-key sort batches as ONE row-batched XLA sort
            if batch == 1:
                prof = jax.tree.map(
                    lambda a: a[None],
                    per_frame(m[0], depth_b[0], intr_b[0], scale_b[0]),
                )
            else:
                prof = jax.vmap(
                    lambda mm, dd, kk, ss: per_frame(mm, dd, kk, ss,
                                                     gcfg_vmap)
                )(m, depth_b, intr_b, scale_b)
            # Data dependency on BOTH the mask and the curvature result so no
            # stage can be dead-code-eliminated across iterations.
            dep = (m & jnp.uint8(1)) ^ (
                prof.mean_curvature[:, None, None] > 1e30
            ).astype(jnp.uint8)
            return f ^ dep[..., None]

        return fused_step

    def bench(forward, batch: int, rt_ms: float, gcfg=None, impl="dense"):
        step = make_fused_step(forward, batch, gcfg or geom_cfg, impl)

        @jax.jit
        def chained(f0):
            final, _ = lax.scan(lambda c, _: (step(c), None), f0, None,
                                length=CHAIN)
            return final

        f0 = jnp.broadcast_to(jnp.asarray(frame), (batch, h, w, 3))
        per_iter_ms, compile_s = _measure_chain(chained, f0, CHAIN, rt_ms)
        return batch * 1000.0 / per_iter_ms, compile_s

    rt_ms = _roundtrip_ms()
    results = {}
    pallas_fwd = pnet
    # BENCH_TRACE_DIR=<dir> captures a jax.profiler trace of one fused chain
    # (TensorBoard-viewable) around the flax-forward measurement.
    from robotic_discovery_platform_tpu.utils.profiling import jax_trace

    with jax_trace(os.environ.get("BENCH_TRACE_DIR")):
        fps_flax, compile_s = bench(None, 1, rt_ms)
    results["flax_b1"] = fps_flax
    results["pallas_b1"], _ = bench(pallas_fwd, 1, rt_ms)
    best_fwd = None
    fps = fps_flax
    if results["pallas_b1"] > fps_flax:
        best_fwd, fps = pallas_fwd, results["pallas_b1"]
    # the opt-in fast profile: stride-2 decimated geometry
    results["fast_stride2_b1"], _ = bench(best_fwd, 1, rt_ms, geom_cfg_fast)
    # Batched serving throughput (cross-stream micro-batching, B frames per
    # dispatch; the PallasUNet auto policy runs these XLA-uniform -- mixed
    # per-layer dispatch and batched Pallas both measure slower). Context
    # for the numbers: b1 already runs the chip at its measured ceiling, so
    # batching targets dispatch amortization, not per-frame speedup.
    for b in (4, 8):
        results[f"batched_b{b}"], _ = bench(best_fwd, b, rt_ms)
    # scan-over-frames batching (ServerConfig.batch_impl="scan"): one
    # dispatch, B=1 VMEM residency -- the round-4 verdict's candidate fix
    # for dense batching's VMEM-spill anti-scaling
    for b in (4, 8):
        results[f"batched_scan_b{b}"], _ = bench(
            best_fwd, b, rt_ms, impl="scan")

    # MFU: conv-only analytic FLOPs over this device's published bf16 peak
    # (the standard matmul-FLOP MFU basis; utils/flops.py, validated vs XLA
    # cost analysis). Per-frame seconds come from the headline fused rate,
    # so geometry/preprocess time COUNTS AGAINST utilization -- this is
    # end-to-end serving MFU, not an isolated-kernel number.
    fwd_flops = flops_lib.unet_forward_flops(256)
    serving_mfu = flops_lib.mfu(fwd_flops, 1.0 / fps, peaks)

    print(
        f"# backend={jax.default_backend()} compile={compile_s:.1f}s "
        f"roundtrip={rt_ms:.1f}ms chain={CHAIN} "
        f"mfu={serving_mfu:.3f} "
        + " ".join(f"{k}={v:.1f}fps" for k, v in results.items()),
        file=sys.stderr,
    )

    baseline_fps = None
    measured = Path(__file__).resolve().parent / "BASELINE_MEASURED.json"
    if measured.exists():
        try:
            baseline_fps = json.loads(measured.read_text())[
                "serving_cpu_per_stage"]["fps"]
        except (KeyError, json.JSONDecodeError):
            baseline_fps = None

    if not np.isfinite(fps) or fps <= 0.0:
        raise RuntimeError(f"measured {fps!r} frames/sec")

    print(json.dumps({
        "metric": "fused_seg_curvature_fps_640x480_1chip",
        "backend": jax.default_backend(),
        "device_kind": device.device_kind,
        "value": round(fps, 2),
        "unit": "frames/sec",
        "vs_baseline": round(fps / (baseline_fps or TARGET_FPS), 3),
        "vs_target": round(fps / TARGET_FPS, 3),
        "batched_fps": {k: round(v, 1) for k, v in results.items()},
        "mfu": round(serving_mfu, 4),
        "mfu_basis": {
            "flops_per_frame": fwd_flops,
            "peak_tflops_bf16": peaks.bf16_tflops,
            "note": "conv-only analytic FLOPs (utils/flops.py) over the "
                    "end-to-end fused frame time (geometry included)",
        },
        "baseline_src": ("measured_reference_cpu" if baseline_fps
                         else "design_target_30fps"),
    }), flush=True)


def serving_pipeline_main(smoke: bool = False, chips: int = 1,
                          dispatch_mode: str = "round_robin",
                          precision: str = "f32") -> None:
    """serving_pipeline_fps: N synthetic concurrent streams through the
    LIVE BatchDispatcher (serving/batching.py), pipelined
    (max_inflight=2) vs serial (pipeline_depth=1), reporting aggregate
    FPS, the measured overlap seconds (rdp_batch_overlap_seconds source),
    the in-flight high-water mark, and a bitwise per-stream parity check
    between the two modes.

    ``chips > 1`` additionally routes the pipelined run across a
    ``make_serving_mesh(chips)`` device mesh (DeviceRouter, round_robin
    or sharded per ``dispatch_mode``) and reports aggregate + per-chip
    FPS, per-chip dispatch balance, and scaling efficiency vs the 1-chip
    pipelined figure; parity stays bitwise against single-chip serial.

    ``smoke`` is the CPU-runnable variant (tiny model, 64x64 frames) CI
    runs -- with ``--chips N`` it exercises the multi-chip path on faked
    CPU devices (XLA_FLAGS=--xla_force_host_platform_device_count) --
    including under RDP_FAULTS="serving.batch.complete:exc:1", where the
    injected completer fault must error-complete its frames and leave the
    dispatcher serving (errored_frames >= 1, value > 0).

    ``precision`` selects the serving tier (ops/pallas/quant.py: f32 /
    bf16 / int8-weight-quantized). Every tier additionally reports parity
    against an f32 reference analyzer over the parity frame set (mask
    IoU, |delta curvature|) and whether the ServerConfig gate thresholds
    pass; the within-tier pipelined-vs-serial check stays bitwise.
    """
    from robotic_discovery_platform_tpu.models.unet import build_unet, init_unet
    from robotic_discovery_platform_tpu.ops import pipeline
    from robotic_discovery_platform_tpu.parallel import mesh as mesh_lib
    from robotic_discovery_platform_tpu.serving.batching import (
        BatchDispatcher,
        DeviceRouter,
    )
    from robotic_discovery_platform_tpu.utils.config import ModelConfig
    from robotic_discovery_platform_tpu.utils.platforms import (
        require_accelerator,
    )

    if smoke:
        h, w, img_size, base = 64, 64, 64, 8
        streams, frames_per_stream, parity_frames = 4, 6, 4
    else:
        require_accelerator("bench.py --serving-pipeline (without --smoke)")
        h, w, img_size, base = 480, 640, 256, 64
        streams, frames_per_stream, parity_frames = 8, 24, 8
    if chips > 1:
        # enough concurrent submitters to keep every chip's window fed
        streams = max(streams, 4 * chips)
        frames_per_stream = max(frames_per_stream, 12)
    max_inflight = 2

    mcfg = ModelConfig(base_features=base, compute_dtype="float32")
    model = build_unet(mcfg)
    variables = init_unet(model, jax.random.key(0), img_size=img_size)
    # precision tier: the served engine binds the transformed pair; the
    # pristine f32 pair stays around as the parity reference
    from robotic_discovery_platform_tpu.ops.pallas import quant
    from robotic_discovery_platform_tpu.utils.config import ServerConfig

    served_model, served_vars, qreport = quant.apply_precision(
        model, variables, precision
    )
    if qreport is not None and qreport.get("layers"):
        print(f"# {precision}: quantized {qreport['layers']} conv kernels "
              f"(max rel err {qreport['max_rel_err']:.2%})",
              file=sys.stderr)
    batch_analyze = pipeline.make_batch_analyzer(served_model,
                                                 img_size=img_size)

    def analyze(frames, depths, intr, scales):
        return batch_analyze(served_vars, frames, depths, intr, scales)

    def make_router() -> DeviceRouter:
        """Mesh + per-placement analyzers, mirroring the server's
        _make_engine: weights are bound to each chip (or mesh-replicated)
        once, never re-transferred per dispatch."""
        mesh = mesh_lib.make_serving_mesh(chips)
        if dispatch_mode == "round_robin":
            analyzers = [
                (lambda f, d_, i, s, _v=v: batch_analyze(_v, f, d_, i, s))
                for v in (jax.device_put(served_vars, dev)
                          for dev in mesh_lib.device_ring(mesh))
            ]
        else:
            v_repl = mesh_lib.shard_pytree(mesh, served_vars)
            analyzers = [
                lambda f, d_, i, s: batch_analyze(v_repl, f, d_, i, s)
            ]
        return DeviceRouter(mesh, dispatch_mode, analyzers)

    rng = np.random.default_rng(0)
    depth = np.full((h, w), 500, np.uint16)
    intr = np.asarray(
        [[0.94 * w, 0, w / 2], [0, 0.94 * w, h / 2], [0, 0, 1]], np.float32
    )
    # one fixed frame set, shared by both modes, so the parity check
    # compares the SAME inputs bit for bit
    stream_frames = [
        [rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
         for _ in range(frames_per_stream)]
        for _ in range(streams)
    ]
    parity_set = [rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
                  for _ in range(parity_frames)]

    def leaves_identical(a, b) -> bool:
        if a is None or b is None:
            return a is b
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        if len(la) != len(lb):
            return False
        for x, y in zip(la, lb):
            x, y = np.asarray(x), np.asarray(y)
            if x.dtype != y.dtype or x.shape != y.shape:
                return False
            eq_nan = np.issubdtype(x.dtype, np.floating)
            if not np.array_equal(x, y, equal_nan=eq_nan):
                return False
        return True

    def run_mode(inflight: int, router=None) -> dict:
        # sharded routing needs max_batch to cover the mesh width; the
        # round-robin and single-chip runs keep the smoke's b<=2 buckets
        mb = (max(2, router.chips)
              if router is not None and router.mode == "sharded" else 2)
        d = BatchDispatcher(
            analyze, window_ms=2.0, max_batch=mb, max_backlog=1024,
            submit_timeout_s=300.0, max_inflight=inflight, router=router,
        )
        errored = 0
        try:
            # warm-up submit: pays its bucket's compile on the first routed
            # chip and absorbs any injected completer fault (CI's
            # graceful-degradation proof)
            try:
                d.submit(parity_set[0], depth, intr, 0.001)
            except Exception:
                errored += 1
            # warm every reachable bucket on EVERY routed placement off
            # the timed path
            for b in sorted({d.bucket_for(n) for n in range(1, mb + 1)}):
                d.warm(
                    np.stack([parity_set[0]] * b),
                    np.stack([depth] * b),
                    np.stack([intr] * b),
                    np.full((b,), 0.001, np.float32),
                )
            # parity phase: sequential b=1 submits, results kept for the
            # cross-mode bitwise comparison
            parity = []
            for f in parity_set:
                try:
                    parity.append(d.submit(f, depth, intr, 0.001))
                except Exception:
                    errored += 1
                    parity.append(None)
            # throughput phase: concurrent streams
            ok = [0] * streams
            errs = [0] * streams

            def stream(s: int) -> None:
                for f in stream_frames[s]:
                    try:
                        d.submit(f, depth, intr, 0.001)
                        ok[s] += 1
                    except Exception:
                        errs[s] += 1

            threads = [threading.Thread(target=stream, args=(s,))
                       for s in range(streams)]
            overlap0 = d.overlap_s_total
            frames0 = list(d.chip_frames)
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            errored += sum(errs)
            return {
                "fps": sum(ok) / wall if wall > 0 else 0.0,
                "overlap_s": d.overlap_s_total - overlap0,
                "high_water": d.inflight_high_water,
                "errored": errored,
                "parity": parity,
                "wall": wall,
                # throughput-phase frames per chip (parity/warm excluded)
                "chip_frames": [a - b for a, b in
                                zip(d.chip_frames, frames0)],
                "chip_dispatches": list(d.chip_dispatches),
            }
        finally:
            d.stop()

    router = make_router() if chips > 1 else None
    pipelined = run_mode(max_inflight, router)
    one_chip = run_mode(max_inflight) if chips > 1 else None
    serial = run_mode(1)
    identical = all(
        leaves_identical(a, b)
        for a, b in zip(pipelined["parity"], serial["parity"])
    )
    # precision parity vs an f32 reference analyzer over the same parity
    # frames, gated by the ServerConfig warm-up thresholds (at f32 the
    # reference is the served model itself, so the report is the trivial
    # 1.0-IoU / 0-delta anchor)
    ref_batch_analyze = pipeline.make_batch_analyzer(model,
                                                     img_size=img_size)
    scfg = ServerConfig()
    ref_outs, got_outs = [], []
    for f, got in zip(parity_set, pipelined["parity"]):
        if got is None:
            continue
        ref = jax.tree.map(
            lambda a: a[0],
            ref_batch_analyze(
                variables, f[None], depth[None], intr[None],
                np.full((1,), 0.001, np.float32),
            ),
        )
        ref_outs.append(ref)
        got_outs.append(got)
    precision_parity = quant.parity_report(ref_outs, got_outs)
    gates_pass = quant.parity_gates_pass(
        precision_parity, scfg.quant_parity_min_iou,
        scfg.quant_parity_max_curv_err,
    )
    chip_note = ""
    if chips > 1:
        base_fps = one_chip["fps"] or 1e-9
        chip_note = (
            f"chips={chips}({dispatch_mode}) "
            f"1chip={one_chip['fps']:.1f}fps "
            f"scaling={pipelined['fps'] / base_fps:.2f}x "
            f"balance={pipelined['chip_frames']} "
        )
    print(
        f"# backend={jax.default_backend()} "
        f"pipelined={pipelined['fps']:.1f}fps "
        f"(overlap={pipelined['overlap_s']:.3f}s "
        f"high_water={pipelined['high_water']}) "
        f"{chip_note}"
        f"serial={serial['fps']:.1f}fps "
        f"(overlap={serial['overlap_s']:.3f}s) identical={identical} "
        f"precision={precision} "
        f"(iou={precision_parity['mask_iou_mean']:.4f} "
        f"curv_err={precision_parity['curvature_err_max']:.4g} "
        f"gates={'pass' if gates_pass else 'FAIL'})",
        file=sys.stderr,
    )
    payload = {
        "metric": "serving_pipeline_fps",
        "backend": jax.default_backend(),
        "precision": precision,
        "parity": {
            **precision_parity,
            "gates_pass": gates_pass,
            "min_iou_gate": scfg.quant_parity_min_iou,
            "max_curv_err_gate": scfg.quant_parity_max_curv_err,
        },
        "value": round(pipelined["fps"], 2),
        "unit": "frames/sec",
        "serial_fps": round(serial["fps"], 2),
        "speedup_vs_serial": round(
            pipelined["fps"] / serial["fps"], 3) if serial["fps"] else 0.0,
        "overlap_seconds": round(pipelined["overlap_s"], 4),
        "serial_overlap_seconds": round(serial["overlap_s"], 4),
        "inflight_high_water": pipelined["high_water"],
        "max_inflight": max_inflight,
        "identical": identical,
        "errored_frames": pipelined["errored"] + serial["errored"],
        "streams": streams,
        "frames_per_stream": frames_per_stream,
        "smoke": smoke,
    }
    if not np.isfinite(payload["value"]) or payload["value"] <= 0.0:
        raise RuntimeError(f"measured {payload['value']!r} frames/sec")
    if chips > 1:
        wall = pipelined["wall"] or 1e-9
        base_fps = one_chip["fps"]
        payload.update({
            "chips": chips,
            "dispatch_mode": dispatch_mode,
            "fps_1chip_pipelined": round(base_fps, 2),
            "scaling_vs_1chip": (round(pipelined["fps"] / base_fps, 3)
                                 if base_fps else 0.0),
            "scaling_efficiency": (round(
                pipelined["fps"] / base_fps / chips, 3) if base_fps
                else 0.0),
            "per_chip_fps": {
                str(i): round(n / wall, 2)
                for i, n in enumerate(pipelined["chip_frames"])
            },
            "chip_frames": pipelined["chip_frames"],
            "chip_dispatches": pipelined["chip_dispatches"],
        })
    print(json.dumps(payload), flush=True)


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--serving-pipeline", action="store_true",
        help="run the serving_pipeline_fps bench (pipelined vs serial "
             "dispatch through the live BatchDispatcher) instead of the "
             "headline fused-graph bench",
    )
    parser.add_argument(
        "--smoke", action="store_true",
        help="CPU-runnable smoke variant of --serving-pipeline",
    )
    parser.add_argument(
        "--chips", type=int, default=1,
        help="route the pipelined serving bench across N mesh chips "
             "(serving/batching.DeviceRouter); with --smoke the devices "
             "are faked CPU devices "
             "(XLA_FLAGS=--xla_force_host_platform_device_count)",
    )
    parser.add_argument(
        "--dispatch-mode", default="round_robin",
        choices=["round_robin", "sharded"],
        help="how --chips routes dispatches: whole buckets round-robined "
             "onto the least-loaded chip, or each bucket sharded over the "
             "mesh 'data' axis",
    )
    parser.add_argument(
        "--precision", default="f32", choices=["f32", "bf16", "int8"],
        help="serving precision tier for --serving-pipeline "
             "(ops/pallas/quant.py): f32 = untransformed (bitwise "
             "identical to today), bf16 = bfloat16 activations, int8 = "
             "bf16 activations + per-channel int8 weight quantization; "
             "non-f32 tiers report parity vs the f32 reference and "
             "whether the ServerConfig gates pass",
    )
    cli = parser.parse_args()
    if cli.serving_pipeline and cli.smoke:
        # the smoke path runs on (faked) CPU devices: pin the platform and
        # force enough virtual devices BEFORE backend init (honors an
        # already-exported XLA_FLAGS count when it is enough)
        from robotic_discovery_platform_tpu.utils.platforms import (
            force_cpu_platform,
        )

        force_cpu_platform(
            min_devices=max(8, cli.chips) if cli.chips > 1 else 1)
    from robotic_discovery_platform_tpu.utils.platforms import (
        enable_compile_cache,
    )

    enable_compile_cache()
    if cli.serving_pipeline:
        serving_pipeline_main(smoke=cli.smoke, chips=cli.chips,
                              dispatch_mode=cli.dispatch_mode,
                              precision=cli.precision)
    else:
        main()
