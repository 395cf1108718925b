"""chip_smoke.py -- the quickest proof that the system still starts on the chip.

Run from the repo root with no arguments, on a machine with one TPU chip:

    python chip_smoke.py

One process follows the README Quickstart at the full width of the ``seg``
model (``ModelConfig()`` as it ships: base 64, bf16 compute, 256^2 model
input, 640x480 frames) on seeded random weights:

1. *device*   the accelerator JAX found, the versions, and one large matmul
              timed to enqueue and to ``block_until_ready``;
2. *train*    ``tools.make_dataset.synthesize`` -> ``train_model`` (one epoch,
              batch 4, checkpoint + registration on);
3. *serve*    ``build_server`` with the default config, ``serving.client``
              streaming seeded ``SyntheticSource`` frames;
4. *dispatch* a second server with micro-batching on: concurrent streams
              over the three ingest wires and the three mask formats; masks
              must agree with phase 3's, and nothing may recompile after
              warm-up;
5. *kernels*  which implementation every stage ran (checked against the
              Mosaic custom calls in the compiled programs), and each Pallas
              stage against its XLA twin on the smoke's own frames.

No phase is wrapped in an ``except``: any failure is a traceback and a
non-zero exit code, and no result line. Without a TPU the script exits
non-zero before running a phase. A run that passed writes two lines to
stdout: the summary (one JSON object: device, versions, per-phase ok /
compile seconds / warm ms per frame, the implementation report, the compile
cache) and, last, the verdict with exactly these keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Timings in the summary are smoke timings (one cold process, a handful of
frames), not benchmark numbers.

``--rehearse`` runs the same phases on the CPU at base 8 / 64^2 with the
Pallas kernels in interpret mode (what tier-1 calls; marked
``"rehearsal": true``). ``--chips N`` (N > 1) is the multi-chip run for an
N-chip host: after phases 1-3 it runs the trainer under
``MeshConfig(data=N)`` and, in place of phases 4-5, the dispatcher over an
N-chip serving mesh in ``round_robin`` and then ``sharded`` mode (pixel
wires; every chip must serve frames, masks must agree with phase 3's).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import tempfile
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

#: the ingest wire x response mask format of each concurrent stream in the
#: dispatcher phase: all three wires and all three formats, every run
STREAMS = (("encoded", 0), ("raw", 1), ("coef", 2), ("encoded", 2))
#: the multi-chip legs ride the pixel lanes only (the coefficient lane's
#: extra graphs per bucket per chip are the one-chip run's to prove)
MESH_STREAMS = (("encoded", 0), ("raw", 1), ("raw", 2), ("encoded", 2))

#: Mosaic kernel names (the ``name=`` of each pallas_call, which is also the
#: name of its jitted wrapper) behind each stage that can choose its
#: implementation
STAGE_KERNELS = {
    "unet_forward": "conv3x3_bn_relu",
    "deproject": "deproject_edge_stats",
    "spline_design": "bspline_design",
    "curvature": "bspline_curvature",
    "jpeg_idct": "dequant_idct",
    "mask_pack": "bitpack_mask",
    "train_conv": "conv3x3_grad_weights",
}

_GEOMETRY = ("deproject", "spline_design", "curvature")

#: What the shipped dispatch rules run on ONE TPU chip, per compiled program.
#: A literal table on purpose: a renamed backend, PALLAS_MAX_ELEMS or a mesh
#: override that quietly hands a stage to XLA fails the smoke instead of
#: rewriting the expectation. Batches > 1 run the forward XLA-uniform
#: (ops/pallas/unet_infer.PALLAS_MAX_ELEMS) and pin the vmapped geometry to
#: XLA (ops/pipeline._analyze_batch); the JPEG IDCT is routed to XLA in
#: ops/pallas/geometry.MOSAIC_REFUSES.
EXPECTED_ON_TPU = {
    "frame_b1": {"unet_forward": "pallas",
                 **dict.fromkeys(_GEOMETRY, "pallas")},
    "batch_b1": {"unet_forward": "pallas", "mask_pack": "pallas",
                 **dict.fromkeys(_GEOMETRY, "pallas")},
    "batch_b4": {"unet_forward": "xla", "mask_pack": "pallas",
                 **dict.fromkeys(_GEOMETRY, "xla")},
    "coef_b1": {"unet_forward": "pallas", "mask_pack": "pallas",
                "jpeg_idct": "xla", **dict.fromkeys(_GEOMETRY, "pallas")},
    "train_step": {"unet_forward": "pallas", "train_conv": "pallas"},
}


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one run. ``FULL`` is the contract; ``REHEARSAL`` is the same
    phases cut to what a CPU finishes in seconds."""

    base_features: int
    compute_dtype: str
    conv_impl: str
    kernel_impl: str
    img_size: int
    width: int
    height: int
    n_images: int
    batch_size: int
    frames: int
    max_batch: int
    matmul_n: int

    def model_config(self, **overrides):
        from robotic_discovery_platform_tpu.utils.config import ModelConfig

        return ModelConfig(**{"base_features": self.base_features,
                              "compute_dtype": self.compute_dtype,
                              "conv_impl": self.conv_impl, **overrides})


FULL = Plan(base_features=64, compute_dtype="bfloat16", conv_impl="auto",
            kernel_impl="auto", img_size=256, width=640, height=480,
            n_images=20, batch_size=4, frames=8, max_batch=4, matmul_n=8192)
REHEARSAL = Plan(base_features=8, compute_dtype="float32", conv_impl="auto",
                 kernel_impl="interpret", img_size=64, width=96, height=64,
                 n_images=10, batch_size=4, frames=4, max_batch=4,
                 matmul_n=256)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class CompileMeter:
    """Per-phase compile seconds and persistent-cache traffic, from JAX's
    own monitoring events (a backend compile that hits the persistent cache
    is counted at its retrieval time)."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.counts: Counter = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event: str, **_) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            self.counts[event.rsplit("/", 1)[1]] += 1

    def take(self) -> dict:
        """Totals since the last call."""
        out = {"compile_s": round(self.seconds, 2),
               "cache_hits": self.counts["cache_hits"],
               "cache_misses": self.counts["cache_misses"]}
        self.seconds = 0.0
        self.counts.clear()
        return out


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# -- phase 1: device ---------------------------------------------------------


def phase_device(plan: Plan) -> dict:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    versions = {"jax": jax.__version__}
    for pkg in ("jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    n = plan.matmul_n
    a = jnp.ones((n, n), jnp.bfloat16)
    matmul = jax.jit(lambda x, y: x @ y)
    matmul(a, a).block_until_ready()  # compile
    enq, blk = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        out = matmul(a, a)
        t1 = time.perf_counter()
        out.block_until_ready()
        t2 = time.perf_counter()
        enq.append(t1 - t0)
        blk.append(t2 - t0)
    enqueue_ms, blocked_ms = 1e3 * min(enq), 1e3 * min(blk)
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "versions": versions,
        "matmul_n": n,
        "matmul_enqueue_ms": round(enqueue_ms, 3),
        "matmul_blocked_ms": round(blocked_ms, 3),
        "matmul_tflops": round(2 * n ** 3 / (blocked_ms * 1e-3) / 1e12, 1),
        # a call that returns long before the result is ready, and a
        # block_until_ready that then waits for it
        "block_until_ready_blocks": blocked_ms > 2 * enqueue_ms,
    }


# -- phase 2: train ----------------------------------------------------------


def phase_train(plan: Plan, work: Path, chips: int) -> dict:
    import numpy as np

    from robotic_discovery_platform_tpu import parallel, tracking
    from robotic_discovery_platform_tpu.tools import make_dataset
    from robotic_discovery_platform_tpu.training.trainer import train_model
    from robotic_discovery_platform_tpu.utils.config import (
        MeshConfig,
        TrainConfig,
    )

    dataset = make_dataset.synthesize(
        work / "dataset", n=plan.n_images, width=plan.width,
        height=plan.height, seed=0,
    )
    model_cfg = plan.model_config()
    cfg = TrainConfig(
        batch_size=plan.batch_size, epochs=1, img_size=plan.img_size, seed=0,
        tracking_uri=f"file:{work}/mlruns", dataset_dir=str(dataset),
        checkpoint_dir=str(work / "checkpoints"),
    )
    def train_losses(res, label: str) -> list:
        """The run's logged train_loss, checked finite. An epoch's loss is
        the mean over its steps: finite only if every step's was."""
        losses = [m["value"] for m in
                  tracking.get_metric_history(res.run_id, "train_loss")]
        check(bool(losses) and np.all(np.isfinite(losses)),
              f"{label} train_loss not finite: {losses}")
        return [round(v, 4) for v in losses]

    res = train_model(cfg, model_cfg)
    out = {"train_loss": train_losses(res, "one-chip"),
           "val_loss": round(float(res.final_metrics["loss"]), 4),
           "registry_version": res.registry_version}
    check(all(np.isfinite(v) for v in res.final_metrics.values()),
          f"validation metrics not finite: {res.final_metrics}")
    check(res.registry_version is not None, "no registry version registered")
    check(any((work / "checkpoints").iterdir()), "no checkpoint written")
    if chips > 1:
        # the data-parallel leg: same job under MeshConfig(data=chips),
        # not registered (phase 3 serves the one-chip model)
        import jax

        mesh = parallel.make_mesh(MeshConfig(data=chips),
                                  devices=jax.devices()[:chips])
        dp = train_model(
            dataclasses.replace(
                cfg, checkpoint_dir=str(work / "checkpoints_dp")),
            model_cfg, mesh=mesh, register=False,
        )
        out[f"dp{chips}_train_loss"] = train_losses(dp, f"dp{chips}")
    return out


# -- phases 3 and 4: serve ---------------------------------------------------


def smoke_frames(plan: Plan) -> list:
    """The smoke's own seeded frames: (color_bgr, depth) pairs."""
    from robotic_discovery_platform_tpu.io.frames import (
        SyntheticSource,
        iter_frames,
    )

    source = SyntheticSource(width=plan.width, height=plan.height, seed=7,
                             n_frames=plan.frames)
    source.start()
    return list(iter_frames(source, plan.frames))


def intrinsics(plan: Plan):
    import numpy as np

    from robotic_discovery_platform_tpu.io.frames import SyntheticSource

    return np.asarray(SyntheticSource(
        width=plan.width, height=plan.height).intrinsics(), np.float32)


def server_config(plan: Plan, work: Path, tag: str, **overrides):
    from robotic_discovery_platform_tpu.utils.config import ServerConfig

    return ServerConfig(
        address="localhost:0",
        tracking_uri=f"file:{work}/mlruns",
        model_img_size=plan.img_size,
        calibration_path=str(work / "no-calibration.npz"),
        metrics_csv=str(work / f"metrics-{tag}.csv"),
        reload_poll_s=0.0,
        **overrides,
    )


def response_mask(resp, shape):
    """The [H, W] 0/1 mask of one response, whichever format it rode."""
    import cv2
    import numpy as np

    from robotic_discovery_platform_tpu.serving import egress

    mask = egress.decode_mask_wire(resp.mask)
    if mask is None:
        png = cv2.imdecode(np.frombuffer(resp.mask, np.uint8),
                           cv2.IMREAD_GRAYSCALE)
        check(png is not None, "response mask is neither packed nor PNG")
        mask = (png > 0).astype(np.uint8)
    check(mask.shape == shape, f"mask shape {mask.shape} != frame {shape}")
    return mask


def check_response(status, mean_k, max_k, coverage, where: str) -> None:
    import math

    check(not status.startswith("ERROR"), f"{where}: status {status!r}")
    check(all(math.isfinite(v) for v in (mean_k, max_k, coverage)),
          f"{where}: non-finite result ({mean_k}, {max_k}, {coverage})")


def encode(frames: list, fmt: str, mask_format: int) -> list:
    """The wire requests of one stream, encoded BEFORE anything is timed
    (the coefficient wire's client-side entropy decode is pure Python and
    would otherwise hold the GIL against the in-process server)."""
    from robotic_discovery_platform_tpu.serving import client as client_lib

    return [client_lib.encode_request(color, depth, fmt=fmt,
                                      mask_format=mask_format)
            for color, depth in frames]


def stream(address: str, requests: list, shape: tuple, where: str) -> list:
    """One gRPC stream of pre-encoded requests; returns the checked
    (mask, mean_curvature, proc_time_ms) per frame."""
    import grpc

    from robotic_discovery_platform_tpu.serving.proto import vision_grpc

    with grpc.insecure_channel(address) as channel:
        stub = vision_grpc.VisionAnalysisServiceStub(channel)
        responses = list(stub.AnalyzeActuatorPerformance(iter(requests)))
    check(len(responses) == len(requests),
          f"{where}: {len(responses)} responses for {len(requests)} frames")
    out = []
    for i, resp in enumerate(responses):
        check_response(resp.status, resp.mean_curvature, resp.max_curvature,
                       resp.mask_coverage, f"{where} frame {i}")
        out.append((response_mask(resp, shape), resp.mean_curvature,
                    resp.proc_time_ms))
    return out


def total_traces() -> int:
    from robotic_discovery_platform_tpu.analysis import recompile

    return sum(e["traces"] for entries in recompile.snapshot().values()
               for e in entries)


def phase_serve_default(plan: Plan, work: Path, geom_cfg,
                        frames: list) -> tuple[dict, dict, dict]:
    """Default config (``batch_window_ms = 0``): the b == 1 frame path.
    Returns (summary, reference masks per wire, lowered programs)."""
    import numpy as np

    from robotic_discovery_platform_tpu.io.frames import SyntheticSource
    from robotic_discovery_platform_tpu.serving import client as client_lib
    from robotic_discovery_platform_tpu.serving import server as server_lib
    from robotic_discovery_platform_tpu.utils.config import ClientConfig

    cfg = server_config(plan, work, "default")
    t_boot = time.perf_counter()
    server, servicer = server_lib.build_server(
        cfg, geom_cfg=geom_cfg, warmup_shape=(plan.width, plan.height))
    boot_s = time.perf_counter() - t_boot
    address = f"localhost:{servicer.bound_port}"
    server.start()
    try:
        traces0 = total_traces()
        # the Quickstart client, headless, over the encoded wire
        t0 = time.perf_counter()
        results = client_lib.run_client(
            ClientConfig(server_address=address,
                         calibration_path=str(work / "no-calibration.npz")),
            source=SyntheticSource(width=plan.width, height=plan.height,
                                   seed=7, n_frames=plan.frames),
            max_frames=plan.frames,
        )
        wall = time.perf_counter() - t0
        check(len(results) == plan.frames,
              f"client got {len(results)} of {plan.frames} frames")
        for i, r in enumerate(results):
            check_response(r.status, r.mean_curvature, r.max_curvature,
                           r.mask_coverage, f"default frame {i}")
        # reference masks per wire for phase 4: the encoded and coef wires
        # decode the same JPEG to the same pixels; the raw wire carries the
        # unencoded frame
        shape = (plan.height, plan.width)
        reference = {
            fmt: [m for m, _, _ in stream(
                address, encode(frames, fmt, 1), shape, f"default {fmt}")]
            for fmt in ("encoded", "raw")
        }
        reference["coef"] = reference["encoded"]
        check(total_traces() == traces0,
              f"default server recompiled after warm-up: "
              f"{total_traces() - traces0} new trace(s)")
        color, depth = frames[0]
        programs = {"frame_b1": servicer.analyze.lower(
            servicer.variables, color[..., ::-1], depth, intrinsics(plan),
            np.float32(servicer.depth_scale))}
        summary = {
            "boot_and_warmup_s": round(boot_s, 1),
            "frames": len(results),
            "coverage_pct": round(float(np.mean(
                [r.mask_coverage for r in results])), 2),
            "warm_ms_per_frame": round(float(np.median(
                [r.proc_time_ms for r in results[1:]])), 2),
            "stream_ms_per_frame": round(1e3 * wall / len(results), 2),
        }
    finally:
        server.stop(grace=None)
        servicer.close()
    return summary, reference, programs


def dispatch_programs(plan: Plan, servicer, frame) -> dict:
    """Lower the very programs that served, on one of the smoke's frames."""
    import cv2
    import numpy as np

    from robotic_discovery_platform_tpu.serving import entropy

    color, depth = frame
    rgb = color[..., ::-1]
    k = intrinsics(plan)
    scale = np.float32(servicer.depth_scale)

    def rep(a, b):
        return np.stack([np.asarray(a)] * b)

    programs = {}
    for b in (1, plan.max_batch):
        programs[f"batch_b{b}"] = servicer.batch_analyze.lower(
            servicer.variables, rep(rgb, b), rep(depth, b), rep(k, b),
            np.full((b,), scale, np.float32))
    ok, jpg = cv2.imencode(".jpg", color)
    check(ok, "jpeg encode failed")
    cf = entropy.parse_jpeg(jpg.tobytes())
    coef = servicer.coef_analyzer(plan.height, plan.width, cf.subsampling)
    programs["coef_b1"] = coef.func.lower(
        *coef.args, rep(cf.y, 1), rep(cf.cb, 1), rep(cf.cr, 1),
        rep(cf.qy, 1), rep(cf.qc, 1), rep(depth, 1), rep(k, 1),
        np.full((1,), scale, np.float32))
    return programs


def phase_serve_dispatch(plan: Plan, work: Path, geom_cfg, frames: list,
                         reference: dict, tag: str,
                         **mesh_cfg) -> tuple[dict, dict]:
    """Micro-batching on: concurrent streams over every wire and format
    (one chip), or over the pixel wires across a serving mesh
    (``mesh_cfg``). Returns (summary, lowered programs)."""
    import numpy as np

    from robotic_discovery_platform_tpu.serving import server as server_lib

    cfg = server_config(plan, work, tag, batch_window_ms=5.0,
                        max_batch=plan.max_batch, egress_pack=True,
                        **mesh_cfg)
    streams_plan = MESH_STREAMS if mesh_cfg else STREAMS
    requests = [encode(frames, fmt, mf) for fmt, mf in streams_plan]
    shape = (plan.height, plan.width)
    t_boot = time.perf_counter()
    server, servicer = server_lib.build_server(
        cfg, geom_cfg=geom_cfg, warmup_shape=(plan.width, plan.height))
    if not mesh_cfg:
        # clients ship format=2 against a pixel-decode server: warm that
        # lane too
        servicer.warmup_coef(plan.width, plan.height)
    boot_s = time.perf_counter() - t_boot
    address = f"localhost:{servicer.bound_port}"
    server.start()
    try:
        traces0 = total_traces()
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(streams_plan)) as pool:
            futures = [pool.submit(stream, address, reqs, shape,
                                   f"{tag} {fmt}/{mf}")
                       for reqs, (fmt, mf) in zip(requests, streams_plan)]
            streams = [f.result() for f in futures]
        wall = time.perf_counter() - t0
        new_traces = total_traces() - traces0
        check(new_traces == 0,
              f"{tag}: {new_traces} recompile(s) after warm-up")
        mismatch = 0.0
        for (fmt, mf), got in zip(streams_plan, streams):
            for i, (mask, _, _) in enumerate(got):
                frac = float(np.mean(mask != reference[fmt][i]))
                mismatch = max(mismatch, frac)
                # bf16 twins of one forward may differ within an ulp of the
                # threshold; anything more is a different answer
                check(frac <= 5e-3,
                      f"{tag}: {fmt}/{mf} frame {i} mask differs from the "
                      f"default-config server's in {frac:.2%} of pixels")
        dispatcher = servicer.dispatcher
        chip_frames = list(dispatcher.chip_frames)
        if mesh_cfg:
            check(all(n > 0 for n in chip_frames),
                  f"{tag}: a chip served no frames: {chip_frames}")
        n = sum(len(s) for s in streams)
        summary = {
            "boot_and_warmup_s": round(boot_s, 1),
            "frames": n,
            "masks_max_mismatch_frac": mismatch,
            "recompiles_after_warmup": new_traces,
            "chip_frames": chip_frames,
            "warm_ms_per_frame": round(float(np.median(
                [t for s in streams for _, _, t in s])), 2),
            "stream_ms_per_frame": round(1e3 * wall / n, 2),
        }
        programs = ({} if mesh_cfg else
                    dispatch_programs(plan, servicer, frames[0]))
    finally:
        server.stop(grace=None)
        servicer.close()
    return summary, programs


# -- phase 5: which code ran -------------------------------------------------


def mosaic_kernels(compiled_text: str) -> Counter:
    """Launch counts of the Mosaic (Pallas) kernels in one compiled program,
    by kernel name. Every kernel is a ``tpu_custom_call`` instruction; its
    name is the pallas_call's ``name=``, read from the ``op_name`` metadata
    (``.../<name>/pallas_call``) or, failing that, from the instruction's
    own name (XLA derives it from the same scope)."""
    kernels: Counter = Counter()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = (re.search(r'op_name="[^"]*?(\w+)/pallas_call', line)
             or re.match(r'\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)? = ',
                         line))
        if m is None:
            log(f"unnamed mosaic kernel: "
                f"{line.split('backend_config=')[0].strip()[:500]}")
        kernels[m.group(1) if m else "unnamed"] += 1
    return kernels


def train_step_program(plan: Plan):
    """The single-device train step ``train_model`` builds, lowered at the
    smoke's batch shape."""
    import jax
    import jax.numpy as jnp
    import optax

    from robotic_discovery_platform_tpu.models import losses as losses_lib
    from robotic_discovery_platform_tpu.models.unet import build_unet
    from robotic_discovery_platform_tpu.training import trainer
    from robotic_discovery_platform_tpu.utils.config import TrainConfig

    cfg = TrainConfig()
    model = build_unet(plan.model_config())
    tx = optax.adam(cfg.learning_rate)
    state = trainer.create_state(model, tx, jax.random.key(0), plan.img_size)
    step = trainer.make_train_step(
        model, tx, losses_lib.make_loss_fn(cfg.loss, cfg.dice_weight),
        donate=False)
    s = plan.img_size
    return step.lower(state,
                      jnp.zeros((plan.batch_size, s, s, 3), jnp.float32),
                      jnp.zeros((plan.batch_size, s, s, 1), jnp.float32))


def phase_kernels(programs: dict, on_tpu: bool) -> dict:
    """The implementation each stage of each program ran, read off the
    compiled text: Pallas means a Mosaic custom call of that kernel is
    there. On a TPU every stage must match :data:`EXPECTED_ON_TPU`; a
    rehearsal only lowers (interpret mode leaves no custom call to find)."""
    report = {}
    for name, lowered in programs.items():
        text = lowered.compile().as_text() if on_tpu else lowered.as_text()
        kernels = mosaic_kernels(text)
        report[name] = {}
        for stage, want in EXPECTED_ON_TPU[name].items():
            ran = "pallas" if kernels[STAGE_KERNELS[stage]] else "xla"
            report[name][stage] = ran
            check(ran == want or not on_tpu,
                  f"{name}: {stage} ran {ran}, the shipped dispatch rules "
                  f"say {want} (Mosaic kernels in the compiled program: "
                  f"{dict(kernels)})")
        log(f"kernels {name}: {report[name]} mosaic={dict(kernels)}")
    return {"implementations": report}


def phase_twins(plan: Plan, work: Path, frames: list, ref_mask) -> dict:
    """Each Pallas stage against its XLA twin on the smoke's own frames
    (``kernel_impl`` pins the path; co-traced in one jit like the tier-1
    tests). Tolerances are the tier-1 tests'; ``bitwise`` records whether
    the chip gave exact equality where a docstring promises it."""
    import cv2
    import jax
    import jax.numpy as jnp
    import numpy as np

    from robotic_discovery_platform_tpu import tracking
    from robotic_discovery_platform_tpu.models.unet import build_unet
    from robotic_discovery_platform_tpu.ops import (
        bspline,
        geometry,
        pipeline,
    )
    from robotic_discovery_platform_tpu.ops import pallas as pallas_ops
    from robotic_discovery_platform_tpu.ops.pallas import conv as pconv
    from robotic_discovery_platform_tpu.ops.pallas import geometry as pgeom
    from robotic_discovery_platform_tpu.ops.pallas import pack as pack_lib
    from robotic_discovery_platform_tpu.serving import entropy
    from robotic_discovery_platform_tpu.utils.config import GeometryConfig

    pal = "interpret" if plan.kernel_impl == "interpret" else "pallas"
    interp = pal == "interpret"
    color, depth = frames[0]
    cfg = GeometryConfig()
    f = 0.94 * plan.width
    par = jnp.asarray([f, f, plan.width / 2, plan.height / 2, 0.001],
                      jnp.float32)
    out = {}

    failed = []

    def close(name, got, want, rtol, atol):
        """Record one comparison; every twin is measured before any failure
        is raised (at the end of the phase), so one run shows them all."""
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        diff = np.abs(got - want)
        out[name] = {"bitwise": bool(np.array_equal(got, want)),
                     "max_abs_diff": float(np.max(diff)),
                     "ref_abs_max": float(np.max(np.abs(want)))}
        if not np.all(diff <= atol + rtol * np.abs(want)):
            failed.append(f"{name} (rtol {rtol}, atol {atol}): {out[name]}")

    # deproject + edge stats (tests/test_pallas_geometry.py)
    @jax.jit
    def deproject_both(m, d, p):
        a = pgeom.deproject_edge_stats(m, d, *p[:5], interpret=interp)
        x, y, z, v = geometry.deproject(m, d, *p[:5])
        return a[:4], (x, y, z, v), a[4]

    got, want, stats = deproject_both(ref_mask, depth, tuple(par))
    close("deproject", jnp.stack(got[:3]), jnp.stack(want[:3]), 1e-6, 1e-9)
    check(bool(jnp.array_equal(got[3], want[3])), "deproject: valid map")
    check(int(stats[4]) == int(jnp.sum(want[3])), "deproject: n_valid")

    # spline design + curvature on this frame's sorted edge points
    x, y, z, v = want
    e_pts, e_w, *_ = geometry._edge_points(x, y, z, v, cfg)
    pts, w = geometry._sort_by_x(e_pts, e_w)
    knots = bspline.clamped_uniform_knots(cfg.num_ctrl, cfg.spline_degree)

    @jax.jit
    def spline_both(pts, w):
        fits = [bspline.fit_bspline(pts, w, knots, cfg.spline_degree,
                                    cfg.spline_smoothing, impl=i)[0]
                for i in (pal, "xla")]
        u = jnp.linspace(0.0, 1.0, cfg.num_samples)
        curv = [bspline.curvature_profile(fits[1], knots, u,
                                          cfg.spline_degree, impl=i)
                for i in (pal, "xla")]
        return fits, curv

    fits, curv = spline_both(pts, w)
    close("spline_design", fits[0], fits[1], 1e-4, 1e-5)
    # curvature divides second derivatives by |r'|^3: compiled, the two
    # paths' last-ulp differences show up at 1e-5 of the profile's peak
    # (interpret mode is exact); 1e-4 of the peak is far inside the
    # engine's own error against ground truth (GEOMETRY_PARITY.json)
    close("curvature", curv[0][0], curv[1][0], 0,
          1e-4 * float(jnp.max(jnp.abs(curv[1][0]))))
    close("curvature_points", curv[0][2], curv[1][2], 1e-5, 1e-6)

    # mask bitpack vs numpy (tests/test_egress.py: exact)
    packed = pack_lib.bitpack_mask(ref_mask[None], impl=pal)
    check(np.array_equal(np.asarray(packed)[0],
                         np.packbits(ref_mask, axis=-1)),
          "mask_pack differs from np.packbits")
    out["mask_pack"] = {"bitwise": True, "max_abs_diff": 0.0}

    # on-chip JPEG decode vs libjpeg (tests/test_pallas_decode.py: exact);
    # the IDCT runs whichever implementation "auto" resolves to
    ok, jpg = cv2.imencode(".jpg", color)
    cf = entropy.parse_jpeg(jpg.tobytes())
    rgb = pipeline.decode_coef_batch(
        cf.y[None], cf.cb[None], cf.cr[None], cf.qy[None], cf.qc[None],
        height=plan.height, width=plan.width, subsampling=cf.subsampling,
        impl=plan.kernel_impl)
    check(np.array_equal(
        np.asarray(rgb)[0],
        cv2.imdecode(jpg, cv2.IMREAD_COLOR)[..., ::-1]),
        "decode_coef_batch differs from cv2.imdecode")
    out["jpeg_decode"] = {
        "bitwise": True, "max_abs_diff": 0.0,
        "idct_impl": pgeom.resolve_impl(plan.kernel_impl, "jpeg_idct"),
    }

    # the served model: Pallas forward vs its XLA twin and vs the f32 Flax
    # forward under highest matmul precision (bf16 compute: one part in 2^8
    # per rounding, so a few 1e-3 of the logit scale)
    model, variables = tracking.load_model(
        "models:/Actuator-Segmenter/latest",
        store=tracking.store_for(f"file:{work}/mlruns"))
    x_in = pipeline.preprocess(jnp.asarray(color[..., ::-1])[None],
                               plan.img_size)
    fwd = {force: pallas_ops.make_pallas_unet(
        model, variables, interpret=interp, force=force)
        for force in ("pallas", "xla")}
    logits = {force: jax.jit(net)(x_in) for force, net in fwd.items()}
    f32_model = build_unet(plan.model_config(compute_dtype="float32",
                                             conv_impl="flax"))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda v, x: f32_model.apply(v, x, train=False))(
            variables, x_in)
    scale = float(jnp.max(jnp.abs(ref)))
    tol = 2e-3 + 2e-2 * scale
    close("forward_vs_xla_twin", logits["pallas"], logits["xla"], 0, tol)
    close("forward_vs_f32_flax", logits["pallas"], ref, 0, tol)
    out["forward_vs_f32_flax"]["logit_abs_max"] = scale

    # training conv: custom-VJP Pallas kernels vs the XLA forms
    # (tests/test_pallas.py tolerances, scaled to the compute dtype)
    rng = np.random.default_rng(3)
    dt = jnp.dtype(plan.compute_dtype)
    c = max(8, plan.base_features)
    xin = jnp.asarray(rng.standard_normal((2, 32, 32, c)), dt)
    wk = jnp.asarray(rng.standard_normal((3, 3, c, c)) * 0.05, jnp.float32)

    def loss(x, k, impl):
        return jnp.sum(pconv.conv3x3(x, k.astype(dt), impl, False)
                       .astype(jnp.float32) ** 2)

    grads = {impl: jax.jit(jax.grad(
        lambda x, k, impl=impl: loss(x, k, impl), argnums=(0, 1)))(xin, wk)
        for impl in (pal, "xla")}
    rel = 1e-2 if dt == jnp.bfloat16 else 1e-4
    for i, name in enumerate(("train_conv_dx", "train_conv_dw")):
        want = np.asarray(grads["xla"][i], np.float32)
        close(name, np.asarray(grads[pal][i], np.float32), want, 0,
              rel * float(np.max(np.abs(want))))
    log(f"twins: {out}")
    check(not failed, "Pallas stages disagree with their XLA twins: "
          + "; ".join(failed))
    return {"twins": out}


# -- driver ------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse", action="store_true",
                        help="run the same phases on the CPU at a tiny size "
                             "with the Pallas kernels in interpret mode")
    parser.add_argument("--chips", type=int, default=1,
                        help="N > 1 adds the multi-chip legs (needs an "
                             "N-chip host)")
    args = parser.parse_args(argv)

    from robotic_discovery_platform_tpu.utils import platforms

    if args.rehearse:
        platforms.force_cpu_platform(min_devices=max(8, args.chips))
    cache_dir = platforms.enable_compile_cache()
    if not args.rehearse:
        platforms.require_accelerator("chip_smoke.py")
    plan = REHEARSAL if args.rehearse else FULL

    import jax

    if args.rehearse:
        # a rehearsal checks control flow, not speed: skip most of XLA's
        # optimization passes so tier-1 pays seconds of compile, not tens
        jax.config.update("jax_disable_most_optimizations", True)
    check(len(jax.devices()) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices; JAX found "
          f"{len(jax.devices())}")

    from robotic_discovery_platform_tpu.utils.config import GeometryConfig

    def cache_entries() -> int | None:
        if cache_dir is None:
            return None
        path = Path(cache_dir)
        return len(list(path.iterdir())) if path.is_dir() else 0

    entries0 = cache_entries()
    meter = CompileMeter()
    t_start = time.perf_counter()
    phases: dict = {}

    def run(name: str, fn, *fn_args, **fn_kwargs):
        """One phase: its summary plus compile seconds, cache traffic and
        wall seconds. A phase that raises ends the run."""
        t0 = time.perf_counter()
        out = fn(*fn_args, **fn_kwargs)
        info = out[0] if isinstance(out, tuple) else out
        phases[name] = {"ok": True, **info, **meter.take(),
                        "wall_s": round(time.perf_counter() - t0, 1)}
        log(f"{name}: {phases[name]}")
        return out

    work = Path(tempfile.mkdtemp(prefix="chip-smoke-"))
    geom_cfg = GeometryConfig(kernel_impl=plan.kernel_impl)
    device = run("device", phase_device, plan)
    run("train", phase_train, plan, work, args.chips)
    frames = smoke_frames(plan)
    _, reference, programs = run(
        "serve_default", phase_serve_default, plan, work, geom_cfg, frames)
    if args.chips > 1:
        for mode in ("round_robin", "sharded"):
            tag = f"serve_mesh{args.chips}_{mode}"
            run(tag, phase_serve_dispatch, plan, work, geom_cfg, frames,
                reference, tag, serving_mesh=args.chips, dispatch_mode=mode)
    else:
        _, batch_programs = run(
            "serve_dispatch", phase_serve_dispatch, plan, work, geom_cfg,
            frames, reference, "serve_dispatch")
        programs.update(batch_programs)
        programs["train_step"] = train_step_program(plan)
        run("kernels", phase_kernels, programs, on_tpu=not args.rehearse)
        run("twins", phase_twins, plan, work, frames,
            reference["encoded"][0])

    entries1 = cache_entries()
    verdict = {
        "ok": True,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    }
    summary = {
        **verdict,
        "rehearsal": bool(args.rehearse),
        "chips": args.chips,
        "versions": device["versions"],
        "block_until_ready": {k: device[k] for k in (
            "matmul_n", "matmul_enqueue_ms", "matmul_blocked_ms",
            "matmul_tflops", "block_until_ready_blocks")},
        "timings_are": "smoke timings, not benchmark numbers",
        "phases": phases,
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries0,
            "entries_after": entries1,
            "compile_s_total": round(sum(
                p.get("compile_s", 0.0) for p in phases.values()), 2),
        },
        "wall_s": round(time.perf_counter() - t_start, 1),
    }
    # two stdout lines: the summary, then -- last, and with exactly these
    # keys, which is what the driver's check parses -- the verdict
    print(json.dumps(summary), flush=True)
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
