"""The vision analysis gRPC server, TPU-backed.

Capability-parity rebuild of the reference server (reference:
services/vision_analysis/server.py): same wire contract, same insecure-port
serving loop, same metrics CSV, same registry-driven model resolution --
with the compute path swapped for the fused XLA graph (ops/pipeline.py) and
the reference's documented-but-missing behaviors implemented:

- the model is resolved through the ``staging`` alias first, falling back to
  the latest version (README.md:147 documents staging; server.py:81 actually
  loads /latest -- SURVEY.md section 2.1 "retraining pipeline");
- ``status``, ``mask_coverage`` and ``proc_time_ms`` response fields are
  populated for real (declared in the proto but never set by the reference);
- per-frame errors produce an error-status response and keep the stream
  alive instead of tearing it down;
- metrics writes are buffered and thread-safe (serving/metrics.py).

Resilience (resilience/ package):

- registry resolution runs under a per-service circuit breaker: a sustained
  registry outage opens the breaker, the hot-reload poller fast-fails
  without touching the network, and the server keeps serving its current
  engine (state transitions are logged once each -- this replaces the old
  module-global rate-limited warning, whose shared timestamp let one
  server's warning silence another's for 60 s);
- each frame honors the client's gRPC deadline and cancellation BEFORE
  paying decode + device time, and dispatcher submits carry that deadline;
- an overloaded batch dispatcher sheds load with RESOURCE_EXHAUSTED; the
  dispatcher itself is pipelined (serving/batching.py: collector/stager ->
  bounded in-flight window -> completer), with
  ServerConfig.max_inflight_dispatches / RDP_INFLIGHT capping how many
  batches hold device memory at once, and its stop() drains both pipeline
  queues so close()/hot-reload teardown never strands a frame;
- the standard grpc.health.v1 health service (serving/health.py) reports
  readiness, flipping to SERVING only after model warm-up and back to
  NOT_SERVING when a drain begins;
- close() drains in-flight streams (bounded by ServerConfig.drain_grace_s)
  before tearing the engines down.

Observability (observability/ package):

- every frame feeds the rdp_* metric families (frames by status, per-stage
  latency histograms, in-flight streams; the batch dispatcher and the
  registry breaker export their own) and ``GET /metrics`` serves them in
  Prometheus text format when ServerConfig.metrics_port / RDP_METRICS_PORT
  is set -- started here, stopped in close();
- each stream adopts the client's ``traceparent`` (W3C trace context) from
  gRPC metadata, so client- and server-side log lines carry the same
  [trace=...] stamp -- and per-frame error statuses / shed
  RESOURCE_EXHAUSTED details carry ``[trace=...]`` too, so a client-side
  failure joins its ``GET /debug/spans`` timeline;
- per-stage and end-to-end latency additionally feed streaming-quantile
  summaries (``rdp_*_summary_seconds``: P^2 p50/p95/p99/p99.9), and when
  ServerConfig.slo_ms / RDP_SLO_MS sets an objective every frame feeds
  the SLO tracker (``rdp_slo_violations_total``, error-budget burn).

Drift observability (monitoring/profile.py):

- every OK/degraded frame's free signals -- mask coverage, mean/max
  curvature, depth-validity fraction, segmentation confidence margin
  (mean |sigmoid-0.5|, computed inside the fused graph) -- feed an online
  DriftMonitor: per-signal sliding windows scored (PSI / Jensen-Shannon)
  against a reference profile loaded from
  ``ServerConfig.drift_profile_path`` / ``RDP_DRIFT_PROFILE``, the served
  registry version's ``drift_profile.json`` artifact, or a self-baseline
  over the first frames; hot-reload re-stamps the reference for the new
  generation;
- sustained scores above ``drift_psi_threshold`` fire ONE structured
  retrain recommendation per excursion (sustain + cooldown hysteresis):
  counted (``rdp_drift_recommendations_total``), pinned in the flight
  recorder, and surfaced -- with live-vs-reference histograms and
  per-signal scores -- at ``GET /debug/drift``;
- all of it is host-side Python bookkeeping off the compute path: the
  f32 serial bitwise-parity guarantee and the jit cache are untouched.

Host-path ingest (serving/ingest.py):

- frame decode runs through the ingest layer: a decode worker pool
  (``ServerConfig.decode_workers`` / ``RDP_DECODE_WORKERS``; 0 = inline,
  the bitwise-parity mode) with per-stream read-ahead, pre-decode
  deadline shedding, and watchdog restart; raw-format wire payloads
  (``Image.format = 1``) bypass ``imdecode`` entirely as zero-copy
  views of the gRPC message buffer;
- per-stream camera geometry (intrinsics + depth scale) is converted --
  and, on the direct path, ``device_put`` -- once per distinct content
  through the geometry cache, not once per frame;
- warm-up's synthetic frame pair is built once per (width, height) per
  process and reused across generations/hot-reloads.

Overload control (serving/admission.py, serving/controller.py):

- the dispatcher's backlog is deadline-aware: at the cap the queued
  frame with the least remaining headroom is evicted (not the newcomer
  blindly rejected), and frames whose deadline is unmeetable are shed
  before staging (``rdp_shed_by_deadline_total``);
- with ServerConfig.controller_enabled / RDP_CONTROLLER, a reactive
  controller consumes the error-budget burn gauge and retunes
  max_inflight / batch window / bucket floor / dispatch mode online,
  with a brownout ladder under sustained burn > 1 whose top rung
  refuses new streams (UNAVAILABLE -> clients fail over);
- a mesh chip whose dispatches keep failing is quarantined by its
  per-chip circuit breaker: removed from the ring, its
  ``rdp.serving.chip.<i>`` health entry flips NOT_SERVING, in-flight
  frames fail over to healthy chips, and a half-open probe dispatch
  reinstates it on recovery.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import threading
import time
from concurrent import futures
from typing import Any, NamedTuple

import grpc
import jax
import numpy as np

from robotic_discovery_platform_tpu import tracking
from robotic_discovery_platform_tpu.io.frames import load_calibration
from robotic_discovery_platform_tpu.models import variants as variants_lib
from robotic_discovery_platform_tpu.monitoring import profile as profile_lib
from robotic_discovery_platform_tpu.observability import (
    events,
    exposition,
    instruments as obs,
    journal as journal_lib,
    recorder as recorder_lib,
    slo as slo_lib,
    trace,
)
from robotic_discovery_platform_tpu.ops import pipeline
from robotic_discovery_platform_tpu.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceeded,
    inject,
)
from robotic_discovery_platform_tpu.resilience import (
    sites as fault_sites,
)
from robotic_discovery_platform_tpu.serving import (
    controller as controller_lib,
    egress as egress_lib,
    entropy as entropy_lib,
    fleet as fleet_lib,
    health as health_lib,
    ingest as ingest_lib,
    rollout as rollout_lib,
    zoo as zoo_lib,
)
from robotic_discovery_platform_tpu.ops.pallas import quant
from robotic_discovery_platform_tpu.serving.batching import (
    OverloadedError,
    resolve_dispatch_mode,
    resolve_precision,
    resolve_serving_chips,
)
from robotic_discovery_platform_tpu.serving.metrics import MetricsWriter
from robotic_discovery_platform_tpu.serving.proto import vision_grpc, vision_pb2
from robotic_discovery_platform_tpu.utils.config import (
    GeometryConfig,
    ServerConfig,
)
from robotic_discovery_platform_tpu.utils import platforms
from robotic_discovery_platform_tpu.utils.logging import get_logger
from robotic_discovery_platform_tpu.utils.profiling import StageTimer

log = get_logger(__name__)


def resolve_serving_version(cfg: ServerConfig, store=None, *,
                            raise_on_error: bool = False) -> int | None:
    """The registry version serving should run: the ``staging`` alias when
    set, else the latest version; None when the registry is empty or
    unreachable (callers decide whether that is fatal). With
    ``raise_on_error`` the failure propagates instead -- that is how the
    service's circuit breaker observes outcomes (serving/server.py used to
    rate-limit this warning through a module-global timestamp shared by
    every server instance; the per-service breaker replaced it).

    Uses a store SCOPED to ``cfg.tracking_uri`` (tracking.store_for):
    the reload poller calls this from a background thread, and mutating
    the process-global tracking URI from there would silently re-point
    every other component's tracking mid-run. Callers that poll should
    pass a cached ``store`` -- rebuilding an MLflow-backed store every
    tick would churn clients and scratch dirs."""
    try:
        inject(fault_sites.SERVING_RESOLVE)
        store = store if store is not None else tracking.store_for(
            cfg.tracking_uri
        )
        version = store.get_alias(cfg.model_name, cfg.model_alias)
        if version is not None:
            return int(version)
        return int(store.latest_version(cfg.model_name)["version"])
    except Exception as exc:
        if raise_on_error:
            raise
        log.warning(
            "registry %s unreachable/empty (%s: %s); serving keeps its "
            "current model", cfg.tracking_uri, type(exc).__name__, exc,
        )
        return None


def resolve_serving_model(cfg: ServerConfig):
    """staging alias first, latest fallback.
    Returns (model, variables, version)."""
    tracking.set_tracking_uri(cfg.tracking_uri)
    version = resolve_serving_version(cfg)
    if version is not None:
        uri = f"models:/{cfg.model_name}/{version}"
        model, variables = tracking.load_model(uri)
        log.info("loaded %s (alias %r first)", uri, cfg.model_alias)
        return model, variables, version
    # fall through for the error message of the plain path
    model, variables = tracking.load_model(f"models:/{cfg.model_name}/latest")
    return model, variables, None


# focal-length default lives with the ingest/geometry machinery now; the
# alias keeps this module's historical import surface (tests use it)
_default_intrinsics = ingest_lib.default_intrinsics


@functools.lru_cache(maxsize=8)
def _warm_frames(width: int, height: int) -> tuple[np.ndarray, np.ndarray]:
    """The synthetic warm-up frame pair for one camera geometry, built --
    and encode/decode-roundtripped -- ONCE per (width, height) per
    process. warmup() used to re-encode its dummy JPEG/PNG on every call,
    so every hot-reload and every test server paid two image encodes and
    two decodes for identical bytes."""
    import cv2

    dummy = np.zeros((height, width, 3), np.uint8)
    ok, png = cv2.imencode(".png", np.zeros((height, width), np.uint16))
    if not ok:
        raise ValueError("warm-up depth encode failed")
    req = vision_pb2.AnalysisRequest(
        color_image=vision_pb2.Image(
            data=cv2.imencode(".jpg", dummy)[1].tobytes(),
            width=width, height=height,
        ),
        depth_image=vision_pb2.Image(data=png.tobytes(), width=width,
                                     height=height),
    )
    rgb, depth, _ = ingest_lib.decode_request(req)
    return rgb, depth


class _FrameResult(NamedTuple):
    """One analyzed frame's host-side outputs (response fields + the
    drift-monitor signals the frame already computed)."""

    mean_k: float
    max_k: float
    spline: np.ndarray
    #: the response ``mask`` payload in the REQUESTED wire format
    #: (mask_format 0 = legacy PNG bytes, 1 = packed bits, 2 = RLE);
    #: empty when egress was skipped for a dead stream
    mask_png: bytes
    coverage: float
    valid: bool
    confidence_margin: float
    depth_valid_fraction: float
    #: the aux head's defect/anomaly score (None for "segment" heads --
    #: i.e. always None on the default model's bitwise path)
    anomaly: float | None = None
    #: the packed-spline response payload (f32 LE triples) for packed
    #: wire formats; b"" on the legacy path, so the response field
    #: serializes to zero bytes and legacy responses stay bitwise
    spline_wire: bytes = b""


class Engine(NamedTuple):
    """One served model generation: everything a frame touches, swapped as
    a unit so a hot-reload can never mix old variables with a new forward
    (SURVEY.md section 3.4: the reference's promotion only takes effect at
    restart -- 'a running server keeps its old model')."""

    analyze: Any
    variables: Any
    dispatcher: Any
    version: int | None
    #: the jitted batched analyzer the dispatcher's closures call (None
    #: without micro-batching) -- kept so diagnostics can lower the very
    #: program that serves (chip_smoke.py's implementation report)
    batch_analyze: Any = None


class VisionAnalysisService(vision_grpc.VisionAnalysisServiceServicer):
    def __init__(
        self,
        model,
        variables,
        intrinsics: np.ndarray | None,
        depth_scale: float,
        cfg: ServerConfig = ServerConfig(),
        geom_cfg: GeometryConfig = GeometryConfig(),
        metrics: MetricsWriter | None = None,
        version: int | None = None,
    ):
        self.cfg = cfg
        self.geom_cfg = geom_cfg
        self.intrinsics = intrinsics
        self.depth_scale = depth_scale
        # Host-path ingest (serving/ingest.py): the decode worker pool
        # (0 workers = inline decode in the handler thread, the
        # bitwise-parity mode) and the per-stream geometry cache that
        # replaces the per-frame np.asarray(intrinsics) conversion and
        # -- on the direct path -- its per-frame device staging.
        self.ingest = ingest_lib.DecodePool(
            ingest_lib.resolve_decode_workers(cfg.decode_workers),
            prefetch=cfg.ingest_prefetch,
            onchip=ingest_lib.resolve_onchip_decode(cfg.onchip_decode),
        )
        if self.ingest.workers:
            log.info("ingest decode pool: %d worker(s), read-ahead %d",
                     self.ingest.workers, self.ingest.prefetch)
        if self.ingest.onchip:
            log.info("on-chip split decode: host entropy-decodes baseline "
                     "JPEG; dequant+IDCT+upsample+color ride the device")
        # Host-path egress (serving/egress.py): the encode worker pool
        # (0 workers = inline encode in the handler thread, the
        # bitwise-parity mode) that takes legacy PNG encode -- and the
        # packed/RLE wire encodes -- off the stream-handler hot path.
        self.egress = egress_lib.EncodePool(
            egress_lib.resolve_egress_workers(cfg.egress_workers)
        )
        if self.egress.workers:
            log.info("egress encode pool: %d worker(s)", self.egress.workers)
        # direct-path (unbatched) decode+analyze graphs for
        # coefficient-lane frames, memoized per (h, w, subsampling);
        # rebuilt lazily after every engine swap (_make_engine clears it)
        self._coef_direct: dict[tuple, Any] = {}  # guarded_by: _coef_direct_lock
        self._coef_direct_lock = threading.Lock()
        self._geom_cache = ingest_lib.GeometryCache()
        # one scoped store for the reload poller's lifetime (thread-safe
        # to build here; rebuilding per poll would churn MLflow clients
        # and scratch dirs)
        self._registry_store = tracking.store_for(cfg.tracking_uri)
        # Serving mesh (multi-chip dispatch): built ONCE at startup and
        # shared by every engine generation -- hot-reload swaps analyzers
        # and variables, never devices. Only meaningful when micro-batching
        # is on (the single-frame path has no dispatch window to route).
        self.dispatch_mode = resolve_dispatch_mode(cfg.dispatch_mode)
        # Serving precision tier (ops/pallas/quant.py): resolved ONCE at
        # startup; every engine generation re-applies it in _make_engine,
        # so a hot-reload of new registry weights re-quantizes. The
        # pre-transform (f32) model/variables of the CURRENT generation
        # are kept as the warm-up parity-gate reference.
        self.precision = resolve_precision(cfg.precision)
        self._pristine: tuple[Any, Any] = (model, variables)
        #: warm-up parity report for bf16/int8 (None at f32 / pre-warm)
        self.parity: dict | None = None
        for p in quant.PRECISIONS:
            obs.SERVING_PRECISION.labels(precision=p).set(
                1.0 if p == self.precision else 0.0
            )
        if self.precision != "f32":
            log.info("serving precision tier: %s", self.precision)
        # Model zoo roster (serving/zoo.py + models/variants.py): the
        # named engine generations this server holds side by side. The
        # empty roster is the legacy single-model server -- one entry
        # (the seed segmenter), no placer, serving path bitwise
        # identical to pre-zoo. The default entry's NAME labels every
        # per-model metric even on legacy servers.
        self._zoo_names = variants_lib.resolve_zoo_models(cfg.zoo_models)
        self.model_label = self._zoo_names[0]
        obs.ZOO_MODELS.set(len(self._zoo_names))
        self._serving_mesh = None
        chips = resolve_serving_chips(cfg.serving_mesh)
        if cfg.batch_window_ms > 0 and chips > 1:
            from robotic_discovery_platform_tpu.parallel import (
                mesh as mesh_lib,
            )

            self._serving_mesh = mesh_lib.make_serving_mesh(chips)
            log.info(
                "serving mesh: %d chip(s), %s dispatch",
                chips, self.dispatch_mode,
            )
        #: devices the batch dispatcher routes across (1 = single-device)
        self.serving_chips = chips if self._serving_mesh is not None else 1
        # resolved BEFORE the first engine build: a controller-enabled
        # server binds BOTH routed layouts (per-chip replicas and the
        # mesh-replicated copy) so the controller can flip dispatch modes
        # online
        self._controller_enabled = controller_lib.resolve_controller_enabled(
            cfg.controller_enabled
        )
        # brownout rung 3: the controller flips this and _enter_stream
        # refuses every other new stream (UNAVAILABLE -> clients fail
        # over; the duty cycle keeps the SLO signal alive). Both ride
        # the stream condition: the writer is the controller thread, the
        # readers are every handler thread.
        self._refusing_streams = False  # guarded_by: _streams_cond
        self._brownout_tick = 0  # guarded_by: _streams_cond
        # ZooPlacer (statistical multiplexing): built BEFORE the engine
        # so the dispatcher can consult it per launch. Only a real
        # multi-model zoo pays for one; the legacy server routes exactly
        # as before.
        self.placer: zoo_lib.ZooPlacer | None = None
        if len(self._zoo_names) > 1:
            self.placer = zoo_lib.ZooPlacer(
                self._zoo_names,
                chips=max(1, chips if self._serving_mesh is not None else 1),
                mode=zoo_lib.resolve_zoo_placement(cfg.zoo_placement),
                interval_s=cfg.zoo_rate_interval_s,
                window=cfg.zoo_rate_window,
                rebalance_s=cfg.zoo_rebalance_s,
                corr_cap=cfg.zoo_corr_cap,
            )
            log.info("model zoo: %s (%s placement over %d chip(s))",
                     ",".join(self._zoo_names), self.placer.mode,
                     self.placer.chips)
        self._engine = self._make_engine(model, variables, version)
        self._warm_shape: tuple[int, int] | None = None
        self._reload_stop: threading.Event | None = None
        self._reload_thread: threading.Thread | None = None
        # at most one reload in flight: the poller thread and direct
        # callers (tests, admin hooks) must not interleave two
        # resolve/build/swap sequences -- unserialized, engines could swap
        # in arbitrary order and a generation's dispatcher could miss its
        # scheduled stop
        self._reload_lock = threading.Lock()
        self._reload_busy = False
        self._closed = False
        # pending grace-delayed (timer, old_dispatcher) teardowns; close()
        # cancels the timers and stops the dispatchers immediately
        self._grace_stops: list[tuple[threading.Timer, Any]] = []
        # Per-service registry breaker: a sustained registry outage opens
        # it and the reload poller fast-fails without touching the network
        # (and without per-tick log spam -- the breaker logs transitions).
        self.registry_breaker = CircuitBreaker(
            failure_threshold=cfg.registry_breaker_failures,
            reset_timeout_s=cfg.registry_breaker_reset_s,
            name=f"registry:{cfg.tracking_uri}",
        )
        # grpc.health.v1 state: NOT_SERVING until warm-up completes
        # (build_server / warmup flip it), NOT_SERVING again once a drain
        # begins.
        self.health = health_lib.HealthServicer()
        self.health.set(vision_grpc.SERVICE_NAME, health_lib.NOT_SERVING)
        # one readiness entry per routed chip: a probe can enumerate
        # rdp.serving.chip.<i> until NOT_FOUND to read the mesh width;
        # the entries flip with overall readiness (set_all)
        for i in range(self.serving_chips):
            self.health.set(f"rdp.serving.chip.{i}", health_lib.NOT_SERVING)
        obs.SERVING_CHIPS.set(self.serving_chips)
        # in-flight stream accounting for graceful drain
        self._streams_cond = threading.Condition()
        self._active_streams = 0  # guarded_by: _streams_cond
        self._draining = False  # guarded_by: _streams_cond
        # Rollout wiring (serving/rollout.py): the shadow tap mirrors a
        # fraction of analyzed frames (inputs + this generation's
        # outputs) to a gated candidate -- installed/cleared by the
        # rollout manager for the SHADOW stage, a single attribute read
        # per frame otherwise. `rollout` is the shared RolloutManager
        # drift recommendations are forwarded to when one is attached.
        self._shadow_hook = None
        self.rollout: rollout_lib.RolloutManager | None = None
        # frames served over this process's lifetime (every terminal
        # status); reported over the replica stats RPC so a fleet
        # front-end can read per-replica progress without scraping
        # /metrics over HTTP. Incremented by every handler thread, so it
        # rides the stream condition too (the bare += it replaces lost
        # counts under concurrent streams).
        self._frames_total = 0  # guarded_by: _streams_cond
        self.metrics = metrics or MetricsWriter(
            cfg.metrics_csv, cfg.metrics_flush_every
        )
        # Prometheus exposition endpoint; build_server starts one when
        # cfg.metrics_port / RDP_METRICS_PORT asks for it, close() stops it
        self.metrics_server: exposition.MetricsServer | None = None
        # elastic membership (serving/fleet.py): set by build_server when
        # registrars are configured; drain() sends Leave, close() stops it
        self.lease_client: fleet_lib.LeaseClient | None = None
        self.bound_port = 0  # set by build_server after add_insecure_port
        # End-to-end latency SLO (observability/slo.py): every frame's
        # total latency feeds the violation counter and the error-budget
        # burn gauge. Off unless cfg.slo_ms / RDP_SLO_MS sets an objective.
        self.slo: slo_lib.SloTracker | None = None
        slo_ms = slo_lib.resolve_slo_ms(cfg.slo_ms)
        if slo_ms is not None:
            self.slo = slo_lib.SloTracker(
                slo_ms / 1e3, budget=cfg.slo_budget, window=cfg.slo_window,
                name="e2e",
                violations=obs.SLO_VIOLATIONS.labels(objective="e2e"),
                # model="" = the all-models aggregate: what the reactive
                # controller and the fleet front-end consume; per-model
                # burn children ride next to it under a zoo
                burn_gauge=obs.SLO_BURN.labels(objective="e2e", model=""),
                objective_gauge=obs.SLO_OBJECTIVE.labels(objective="e2e"),
            )
            log.info("SLO tracking: %.1f ms objective, %.2f%% budget",
                     slo_ms, 100 * cfg.slo_budget)
        # Online drift monitor (monitoring/profile.py): every served
        # frame's free signals feed per-signal sliding windows scored
        # against a reference profile (registry artifact / explicit path /
        # self-baseline). Strictly host-side deque+histogram bookkeeping
        # OFF the compute path -- no device transfers, no jit retraces --
        # so the f32 serial bitwise-parity guarantee is untouched.
        self.drift: profile_lib.DriftMonitor | None = None
        if cfg.drift_enabled:
            reference = self._load_drift_profile(version)
            self.drift = profile_lib.DriftMonitor(
                reference=reference,
                window=cfg.drift_window,
                baseline_frames=cfg.drift_baseline_frames,
                score_every=cfg.drift_score_every,
                psi_threshold=cfg.drift_psi_threshold,
                sustain_s=cfg.drift_sustain_s,
                cooldown_s=cfg.drift_cooldown_s,
                generation=version,
                on_score=self._on_drift_score,
                on_recommendation=self._on_drift_recommendation,
            )
            obs.DRIFT_REFERENCE_AGE.set(
                -1.0 if reference is None else reference.age_s
            )
        # Reactive SLO controller (serving/controller.py): consumes the
        # tracker's burn signal and retunes the LIVE engine's dispatcher
        # (the indirection follows hot-reload swaps). Needs an objective
        # to control against and a dispatcher to actuate.
        self.controller: controller_lib.ReactiveController | None = None
        if (self._controller_enabled and self.slo is not None
                and cfg.batch_window_ms > 0):
            self.controller = controller_lib.ReactiveController(
                dispatcher=lambda: self._engine.dispatcher,
                burn=lambda: self.slo.burn,
                refuse_streams=self._set_refuse_streams,
                interval_s=cfg.controller_interval_s,
                burn_high=cfg.controller_burn_high,
                burn_low=cfg.controller_burn_low,
                sustain_s=cfg.controller_sustain_s,
                cooldown_s=cfg.controller_cooldown_s,
                inflight_cap=cfg.controller_inflight_cap,
                samples=lambda: self.slo.observed_total,
            )
            self.controller.start()
        elif self._controller_enabled:
            log.warning(
                "controller enabled but idle: it needs slo_ms > 0 (got "
                "%s) and batch_window_ms > 0 (got %s)",
                cfg.slo_ms, cfg.batch_window_ms,
            )
        # Model zoo entries (serving/zoo.py): the default entry is this
        # server's legacy engine state under its catalog name; extras
        # are built from their own registry entries and bound onto the
        # SHARED dispatcher. Per-model frame counts ride the stream
        # condition like _frames_total.
        self._model_frames: dict[str, int] = {}  # guarded_by: _streams_cond
        self.zoo = zoo_lib.ModelZoo(default=self.model_label)
        self.zoo.add(zoo_lib.ZooEntry(
            name=self.model_label,
            variant=variants_lib.VARIANTS[self.model_label],
            analyze=None,  # the default model reads through self._engine
            variables=None, version=version, precision=self.precision,
        ))
        self._build_zoo_entries(version)

    def _set_refuse_streams(self, refusing: bool) -> None:
        """Controller brownout rung 3 actuator."""
        with self._streams_cond:
            changed = refusing != self._refusing_streams
            self._refusing_streams = refusing
        if changed:
            log.warning(
                "overload brownout: %s new analysis streams",
                "refusing" if refusing else "accepting",
            )

    def _on_chip_health(self, chip: int, serving: bool) -> None:
        """DeviceRouter quarantine hook: a quarantined chip's
        ``rdp.serving.chip.<i>`` health entry goes NOT_SERVING so probes
        and dashboards see the degraded mesh; reinstatement flips it
        back."""
        self.health.set(
            f"rdp.serving.chip.{chip}",
            health_lib.SERVING if serving else health_lib.NOT_SERVING,
        )

    # -- drift observability ------------------------------------------------

    def _load_drift_profile(
            self, version: int | None, model_name: str | None = None,
            allow_explicit: bool = True,
    ) -> profile_lib.FeatureProfile | None:
        """Resolve the reference profile: an explicit path
        (cfg.drift_profile_path / RDP_DRIFT_PROFILE) wins, else the
        ``drift_profile.json`` artifact next to the served registry
        version's weights; None means self-baseline. ``model_name``
        selects the registry entry (default: the server's default
        model); the explicit-path override only ever applies to the
        default model -- one path cannot reference M distributions."""
        model_name = model_name or self.cfg.model_name
        if allow_explicit:
            path = profile_lib.resolve_drift_profile_path(
                self.cfg.drift_profile_path
            )
            if path is not None:
                try:
                    return profile_lib.FeatureProfile.load(path)
                except Exception as exc:
                    log.warning(
                        "drift profile %s unusable (%s: %s); falling back "
                        "to registry artifact / self-baseline",
                        path, type(exc).__name__, exc,
                    )
        if version is None:
            return None
        try:
            artifact = (
                self._registry_store.version_path(
                    model_name, version
                ) / profile_lib.DRIFT_PROFILE_FILE
            )
            if artifact.exists():
                return profile_lib.FeatureProfile.load(artifact)
        except Exception as exc:
            log.warning(
                "no drift profile artifact for %s v%s (%s: %s); "
                "self-baselining", model_name, version,
                type(exc).__name__, exc,
            )
        return None

    def _on_drift_score(self, signal: str,
                        score: profile_lib.DriftScore) -> None:
        obs.DRIFT_SCORE.labels(signal=signal,
                               model=self.model_label).set(score.psi)
        if self.drift is not None:
            age = self.drift.reference_age_s
            obs.DRIFT_REFERENCE_AGE.set(-1.0 if age is None else age)

    def _on_model_drift_score(self, model: str, signal: str,
                              score: profile_lib.DriftScore) -> None:
        """Per-zoo-model drift scoring hook (extras; the default model's
        monitor keeps the legacy ``_on_drift_score`` path)."""
        obs.DRIFT_SCORE.labels(signal=signal, model=model).set(score.psi)

    def _on_model_drift_recommendation(
            self, model: str,
            rec: profile_lib.RetrainRecommendation) -> None:
        """A non-default zoo model drifted: counted, pinned, logged. NOT
        forwarded to the rollout manager -- the drain/retrain/shadow
        cycle drives the default model's generation; extra zoo models
        retrain through their own registry workflow (their promotion is
        an alias move this server's reload poller does not watch yet)."""
        obs.DRIFT_RECOMMENDATIONS.inc()
        recorder_lib.RECORDER.pin(recorder_lib.RECORDER.record_event(
            "serving.drift_recommendation", model=model,
            signals=",".join(rec.signals),
            generation=str(rec.generation),
            reference=rec.reference_source,
            reason=rec.reason,
        ))
        journal_lib.JOURNAL.append(
            events.DRIFT_RECOMMENDATION, rec.reason, model=model,
            signals=",".join(rec.signals), generation=str(rec.generation),
        )
        log.warning("DRIFT[%s]: %s -- recommend retraining", model,
                    rec.reason)

    def _on_drift_recommendation(
            self, rec: profile_lib.RetrainRecommendation) -> None:
        """Hysteresis-gated: at most one of these per sustained excursion.
        Counted, pinned in the flight recorder (a recommendation is
        evidence that must survive ring wrap-around), logged -- and, when
        a rollout manager is attached (serving/rollout.py), handed to it:
        the recommendation becomes a supervised drain -> retrain ->
        shadow -> gate -> promote cycle instead of terminating here."""
        obs.DRIFT_RECOMMENDATIONS.inc()
        recorder_lib.RECORDER.pin(recorder_lib.RECORDER.record_event(
            "serving.drift_recommendation",
            signals=",".join(rec.signals),
            generation=str(rec.generation),
            reference=rec.reference_source,
            reason=rec.reason,
        ))
        journal_lib.JOURNAL.append(
            events.DRIFT_RECOMMENDATION, rec.reason,
            signals=",".join(rec.signals), generation=str(rec.generation),
        )
        log.warning(
            "DRIFT: %s -- recommend retraining (workflows.retraining)",
            rec.reason,
        )
        manager = self.rollout
        if manager is not None:
            try:
                manager.on_recommendation(rec)
            except Exception:  # pragma: no cover - manager bug
                log.exception("rollout manager rejected the "
                              "recommendation")

    def _apply_drift_reference(
            self, version: int | None,
            reference: profile_lib.FeatureProfile | None) -> None:
        """Adopt the swapped-in generation's drift reference -- its
        profile artifact when it shipped one, else a fresh self-baseline,
        re-stamping the reference generation either way. Callers hold
        ``_reload_lock``: the reference must change in the SAME critical
        section as the engine swap, so a scrape can never pair new
        weights with the old reference (or vice versa)."""
        if self.drift is None:
            return
        if reference is not None:
            self.drift.set_reference(reference)
            obs.DRIFT_REFERENCE_AGE.set(reference.age_s)
        else:
            self.drift.rebaseline(generation=version)
            obs.DRIFT_REFERENCE_AGE.set(-1.0)

    def version_and_reference(self) -> tuple[int | None, object]:
        """The (engine generation, drift reference generation) pair read
        under the reload lock -- the consistency the promotion swap
        guarantees: both move together, so this never returns a mixed
        pair (tests and /debug consumers assert it)."""
        with self._reload_lock:
            version = self._engine.version
            if self.drift is None:
                return version, None
            ref = self.drift.reference
            gen = (ref.generation if ref is not None
                   and ref.generation is not None
                   else self.drift.generation)
            return version, gen

    def drift_debug(self) -> dict:
        """The ``GET /debug/drift`` payload. Snapshot and engine version
        are read under the reload lock so a mid-promotion request sees a
        consistent (weights, reference) pair."""
        if self.drift is None:
            return {"enabled": False,
                    "reason": "drift monitoring disabled "
                              "(ServerConfig.drift_enabled)"}
        with self._reload_lock:
            snap = self.drift.snapshot()
            snap["model_version"] = self._engine.version
        return snap

    @property
    def variables(self):
        return self._engine.variables

    @property
    def analyze(self):
        return self._engine.analyze

    @property
    def dispatcher(self):
        return self._engine.dispatcher

    @property
    def batch_analyze(self):
        return self._engine.batch_analyze

    def coef_analyzer(self, height: int, width: int,
                      subsampling: str = "420"):
        """The default model's coefficient-lane analyzer for one camera
        geometry, as ``functools.partial(jitted_analyzer, variables)`` --
        what the dispatcher memoizes per (geometry, subsampling)."""
        return self._coef_factory_fn("", height, width, subsampling)

    @property
    def current_version(self) -> int | None:
        return self._engine.version

    def _make_engine(self, model, variables, version) -> Engine:
        cfg, geom_cfg = self.cfg, self.geom_cfg
        # precision tier applied per GENERATION: the pristine (f32) pair is
        # kept for the parity gate, the engine binds the transformed pair.
        # At f32 apply_precision returns its inputs untouched, so that tier
        # stays bitwise identical to pre-tier serving.
        self._pristine = (model, variables)
        model, variables, qreport = quant.apply_precision(
            model, variables, self.precision
        )
        if qreport is not None and qreport.get("layers"):
            log.info(
                "int8-quantized %d conv kernels for version %s "
                "(max |err| %.3g, %.1f%% rel; %d int8 bytes vs %d f32)",
                qreport["layers"], version, qreport["max_abs_err"],
                100 * qreport["max_rel_err"], qreport["int8_bytes"],
                qreport["f32_bytes"],
            )
        # Stage the weight tree explicitly ONCE per engine generation
        # (already the per-chip policy under a serving mesh): a
        # checkpoint-restored tree can surface as host numpy, and passing
        # that to the jitted analyzer re-transfers every weight on every
        # dispatch -- implicitly, which RDP_TRANSFER_GUARD=strict rightly
        # refuses. Gated on the tree actually holding host arrays so an
        # all-device tree keeps OBJECT identity (the f32 tier's
        # bitwise-identical-by-construction contract is literally "same
        # objects in, same objects out").
        if any(not isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(variables)):
            variables = jax.device_put(variables)
        if self._serving_mesh is not None:
            # the Pallas-fused forward closes over default-device buffers
            # and has no partitioning rules, so under a serving mesh every
            # chip runs the Flax/XLA forward (the trainer applies the same
            # policy under its mesh)
            if cfg.model_forward == "pallas":
                log.warning(
                    "model_forward='pallas' cannot route across a serving "
                    "mesh; using the Flax/XLA forward on every chip"
                )
            forward = None
        else:
            forward = self._build_forward(model, variables, cfg)
        analyze = pipeline.make_frame_analyzer(
            model, img_size=cfg.model_img_size, geom_cfg=geom_cfg,
            forward=forward,
        )
        batch_geom_cfg = self._batch_geom_cfg()

        # Coefficient-lane analyzer factory (split JPEG decode): builds
        # the decode+analyze graph for one (geometry, subsampling), closed
        # over THIS generation's model + variables. Shared by the batch
        # dispatcher (lazily memoized per key) and the direct path
        # (self._coef_direct). Default model only: zoo extras keep pixel
        # formats -- their variables never ride this closure.
        def coef_factory(model_key: str, height: int, width: int,
                         subsampling: str, _model=model,
                         _variables=variables, _forward=forward):
            if model_key:
                raise ValueError(
                    "the coefficient lane serves the default model only; "
                    f"model {model_key!r} frames must use pixel formats"
                )
            coef_analyze = pipeline.make_coef_batch_analyzer(
                _model, img_size=cfg.model_img_size, geom_cfg=batch_geom_cfg,
                forward=_forward, height=height, width=width,
                subsampling=subsampling, pack=cfg.egress_pack,
            )
            return functools.partial(coef_analyze, _variables)

        self._coef_factory_fn = coef_factory
        with self._coef_direct_lock:
            # stale closures must not outlive the generation that built
            # them -- direct coef graphs rebuild lazily on first use
            self._coef_direct.clear()
        dispatcher = batch_analyze = None
        if cfg.batch_window_ms > 0:
            from robotic_discovery_platform_tpu.serving.batching import (
                BatchDispatcher,
                DeviceRouter,
                resolve_max_inflight,
            )

            if cfg.batch_impl == "dense":
                make_batched = pipeline.make_batch_analyzer
            elif cfg.batch_impl == "scan":
                make_batched = pipeline.make_scan_batch_analyzer
            else:
                raise ValueError(f"unknown batch_impl {cfg.batch_impl!r}")
            # egress_pack: the analyzer graph ends in the fused egress pack
            # stage (ops/pipeline.pack_analysis), so the completer's D2H
            # is ONE [B, P] uint8 fetch per dispatch and dispatcher
            # results are serving/egress.PackedResult rows
            batch_analyze = make_batched(
                model, img_size=cfg.model_img_size, geom_cfg=batch_geom_cfg,
                forward=forward, pack=cfg.egress_pack,
            )
            router = None
            if self._serving_mesh is not None:
                from robotic_discovery_platform_tpu.parallel import (
                    mesh as mesh_lib,
                )

                # bind the model weights to each placement ONCE per engine
                # generation: per-chip replicas (round_robin) or one
                # mesh-replicated copy (sharded). Passing uncommitted
                # variables would re-transfer the whole weight tree on
                # every routed dispatch.
                chips = self.serving_chips
                analyzers = None
                sharded_analyzer = None
                if self.dispatch_mode == "round_robin":
                    analyzers = [
                        (lambda frames, depths, intr, scales, _v=v:
                         batch_analyze(_v, frames, depths, intr, scales))
                        for v in (
                            jax.device_put(variables, d)
                            for d in mesh_lib.device_ring(self._serving_mesh)
                        )
                    ]
                    # controller-enabled round_robin servers additionally
                    # bind the mesh-replicated layout when the geometry
                    # permits it, so the controller can flip to sharded
                    # dispatch online (one extra replicated weight copy)
                    if (self._controller_enabled
                            and not (chips & (chips - 1))
                            and cfg.max_batch >= chips
                            and cfg.max_batch % chips == 0):
                        v_repl = mesh_lib.shard_pytree(
                            self._serving_mesh, variables
                        )
                        sharded_analyzer = (
                            lambda frames, depths, intr, scales:
                            batch_analyze(v_repl, frames, depths, intr,
                                          scales)
                        )
                else:
                    v_repl = mesh_lib.shard_pytree(
                        self._serving_mesh, variables
                    )
                    analyzers = [
                        lambda frames, depths, intr, scales: batch_analyze(
                            v_repl, frames, depths, intr, scales
                        )
                    ]
                router = DeviceRouter(
                    self._serving_mesh, self.dispatch_mode, analyzers,
                    sharded_analyzer=sharded_analyzer,
                    breaker_failures=cfg.chip_breaker_failures,
                    breaker_reset_s=cfg.chip_breaker_reset_s,
                    on_health=self._on_chip_health,
                )
            dispatcher = BatchDispatcher(
                lambda frames, depths, intr, scales: batch_analyze(
                    variables, frames, depths, intr, scales
                ),
                window_ms=cfg.batch_window_ms,
                max_batch=cfg.max_batch,
                max_backlog=cfg.max_backlog,
                submit_timeout_s=cfg.submit_deadline_s,
                watchdog_interval_s=cfg.watchdog_interval_s,
                max_inflight=resolve_max_inflight(
                    cfg.max_inflight_dispatches
                ),
                router=router,
                admission=cfg.admission_policy,
                placer=self.placer,
                model_label=self.model_label,
                coef_analyzer_factory=coef_factory,
            )
            # a hot-reload builds a FRESH dispatcher for the new default
            # generation; the zoo's extra models (whose generations did
            # not move) re-bind onto it so their serving is uninterrupted
            existing_zoo = getattr(self, "zoo", None)
            if existing_zoo is not None:
                for entry in existing_zoo.extras():
                    if entry.batch_analyze is not None:
                        dispatcher.bind_model(
                            entry.name, entry.batch_analyze,
                            entry.per_chip_analyzers,
                            entry.sharded_analyzer,
                        )
        return Engine(analyze, variables, dispatcher, version,
                      batch_analyze)

    def _batch_geom_cfg(self) -> GeometryConfig:
        """The geometry config of the BATCHED analyzers. Under a serving
        mesh the forward's rule holds for their other kernels too (fused
        geometry, mask pack): one jitted analyzer serves every placement of
        the mesh, the sharded one included, and a sharded jit refuses them
        ("Mosaic kernels cannot be automatically partitioned"). So "auto"
        means XLA there; an explicit ``kernel_impl`` pin stands."""
        if (self._serving_mesh is not None
                and self.geom_cfg.kernel_impl == "auto"):
            return dataclasses.replace(self.geom_cfg, kernel_impl="xla")
        return self.geom_cfg

    @staticmethod
    def _build_forward(model, variables, cfg: ServerConfig):
        """Pick the model-forward implementation per ServerConfig.model_forward
        ("auto" = Pallas-fused kernels on TPU, Flax/XLA otherwise)."""
        from robotic_discovery_platform_tpu.ops import pallas as pallas_ops

        mode = cfg.model_forward
        if mode == "flax" or (mode == "auto" and not pallas_ops.use_pallas()):
            return None
        if mode not in ("auto", "pallas"):
            raise ValueError(f"unknown model_forward {mode!r}")
        pnet = pallas_ops.make_pallas_unet(model, variables)
        log.info("serving with Pallas-fused U-Net forward")
        return lambda _variables, x: pnet(x)

    # -- model zoo -----------------------------------------------------------

    def _build_zoo_entries(self, default_version: int | None) -> None:
        """Load and bind every non-default zoo model: its own registry
        entry (alias-first, like the default), precision transform,
        analyzers bound onto the SHARED dispatcher, per-model drift
        monitor, and per-model SLO tracker. A model whose registry entry
        is missing is skipped with a warning -- the server serves what
        exists rather than refusing to boot (the zoo is additive)."""
        cfg = self.cfg
        self._model_slo: dict[str, slo_lib.SloTracker] = {}
        if len(self._zoo_names) > 1:
            slo_ms = slo_lib.resolve_slo_ms(cfg.slo_ms)
            if slo_ms is not None:
                # per-model burn for the default model too; the
                # aggregate tracker (self.slo, model="") keeps feeding
                # the controller and the fleet
                self._model_slo[self.model_label] = slo_lib.SloTracker(
                    slo_ms / 1e3, budget=cfg.slo_budget,
                    window=cfg.slo_window,
                    name=f"e2e/{self.model_label}",
                    burn_gauge=obs.SLO_BURN.labels(
                        objective="e2e", model=self.model_label),
                )
        for name in self._zoo_names[1:]:
            variant = variants_lib.VARIANTS[name]
            reg_name = variants_lib.registered_name(
                variant, cfg.model_name)
            try:
                alias = self._registry_store.get_alias(
                    reg_name, cfg.model_alias)
                version = (int(alias) if alias is not None else int(
                    self._registry_store.latest_version(
                        reg_name)["version"]))
                zmodel, zvariables = tracking.load_model(
                    f"models:/{reg_name}/{version}",
                    store=self._registry_store,
                )
            except Exception as exc:
                log.warning(
                    "zoo model %r (%s) unavailable (%s: %s); serving "
                    "without it", name, reg_name,
                    type(exc).__name__, exc,
                )
                continue
            try:
                entry = self._make_zoo_entry(name, variant, reg_name,
                                             zmodel, zvariables, version)
            except Exception:
                log.exception("zoo model %r failed to build; serving "
                              "without it", name)
                continue
            self.zoo.add(entry)
            log.info("zoo model %r: %s v%s (%s tier, %s head)",
                     name, reg_name, version, entry.precision,
                     variant.head)

    def _make_zoo_entry(self, name: str, variant, reg_name: str,
                        model, variables,
                        version: int | None) -> zoo_lib.ZooEntry:
        """One non-default zoo entry: mirror of the default engine build
        (precision transform, explicit weight staging, per-chip/sharded
        router bindings) against this model's own weights."""
        cfg, geom_cfg = self.cfg, self.geom_cfg
        pristine = (model, variables)
        model_q, variables_q, qreport = quant.apply_precision(
            model, variables, self.precision
        )
        if qreport is not None and qreport.get("layers"):
            log.info("int8-quantized %d conv kernels for zoo model %r",
                     qreport["layers"], name)
        if any(not isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(variables_q)):
            variables_q = jax.device_put(variables_q)
        # zoo extras always run the Flax/XLA forward: the Pallas-fused
        # net binds one model's weights at build time and has no
        # multi-model dispatch (same policy as serving meshes)
        analyze = pipeline.make_frame_analyzer(
            model_q, img_size=cfg.model_img_size, geom_cfg=geom_cfg,
        )
        batch_analyze = per_chip = sharded = None
        dispatcher = self._engine.dispatcher
        if dispatcher is not None:
            make_batched = (pipeline.make_batch_analyzer
                            if cfg.batch_impl == "dense"
                            else pipeline.make_scan_batch_analyzer)
            batched = make_batched(
                model_q, img_size=cfg.model_img_size,
                geom_cfg=self._batch_geom_cfg(),
                pack=cfg.egress_pack,
            )
            batch_analyze = (
                lambda frames, depths, intr, scales,
                       _b=batched, _v=variables_q:
                _b(_v, frames, depths, intr, scales)
            )
            if self._serving_mesh is not None:
                from robotic_discovery_platform_tpu.parallel import (
                    mesh as mesh_lib,
                )

                if self.dispatch_mode == "round_robin":
                    # per-(model, chip) committed weight replicas, like
                    # the default model's router bindings: an
                    # uncommitted tree would re-transfer per dispatch
                    per_chip = [
                        (lambda frames, depths, intr, scales,
                                _b=batched, _v=v:
                         _b(_v, frames, depths, intr, scales))
                        for v in (
                            jax.device_put(variables_q, d)
                            for d in mesh_lib.device_ring(
                                self._serving_mesh)
                        )
                    ]
                else:
                    v_repl = mesh_lib.shard_pytree(
                        self._serving_mesh, variables_q
                    )
                    sharded = (
                        lambda frames, depths, intr, scales,
                               _b=batched, _v=v_repl:
                        _b(_v, frames, depths, intr, scales)
                    )
            dispatcher.bind_model(name, batch_analyze, per_chip, sharded)
        drift = None
        if cfg.drift_enabled:
            reference = self._load_drift_profile(
                version, model_name=reg_name, allow_explicit=False)
            drift = profile_lib.DriftMonitor(
                reference=reference,
                window=cfg.drift_window,
                baseline_frames=cfg.drift_baseline_frames,
                score_every=cfg.drift_score_every,
                psi_threshold=cfg.drift_psi_threshold,
                sustain_s=cfg.drift_sustain_s,
                cooldown_s=cfg.drift_cooldown_s,
                generation=version,
                on_score=functools.partial(
                    self._on_model_drift_score, name),
                on_recommendation=functools.partial(
                    self._on_model_drift_recommendation, name),
            )
        slo_ms = slo_lib.resolve_slo_ms(cfg.slo_ms)
        slo_tracker = None
        if slo_ms is not None:
            slo_tracker = slo_lib.SloTracker(
                slo_ms / 1e3, budget=cfg.slo_budget,
                window=cfg.slo_window, name=f"e2e/{name}",
                burn_gauge=obs.SLO_BURN.labels(objective="e2e",
                                               model=name),
            )
            self._model_slo[name] = slo_tracker
        return zoo_lib.ZooEntry(
            name=name, variant=variant, analyze=analyze,
            variables=variables_q, version=version,
            precision=self.precision, pristine=pristine, drift=drift,
            slo=slo_tracker, batch_analyze=batch_analyze,
            per_chip_analyzers=per_chip, sharded_analyzer=sharded,
        )

    def _resolve_model(self, name: str) -> tuple[str, Any]:
        """Map one wire ``model`` field to (metric label, zoo entry).
        "" and the default name both resolve to (default label, None) --
        None meaning "use the legacy engine path", which is how the
        default model stays byte-for-byte pre-zoo. Unknown names raise
        :class:`zoo_lib.UnknownModelError` (a per-frame error)."""
        if not name or name == self.model_label:
            return self.model_label, None
        entry = self.zoo.get(name)
        if entry is None:
            raise zoo_lib.UnknownModelError(
                f"model {name!r} is not in this server's zoo "
                f"({', '.join(self.zoo.names())})"
            )
        return name, entry

    def zoo_debug(self) -> dict:
        """The ``GET /debug/zoo`` payload: roster, per-model versions /
        heads / frame counts, the placer's live placement + rate
        correlations, and the (model, placement, bucket) warm set."""
        with self._streams_cond:
            frames = dict(self._model_frames)
        models = {}
        for n in self.zoo.names():
            e = self.zoo.get(n)
            models[n] = {
                "version": (self._engine.version
                            if n == self.model_label else e.version),
                "head": e.variant.head,
                "registered_name": variants_lib.registered_name(
                    e.variant, self.cfg.model_name),
                "precision": e.precision,
                "frames": frames.get(n, 0),
                "parity": e.parity if n != self.model_label else self.parity,
            }
        dispatcher = self._engine.dispatcher
        return {
            "enabled": len(self._zoo_names) > 1,
            "default": self.model_label,
            "models": models,
            "placement": (self.placer.snapshot()
                          if self.placer is not None else None),
            "warmed": (sorted(
                [list(map(str, k)) for k in dispatcher.warmed])
                if dispatcher is not None else []),
        }

    # -- per-frame ----------------------------------------------------------

    def _decode(self, request: vision_pb2.AnalysisRequest):
        """One inline decode through the ingest core (RGB out; the
        BGR->RGB conversion now lives in decode, one cv2 pass)."""
        rgb, depth, _ = self.ingest.decode(request)
        return rgb, depth

    def _analyze_frame(self, rgb: np.ndarray, depth: np.ndarray,
                       timer: StageTimer | None = None,
                       timeout_s: float | None = None,
                       model: str = "",
                       mask_format: int = 0,
                       active=None):
        inject(fault_sites.SERVING_ANALYZE)
        timer = timer or StageTimer()
        t_entry = time.monotonic()
        # split-decode frames carry coefficients, not pixels: the device
        # decodes them fused ahead of the analyzer (CoefficientFrame's
        # .shape property keeps every geometry read below uniform)
        coef = isinstance(rgb, entropy_lib.CoefficientFrame)
        h, w = rgb.shape[:2]
        # per-stream geometry cache: identical intrinsics content never
        # re-converts to float32 (and, on the direct path, never
        # re-stages) -- the per-frame np.asarray at the old call sites
        # is one dict hit now
        geom = self._geom_cache.lookup(self.intrinsics, w, h,
                                       self.depth_scale)
        # zoo resolution: "" / the default name keep the legacy engine
        # path verbatim (entry None); an unknown name is a per-frame
        # error raised before any device work
        _, entry = self._resolve_model(model)
        # ONE read of the engine per frame: analyze/variables/dispatcher
        # swap together, so a concurrent hot-reload cannot mix generations
        eng = self._engine
        with timer.stage("device"):
            if eng.dispatcher is not None:
                # coalesce with co-arriving frames of the SAME model from
                # other streams; the submit carries the caller's
                # remaining deadline so a cancelled/expired client frees
                # this thread instead of parking it on an unbounded wait
                submit = (eng.dispatcher.submit_coef if coef
                          else eng.dispatcher.submit)
                out = submit(
                    rgb, depth, geom.k_f32, self.depth_scale,
                    timeout_s=timeout_s,
                    model=entry.name if entry is not None else "",
                )
            elif coef:
                out = self._analyze_coef_direct(rgb, depth, geom, entry)
            else:
                # explicit H2D for the frame inputs: the jitted entry runs
                # under the transfer guard, and relying on implicit
                # per-call transfers is exactly the host-path tax the
                # guard exists to flag (device_put is async -- it does
                # not block the handler thread). Intrinsics + depth scale
                # ride the geometry cache's committed copies: staged once
                # per distinct content, not once per frame.
                k_dev, scale_dev = geom.staged()
                frames_dev = jax.device_put((rgb, depth))
                if entry is not None:
                    out = entry.analyze(entry.variables, *frames_dev,
                                        k_dev, scale_dev)
                else:
                    out = eng.analyze(eng.variables, *frames_dev, k_dev,
                                      scale_dev)
            if isinstance(out, jax.Array):
                # the direct coefficient path under packing hands back a
                # bare [P] uint8 payload row (its own single fetch)
                out = egress_lib.PackedResult(np.asarray(out))
            packed = out if isinstance(out, egress_lib.PackedResult) else None
            if packed is not None:
                # packed egress: the scalars ride the f32 sidecar of the
                # completer's single per-dispatch fetch -- bitwise the
                # values the legacy per-leaf fetches carried; the
                # full-resolution mask only unpacks when something
                # actually needs pixels
                mask = None
                coverage, mean_k, max_k, valid, margin = packed.scalars()
                spline = (packed.spline() if not mask_format
                          else np.zeros((0, 3), np.float32))
            else:
                # host fetch of the fused result (direct pixel path)
                mask = np.asarray(out.mask)
                coverage = float(out.mask_coverage)
                prof = out.profile
                valid = bool(prof.valid)
                mean_k = float(prof.mean_curvature) if valid else 0.0
                max_k = float(prof.max_curvature) if valid else 0.0
                spline = (np.asarray(prof.spline_points) if valid
                          else np.zeros((0, 3)))
                margin = float(np.asarray(out.confidence_margin))
            # drift signal the frame already paid for: the depth-validity
            # fraction is one host-side count over the raw depth frame
            depth_valid = float(np.count_nonzero(depth)) / max(depth.size, 1)
        try:
            spline_wire = b""
            if mask_format:
                # packed wire formats skip the per-point Point3D loop:
                # the spline rides packed_spline as f32 LE triples
                spline_wire = (packed.spline_wire() if packed is not None
                               else np.ascontiguousarray(
                                   spline, dtype="<f4").tobytes())
                spline = np.zeros((0, 3), np.float32)
            # bugfix (ISSUE 20): a frame whose stream is already cancelled
            # or whose deadline expired while it rode the device must not
            # pay encode cost (PNG + the mask*255 full-frame allocation)
            # for an answer nobody will receive
            dead = ((active is not None and not active())
                    or (timeout_s is not None
                        and time.monotonic() - t_entry >= timeout_s))
            with timer.stage("encode"):
                if dead:
                    mask_bytes = b""
                elif mask_format == egress_lib.MASK_FORMAT_BITS:
                    # zero-transform: the wire payload IS the packed
                    # staging rows behind a small header
                    bits = (packed.mask_bits if packed is not None
                            else np.packbits(mask, axis=-1))
                    shape = ((packed.h, packed.w) if packed is not None
                             else mask.shape[:2])
                    mask_bytes = self.egress.encode(
                        "bits", bits=bits, shape=shape, timeout_s=timeout_s
                    )
                elif mask_format == egress_lib.MASK_FORMAT_RLE:
                    mask_bytes = self.egress.encode(
                        "rle", mask=mask,
                        bits=packed.mask_bits if packed is not None else None,
                        shape=((packed.h, packed.w) if packed is not None
                               else mask.shape[:2]),
                        timeout_s=timeout_s,
                    )
                else:
                    # legacy PNG (and any unknown mask_format): the
                    # historical wire bytes exactly
                    m = packed.unpack_mask() if packed is not None else mask
                    mask_bytes = self.egress.encode(
                        "png", mask=m, timeout_s=timeout_s
                    )
            anomaly = None
            if entry is not None and entry.variant.head == "anomaly":
                # the aux head's product: defect/anomaly score off the
                # confidence margin the fused graph already computed
                anomaly = variants_lib.anomaly_score(margin)
                obs.MODEL_ANOMALY_SCORE.observe(anomaly)
            res = _FrameResult(mean_k, max_k, spline, mask_bytes,
                               coverage, valid, margin, depth_valid,
                               anomaly, spline_wire)
            if (entry is None and not coef
                    and self._shadow_hook is not None):
                # only default-model frames mirror to a rollout shadow:
                # the shadow diff gates the DEFAULT generation's
                # replacement -- and only pixel frames can (a split-decode
                # frame's RGB never materializes on the host, which is its
                # point). Checked here so a packed frame only unpacks its
                # mask when a shadow tap is actually installed.
                if mask is None:
                    mask = packed.unpack_mask()
                self._mirror_shadow(rgb, depth, geom.k_f32, mask, res)
            return res
        finally:
            # hand the packed row's share of the pooled staging buffer
            # back to the dispatcher (everything needed was copied out)
            if packed is not None:
                packed.release()

    def _analyze_coef_direct(self, frame, depth, geom, entry):
        """Direct-path (unbatched) ride for a coefficient-lane frame: the
        batch-1 decode+analyze graph, lazily built + memoized per
        (h, w, subsampling) for the current engine generation, with the
        leading batch axis squeezed off the result tree."""
        if entry is not None:
            raise ValueError(
                "the coefficient lane serves the default model only; "
                f"model {entry.name!r} frames must use pixel formats"
            )
        key = (frame.height, frame.width, frame.subsampling)
        with self._coef_direct_lock:
            analyze = self._coef_direct.get(key)
        if analyze is None:
            analyze = self._coef_factory_fn(
                "", frame.height, frame.width, frame.subsampling
            )
            with self._coef_direct_lock:
                analyze = self._coef_direct.setdefault(key, analyze)
        staged = pipeline.stage_coef_batch(
            frame.y[None], frame.cb[None], frame.cr[None],
            frame.qy[None], frame.qc[None], depth[None],
            geom.k_f32[None],
            np.asarray([self.depth_scale], np.float32),
        )
        out = analyze(*staged)
        return jax.tree.map(lambda a: a[0], out)

    def _observe_drift(self, res: _FrameResult,
                       entry=None) -> None:
        """Feed one analyzed frame's signals to its model's drift
        monitor and the confidence-margin histogram -- pure host-side
        Python, after the response is already built."""
        obs.MODEL_CONFIDENCE_MARGIN.observe(res.confidence_margin)
        monitor = self.drift if entry is None else entry.drift
        if monitor is None:
            return
        monitor.observe_frame({
            "mask_coverage": res.coverage,
            "mean_curvature": res.mean_k if res.valid else math.nan,
            "max_curvature": res.max_k if res.valid else math.nan,
            "depth_valid_fraction": res.depth_valid_fraction,
            "confidence_margin": res.confidence_margin,
        })

    def _enter_stream(self) -> bool:
        with self._streams_cond:
            if self._draining or self._closed:
                return False
            if self._refusing_streams:
                # brownout rung 3 duty-cycles: every other new stream is
                # refused. Refusing ALL streams would starve the SLO
                # signal (refused streams never observe a frame) and
                # deadlock the ladder at its top rung; half keeps burn
                # flowing so the symmetric exit stays reachable.
                self._brownout_tick += 1
                if self._brownout_tick % 2:
                    return False
            self._active_streams += 1
        obs.INFLIGHT_STREAMS.inc()
        return True

    def _exit_stream(self) -> None:
        obs.INFLIGHT_STREAMS.dec()
        with self._streams_cond:
            self._active_streams -= 1
            self._streams_cond.notify_all()

    @property
    def active_streams(self) -> int:
        with self._streams_cond:
            return self._active_streams

    @property
    def is_draining(self) -> bool:
        with self._streams_cond:
            return self._draining

    def set_draining(self, draining: bool) -> None:
        """Rollout drain control: flip ONLY the draining flag. Unlike
        :meth:`drain` (the shutdown path), health stays SERVING -- the
        fleet front-end reads ``draining`` off the stats RPC and stops
        placing NEW streams here while in-flight streams finish normally
        (graceful drain, not failover), and ``set_draining(False)``
        reverses it (rollback / rejoin). New direct-dial streams are
        refused UNAVAILABLE meanwhile, exactly like a shutdown drain.
        A closed service cannot be un-drained."""
        draining = bool(draining)
        with self._streams_cond:
            if self._closed and not draining:
                return
            changed = self._draining != draining
            self._draining = draining
            self._streams_cond.notify_all()
        if changed:
            log.info(
                "replica %s: %s new streams (health stays up)",
                "draining" if draining else "un-draining",
                "refusing" if draining else "accepting",
            )

    def set_shadow(self, hook) -> None:
        """Install (or clear with ``None``) the rollout shadow tap: a
        callable receiving one :class:`~robotic_discovery_platform_tpu.
        serving.rollout.ShadowSample` per analyzed frame. The hook is
        invoked on the handler thread AFTER the response is computed and
        must never block (the rollout ShadowRunner's hook samples and
        ``put_nowait``s)."""
        self._shadow_hook = hook

    def _mirror_shadow(self, rgb, depth, k, mask,
                       res: _FrameResult) -> None:
        """One attribute read per frame when no tap is installed; with a
        tap, hand the frame's inputs + this generation's outputs to the
        rollout shadow. A hook failure never fails the frame."""
        hook = self._shadow_hook
        if hook is None:
            return
        try:
            hook(rollout_lib.ShadowSample(
                rgb=rgb, depth=depth, k=np.asarray(k),
                depth_scale=self.depth_scale, mask=mask,
                coverage=res.coverage, mean_curvature=res.mean_k,
                max_curvature=res.max_k, valid=res.valid,
                confidence_margin=res.confidence_margin,
                depth_valid_fraction=res.depth_valid_fraction,
            ))
        except Exception:  # noqa: BLE001 - shadow must not fail serving
            log.exception("shadow mirror hook failed; frame served "
                          "normally")

    def replica_stats(self) -> dict:
        """The lightweight per-replica stats payload the fleet front-end
        scrapes over gRPC (serving/fleet.add_replica_stats_to_server):
        in-flight streams + error-budget burn feed least-loaded placement
        and the FleetController's weighted ring; the rest is diagnostics
        a fleet dashboard wants next to them."""
        eng = self._engine
        router = eng.dispatcher.router if eng.dispatcher is not None else None
        # version + drift reference generation as ONE consistent pair
        # (read under the reload lock): a scrape racing a promotion sees
        # either the old pair or the new pair, never a mix
        version, drift_generation = self.version_and_reference()
        host, role = trace.identity()
        with self._streams_cond:
            model_frames = dict(self._model_frames)
        # per-model demand next to the aggregate: the capacity planner's
        # per-model rate inputs (ROADMAP) and the fleet dashboard's
        # multi-tenant view ride this block
        rates = self.placer.rates() if self.placer is not None else {}
        models = {
            name: {
                "frames": model_frames.get(name, 0),
                "rate": round(rates.get(name, 0.0), 3),
            }
            for name in self.zoo.names()
        }
        return {
            "inflight_streams": self.active_streams,
            "frames_total": self._frames_total,
            "models": models,
            "burn": self.slo.burn if self.slo is not None else 0.0,
            "slo_ms": self.cfg.slo_ms,
            "chips": self.serving_chips,
            "quarantined_chips": (len(router.quarantined)
                                  if router is not None else 0),
            "version": version,
            "drift_generation": drift_generation,
            "draining": self.is_draining,
            "refusing_streams": self._refusing_streams,
            "pid": os.getpid(),
            # observability-plane discovery: the fleet front-end scrapes
            # this replica's /metrics + /debug/spans for federation and
            # cross-host trace stitching at the advertised port (0 = no
            # metrics endpoint), attributing them to host/role identity
            "metrics_port": (self.metrics_server.port
                             if self.metrics_server is not None else 0),
            "host": host,
            "role": role,
        }

    def AnalyzeActuatorPerformance(self, request_iterator, context):
        if not self._enter_stream():
            context.abort(grpc.StatusCode.UNAVAILABLE,
                          "server is draining or in overload brownout; "
                          "retry against another replica")
        # Adopt the client's trace: the stream runs inside a span whose
        # trace ID came over the wire (traceparent metadata), so client-
        # and server-side log lines for the same stream carry the same
        # [trace=...] stamp. No metadata -> a fresh server-side trace.
        # (Setting the contextvar inside this generator deliberately leaks
        # to the handler thread between yields: gRPC drives one stream's
        # generator from one thread, and log lines emitted while it runs
        # should carry the stream's trace.)
        remote = trace.from_metadata(context.invocation_metadata())
        try:
            yield from self._stream_frames(request_iterator, context, remote)
        finally:
            self._exit_stream()

    def _stream_frames(self, request_iterator, context, remote):
        with trace.span("serving.stream", parent=remote):
            log.info(
                "analysis stream opened (%s trace)",
                "client" if remote is not None else "local",
            )
            # per-stream stage breakdown (decode / device / encode),
            # summarized at stream end so proc_time_ms has an explanation
            # in the logs -- and routed sample-by-sample into the
            # rdp_stage_latency_seconds histogram (ONE timing system: the
            # exported histogram and the log summary observe the same
            # measurements)
            def _observe_stage(stage: str, dt: float) -> None:
                # the host-split decode AND encode samples are observed by
                # the ingest/egress pools themselves (actual work wherever
                # it ran); the handler-side numbers here are just the WAIT
                # when a pool ran the stage off-thread
                obs.STAGE_LATENCY.labels(stage=stage).observe(dt)
                obs.STAGE_LATENCY_SUMMARY.labels(stage=stage).observe(dt)

            timer = StageTimer(observer=_observe_stage)
            # ingest iterator: cancellation + client-deadline checks, and
            # decode itself, live in serving/ingest.py now. With
            # decode_workers = 0 this is the historical inline
            # read-check-decode loop, bitwise; with workers it reads
            # ahead so frame k+1 decodes while frame k rides the device.
            frames = self.ingest.iter_decoded(
                request_iterator,
                active=context.is_active,
                time_remaining=context.time_remaining,
            )
            for inf in frames:
                remaining = inf.time_remaining
                t0 = time.perf_counter()
                label = self.model_label
                entry = None
                try:
                    # handler-side decode cost (inline: the decode itself;
                    # pooled: the wait, ~0 once read-ahead is primed)
                    timer.observe("decode", inf.wait_s)
                    if inf.error is not None:
                        raise inf.error
                    label, entry = self._resolve_model(inf.model)
                    res = self._analyze_frame(inf.rgb, inf.depth, timer,
                                              timeout_s=remaining,
                                              model=inf.model,
                                              mask_format=inf.mask_format,
                                              active=context.is_active)
                    status = ("OK" if res.valid
                              else "DEGRADED: insufficient geometry")
                    if res.anomaly is not None:
                        # the aux head's verdict rides the status text:
                        # wire-compatible (clients key on OK/DEGRADED/
                        # ERROR prefixes), and only ever present on
                        # frames that explicitly asked for this model
                        status += f" anomaly={res.anomaly:.4f}"
                    # packed wire formats carry the spline as
                    # packed_spline bytes and res.spline is empty (the
                    # per-point Point3D loop runs zero times); on the
                    # legacy path spline_wire is b"" and serializes to
                    # zero bytes -- pre-PR responses stay bitwise
                    response = vision_pb2.AnalysisResponse(
                        mean_curvature=res.mean_k,
                        max_curvature=res.max_k,
                        spline_points=[
                            vision_pb2.Point3D(x=float(p[0]), y=float(p[1]), z=float(p[2]))
                            for p in res.spline
                        ],
                        status=status,
                        mask=res.mask_png,
                        mask_coverage=res.coverage,
                        packed_spline=res.spline_wire,
                    )
                    self.metrics.append(res.mean_k, res.max_k, res.coverage)
                    self._observe_drift(res, entry)
                    status_label = "ok" if res.valid else "degraded"
                except zoo_lib.UnknownModelError as exc:
                    # a typo'd model name is a bad frame, not a dead
                    # stream: per-frame error, bounded metric
                    # cardinality (requested names never become labels)
                    label = "unknown"
                    response = vision_pb2.AnalysisResponse(
                        status=f"ERROR: UnknownModel: {exc} "
                               f"[trace={trace.current_trace_id() or '-'}]"
                    )
                    status_label = "error"
                except OverloadedError as exc:
                    # load shedding is a STREAM-level, retryable condition:
                    # surface the standard backpressure status instead of a
                    # per-frame error payload the client cannot distinguish
                    # from a bad frame. The trace ID rides the details so
                    # the client-side failure joins its /debug/spans
                    # timeline; a shed frame also burned SLO budget.
                    obs.FRAMES.labels(status="shed", model=label).inc()
                    if self.slo is not None:
                        self.slo.observe(float("inf"), ok=False)
                    mslo = self._model_slo.get(label)
                    if mslo is not None:
                        mslo.observe(float("inf"), ok=False)
                    context.abort(
                        grpc.StatusCode.RESOURCE_EXHAUSTED,
                        f"{exc} [trace={trace.current_trace_id() or '-'}]",
                    )
                except DeadlineExceeded as exc:
                    # per-submit deadline (client deadline or
                    # cfg.submit_deadline_s) ran out while the frame was
                    # queued/processing: report per-frame and keep the
                    # stream alive -- the handler thread is free again
                    log.warning("frame missed its deadline: %s", exc)
                    response = vision_pb2.AnalysisResponse(
                        status=f"ERROR: DeadlineExceeded: {exc} "
                               f"[trace={trace.current_trace_id() or '-'}]"
                    )
                    status_label = "deadline"
                except Exception as exc:  # keep the stream alive per frame
                    log.exception("analysis error")
                    # trace ID in the wire status AND a pinned recorder
                    # event: the client-side failure and the server-side
                    # /debug/spans evidence join on the same 32-hex ID
                    trace_id = trace.current_trace_id()
                    recorder_lib.RECORDER.record_event(
                        "serving.frame_error", trace_id=trace_id,
                        error=f"{type(exc).__name__}: {exc}",
                    )
                    response = vision_pb2.AnalysisResponse(
                        status=f"ERROR: {type(exc).__name__}: {exc} "
                               f"[trace={trace_id or '-'}]"
                    )
                    status_label = "error"
                total_s = time.perf_counter() - t0
                response.proc_time_ms = total_s * 1e3
                with self._streams_cond:
                    self._frames_total += 1
                    self._model_frames[label] = (
                        self._model_frames.get(label, 0) + 1)
                obs.FRAMES.labels(status=status_label, model=label).inc()
                obs.STAGE_LATENCY.labels(stage="total").observe(total_s)
                obs.STAGE_LATENCY_SUMMARY.labels(stage="total").observe(
                    total_s)
                obs.FRAME_LATENCY_SUMMARY.observe(total_s)
                frame_ok = status_label in ("ok", "degraded")
                if self.slo is not None:
                    self.slo.observe(total_s, ok=frame_ok)
                mslo = self._model_slo.get(label)
                if mslo is not None:
                    # per-model burn next to the aggregate: which tenant
                    # is burning its budget is the question multi-model
                    # dashboards (and the capacity planner) ask
                    mslo.observe(total_s, ok=frame_ok)
                yield response
            self.metrics.flush()
            if timer.totals:
                log.info("stream stage breakdown: %s", timer.summary())

    # -- hot-reload ---------------------------------------------------------

    def _resolve_version(self) -> int | None:
        """Registry resolution under the per-service circuit breaker.

        Closed: failures log a warning and count toward the threshold.
        Open: the poll is skipped entirely -- no network touch, no log
        line, serving keeps its current engine; the breaker logs the
        open/half-open/closed transitions exactly once each."""
        try:
            return self.registry_breaker.call(
                lambda: resolve_serving_version(
                    self.cfg, self._registry_store, raise_on_error=True
                )
            )
        except CircuitOpenError:
            return None
        except Exception as exc:
            log.warning(
                "registry %s unreachable/empty (%s: %s); serving keeps "
                "its current model (breaker: %d/%d failures)",
                self.cfg.tracking_uri, type(exc).__name__, exc,
                self.registry_breaker.failure_count,
                self.registry_breaker.failure_threshold,
            )
            return None

    def start_reloader(self) -> None:
        """Poll the registry every ``cfg.reload_poll_s`` seconds; when the
        staging alias (or latest version) moves, build + warm the new
        model OFF the serving path and atomically swap it in -- promotion
        takes effect on a RUNNING server, closing the reference's
        implicit-handoff gap (SURVEY.md section 3.4)."""
        if self.cfg.reload_poll_s <= 0 or self._reload_thread is not None:
            return
        self._reload_stop = threading.Event()

        def loop():
            while not self._reload_stop.wait(self.cfg.reload_poll_s):
                try:
                    self.maybe_reload()
                except Exception:
                    log.exception("model hot-reload failed; keeping current")

        self._reload_thread = threading.Thread(
            target=loop, name="model-reloader", daemon=True
        )
        self._reload_thread.start()

    def maybe_reload(self) -> bool:
        """One reload check; returns True when a new version was swapped in.

        The expensive phase (registry resolve, model load, engine build,
        XLA warm) runs OUTSIDE ``_reload_lock``, guarded by a busy flag so
        at most one reload is ever in flight; the lock is held only for the
        engine swap. close() and warmup() therefore block at most for a
        swap, never for a compile (review finding: a SIGTERM mid-reload
        must not stall shutdown for a full warm)."""
        with self._reload_lock:
            if self._closed or self._reload_busy:
                return False
            self._reload_busy = True
            current_version = self._engine.version
        engine = None
        try:
            version = self._resolve_version()
            if version is None or version == current_version:
                return False
            # scoped store: this runs on the poller thread (see
            # resolve_serving_version's docstring)
            model, variables = tracking.load_model(
                f"models:/{self.cfg.model_name}/{version}",
                store=self._registry_store,
            )
            engine = self._make_engine(model, variables, version)
            # the new generation's drift reference is RESOLVED here
            # (registry I/O, off-lock) but ADOPTED inside the swap's
            # critical section below: engine generation and drift
            # reference move atomically, so a concurrent scrape never
            # pairs new weights with the old reference
            drift_reference = (self._load_drift_profile(version)
                               if self.drift is not None else None)
            if self._closed:
                return False  # skip the warm entirely; finally cleans up
            # compile + run every graph live frames will hit, off the
            # serving path, so in-flight streams never pay the new
            # generation's XLA compilation -- including the dispatcher's
            # per-bucket batched graphs when micro-batching is on.
            # Snapshot-and-recheck: a concurrent warmup() can record a NEW
            # camera shape while we warm for the old one (or for none);
            # the swap below only proceeds once the engine is warm for the
            # shape that is current at swap time, else we re-warm.
            old = None
            warmed_shape = object()  # sentinel: warmed for nothing yet
            while True:
                shape = self._warm_shape
                if shape is not None and shape != warmed_shape:
                    self._warm_engine(engine, shape)
                warmed_shape = shape
                with self._reload_lock:
                    if self._closed:
                        return False  # never swap into a closed service
                    if (self._warm_shape is not None
                            and self._warm_shape != warmed_shape):
                        continue  # warmup() raced us; warm the new shape
                    old, self._engine = self._engine, engine
                    engine = None  # went live; finally must not stop it
                    # same critical section as the engine swap: the new
                    # generation's reference (artifact or re-baseline)
                    # goes live with its weights, never after them
                    self._apply_drift_reference(version, drift_reference)
                    if old.dispatcher is not None:
                        # Grace-delayed stop: a frame thread that read the
                        # OLD engine just before the swap may still be
                        # about to submit(); give in-flight frames ample
                        # time to finish on the old dispatcher before
                        # tearing it down (stop() itself is drain-safe, so
                        # a straggler past the grace window gets a
                        # per-frame error, not a hang -- and per-frame
                        # errors don't drop the stream).
                        t = threading.Timer(
                            self.cfg.reload_grace_s, old.dispatcher.stop
                        )
                        t.daemon = True
                        self._grace_stops = [
                            (tm, d) for tm, d in self._grace_stops
                            if tm.is_alive()
                        ]
                        self._grace_stops.append((t, old.dispatcher))
                        t.start()
                    break
            log.info("hot-reloaded model: version %s -> %s",
                     old.version, version)
            return True
        finally:
            # never went live (error, closed mid-build/-warm, or the swap
            # was refused): tear down its dispatcher (whose collector
            # thread started in _make_engine) so a repeatedly-failing
            # promotion can't leak one thread plus its compiled graphs per
            # poll tick
            if engine is not None and engine.dispatcher is not None:
                engine.dispatcher.stop()
            with self._reload_lock:
                self._reload_busy = False

    def _warm_engine(self, engine: Engine,
                     shape: tuple[int, int] | None = None) -> None:
        """Pre-compile the graphs live frames will actually dispatch to on
        ``engine``: the batched per-bucket graphs when it carries a
        dispatcher (the path every frame takes then), the single-frame
        analyze otherwise. ``shape`` pins the camera (w, h) explicitly
        (reload's snapshot-and-recheck needs that); defaults to the shape
        warmup() recorded, a no-op when there is none yet."""
        shape = shape if shape is not None else self._warm_shape
        if shape is None:
            return
        w, h = shape
        k = (self.intrinsics if self.intrinsics is not None
             else _default_intrinsics(w, h))
        if engine.dispatcher is None:
            engine.analyze(
                engine.variables,
                np.zeros((h, w, 3), np.uint8),
                np.zeros((h, w), np.uint16),
                np.asarray(k, np.float32),
                np.float32(self.depth_scale),
            )
            return
        # the dispatcher pads each dispatch to min(next_pow2(n), max_batch)
        # -- with a sharded router the floor rises to the chip count -- so
        # the reachable bucket sizes are bucket_for() over the powers of
        # two below max_batch plus max_batch itself (the top bucket even
        # when it is not a power of two). warm() compiles each bucket on
        # EVERY routed placement, so a load burst's first dispatch to any
        # chip is already compiled.
        dispatcher = engine.dispatcher
        sizes, b = set(), 1
        while b < self.cfg.max_batch:
            sizes.add(dispatcher.bucket_for(b))
            b *= 2
        sizes.add(dispatcher.bucket_for(self.cfg.max_batch))
        for b in sorted(sizes):
            dispatcher.warm(
                np.zeros((b, h, w, 3), np.uint8),
                np.zeros((b, h, w), np.uint16),
                np.repeat(np.asarray(k, np.float32)[None], b, 0),
                np.full((b,), self.depth_scale, np.float32),
            )

    def warmup(self, width: int, height: int) -> None:
        """Pre-compile the fused graph for a camera geometry so the first
        real frame does not pay XLA compilation. The synthetic warm frame
        pair is built (and image-roundtripped) once per (width, height)
        per process -- every later warmup()/hot-reload warm for the same
        camera reuses it instead of re-encoding identical bytes."""
        self._warm_shape = (width, height)
        color, depth = _warm_frames(width, height)
        # pre-compile every graph a load burst could hit (single-frame or
        # per-bucket batched -- shared with the hot-reload warm) BEFORE
        # exercising the real per-frame path: the exercise frame's
        # dispatch ride feeds the admission service-time estimate, and a
        # ride that pays XLA compilation would poison it (every early
        # deadline would look unmeetable). Under the reload lock:
        # otherwise a poll tick that read _warm_shape as None could swap
        # in a never-warmed engine while we warm the old one.
        with self._reload_lock:
            self._warm_engine(self._engine)
        self._analyze_frame(color, depth)
        # CAPPED zoo warm (lazy elsewhere): each extra model pre-compiles
        # zoo_eager_warm home placements for the single-frame bucket;
        # every other (model, chip, bucket) combo compiles on its first
        # dispatch -- an M-model zoo must not multiply startup by
        # M x chips x buckets
        self._warm_zoo(width, height)
        # bf16/int8 tiers must PROVE parity against the f32 goldens before
        # readiness ever flips -- a quantized engine that fails its gate
        # never serves a frame (per zoo model: each entry gates against
        # its OWN pristine f32 pair)
        self._parity_gate(width, height)
        if self.ingest.onchip:
            # on-chip split decode: every baseline JPEG this server
            # admits rides the coefficient lane, so readiness must also
            # imply THOSE graphs are compiled -- otherwise the first
            # live burst pays the fused decode+analyze compilation
            # inside its frame deadlines
            self.warmup_coef(width, height)
        # readiness flips ONLY here: a probe sees SERVING once the first
        # real frame path has compiled and run, never before
        self.mark_ready()
        log.info("warmed up %dx%d analyzer on %s", width, height,
                 jax.default_backend())

    def warmup_coef(self, width: int, height: int,
                    subsampling: str = "420") -> None:
        """Pre-compile the coefficient-lane (``format = 2``) graphs for a
        camera geometry: the direct single-frame decode+analyze when the
        server has no dispatcher, otherwise every reachable bucket via
        ``warm_coef`` (the same bucket sweep ``_warm_engine`` runs for
        the pixel lane). ``warmup()`` calls this automatically when the
        server itself runs with on-chip decode enabled; benches and
        deployments whose CLIENTS ship ``format = 2`` against a
        pixel-decode server call it explicitly before load arrives."""
        import cv2

        color, depth = _warm_frames(width, height)
        sf = {
            "444": cv2.IMWRITE_JPEG_SAMPLING_FACTOR_444,
            "420": cv2.IMWRITE_JPEG_SAMPLING_FACTOR_420,
            "422": cv2.IMWRITE_JPEG_SAMPLING_FACTOR_422,
        }[subsampling]
        ok, jpg = cv2.imencode(
            ".jpg", color[..., ::-1],
            [int(cv2.IMWRITE_JPEG_SAMPLING_FACTOR), int(sf)],
        )
        if not ok:
            raise ValueError("warm-up coefficient encode failed")
        cf = entropy_lib.parse_jpeg(jpg.tobytes())
        dispatcher = self._engine.dispatcher
        if dispatcher is None:
            # direct (unbatched) path: exercising one coefficient frame
            # memoizes its decode+analyze graph in _coef_direct
            self._analyze_frame(cf, depth)
            return
        k = np.asarray(
            self.intrinsics if self.intrinsics is not None
            else _default_intrinsics(width, height), np.float32,
        )
        sizes, b = set(), 1
        while b < self.cfg.max_batch:
            sizes.add(dispatcher.bucket_for(b))
            b *= 2
        sizes.add(dispatcher.bucket_for(self.cfg.max_batch))
        for b in sorted(sizes):
            dispatcher.warm_coef(
                cf,
                np.zeros((b, height, width), np.uint16),
                np.repeat(k[None], b, 0),
                np.full((b,), self.depth_scale, np.float32),
            )

    def _warm_zoo(self, width: int, height: int) -> None:
        """Capped eager warm for the non-default zoo entries."""
        if len(self.zoo) <= 1:
            return
        color, depth = _warm_frames(width, height)
        k = np.asarray(
            self.intrinsics if self.intrinsics is not None
            else _default_intrinsics(width, height), np.float32,
        )
        dispatcher = self._engine.dispatcher
        full = self.cfg.zoo_eager_warm < 0
        for entry in self.zoo.extras():
            if dispatcher is None:
                entry.analyze(
                    entry.variables, color, depth, k,
                    np.float32(self.depth_scale),
                )
                continue
            if full:
                # zoo_eager_warm < 0: the pre-zoo full eager warm per
                # model -- every reachable bucket on every placement
                # (benchmarks measuring steady-state multiplexing, and
                # deployments that prefer slow boots over first-burst
                # compile stalls)
                sizes, b = set(), 1
                while b < self.cfg.max_batch:
                    sizes.add(dispatcher.bucket_for(b))
                    b *= 2
                sizes.add(dispatcher.bucket_for(self.cfg.max_batch))
            else:
                sizes = {dispatcher.bucket_for(1)}
            home: list[int] | None = None
            if (not full and self.placer is not None
                    and self.serving_chips > 1):
                cap = max(1, int(self.cfg.zoo_eager_warm))
                home = list(self.placer.chips_for(entry.name)[:cap])
            for b in sorted(sizes):
                dispatcher.warm(
                    np.repeat(color[None], b, 0),
                    np.repeat(depth[None], b, 0),
                    np.repeat(k[None], b, 0),
                    np.full((b,), self.depth_scale, np.float32),
                    model=entry.name, chips=home,
                )

    def _parity_gate(self, width: int, height: int) -> dict | None:
        """Warm-up parity check for the reduced-precision tiers: run the
        golden synthetic frames through BOTH an f32 reference analyzer
        (built from each generation's pristine variables) and the live
        engine path (dispatcher when batching, single-frame analyze
        otherwise), publish the rdp_quant_parity_* gauges per zoo model,
        and refuse to come up when the thresholds are breached. No-op at
        f32. Every zoo entry gates against its OWN goldens -- one
        model's quantization error can never hide behind another's."""
        if self.precision == "f32":
            return None
        eng = self._engine
        report = self._parity_gate_for(
            self.model_label, self._pristine,
            got_path=(None if eng.dispatcher is not None else
                      (eng.analyze, eng.variables)),
            submit_model="", width=width, height=height,
        )
        self.parity = report
        for entry in self.zoo.extras():
            entry.parity = self._parity_gate_for(
                entry.name, entry.pristine,
                got_path=(None if eng.dispatcher is not None else
                          (entry.analyze, entry.variables)),
                submit_model=entry.name, width=width, height=height,
            )
        return report

    def _parity_gate_for(self, name: str, pristine, got_path,
                         submit_model: str, width: int,
                         height: int) -> dict:
        """One model's golden-frame parity gate (fail-closed)."""
        cfg = self.cfg
        ref_model, ref_variables = pristine
        ref_analyze = pipeline.make_frame_analyzer(
            ref_model, img_size=cfg.model_img_size, geom_cfg=self.geom_cfg
        )
        k = np.asarray(
            self.intrinsics if self.intrinsics is not None
            else _default_intrinsics(width, height), np.float32,
        )
        scale = np.float32(self.depth_scale)
        eng = self._engine
        refs, gots = [], []
        for rgb, depth in quant.golden_frames(
            cfg.quant_parity_frames, height, width
        ):
            refs.append(ref_analyze(ref_variables, rgb, depth, k, scale))
            if got_path is None:
                got = eng.dispatcher.submit(
                    rgb, depth, k, float(scale), model=submit_model)
                if isinstance(got, egress_lib.PackedResult):
                    # the packed serving path: reconstruct the
                    # FrameAnalysis view the parity report reads (mask +
                    # scalars are exact through the pack/unpack pair)
                    analysis = got.to_analysis()
                    got.release()
                    got = analysis
                gots.append(got)
            else:
                analyze, variables = got_path
                gots.append(analyze(variables, rgb, depth, k, scale))
        report = quant.parity_report(refs, gots)
        obs.QUANT_PARITY_IOU.labels(model=name).set(
            report["mask_iou_mean"])
        obs.QUANT_PARITY_CURV.labels(stat="mean", model=name).set(
            report["curvature_err_mean"])
        obs.QUANT_PARITY_CURV.labels(stat="max", model=name).set(
            report["curvature_err_max"])
        if not quant.parity_gates_pass(
            report, cfg.quant_parity_min_iou, cfg.quant_parity_max_curv_err
        ):
            raise RuntimeError(
                f"{self.precision} serving of model {name!r} failed its "
                f"parity gate vs the f32 goldens: mean IoU "
                f"{report['mask_iou_mean']:.4f} "
                f"(floor {cfg.quant_parity_min_iou}), max |d curvature| "
                f"{report['curvature_err_max']:.4f} (ceiling "
                f"{cfg.quant_parity_max_curv_err}) over "
                f"{report['frames']} frames"
            )
        log.info(
            "%s parity gate passed for %s: mean IoU %.4f, curvature err "
            "mean %.4g / max %.4g over %d goldens",
            self.precision, name, report["mask_iou_mean"],
            report["curvature_err_mean"], report["curvature_err_max"],
            report["frames"],
        )
        return report

    def mark_ready(self) -> None:
        self.health.set_all(health_lib.SERVING)
        journal_lib.JOURNAL.append(
            events.SERVER_READY, version=str(self.current_version))

    def drain(self, timeout_s: float | None = None) -> bool:
        """Begin graceful shutdown: flip readiness to NOT_SERVING, refuse
        new streams (UNAVAILABLE, so clients fail over), and wait up to
        ``timeout_s`` (default ``cfg.drain_grace_s``) for in-flight streams
        to finish. Returns True when the server drained fully. Idempotent;
        close() calls it first."""
        timeout_s = self.cfg.drain_grace_s if timeout_s is None else timeout_s
        with self._streams_cond:
            already = self._draining
            self._draining = True
        if not already:
            self.health.set_all(health_lib.NOT_SERVING)
            journal_lib.JOURNAL.append(
                events.SERVER_DRAIN, streams=str(self.active_streams))
            # graceful departure beats lease expiry: tell every registrar
            # NOW so front-ends mark this member draining (left) instead
            # of waiting a TTL to quarantine it as failed
            if self.lease_client is not None:
                self.lease_client.leave()
            log.info("draining: readiness down, waiting for %d in-flight "
                     "stream(s)", self.active_streams)
        deadline = time.monotonic() + timeout_s
        with self._streams_cond:
            while self._active_streams > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    log.warning(
                        "drain grace (%.1fs) expired with %d stream(s) "
                        "still in flight", timeout_s, self._active_streams,
                    )
                    return False
                self._streams_cond.wait(remaining)
        return True

    def close(self) -> None:
        # readiness down + bounded wait for in-flight streams BEFORE
        # tearing down the engines they are using
        self.drain()
        # flag first: an in-flight reload re-checks it before swapping, so
        # a generation built after this point never goes live
        self._closed = True
        if self.lease_client is not None:
            self.lease_client.stop()
            self.lease_client = None
        if self.controller is not None:
            self.controller.stop()
        if self._reload_stop is not None:
            self._reload_stop.set()
        if self._reload_thread is not None:
            self._reload_thread.join(timeout=5)
            self._reload_thread = None
        # flush pending grace-delayed teardowns NOW: cancel each timer and
        # stop its dispatcher immediately (stop() is drain-safe and
        # idempotent, so racing an already-fired timer is harmless) --
        # otherwise a close() shortly after a reload would leave a live
        # timer firing against torn-down state. An in-flight reload is NOT
        # waited for: its swap re-checks _closed under this same lock, so
        # any swap serialized after this drain is refused and the reload's
        # finally-block stops the never-live dispatcher itself.
        with self._reload_lock:
            pending, self._grace_stops = self._grace_stops, []
            engine = self._engine
        for timer, dispatcher in pending:
            timer.cancel()
            dispatcher.stop()
        if engine.dispatcher is not None:
            engine.dispatcher.stop()
        self.ingest.stop()
        self.egress.stop()
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None
        self.metrics.close()


def build_server(
    cfg: ServerConfig = ServerConfig(),
    geom_cfg: GeometryConfig | None = None,
    warmup_shape: tuple[int, int] | None = None,
) -> tuple[grpc.Server, VisionAnalysisService]:
    """Load every resource and return an unstarted (server, servicer).
    Aborts (raises) when the model or calibration is unusable, mirroring the
    reference's fail-fast startup (server.py:168-170).

    ``geom_cfg`` defaults to the serving geometry profile
    (``stride=cfg.geometry_stride``); pass an explicit GeometryConfig to
    override (e.g. stride=1 for reference-exact dense semantics)."""
    platforms.enable_compile_cache()
    if geom_cfg is None:
        geom_cfg = GeometryConfig(stride=cfg.geometry_stride)
    # this process serves frames: spans and journal events it records are
    # attributed to the replica role in merged multi-process output (the
    # front-end's stitched /debug/trace and federated journal reads)
    trace.set_identity(role="replica")
    model, variables, version = resolve_serving_model(cfg)
    intrinsics = None
    depth_scale = cfg.default_depth_scale
    try:
        intrinsics, _, scale = load_calibration(cfg.calibration_path)
        if scale is not None:
            depth_scale = scale
        log.info("calibration loaded from %s", cfg.calibration_path)
    except (FileNotFoundError, KeyError) as exc:
        log.warning(
            "no calibration at %s (%s); using focal-length defaults",
            cfg.calibration_path, exc,
        )
    servicer = VisionAnalysisService(
        model, variables, intrinsics, depth_scale, cfg, geom_cfg,
        version=version,
    )
    # /metrics rides the servicer lifecycle: up before the first frame,
    # down in servicer.close() (cfg.metrics_port / RDP_METRICS_PORT;
    # off by default)
    servicer.metrics_server = exposition.maybe_start_metrics_server(
        cfg.metrics_port
    )
    if servicer.metrics_server is not None:
        # /debug/drift serves the monitor's live state (histograms,
        # scores, recommendation ladder) next to /debug/spans
        servicer.metrics_server.set_drift_provider(servicer.drift_debug)
        # /debug/zoo: roster, per-model versions/frames, live placement
        # + rate correlations, and the (model, placement, bucket) warm set
        servicer.metrics_server.set_zoo_provider(servicer.zoo_debug)
        # /debug/rollout resolves the manager per request, so attaching
        # one after boot (rollout_lib.attach_rollout) makes the endpoint
        # live without re-wiring
        servicer.metrics_server.set_rollout_provider(
            lambda: (servicer.rollout.snapshot()
                     if servicer.rollout is not None
                     else {"enabled": False,
                           "reason": "no rollout manager attached "
                                     "(RolloutConfig.enabled / "
                                     "RDP_ROLLOUT)"})
        )
    if warmup_shape is not None:
        servicer.warmup(*warmup_shape)  # flips readiness at the end
    else:
        # no warm-up requested: the model is loaded and the engine built,
        # which is as warm as this deployment gets -- readiness up now
        servicer.mark_ready()
    servicer.start_reloader()
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=cfg.max_workers))
    vision_grpc.add_VisionAnalysisServiceServicer_to_server(servicer, server)
    # standard grpc.health.v1 surface: `grpc_health_probe -addr=...` and
    # Kubernetes native gRPC probes work against this port unmodified
    health_lib.add_HealthServicer_to_server(servicer.health, server)
    # replica stats next to health: the fleet front-end scrapes in-flight
    # streams + error-budget burn here to place streams (serving/fleet.py).
    # Drain rides the same surface so the autoscaler can retire this
    # member remotely through the exact PR 13 set_draining path.
    fleet_lib.add_replica_stats_to_server(
        server, servicer.replica_stats, drain=servicer.set_draining)
    port = server.add_insecure_port(cfg.address)
    # the OS-assigned port when cfg.address asked for :0 -- replica.py's
    # worker main reports THIS port instead of binding a second one, so
    # the advertised lease endpoint and the parent's handle always agree
    servicer.bound_port = port
    # elastic membership: when registrars are configured
    # (cfg.fleet_registrars / RDP_FLEET_REGISTRARS) this replica announces
    # itself and renews its lease; a replica respawned on a NEW port
    # rejoins the fleet with zero config edits because the advertised
    # endpoint defaults to the port the OS just bound
    registrars = fleet_lib.resolve_fleet_registrars(cfg.fleet_registrars)
    if registrars:
        advertise = fleet_lib.resolve_fleet_advertise(
            cfg.fleet_advertise, default=f"localhost:{port}")
        servicer.lease_client = fleet_lib.LeaseClient(
            registrars,
            endpoint=advertise,
            metrics_port=(servicer.metrics_server.port
                          if servicer.metrics_server is not None else 0),
            version=str(servicer.current_version),
            ttl_s=cfg.fleet_lease_ttl_s,
        )
        servicer.lease_client.start()
        log.info("fleet lease: advertising %s to %s (ttl %.1fs)",
                 advertise, ",".join(registrars), cfg.fleet_lease_ttl_s)
    return server, servicer


def serve(cfg: ServerConfig = ServerConfig(), warmup_shape=(640, 480)) -> None:
    server, servicer = build_server(cfg, warmup_shape=warmup_shape)
    server.start()
    log.info("vision analysis server listening on %s", cfg.address)
    try:
        server.wait_for_termination()
    except KeyboardInterrupt:
        log.info("interrupt: beginning graceful shutdown")
    finally:
        # readiness down first so load balancers stop routing here, then a
        # bounded drain of in-flight streams, then the hard stop
        servicer.drain()
        server.stop(grace=cfg.drain_grace_s).wait()
        servicer.close()


if __name__ == "__main__":
    from robotic_discovery_platform_tpu.utils.config import parse_config

    serve(parse_config().server)
