"""Resilience layer: deterministic unit tests (fake clock, zero real
sleeps) for RetryPolicy / Deadline / CircuitBreaker / fault registry, plus
chaos tests that drive the LIVE gRPC server and the REST tracking store
through RDP_FAULTS-style injection at real call sites (no monkeypatching):

- a transient registry flake (2 injected ConnectionErrors) recovers on the
  3rd attempt inside one hot-reload poll, without dropping a served frame;
- a sustained registry outage opens the circuit breaker, the poller stops
  touching the network, and the server keeps answering
  AnalyzeActuatorPerformance from its current engine;
- an overloaded batch dispatcher sheds load with RESOURCE_EXHAUSTED;
- a cancelled stream frees its handler thread (active-stream gauge -> 0);
- a collector thread killed outside _run_group's guard error-completes its
  pending submitters (no hang) and is restarted by the watchdog.
"""

import random
import threading
import time

import grpc
import numpy as np
import pytest

from robotic_discovery_platform_tpu import tracking
from robotic_discovery_platform_tpu.io.frames import SyntheticSource
from robotic_discovery_platform_tpu.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    Deadline,
    DeadlineExceeded,
    InjectedHTTPError,
    RetryPolicy,
    configure_faults,
    default_retryable,
    fired,
)
from robotic_discovery_platform_tpu.resilience.faults import FaultRegistry
from robotic_discovery_platform_tpu.serving import client as client_lib
from robotic_discovery_platform_tpu.serving import health as health_lib
from robotic_discovery_platform_tpu.serving import server as server_lib
from robotic_discovery_platform_tpu.serving.batching import (
    BatchDispatcher,
    OverloadedError,
)
from robotic_discovery_platform_tpu.tracking.rest_backend import (
    FAULT_SITE,
    MlflowRestError,
    RestMlflowStore,
)
from robotic_discovery_platform_tpu.utils.config import (
    ClientConfig,
    ModelConfig,
    ServerConfig,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    """No fault spec may leak across tests."""
    yield
    configure_faults(None)


class FakeClock:
    """Injectable clock + sleep: time only moves when told to."""

    def __init__(self, start: float = 0.0):
        self.now = start
        self.sleeps: list[float] = []

    def __call__(self) -> float:
        return self.now

    def sleep(self, s: float) -> None:
        self.sleeps.append(s)
        self.now += s

    def advance(self, s: float) -> None:
        self.now += s


def _policy(clk: FakeClock, **kw) -> RetryPolicy:
    kw.setdefault("jitter", 0.0)
    return RetryPolicy(clock=clk, sleep=clk.sleep,
                       rng=random.Random(0), **kw)


# ---------------------------------------------------------------------------
# Deadline
# ---------------------------------------------------------------------------


def test_deadline_fake_clock():
    clk = FakeClock()
    d = Deadline.after(5.0, clock=clk)
    assert d.remaining() == pytest.approx(5.0)
    assert not d.expired()
    clk.advance(4.0)
    assert d.remaining() == pytest.approx(1.0)
    d.check("resolve")  # within budget: no raise
    clk.advance(2.0)
    assert d.expired()
    assert d.remaining() == 0.0
    with pytest.raises(DeadlineExceeded, match="resolve"):
        d.check("resolve")


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------


def test_retry_recovers_after_transient_failures():
    clk = FakeClock()
    p = _policy(clk, max_attempts=4, base_delay_s=0.1, multiplier=2.0)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise ConnectionError("transient")
        return "ok"

    assert p.call(flaky) == "ok"
    assert calls["n"] == 3
    # exponential schedule, entirely on the fake clock
    assert clk.sleeps == pytest.approx([0.1, 0.2])


def test_retry_non_retryable_raises_immediately():
    clk = FakeClock()
    p = _policy(clk, max_attempts=5)
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise ValueError("deterministic bug")

    with pytest.raises(ValueError):
        p.call(broken)
    assert calls["n"] == 1 and clk.sleeps == []


def test_retry_exhausts_attempts_and_raises_underlying_error():
    clk = FakeClock()
    p = _policy(clk, max_attempts=3, base_delay_s=0.1)
    calls = {"n": 0}

    def always_down():
        calls["n"] += 1
        raise ConnectionError("still down")

    with pytest.raises(ConnectionError, match="still down"):
        p.call(always_down)
    assert calls["n"] == 3
    assert len(clk.sleeps) == 2


def test_retry_respects_deadline_budget():
    """A retry whose backoff would overshoot the deadline re-raises instead
    of sleeping into a guaranteed timeout."""
    clk = FakeClock()
    p = _policy(clk, max_attempts=10, base_delay_s=1.0)
    deadline = Deadline.after(0.5, clock=clk)
    calls = {"n": 0}

    def always_down():
        calls["n"] += 1
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        p.call(always_down, deadline=deadline)
    assert calls["n"] == 1 and clk.sleeps == []


def test_retry_jitter_is_seeded_and_bounded():
    import itertools

    def schedule(seed):
        p = RetryPolicy(base_delay_s=1.0, multiplier=2.0, max_delay_s=8.0,
                        jitter=0.25, rng=random.Random(seed))
        return list(itertools.islice(p.delays(), 5))

    assert schedule(42) == schedule(42)  # same seed -> same schedule
    for ideal, got in zip([1.0, 2.0, 4.0, 8.0, 8.0], schedule(42)):
        assert ideal * 0.75 <= got <= ideal * 1.25


def test_default_retryable_classification():
    import requests

    assert default_retryable(ConnectionError())
    assert default_retryable(TimeoutError())
    assert default_retryable(requests.exceptions.ConnectionError())
    assert default_retryable(requests.exceptions.Timeout())
    assert default_retryable(MlflowRestError(500, "INTERNAL_ERROR", "x"))
    assert default_retryable(MlflowRestError(503, "TEMPORARILY_UNAVAILABLE", "x"))
    assert default_retryable(MlflowRestError(429, "REQUEST_LIMIT_EXCEEDED", "x"))
    assert default_retryable(InjectedHTTPError("site", 500))
    assert not default_retryable(MlflowRestError(404, "RESOURCE_DOES_NOT_EXIST", "x"))
    assert not default_retryable(MlflowRestError(400, "INVALID_PARAMETER_VALUE", "x"))
    assert not default_retryable(ValueError("bug"))
    assert not default_retryable(DeadlineExceeded("budget blown"))


# ---------------------------------------------------------------------------
# CircuitBreaker
# ---------------------------------------------------------------------------


def test_breaker_opens_after_threshold_and_fast_fails():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=3, reset_timeout_s=30.0,
                       clock=clk, name="t")
    calls = {"n": 0}

    def down():
        calls["n"] += 1
        raise ConnectionError("down")

    for _ in range(3):
        with pytest.raises(ConnectionError):
            b.call(down)
    assert b.state == "open"
    # open: the dependency is NOT touched
    with pytest.raises(CircuitOpenError):
        b.call(down)
    assert calls["n"] == 3
    assert b.retry_in_s() == pytest.approx(30.0)


def test_breaker_half_open_probe_closes_on_success():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout_s=10.0, clock=clk)
    with pytest.raises(ConnectionError):
        b.call(lambda: (_ for _ in ()).throw(ConnectionError()))
    assert b.state == "open"
    clk.advance(10.0)
    assert b.state == "half_open"
    assert b.call(lambda: "ok") == "ok"
    assert b.state == "closed"
    assert b.failure_count == 0


def test_breaker_half_open_probe_failure_reopens():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout_s=10.0, clock=clk)
    b.record_failure(ConnectionError("first"))
    assert b.state == "open"
    clk.advance(10.0)
    with pytest.raises(ConnectionError):
        b.call(lambda: (_ for _ in ()).throw(ConnectionError("probe")))
    assert b.state == "open"
    # a fresh full reset window applies
    clk.advance(9.9)
    assert b.state == "open"
    clk.advance(0.2)
    assert b.state == "half_open"


def test_breaker_half_open_admits_single_probe():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout_s=1.0, clock=clk)
    b.record_failure()
    clk.advance(1.0)
    assert b.allow()  # the probe slot
    assert not b.allow()  # concurrent caller while the probe is in flight
    b.record_success()
    assert b.allow()


# ---------------------------------------------------------------------------
# Fault registry
# ---------------------------------------------------------------------------


def test_fault_spec_parsing_counts_and_exhaustion():
    reg = FaultRegistry("a.b:conn:2, c.d:exc:1")
    for _ in range(2):
        with pytest.raises(ConnectionError):
            reg.inject("a.b")
    reg.inject("a.b")  # exhausted: no-op
    assert reg.fired("a.b") == 2
    with pytest.raises(RuntimeError, match="injected fault"):
        reg.inject("c.d")
    reg.inject("unknown.site")  # unconfigured site: no-op
    assert reg.fired("unknown.site") == 0


def test_fault_unlimited_and_http_kinds():
    reg = FaultRegistry("s:http500:-1")
    for _ in range(5):
        with pytest.raises(InjectedHTTPError) as exc_info:
            reg.inject("s")
        assert exc_info.value.status == 500
    assert reg.fired("s") == 5
    reg.configure("s:http429:1")
    with pytest.raises(InjectedHTTPError) as exc_info:
        reg.inject("s")
    assert exc_info.value.status == 429
    assert reg.fired("s") == 1  # configure() reset the counters


def test_fault_bad_specs_rejected():
    with pytest.raises(ValueError, match="site:kind:count"):
        FaultRegistry("missing-colons")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultRegistry("a:frobnicate:1")


# ---------------------------------------------------------------------------
# BatchDispatcher: bounded queue, submit deadline, collector watchdog
# ---------------------------------------------------------------------------

_FRAME = np.zeros((8, 8, 3), np.uint8)
_DEPTH = np.zeros((8, 8), np.uint16)
_K = np.eye(3, dtype=np.float32)


def _blocking_analyze(release: threading.Event, entered=None):
    def analyze(frames, depths, intr, scales):
        if entered is not None:
            entered.set()
        release.wait(30.0)
        return {"coverage": np.full((len(frames),), 1.0)}

    return analyze


def test_dispatcher_sheds_load_at_backlog_cap():
    release, entered = threading.Event(), threading.Event()
    d = BatchDispatcher(_blocking_analyze(release, entered), window_ms=1.0,
                        max_batch=1, max_backlog=1, submit_timeout_s=30.0)
    try:
        threads = []
        outcomes = []

        def bg_submit():
            try:
                outcomes.append(d.submit(_FRAME, _DEPTH, _K, 0.001))
            except BaseException as exc:
                outcomes.append(exc)

        # first frame: picked up by the collector, blocks in analyze
        threads.append(threading.Thread(target=bg_submit))
        threads[0].start()
        # the collector must have popped it first: an empty queue alone
        # says nothing while the thread has yet to put its frame there
        assert entered.wait(10)
        # second frame: queued (backlog 1 == cap reached)
        threads.append(threading.Thread(target=bg_submit))
        threads[1].start()
        deadline = time.monotonic() + 10
        while d._q.qsize() < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        # third frame: shed synchronously
        with pytest.raises(OverloadedError, match="shedding load"):
            d.submit(_FRAME, _DEPTH, _K, 0.001)
        release.set()
        for t in threads:
            t.join(timeout=30)
        assert all(not isinstance(o, BaseException) for o in outcomes)
    finally:
        release.set()
        d.stop()


def test_dispatcher_submit_deadline_frees_caller():
    release = threading.Event()
    d = BatchDispatcher(_blocking_analyze(release), window_ms=1.0,
                        max_batch=1, submit_timeout_s=30.0)
    try:
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded, match="per-submit deadline"):
            d.submit(_FRAME, _DEPTH, _K, 0.001, timeout_s=0.2)
        assert time.monotonic() - t0 < 10.0  # freed by the deadline, fast
    finally:
        release.set()
        d.stop()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_collector_death_fails_pending_and_watchdog_restarts():
    """Satellite regression: the collector dying OUTSIDE _run_group's guard
    used to strand every submitter on done.wait() forever. Now the watchdog
    error-completes them and restarts the collector."""
    calls = {"n": 0}

    def analyze(frames, depths, intr, scales):
        calls["n"] += 1
        return {"coverage": np.full((len(frames),), 7.0)}

    # the fault fires in _loop between _collect() and the dispatch guard --
    # exactly the uncovered window
    configure_faults("serving.batch.collect:exc:1")
    d = BatchDispatcher(analyze, window_ms=1.0, max_batch=4,
                        watchdog_interval_s=0.05)
    try:
        with pytest.raises(RuntimeError, match="collector died"):
            d.submit(_FRAME, _DEPTH, _K, 0.001, timeout_s=30.0)
        # restarted collector serves the next submit normally
        deadline = time.monotonic() + 10
        while d.collector_restarts == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert d.collector_restarts == 1
        out = d.submit(_FRAME, _DEPTH, _K, 0.001, timeout_s=30.0)
        assert float(out["coverage"]) == 7.0
        assert calls["n"] == 1
    finally:
        d.stop()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_dispatcher_without_watchdog_still_bounded():
    """Even with the watchdog disabled, a dead collector cannot hang a
    submitter past its deadline."""
    configure_faults("serving.batch.collect:exc:1")
    d = BatchDispatcher(lambda *a: None, window_ms=1.0,
                        watchdog_interval_s=0.0)
    try:
        with pytest.raises(DeadlineExceeded):
            d.submit(_FRAME, _DEPTH, _K, 0.001, timeout_s=0.2)
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# REST tracking store chaos (FakeMlflowServer over a real socket)
# ---------------------------------------------------------------------------


def _rest_store(uri: str, clk: FakeClock, attempts: int = 3) -> RestMlflowStore:
    return RestMlflowStore(
        uri,
        retry=RetryPolicy(max_attempts=attempts, base_delay_s=0.1,
                          jitter=0.0, clock=clk, sleep=clk.sleep),
    )


def test_rest_store_retries_transient_connection_faults():
    from fake_mlflow_server import FakeMlflowServer

    clk = FakeClock()
    with FakeMlflowServer() as uri:
        store = _rest_store(uri, clk)
        configure_faults(f"{FAULT_SITE}:conn:2")
        # one logical call; the 2 injected failures retry internally and
        # the 3rd attempt lands on the real socket
        exp_id = store.get_or_create_experiment("chaos")
        assert exp_id
        assert fired(FAULT_SITE) == 2
        assert clk.sleeps == pytest.approx([0.1, 0.2])  # no real sleeps
        store.close()


def test_rest_store_retries_injected_http_500():
    from fake_mlflow_server import FakeMlflowServer

    clk = FakeClock()
    with FakeMlflowServer() as uri:
        store = _rest_store(uri, clk)
        configure_faults(f"{FAULT_SITE}:http500:1")
        assert store.get_or_create_experiment("chaos-500")
        assert fired(FAULT_SITE) == 1
        store.close()


def test_rest_store_surfaces_sustained_outage():
    from fake_mlflow_server import FakeMlflowServer

    clk = FakeClock()
    with FakeMlflowServer() as uri:
        store = _rest_store(uri, clk, attempts=3)
        configure_faults(f"{FAULT_SITE}:conn:-1")
        with pytest.raises(ConnectionError):
            store.get_or_create_experiment("chaos-down")
        assert fired(FAULT_SITE) == 3  # every attempt consumed a fault
        store.close()


# ---------------------------------------------------------------------------
# Live gRPC server chaos
# ---------------------------------------------------------------------------


def _register_model(seed: int = 0, name: str = "Actuator-Segmenter") -> int:
    """Log + alias a tiny model through the CURRENT tracking URI."""
    import jax

    from robotic_discovery_platform_tpu.models.unet import build_unet, init_unet

    mcfg = ModelConfig(base_features=8, compute_dtype="float32")
    model = build_unet(mcfg)
    variables = init_unet(model, jax.random.key(seed), img_size=64)
    tracking.set_experiment("Actuator Segmentation")
    with tracking.start_run():
        version = tracking.log_model(variables, mcfg,
                                     registered_model_name=name)
    tracking.Client().set_registered_model_alias(name, "staging", version)
    return version


@pytest.fixture()
def rest_registry(monkeypatch):
    """A REST-backed registry (fake MLflow server over a real socket) with
    one model version; the store's HTTP retry layer is configured for zero
    real backoff so chaos runs stay fast."""
    from fake_mlflow_server import FakeMlflowServer

    monkeypatch.setenv("RDP_HTTP_RETRIES", "3")
    monkeypatch.setenv("RDP_HTTP_BACKOFF_S", "0")
    prev_uri = tracking.get_tracking_uri()
    with FakeMlflowServer() as http_uri:
        uri = f"mlflow-rest+{http_uri}"
        tracking.set_tracking_uri(uri)
        v1 = _register_model(seed=0)
        yield uri, v1
        tracking.set_tracking_uri(prev_uri)


def _build_server(uri: str, tmp_path, **overrides):
    cfg = ServerConfig(
        address="localhost:0",
        tracking_uri=uri,
        model_img_size=64,
        metrics_csv=str(tmp_path / "metrics.csv"),
        calibration_path=str(tmp_path / "missing.npz"),
        reload_poll_s=0.0,  # maybe_reload() is driven directly
        **overrides,
    )
    server, servicer = server_lib.build_server(cfg)
    port = server.add_insecure_port("localhost:0")
    server.start()
    return server, servicer, f"localhost:{port}"


def test_hot_reload_recovers_through_registry_flake(rest_registry, tmp_path):
    """Acceptance: with RDP_FAULTS injecting 2 consecutive ConnectionErrors,
    a hot-reload poll recovers on the 3rd attempt -- and the stream served
    across the poll never drops a frame."""
    uri, v1 = rest_registry
    server, servicer, address = _build_server(uri, tmp_path)
    try:
        assert servicer.current_version == v1
        v2 = _register_model(seed=1)
        assert v2 > v1
        configure_faults("tracking.rest.request:conn:2")

        results = {}

        def stream():
            results["frames"] = client_lib.run_client(
                ClientConfig(server_address=address,
                             calibration_path="none.npz"),
                source=SyntheticSource(width=64, height=64, n_frames=6),
                max_frames=6,
            )

        t = threading.Thread(target=stream)
        t.start()
        # the poll happens while the stream is live
        assert servicer.maybe_reload()
        t.join(timeout=120)
        assert fired("tracking.rest.request") == 2  # recovered on attempt 3
        assert servicer.current_version == v2
        assert servicer.registry_breaker.state == "closed"
        # no dropped/errored frame around the reload
        assert len(results["frames"]) == 6
        assert all(not r.status.startswith("ERROR")
                   for r in results["frames"])
    finally:
        server.stop(grace=None)
        servicer.close()


def test_breaker_opens_on_sustained_outage_and_serving_continues(
        rest_registry, tmp_path, monkeypatch):
    """Acceptance: under a forced sustained registry outage the breaker
    opens (polls stop touching the network) and the server keeps answering
    AnalyzeActuatorPerformance from its current engine."""
    monkeypatch.setenv("RDP_HTTP_RETRIES", "1")  # 1 fault == 1 resolve
    uri, v1 = rest_registry
    server, servicer, address = _build_server(
        uri, tmp_path,
        registry_breaker_failures=2, registry_breaker_reset_s=300.0,
    )
    try:
        configure_faults("tracking.rest.request:conn:-1")
        assert not servicer.maybe_reload()
        assert servicer.registry_breaker.state == "closed"
        assert not servicer.maybe_reload()
        assert servicer.registry_breaker.state == "open"
        touched = fired("tracking.rest.request")
        # open breaker: further polls never reach the transport
        for _ in range(3):
            assert not servicer.maybe_reload()
        assert fired("tracking.rest.request") == touched
        # ... and serving is unaffected: the current engine answers
        frames = client_lib.run_client(
            ClientConfig(server_address=address,
                         calibration_path="none.npz"),
            source=SyntheticSource(width=64, height=64, n_frames=3),
            max_frames=3,
        )
        assert len(frames) == 3
        assert all(not r.status.startswith("ERROR") for r in frames)
        assert servicer.current_version == v1
    finally:
        server.stop(grace=None)
        servicer.close()


# ---------------------------------------------------------------------------
# Health / readiness, drain, cancellation, load shedding (file registry)
# ---------------------------------------------------------------------------


@pytest.fixture()
def file_registry(tmp_path):
    prev_uri = tracking.get_tracking_uri()
    uri = f"file:{tmp_path}/mlruns"
    tracking.set_tracking_uri(uri)
    _register_model(seed=0)
    yield uri
    tracking.set_tracking_uri(prev_uri)


def test_health_servicer_unit():
    h = health_lib.HealthServicer()
    assert h.get("") == health_lib.NOT_SERVING
    h.set("svc", health_lib.NOT_SERVING)
    h.set_all(health_lib.SERVING)
    assert h.get("") == health_lib.SERVING
    assert h.get("svc") == health_lib.SERVING
    assert h.get("never-registered") is None


def test_health_endpoint_and_drain_flip(file_registry, tmp_path):
    server, servicer, address = _build_server(file_registry, tmp_path)
    channel = grpc.insecure_channel(address)
    try:
        stub = health_lib.HealthStub(channel)
        pb = health_lib.health_pb2
        # ready after build (model loaded; no warm-up shape was requested)
        assert stub.Check(pb.HealthCheckRequest()).status == health_lib.SERVING
        assert stub.Check(
            pb.HealthCheckRequest(service=server_lib.vision_grpc.SERVICE_NAME)
        ).status == health_lib.SERVING
        with pytest.raises(grpc.RpcError) as exc_info:
            stub.Check(pb.HealthCheckRequest(service="no.such.Service"))
        assert exc_info.value.code() == grpc.StatusCode.NOT_FOUND
        # drain: readiness down, new streams refused with UNAVAILABLE
        assert servicer.drain(timeout_s=5.0)
        assert stub.Check(pb.HealthCheckRequest()).status == (
            health_lib.NOT_SERVING)
        with pytest.raises(grpc.RpcError) as exc_info:
            client_lib.run_client(
                ClientConfig(server_address=address,
                             calibration_path="none.npz"),
                source=SyntheticSource(width=64, height=64, n_frames=1),
                max_frames=1,
                retry=RetryPolicy(max_attempts=1),
            )
        assert exc_info.value.code() == grpc.StatusCode.UNAVAILABLE
    finally:
        channel.close()
        server.stop(grace=None)
        servicer.close()


def test_readiness_flips_only_after_warmup(file_registry, tmp_path):
    """build_server with a warm-up shape: NOT_SERVING until the warm
    completes (probes must not route traffic to a cold, still-compiling
    server)."""
    cfg = ServerConfig(
        address="localhost:0",
        tracking_uri=file_registry,
        model_img_size=64,
        metrics_csv=str(tmp_path / "metrics.csv"),
        calibration_path=str(tmp_path / "missing.npz"),
        reload_poll_s=0.0,
    )
    model, variables, version = server_lib.resolve_serving_model(cfg)
    servicer = server_lib.VisionAnalysisService(
        model, variables, None, 0.001, cfg, version=version,
    )
    try:
        assert servicer.health.get("") == health_lib.NOT_SERVING
        servicer.warmup(64, 64)
        assert servicer.health.get("") == health_lib.SERVING
    finally:
        servicer.close()


def test_cancelled_stream_frees_handler_thread(file_registry, tmp_path):
    import queue as queue_lib

    server, servicer, address = _build_server(file_registry, tmp_path)
    channel = grpc.insecure_channel(address)
    try:
        from robotic_discovery_platform_tpu.serving.proto import vision_grpc

        stub = vision_grpc.VisionAnalysisServiceStub(channel)
        src = SyntheticSource(width=64, height=64, n_frames=1)
        src.start()
        color, depth = src.get_frames()
        req = client_lib.encode_request(color, depth)
        q: queue_lib.Queue = queue_lib.Queue()

        def requests():
            while True:
                item = q.get()
                if item is None:
                    return
                yield item

        call = stub.AnalyzeActuatorPerformance(requests())
        q.put(req)
        next(call)  # one response: the stream is live server-side
        assert servicer.active_streams == 1
        call.cancel()
        deadline = time.monotonic() + 30
        while servicer.active_streams > 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert servicer.active_streams == 0  # handler thread freed
        q.put(None)
    finally:
        channel.close()
        server.stop(grace=None)
        servicer.close()


def test_overloaded_dispatcher_sheds_with_resource_exhausted(
        file_registry, tmp_path):
    """Acceptance: an overloaded dispatcher surfaces standard gRPC
    backpressure (RESOURCE_EXHAUSTED), not a hang and not an opaque
    per-frame error. max_backlog=0 makes every submit an overload, so the
    very first frame proves the full client-visible path."""
    server, servicer, address = _build_server(
        file_registry, tmp_path, batch_window_ms=5.0, max_backlog=0,
    )
    try:
        assert servicer.dispatcher is not None
        with pytest.raises(grpc.RpcError) as exc_info:
            client_lib.run_client(
                ClientConfig(server_address=address,
                             calibration_path="none.npz"),
                source=SyntheticSource(width=64, height=64, n_frames=2),
                max_frames=2,
            )
        assert exc_info.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
    finally:
        server.stop(grace=None)
        servicer.close()


def test_client_stream_setup_retries_through_fault(file_registry, tmp_path):
    """serving/client.py rides the shared RetryPolicy for stream setup: an
    injected connection fault on the first attempt is retried and the
    re-opened stream completes normally."""
    server, servicer, address = _build_server(file_registry, tmp_path)
    try:
        configure_faults("client.stream:conn:1")
        frames = client_lib.run_client(
            ClientConfig(server_address=address,
                         calibration_path="none.npz"),
            source=SyntheticSource(width=64, height=64, n_frames=3),
            max_frames=3,
            retry=RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0),
        )
        assert fired("client.stream") == 1
        assert len(frames) == 3
        assert all(not r.status.startswith("ERROR") for r in frames)
    finally:
        server.stop(grace=None)
        servicer.close()


def test_forced_resolve_outage_degrades_gracefully(file_registry, tmp_path):
    """The CI fault-matrix scenario, in-process: with the resolve site
    forced down, build_server still comes up (latest-version fallback) and
    serves frames; the breaker records the failing polls."""
    configure_faults("serving.resolve:exc:-1")
    server, servicer, address = _build_server(file_registry, tmp_path)
    try:
        assert servicer.current_version is None  # fallback path loaded latest
        assert not servicer.maybe_reload()
        frames = client_lib.run_client(
            ClientConfig(server_address=address,
                         calibration_path="none.npz"),
            source=SyntheticSource(width=64, height=64, n_frames=2),
            max_frames=2,
        )
        assert len(frames) == 2
        assert all(not r.status.startswith("ERROR") for r in frames)
    finally:
        server.stop(grace=None)
        servicer.close()
