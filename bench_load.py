"""Open-loop tail-latency harness for the live gRPC analysis server.

``bench.py`` measures CLOSED-loop throughput: every stream waits for its
previous frame before sending the next, so the server never sees more
load than it can absorb and queueing delay is invisible. Production
serving is judged the other way around -- requests arrive whether or not
the server is keeping up (InferLine's SLO-driven planning and Clockwork's
predictable-tail argument, PAPERS.md) -- so this harness generates
**open-loop** arrivals (Poisson, or a replayed inter-arrival trace)
against the live server and reports what the tail actually looks like:

- p50 / p95 / p99 / p99.9 latency per offered-load level, measured from
  each request's *scheduled* arrival time (queueing delay counts;
  no coordinated omission);
- SLO violation rate against ``--slo-ms`` (errors and sheds count as
  violations -- a failed frame never met its objective);
- goodput (ok responses/sec) vs offered load.

Results go to ``LOADBENCH.json`` (one row per offered-load level) and ONE
JSON summary line on stdout. A harness failure is a traceback and a
non-zero exit code; failed REQUESTS are data (counted as errors and SLO
violations in the rows).

Processes and devices: ``--smoke`` pins this process to the CPU by argument
(``boot_smoke_server`` / ``run_fleet_mode`` call ``force_cpu_platform``) and
``--fleet`` children are CPU replicas (``serving/replica.spawn_local_
replicas``); ``--server`` is a pure gRPC client that never touches JAX, so
it can sit beside the one server process that holds the chip.

Overload-control comparison (PR 7): ``--controller {off,on,both}`` runs
the same offered-load ladder against a server with the overload control
plane off (FIFO admission, no reactive controller -- the PR 2 behavior)
and/or on (deadline-aware admission + the serving/controller.py reactive
tuner), tagging every LOADBENCH.json row with its leg. Loads may be
given relative to measured capacity (``--loads 0.75x,1.75x``: a short
closed-loop burst measures capacity first), which is how the policy is
validated open-loop at a known overload factor instead of by closed-loop
FPS. ``--deadline-ms`` puts a real per-request gRPC deadline on every
arrival (default 2x the SLO) so deadline-aware shedding has deadlines to
work with; ``--chips N`` boots the smoke server over N faked CPU mesh
chips, which is how CI's quarantine leg drives ``serving.chip.<i>.
dispatch`` faults through failover.

Usage:
    python bench_load.py --smoke                # self-hosted CPU server
    python bench_load.py --server host:50051 --loads 50,100,200
    python bench_load.py --smoke --trace gaps.json   # replay (ms gaps)
    python bench_load.py --smoke --controller both --loads 0.75x,1.75x

``--smoke`` boots an in-process CPU server (tiny model, 64x64 frames,
micro-batching on so the flight recorder and the ``serving.batch.*``
fault sites are exercised) and is what CI's ``load-smoke`` and
``overload-smoke`` jobs run -- including under fault injection, where
injected failures must surface as counted violations, never a crash.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

#: (percentile, row key) for every reported quantile
PERCENTILES = ((50, "p50_ms"), (95, "p95_ms"), (99, "p99_ms"),
               (99.9, "p999_ms"))

def _emit_result(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


# -- arrival processes -------------------------------------------------------


def poisson_arrivals(rate_hz: float, duration_s: float,
                     rng: np.random.Generator) -> list[float]:
    """Arrival offsets (seconds from window start) of a Poisson process:
    exponential inter-arrival gaps at ``rate_hz``."""
    out: list[float] = []
    t = float(rng.exponential(1.0 / rate_hz))
    while t < duration_s:
        out.append(t)
        t += float(rng.exponential(1.0 / rate_hz))
    return out


def trace_arrivals(path: str) -> list[float]:
    """Replayed arrivals from a recorded trace: either a bare JSON
    array of inter-arrival gaps in MILLISECONDS (the shape a production
    access log reduces to) or the object form
    ``{"gaps_ms": [...], "models": [...]}`` that
    ``tools/journal_to_trace.py`` writes and the fleet simulator
    (``sim/workload.py``) replays -- one trace file drives both the
    live bench and the sim. The open-loop bench is single-model, so
    per-arrival model labels are ignored here."""
    gaps_ms = json.loads(Path(path).read_text())
    if isinstance(gaps_ms, dict):
        gaps_ms = gaps_ms.get("gaps_ms")
    if not isinstance(gaps_ms, list) or not gaps_ms:
        raise ValueError(f"{path}: expected a non-empty JSON array of "
                         "inter-arrival milliseconds (bare or under "
                         "'gaps_ms')")
    out, t = [], 0.0
    for g in gaps_ms:
        t += float(g) / 1e3
        out.append(t)
    return out


# -- measurement -------------------------------------------------------------


def parse_loads(spec: str) -> list[tuple[float, bool]]:
    """Offered-load entries: plain frames/sec, or capacity multiples
    suffixed ``x`` (``1.5x`` = 1.5 times the measured closed-loop
    capacity). Returns (value, is_multiplier) pairs."""
    out = []
    for token in spec.split(","):
        token = token.strip()
        if not token:
            continue
        if token.lower().endswith("x"):
            out.append((float(token[:-1]), True))
        else:
            out.append((float(token), False))
    if not out:
        raise ValueError(f"no loads in {spec!r}")
    return out


def measure_capacity(stub, request, seconds: float = 2.0,
                     streams: int = 4) -> float:
    """Closed-loop capacity estimate: ``streams`` workers each fire
    one-frame requests back-to-back for ``seconds``; capacity is the
    aggregate completed ok/sec. Used to anchor ``Nx`` offered loads at a
    known overload factor."""
    stop_t = time.perf_counter() + seconds
    counts = [0] * streams

    def worker(i: int) -> None:
        while time.perf_counter() < stop_t:
            try:
                for resp in stub.AnalyzeActuatorPerformance(iter([request])):
                    if not resp.status.startswith("ERROR"):
                        counts[i] += 1
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(streams)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return sum(counts) / wall if wall > 0 else 0.0


def summarize_level(lat_ms: list[float], errors: int, offered_rps: float,
                    wall_s: float, slo_ms: float | None) -> dict:
    """One LOADBENCH.json row: tail percentiles + violation rate +
    goodput for one offered-load level."""
    arr = np.asarray(sorted(lat_ms), dtype=float)
    n_total = int(arr.size) + errors
    row = {
        "offered_rps": round(offered_rps, 3),
        "arrivals": n_total,
        "n": int(arr.size),
        "errors": errors,
        "achieved_rps": round(n_total / wall_s, 3) if wall_s > 0 else 0.0,
        "goodput_rps": round(arr.size / wall_s, 3) if wall_s > 0 else 0.0,
        "wall_s": round(wall_s, 3),
    }
    for pct, key in PERCENTILES:
        row[key] = (round(float(np.percentile(arr, pct)), 3)
                    if arr.size else None)
    if slo_ms is not None:
        violations = int(np.count_nonzero(arr > slo_ms)) + errors
        row["slo_ms"] = slo_ms
        row["violations"] = violations
        row["violation_rate"] = (round(violations / n_total, 4)
                                 if n_total else 0.0)
    return row


def run_level(stub, request, arrivals: list[float], workers: int,
              deadline_s: float | None = None
              ) -> tuple[list[float], int, float]:
    """Fire one offered-load level: every arrival opens a one-frame
    stream at its scheduled time (late workers start late and the delay
    COUNTS -- latency is measured from the scheduled arrival, the
    open-loop discipline that makes queueing visible). ``deadline_s``
    puts a real gRPC deadline on each request, so server-side
    deadline-aware shedding sees the budget the client actually has."""
    lat_ms: list[float] = []
    errors = 0
    lock = threading.Lock()
    t0 = time.perf_counter()

    def one(offset_s: float) -> None:
        nonlocal errors
        target = t0 + offset_s
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        ok = False
        try:
            status = None
            for resp in stub.AnalyzeActuatorPerformance(
                    iter([request]), timeout=deadline_s):
                status = resp.status
            ok = status is not None and not status.startswith("ERROR")
        except Exception:
            ok = False
        done = time.perf_counter()
        with lock:
            if ok:
                lat_ms.append((done - target) * 1e3)
            else:
                errors += 1

    with ThreadPoolExecutor(max_workers=workers) as pool:
        for offset in arrivals:
            pool.submit(one, offset)
    wall = time.perf_counter() - t0
    return lat_ms, errors, wall


# -- fleet mode --------------------------------------------------------------


def _one_shot(stub, request, deadline_s=None) -> bool:
    """One single-frame stream; True when it completed OK."""
    try:
        status = None
        for resp in stub.AnalyzeActuatorPerformance(iter([request]),
                                                    timeout=deadline_s):
            status = resp.status
        return status is not None and not status.startswith("ERROR")
    except Exception:
        return False


def _warm_fleet(stub, request, fe, endpoints, tries: int = 40) -> int:
    """Warm EVERY live replica through the front-end (each pays its own
    XLA compile on its first frame): fire concurrent single-frame streams
    until each placeable replica has served at least one, counting (not
    failing on) errors -- an armed one-shot RDP_FAULTS on one replica is
    absorbed here, exactly like the single-server warm phase."""
    errors = 0
    want = set(endpoints)
    for _ in range(tries):
        served = {r.endpoint for r in fe.router.replicas
                  if r.endpoint in want and r.frames > 0}
        live = {r.endpoint for r in fe.router.replicas
                if r.endpoint in want and r.placeable}
        if live and live <= served:
            break
        with ThreadPoolExecutor(max_workers=2 * len(want)) as pool:
            results = list(pool.map(
                lambda _: _one_shot(stub, request),
                range(2 * len(want)),
            ))
        errors += sum(1 for ok in results if not ok)
    return errors


def run_fleet_mode(cli, slo_ms: float, deadline_s: float | None,
                   load_spec, duration: float, frame_wh) -> None:
    """The ``--fleet N`` legs: N replica subprocesses (each a full
    serving/server.py process on faked CPU devices, sharing one tiny
    registry) behind the in-process fleet front-end.

    Three legs, identical Poisson arrivals (same seed) so goodput is
    comparable: ``1-replica`` (front-end over one replica -- the
    scaling/parity anchor), ``N-replica`` (the whole fleet), and
    ``replica-kill`` (one replica SIGKILLed mid-level: every accepted
    frame must still terminate, the victim must drop out of placement
    via grpc.health.v1, and -- once respawned on its old port -- rejoin
    through the half-open probe). Rows land in LOADBENCH.json tagged
    ``fleet_leg`` under the usual one-JSON-line contract."""
    from robotic_discovery_platform_tpu.utils.platforms import (
        enable_compile_cache,
        force_cpu_platform,
    )

    force_cpu_platform(min_devices=1)
    enable_compile_cache()

    import grpc

    from robotic_discovery_platform_tpu.io.frames import SyntheticSource
    from robotic_discovery_platform_tpu.serving import (
        client as client_lib,
        frontend as frontend_lib,
        replica as replica_lib,
    )
    from robotic_discovery_platform_tpu.serving.proto import vision_grpc
    from robotic_discovery_platform_tpu.utils.config import ServerConfig

    n = cli.fleet
    w, h = frame_wh
    loads = [v for v, mult in load_spec if not mult] or [10.0]
    if len(loads) != len(load_spec):
        raise ValueError("--fleet legs need absolute loads (no 'Nx' "
                         "capacity multiples)")

    tmp = Path(tempfile.mkdtemp(prefix="rdp-fleet-bench-"))
    uri = replica_lib.register_tiny_model(tmp / "mlruns", img_size=w)
    per_env = {}
    if cli.fleet_fault:
        # arm the fault on replica 0 ONLY: the point of the fleet fault
        # leg is one degraded member inside a healthy fleet
        per_env[0] = {"RDP_FAULTS": cli.fleet_fault}
    replicas = replica_lib.spawn_local_replicas(
        n, uri, img_size=w, slo_ms=slo_ms, per_replica_env=per_env,
        force_cpu=1,  # CPU replicas by argument: one process per chip
        metrics_port=-1,  # ephemeral /metrics: the federation scrape
                          # target for the obs-overhead legs
    )
    endpoints = [r.endpoint for r in replicas]
    replica_lib.wait_serving(endpoints)

    source = SyntheticSource(width=w, height=h, seed=cli.seed, n_frames=1)
    source.start()
    color, depth = source.get_frames()
    source.stop()
    request = client_lib.encode_request(color, depth)

    legs = [("1-replica", endpoints[:1], False),
            (f"{n}-replica", endpoints, False),
            ("replica-kill", endpoints, True)]
    rows: list[dict] = []
    leg_summaries: dict[str, dict] = {}
    warm_errors = 0
    kill_report: dict = {}
    try:
        for leg_name, eps, kill in legs:
            fcfg = ServerConfig(
                address="localhost:0",
                fleet_replicas=",".join(eps),
                fleet_poll_s=0.15,
                fleet_probe_timeout_s=1.0,
                fleet_breaker_failures=1,
                fleet_breaker_reset_s=1.0,
            )
            f_server, fe = frontend_lib.build_frontend(fcfg)
            fport = f_server.add_insecure_port("localhost:0")
            f_server.start()
            channel = grpc.insecure_channel(f"localhost:{fport}")
            stub = vision_grpc.VisionAnalysisServiceStub(channel)
            try:
                if not fe.router.wait_live(len(eps), timeout_s=60):
                    raise RuntimeError(
                        f"leg {leg_name}: only {fe.router.live_count} of "
                        f"{len(eps)} replicas became placeable")
                warm_errors += _warm_fleet(stub, request, fe, eps)
                # identical arrival schedule per leg: fresh rng, same seed
                rng = np.random.default_rng(cli.seed)
                leg_rows = []
                pinned: dict[str, int] = {"sent": 0, "responses": 0,
                                          "errors": 0,
                                          "stream_failures": 0}
                pinned_lock = threading.Lock()

                def pinned_stream():
                    """One long-lived stream held OPEN across the kill:
                    with one of these per replica (ring walk spreads
                    them), the victim always has a live stream whose
                    frames must fail over -- deterministic failover
                    evidence at any offered load."""
                    def gen():
                        end = time.monotonic() + duration + 1.0
                        while time.monotonic() < end:
                            with pinned_lock:
                                pinned["sent"] += 1
                            yield request
                            time.sleep(0.15)

                    try:
                        for resp in stub.AnalyzeActuatorPerformance(
                                gen(), timeout=duration + 30):
                            with pinned_lock:
                                pinned["responses"] += 1
                                if resp.status.startswith("ERROR"):
                                    pinned["errors"] += 1
                    except Exception:
                        with pinned_lock:
                            pinned["stream_failures"] += 1

                for rate in loads:
                    arrivals = poisson_arrivals(rate, duration, rng)
                    if not arrivals:
                        continue
                    dropout_seen = threading.Event()
                    victim = replicas[-1]
                    pinned_threads: list[threading.Thread] = []
                    if kill:
                        for _ in eps:
                            t = threading.Thread(target=pinned_stream,
                                                 daemon=True)
                            t.start()
                            pinned_threads.append(t)
                        time.sleep(0.3)  # both streams placed pre-kill

                        def do_kill(victim=victim, fe=fe):
                            victim.kill()
                            deadline = time.monotonic() + 5.0
                            while time.monotonic() < deadline:
                                if fe.router.live_count < len(eps):
                                    dropout_seen.set()
                                    return
                                time.sleep(0.05)

                        killer = threading.Timer(0.45 * duration, do_kill)
                        killer.daemon = True
                        killer.start()
                    lat_ms, errors, wall = run_level(
                        stub, request, arrivals, cli.workers, deadline_s)
                    row = summarize_level(lat_ms, errors, rate, wall,
                                          slo_ms)
                    row["fleet_leg"] = leg_name
                    row["replicas"] = len(eps)
                    leg_rows.append(row)
                    print(f"# fleet leg={leg_name} offered={rate:.1f}rps "
                          f"n={len(lat_ms)} errors={errors} "
                          f"p99={row['p99_ms']}", file=sys.stderr)
                    if kill:
                        killer.join(timeout=duration)
                        for t in pinned_threads:
                            t.join(timeout=duration + 60)
                rows.extend(leg_rows)
                top = leg_rows[-1] if leg_rows else {}
                leg_summaries[leg_name] = {
                    "offered_rps": top.get("offered_rps"),
                    "arrivals": top.get("arrivals"),
                    "n": top.get("n"),
                    "errors": top.get("errors"),
                    "goodput_rps": top.get("goodput_rps"),
                    "p99_ms": top.get("p99_ms"),
                    "violation_rate": top.get("violation_rate"),
                    "balance": [r.frames for r in fe.router.replicas],
                }
                if kill:
                    kill_report = {
                        "dropped_out": dropout_seen.is_set(),
                        "pinned": dict(pinned),
                        "failovers": fe.router.failovers_total,
                        "failover_frames_rerouted":
                            fe.router.failover_frames_rerouted,
                        "failover_frames_error_completed":
                            fe.router.failover_frames_error_completed,
                        "rejoined": False,
                    }
                    # respawn the victim on its old port: the static
                    # endpoint list has not changed, so health-gated
                    # rejoin through the half-open probe is the whole
                    # recovery story
                    fresh = replica_lib.respawn_replica(replicas[-1])
                    replicas[-1] = fresh
                    replica_lib.wait_serving([fresh.endpoint])
                    kill_report["rejoined"] = fe.router.wait_live(
                        len(eps), timeout_s=30)
            finally:
                channel.close()
                f_server.stop(grace=None)
                fe.close()

        # -- observability overhead: federation + journal on vs off ------
        # Identical arrivals against the full fleet twice: once with the
        # observability plane quiet (journal disabled, no federated
        # scraping) and once with it fully hot (journal on, the
        # federator's cache poll running AND a scraper rendering
        # /federate every 250 ms -- the realistic Prometheus load). The
        # p99 delta is what the plane costs on the hot path; CI gates it
        # to a small bound.
        from robotic_discovery_platform_tpu.observability import (
            journal as journal_lib,
        )

        obs_rows: dict[str, dict] = {}
        federate_renders = 0
        for leg_name, plane_on in (("obs-off", False), ("obs-on", True)):
            fcfg = ServerConfig(
                address="localhost:0",
                fleet_replicas=",".join(endpoints),
                fleet_poll_s=0.15,
                fleet_probe_timeout_s=1.0,
                fleet_breaker_failures=1,
                fleet_breaker_reset_s=1.0,
            )
            f_server, fe = frontend_lib.build_frontend(fcfg)
            fport = f_server.add_insecure_port("localhost:0")
            f_server.start()
            channel = grpc.insecure_channel(f"localhost:{fport}")
            stub = vision_grpc.VisionAnalysisServiceStub(channel)
            journal_lib.JOURNAL.set_enabled(plane_on)
            scraper_stop = threading.Event()
            scraper = None
            if plane_on:
                fe.federator.start()  # the last-good cache poll

                def scrape_loop(fed=fe.federator):
                    while not scraper_stop.wait(0.25):
                        try:
                            fed.render()
                        except Exception:  # noqa: BLE001 - keep scraping
                            pass

                scraper = threading.Thread(target=scrape_loop,
                                           daemon=True)
                scraper.start()
            try:
                if not fe.router.wait_live(n, timeout_s=60):
                    raise RuntimeError(
                        f"leg {leg_name}: fleet never became placeable")
                warm_errors += _warm_fleet(stub, request, fe, endpoints)
                rng = np.random.default_rng(cli.seed)
                arrivals = poisson_arrivals(loads[-1], duration, rng)
                lat_ms, errors, wall = run_level(
                    stub, request, arrivals, cli.workers, deadline_s)
                row = summarize_level(lat_ms, errors, loads[-1], wall,
                                      slo_ms)
                row["fleet_leg"] = leg_name
                row["replicas"] = n
                rows.append(row)
                obs_rows[leg_name] = row
                if plane_on:
                    federate_renders = fe.federator.renders
                print(f"# fleet leg={leg_name} offered={loads[-1]:.1f}rps "
                      f"n={len(lat_ms)} errors={errors} "
                      f"p99={row['p99_ms']}", file=sys.stderr)
            finally:
                scraper_stop.set()
                if scraper is not None:
                    scraper.join(timeout=5)
                journal_lib.JOURNAL.set_enabled(True)
                channel.close()
                f_server.stop(grace=None)
                fe.close()
    finally:
        replica_lib.stop_replicas(replicas)

    p99_off = obs_rows.get("obs-off", {}).get("p99_ms")
    p99_on = obs_rows.get("obs-on", {}).get("p99_ms")
    p50_off = obs_rows.get("obs-off", {}).get("p50_ms")
    p50_on = obs_rows.get("obs-on", {}).get("p50_ms")
    obs_overhead = {
        "p99_off_ms": p99_off,
        "p99_on_ms": p99_on,
        "delta_ms": (round(p99_on - p99_off, 3)
                     if p99_on is not None and p99_off is not None
                     else None),
        "p50_off_ms": p50_off,
        "p50_on_ms": p50_on,
        "p50_delta_ms": (round(p50_on - p50_off, 3)
                         if p50_on is not None and p50_off is not None
                         else None),
        "federate_renders": federate_renders,
    }

    one = leg_summaries.get("1-replica", {})
    full = leg_summaries.get(f"{n}-replica", {})
    fleet_block = {
        "replicas": n,
        "legs": leg_summaries,
        "kill": kill_report,
        "scaling_vs_1": (round(full["goodput_rps"] / one["goodput_rps"],
                               3)
                         if one.get("goodput_rps") else None),
        "fault": cli.fleet_fault or None,
        "obs_overhead": obs_overhead,
    }

    payload = {
        "metric": "open_loop_tail_latency",
        "backend": "cpu",
        "unit": "ms",
        "arrivals": "poisson",
        "smoke": True,
        "slo_ms": slo_ms,
        "deadline_ms": (deadline_s * 1e3 if deadline_s else 0.0),
        "workers": cli.workers,
        "frame": [w, h],
        "fleet": fleet_block,
        "rows": rows,
    }
    Path(cli.out).write_text(json.dumps(payload, indent=2) + "\n")

    top = rows[-1] if rows else {}
    p99 = top.get("p99_ms")
    _emit_result({
        "metric": "open_loop_tail_latency",
        "backend": "cpu",
        "value": p99 if p99 is not None and math.isfinite(p99) else 0.0,
        "unit": "ms",
        "offered_rps": top.get("offered_rps", 0.0),
        "goodput_rps": top.get("goodput_rps", 0.0),
        "violation_rate": top.get("violation_rate", 0.0),
        "errors": warm_errors + sum(r["errors"] for r in rows),
        "warm_errors": warm_errors,
        "levels": len(rows),
        "fleet": fleet_block,
        "out": cli.out,
        "smoke": True,
    })


# -- host-path profile -------------------------------------------------------

#: the rdp_host_stage_split_seconds stages, handler order ("entropy" is
#: the split-decode host half, observed alongside "decode" for
#: format=coef frames -- NOT added into host_us, that would double-count)
HOST_SPLIT_STAGES = ("decode", "entropy", "admit", "stage_host", "h2d",
                     "launch", "device", "d2h", "encode")
#: the "host-side per-frame microseconds" headline: decode work + pooled
#: staging + the explicit H2D enqueue (what the ingest overhaul attacks)
HOST_US_STAGES = ("decode", "stage_host", "h2d")


def _host_snapshot() -> dict[str, tuple[float, int]]:
    """(sum_seconds, count) per tracked family/stage, read straight from
    the in-process REGISTRY (the smoke server shares our process, so no
    scrape parse); host_profile_delta diffs two of these."""
    from robotic_discovery_platform_tpu.observability import (
        instruments as obs,
    )

    snap: dict[str, tuple[float, int]] = {}
    for stage in HOST_SPLIT_STAGES:
        child = obs.HOST_STAGE_SPLIT.labels(stage=stage)
        snap[f"split.{stage}"] = (child.sum, child.count)
    for stage in ("decode", "device", "encode", "total"):
        child = obs.STAGE_LATENCY.labels(stage=stage)
        snap[f"stage.{stage}"] = (child.sum, child.count)
    return snap


def host_profile_delta(before: dict, after: dict) -> dict:
    """One measured window's per-frame microsecond split: every stage's
    (sum delta) / (frames delta), so per-dispatch and per-frame
    observations normalize identically."""
    frames = after["stage.total"][1] - before["stage.total"][1]
    per_us = {}
    for key in after:
        ds = after[key][0] - before[key][0]
        per_us[key] = round(1e6 * ds / frames, 2) if frames else 0.0
    split_us = {s: per_us[f"split.{s}"] for s in HOST_SPLIT_STAGES}
    handler_us = {s: per_us[f"stage.{s}"]
                  for s in ("decode", "device", "encode")}
    total_us = per_us["stage.total"]
    return {
        "frames": int(frames),
        "split_us": split_us,
        "handler_us": handler_us,
        "total_us": total_us,
        # the CI sanity gate: the handler-side stages are a partition of
        # the per-frame total (response assembly is the remainder)
        "handler_sum_us": round(sum(handler_us.values()), 2),
        "host_us": round(sum(split_us[s] for s in HOST_US_STAGES), 2),
    }


def run_host_profile(cli, slo_ms: float, deadline_s: float | None,
                     load_spec, duration: float, frame_wh) -> None:
    """``--host-profile``: the ingest overhaul's before/after proof.

    Three legs at the SAME offered load (same Poisson seed): ``before``
    = the pre-overhaul host path (inline decode in the handler thread,
    JPEG/PNG wire payloads), ``after`` = the overhauled path (decode
    worker pool + raw-format zero-copy payloads), and ``coef`` = the
    split-decode wire (format=2 coefficient payloads; the host's whole
    color decode is frombuffer views, dequant+IDCT+upsample+convert run
    on-device ahead of the analyzer). Each leg's per-frame microseconds
    are split into decode / entropy / admit / stage-host / H2D / launch
    / device / D2H / encode by diffing the in-process
    ``rdp_host_stage_split_seconds`` and ``rdp_stage_latency_seconds``
    families around the measured window, and all splits land in
    LOADBENCH.json rows tagged ``host_leg`` together with each leg's
    ``wire_bytes_per_frame``. The headlines: the before->after reduction
    in host-side microseconds (decode + staging) and the before->coef
    reduction in host-side DECODE microseconds (the JPEG-wire leg's
    imdecode cost vs the coefficient leg's byte routing).

    Four more legs profile the EGRESS overhaul on the raw ingest wire:
    ``egress_before`` (device pack stage off, inline PNG encode),
    ``egress_png`` / ``egress_bits`` / ``egress_rle`` (packed D2H, the
    encode pool, response mask_format 0/1/2). The headline is the
    before->bits reduction in per-frame D2H + encode microseconds plus
    the per-format response mask payload bytes; both land under
    ``host_profile.egress`` for the CI egress-smoke gate."""
    import grpc

    from robotic_discovery_platform_tpu.io.frames import SyntheticSource
    from robotic_discovery_platform_tpu.serving import client as client_lib
    from robotic_discovery_platform_tpu.serving.proto import vision_grpc

    w, h = frame_wh
    abs_loads = [v for v, mult in load_spec if not mult]
    rate = abs_loads[0] if abs_loads else 15.0
    after_workers = (cli.decode_workers if cli.decode_workers
                     else 4)
    legs = (("before", 0, "encoded"),
            ("after", after_workers, "raw"),
            ("coef", after_workers, "coef"))
    rows: list[dict] = []
    profiles: dict[str, dict] = {}
    wire_bytes: dict[str, int] = {}
    warm_errors = 0
    source = SyntheticSource(width=w, height=h, seed=cli.seed, n_frames=1)
    source.start()
    color, depth = source.get_frames()
    source.stop()
    for name, workers, fmt in legs:
        server, servicer, address = boot_smoke_server(
            slo_ms, decode_workers=workers)
        channel = grpc.insecure_channel(address)
        stub = vision_grpc.VisionAnalysisServiceStub(channel)
        try:
            request = client_lib.encode_request(color, depth, fmt=fmt)
            for _ in range(3):
                try:
                    resps = list(
                        stub.AnalyzeActuatorPerformance(iter([request]))
                    )
                    if any(r.status.startswith("ERROR") for r in resps):
                        warm_errors += 1
                except Exception:
                    warm_errors += 1
            servicer.warmup(w, h)
            if fmt == "coef":
                # this leg's clients ship format=2 against a
                # pixel-decode server: warm the coefficient-lane
                # buckets too, or their first dispatches pay the fused
                # decode+analyze compilation inside the measured window
                servicer.warmup_coef(w, h)
            snap0 = _host_snapshot()
            arrivals = poisson_arrivals(
                rate, duration, np.random.default_rng(cli.seed))
            lat_ms, errors, wall = run_level(
                stub, request, arrivals, cli.workers, deadline_s)
            prof = host_profile_delta(snap0, _host_snapshot())
            row = summarize_level(lat_ms, errors, rate, wall, slo_ms)
            row["host_leg"] = name
            row["decode_workers"] = workers
            row["wire_format"] = fmt
            row["wire_bytes_per_frame"] = request.ByteSize()
            row["host_profile"] = prof
            rows.append(row)
            profiles[name] = prof
            wire_bytes[name] = request.ByteSize()
            print(f"# host leg={name} workers={workers} fmt={fmt} "
                  f"wire={request.ByteSize()}B "
                  f"host_us={prof['host_us']} split={prof['split_us']}",
                  file=sys.stderr)
        finally:
            channel.close()
            server.stop(grace=None)
            servicer.close()

    # -- egress legs (PR 20): the response-path mirror of the ingest
    # comparison, all on the raw ingest wire so decode cost is constant.
    # "egress_before" disables the device pack stage (the pre-pack
    # FrameAnalysis multi-leaf fetch + inline PNG encode); the packed
    # legs differ only in the response mask_format (0=PNG through the
    # encode pool, 1=bits, 2=RLE). The gated numbers: per-frame d2h +
    # encode microseconds (before vs bits) and the response mask payload
    # bytes per leg (PNG vs packed).
    egress_legs = (
        ("egress_before", {"egress_pack": False, "egress_workers": 0}, 0),
        ("egress_png", {"egress_workers": 4}, 0),
        ("egress_bits", {"egress_workers": 4}, 1),
        ("egress_rle", {"egress_workers": 4}, 2),
    )
    egress_profiles: dict[str, dict] = {}
    mask_bytes: dict[str, int] = {}
    for name, extra, mf in egress_legs:
        server, servicer, address = boot_smoke_server(
            slo_ms, decode_workers=after_workers, extra_cfg=extra)
        channel = grpc.insecure_channel(address)
        stub = vision_grpc.VisionAnalysisServiceStub(channel)
        try:
            request = client_lib.encode_request(
                color, depth, fmt="raw", mask_format=mf)
            for _ in range(3):
                try:
                    resps = list(
                        stub.AnalyzeActuatorPerformance(iter([request]))
                    )
                    if any(r.status.startswith("ERROR") for r in resps):
                        warm_errors += 1
                except Exception:
                    warm_errors += 1
            servicer.warmup(w, h)
            # one probe response records the leg's mask payload size
            # (identical frame on every leg, so the ratios are exact)
            probe = list(stub.AnalyzeActuatorPerformance(iter([request])))
            if probe and not probe[0].status.startswith("ERROR"):
                mask_bytes[name] = len(probe[0].mask)
            snap0 = _host_snapshot()
            arrivals = poisson_arrivals(
                rate, duration, np.random.default_rng(cli.seed))
            lat_ms, errors, wall = run_level(
                stub, request, arrivals, cli.workers, deadline_s)
            prof = host_profile_delta(snap0, _host_snapshot())
            row = summarize_level(lat_ms, errors, rate, wall, slo_ms)
            row["host_leg"] = name
            row["decode_workers"] = after_workers
            row["wire_format"] = "raw"
            row["mask_format"] = mf
            row["wire_bytes_per_frame"] = request.ByteSize()
            row["response_mask_bytes"] = mask_bytes.get(name, 0)
            row["host_profile"] = prof
            rows.append(row)
            egress_profiles[name] = prof
            print(f"# host leg={name} mask_format={mf} "
                  f"resp_mask={mask_bytes.get(name, 0)}B "
                  f"d2h_us={prof['split_us']['d2h']} "
                  f"encode_us={prof['split_us']['encode']}",
                  file=sys.stderr)
        finally:
            channel.close()
            server.stop(grace=None)
            servicer.close()

    before, after = profiles["before"], profiles["after"]
    coef = profiles.get("coef")
    reduction = (1.0 - after["host_us"] / before["host_us"]
                 if before["host_us"] > 0 else 0.0)
    host_block = {
        "offered_rps": rate,
        "frame": [w, h],
        "before": before,
        "after": after,
        "host_us_before": before["host_us"],
        "host_us_after": after["host_us"],
        "reduction_pct": round(100.0 * reduction, 1),
        "wire_bytes_per_frame": wire_bytes,
    }
    if coef is not None:
        # split-decode headline: the JPEG-wire leg's per-frame host
        # DECODE microseconds (imdecode + cvtColor) vs the coefficient
        # leg's (frombuffer views; the "entropy" stage is a labeled VIEW
        # of the same work, not an addend) -- the number the CI
        # decode-smoke gate reads
        decode_before = before["split_us"]["decode"]
        decode_coef = coef["split_us"]["decode"]
        host_block["coef"] = coef
        host_block["decode_us_before"] = decode_before
        host_block["decode_us_coef"] = round(decode_coef, 2)
        host_block["coef_decode_reduction_pct"] = round(
            100.0 * (1.0 - decode_coef / decode_before)
            if decode_before > 0 else 0.0, 1)
        host_block["coef_host_reduction_pct"] = round(
            100.0 * (1.0 - coef["host_us"] / before["host_us"])
            if before["host_us"] > 0 else 0.0, 1)

    if egress_profiles:
        # egress headline: per-frame response-path host microseconds
        # (D2H fetch + mask encode) on the pre-pack leg vs the packed
        # bits leg, and the response mask payload per format. The CI
        # egress-smoke gate reads egress_reduction_pct (>= 30) and
        # wire_ratio_png_over_rle (>= 4; RLE, not bits -- bitpacked rows
        # are fixed-size and can exceed PNG on sparse masks).
        def _d2h_encode(p: dict) -> float:
            return p["split_us"]["d2h"] + p["split_us"]["encode"]

        eg_before = _d2h_encode(egress_profiles["egress_before"])
        eg_packed = _d2h_encode(egress_profiles["egress_bits"])
        egress_block = {
            "legs": egress_profiles,
            "d2h_encode_us": {n: round(_d2h_encode(p), 2)
                              for n, p in egress_profiles.items()},
            "d2h_encode_us_before": round(eg_before, 2),
            "d2h_encode_us_packed": round(eg_packed, 2),
            "egress_reduction_pct": round(
                100.0 * (1.0 - eg_packed / eg_before)
                if eg_before > 0 else 0.0, 1),
            "response_mask_bytes": mask_bytes,
        }
        png_b = mask_bytes.get("egress_png", 0)
        rle_b = mask_bytes.get("egress_rle", 0)
        bits_b = mask_bytes.get("egress_bits", 0)
        if png_b and rle_b:
            egress_block["wire_ratio_png_over_rle"] = round(
                png_b / rle_b, 2)
        if png_b and bits_b:
            egress_block["wire_ratio_png_over_bits"] = round(
                png_b / bits_b, 2)
        host_block["egress"] = egress_block

    import jax

    payload = {
        "metric": "open_loop_tail_latency",
        "backend": jax.default_backend(),
        "unit": "ms",
        "arrivals": "poisson",
        "smoke": True,
        "slo_ms": slo_ms,
        "deadline_ms": (deadline_s * 1e3 if deadline_s else 0.0),
        "workers": cli.workers,
        "frame": [w, h],
        "host_profile": host_block,
        "rows": rows,
    }
    Path(cli.out).write_text(json.dumps(payload, indent=2) + "\n")

    top = rows[-1] if rows else {}
    p99 = top.get("p99_ms")
    _emit_result({
        "metric": "open_loop_tail_latency",
        "backend": jax.default_backend(),
        "value": p99 if p99 is not None and math.isfinite(p99) else 0.0,
        "unit": "ms",
        "offered_rps": rate,
        "goodput_rps": top.get("goodput_rps", 0.0),
        "violation_rate": top.get("violation_rate", 0.0),
        "errors": warm_errors + sum(r["errors"] for r in rows),
        "warm_errors": warm_errors,
        "levels": len(rows),
        "host": host_block,
        "out": cli.out,
        "smoke": True,
    })


# -- multi-model statistical multiplexing ------------------------------------


def modulated_poisson_arrivals(mean_rate: float, duration_s: float,
                               period_s: float, phase: float,
                               rng: np.random.Generator,
                               peak_frac: float = 0.9) -> list[float]:
    """Square-wave-modulated Poisson arrivals: the model is BURSTY --
    rate_hi during its active half-period, rate_lo otherwise, with
    ``peak_frac`` of the traffic landing in the active half. Two models
    with phases 0.0 and 0.5 are perfectly anti-correlated: one peaks
    exactly while the other sleeps (the AlpaServe multiplexing case)."""
    hi = 2.0 * mean_rate * peak_frac
    lo = max(2.0 * mean_rate * (1.0 - peak_frac), 1e-3)
    out: list[float] = []
    t = 0.0
    while True:
        cycle = ((t / period_s) + phase) % 1.0
        rate = hi if cycle < 0.5 else lo
        t += float(rng.exponential(1.0 / rate))
        if t >= duration_s:
            return out
        out.append(t)


def run_mixed_level(stub, requests: dict, schedule: list[tuple[float, str]],
                    workers: int, deadline_s: float | None,
                    slo_ms: float) -> dict:
    """Fire one mixed-model offered-load level: ``schedule`` is a merged
    [(offset_s, model)] list; latency/violation bookkeeping is kept PER
    MODEL (the multi-tenant question is who burned whose budget)."""
    per: dict[str, dict] = {
        m: {"lat_ms": [], "errors": 0} for m in requests
    }
    lock = threading.Lock()
    t0 = time.perf_counter()

    def one(offset_s: float, model: str) -> None:
        target = t0 + offset_s
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        ok = False
        try:
            status = None
            for resp in stub.AnalyzeActuatorPerformance(
                    iter([requests[model]]), timeout=deadline_s):
                status = resp.status
            ok = status is not None and not status.startswith("ERROR")
        except Exception:
            ok = False
        done = time.perf_counter()
        with lock:
            if ok:
                per[model]["lat_ms"].append((done - target) * 1e3)
            else:
                per[model]["errors"] += 1

    with ThreadPoolExecutor(max_workers=workers) as pool:
        for offset, model in schedule:
            pool.submit(one, offset, model)
    wall = time.perf_counter() - t0

    models = {}
    all_lat: list[float] = []
    total_errors = 0
    for m, d in per.items():
        offered = sum(1 for _, mm in schedule if mm == m) / max(wall, 1e-9)
        models[m] = summarize_level(d["lat_ms"], d["errors"], offered,
                                    wall, slo_ms)
        all_lat.extend(d["lat_ms"])
        total_errors += d["errors"]
    row = summarize_level(all_lat, total_errors,
                          len(schedule) / max(wall, 1e-9), wall, slo_ms)
    row["models"] = models
    return row


def run_multimodel_mode(cli, slo_ms: float, deadline_s: float | None,
                        duration: float, frame_wh) -> None:
    """``--models seg,aux``: the statistical-multiplexing proof.

    Two (or more) zoo models receive phase-shifted (anti-correlated)
    square-wave Poisson arrivals against three server shapes at the SAME
    total chip count:

    - ``baseline-<m>`` -- each model ALONE on the full mesh at its own
      schedule (the pre-contention violation ceiling);
    - ``multiplexed``  -- one zoo server, shared placement: every
      model's burst may use every chip (AlpaServe co-location);
    - ``dedicated``    -- the same zoo server with the static
      chips/M-per-model partition (silicon per model).

    The claim gated in CI: multiplexed aggregate goodput >= dedicated at
    equal chips, with each model's multiplexed violation rate under its
    single-model baseline ceiling. ``--zoo-fault SPEC`` adds a fourth
    leg with the fault armed (e.g. serving.model.aux.dispatch:exc:-1)
    proving zero cross-model frame loss."""
    import grpc

    from robotic_discovery_platform_tpu.io.frames import SyntheticSource
    from robotic_discovery_platform_tpu.resilience import configure_faults
    from robotic_discovery_platform_tpu.serving import client as client_lib
    from robotic_discovery_platform_tpu.serving.proto import vision_grpc

    models = [m.strip() for m in cli.models.split(",") if m.strip()]
    if len(models) < 2:
        raise ValueError("--models needs at least two zoo models")
    chips = cli.chips if cli.chips > 1 else 4
    rate = cli.model_rate
    period = cli.period or max(2.0, duration / 2.0)
    zoo_spec = ",".join(models)
    w, h = frame_wh

    source = SyntheticSource(width=w, height=h, seed=cli.seed, n_frames=1)
    source.start()
    color, depth = source.get_frames()
    source.stop()
    requests = {
        m: client_lib.encode_request(color, depth,
                                     model=("" if m == models[0] else m))
        for m in models
    }

    def schedules() -> dict[str, list[float]]:
        """Identical per-model arrival schedules for every leg (fresh
        rng, same seed), phases spread so the models anti-correlate."""
        rng = np.random.default_rng(cli.seed)
        return {
            m: modulated_poisson_arrivals(
                rate, duration, period, i / len(models), rng)
            for i, m in enumerate(models)
        }

    def boot(zoo, placement, fault=None):
        if fault:
            configure_faults(fault)
        return boot_smoke_server(
            slo_ms, chips=chips, zoo_models=zoo,
            zoo_placement=placement,
            # placer timing fine enough to resolve the burst phases:
            # each half-period must span several rate intervals, or the
            # correlation estimate aliases and a mis-detected positive
            # correlation confines an anti-correlated model mid-run
            extra_cfg={
                "zoo_rate_interval_s": max(0.25, period / 8.0),
                "zoo_rebalance_s": max(1.0, period / 2.0),
                # the correlation window must cover the MEASURED phase
                # only: stretching it back over the warm phase's shared
                # silence correlates every model positively with every
                # other and buries the anti-phase signal
                "zoo_rate_window": max(
                    8, int(duration / max(0.25, period / 8.0))),
            },
        )

    def warm(stub, servicer, reqs):
        errors = 0
        for req in reqs:
            for _ in range(2):
                try:
                    resps = list(
                        stub.AnalyzeActuatorPerformance(iter([req])))
                    if any(r.status.startswith("ERROR") for r in resps):
                        errors += 1
                except Exception:
                    errors += 1
        servicer.warmup(w, h)
        return errors

    legs: list[tuple[str, str, list[str], str | None]] = [
        *[(f"baseline-{m}", zoo_spec, [m], None) for m in models],
        ("multiplexed", zoo_spec, models, None),
        ("dedicated", zoo_spec, models, None),
    ]
    if cli.zoo_fault:
        legs.append(("fault", zoo_spec, models, cli.zoo_fault))

    rows: list[dict] = []
    leg_rows: dict[str, dict] = {}
    warm_errors = 0
    try:
        for leg_name, zoo, active, fault in legs:
            placement = ("dedicated" if leg_name == "dedicated"
                         else "shared")
            server, servicer, address = boot(zoo, placement, fault)
            channel = grpc.insecure_channel(address)
            stub = vision_grpc.VisionAnalysisServiceStub(channel)
            try:
                warm_errors += warm(stub, servicer,
                                    [requests[m] for m in active])
                sched = schedules()
                merged = sorted(
                    [(t, m) for m in active for t in sched[m]]
                )
                row = run_mixed_level(stub, requests, merged,
                                      cli.workers, deadline_s, slo_ms)
                row["multimodel_leg"] = leg_name
                row["chips"] = chips
                row["placement"] = placement
                row["active_models"] = active
                if servicer.placer is not None:
                    row["placer"] = servicer.placer.snapshot()
                rows.append(row)
                leg_rows[leg_name] = row
                per = {m: (row["models"][m]["violation_rate"],
                           row["models"][m]["goodput_rps"])
                       for m in active}
                print(f"# multimodel leg={leg_name} placement={placement} "
                      f"goodput={row['goodput_rps']} per-model "
                      f"(viol, goodput)={per}", file=sys.stderr)
            finally:
                channel.close()
                server.stop(grace=None)
                servicer.close()
                if fault:
                    configure_faults(None)
    finally:
        configure_faults(None)

    mux = leg_rows.get("multiplexed", {})
    ded = leg_rows.get("dedicated", {})
    ceilings = {
        m: leg_rows.get(f"baseline-{m}", {}).get("models", {}).get(
            m, {}).get("violation_rate")
        for m in models
    }
    fault_row = leg_rows.get("fault")
    mux_placer = mux.get("placer", {})
    corr = mux_placer.get("correlation", {})
    gates = {
        # (a) multiplexing vs the dedicated partition at equal chips.
        # NOTE the honest caveat this container imposes: the faked CPU
        # "chips" share ONE core, so partitioning cannot reduce a
        # model's available COMPUTE here and the capacity half of the
        # AlpaServe claim is only measurable on real hardware (same
        # standing TPU-window item as multi-chip scaling). What the
        # smoke CAN prove: at equal total chips the shared placement
        # matches the partition's goodput while absorbing each model's
        # bursts with a materially better tail (the burst rides every
        # window the quiet model is not using).
        "goodput_multiplexed": mux.get("goodput_rps"),
        "goodput_dedicated": ded.get("goodput_rps"),
        "multiplexed_ge_dedicated": (
            mux.get("goodput_rps", 0.0)
            >= 0.95 * ded.get("goodput_rps", 0.0)
        ),
        "p99_multiplexed_ms": mux.get("p99_ms"),
        "p99_dedicated_ms": ded.get("p99_ms"),
        # the anti-correlation must actually have been MEASURED (the
        # placer's co-location decision is evidence-driven, not luck)
        "measured_correlation": corr,
        "anti_correlated": all(v < 0 for v in corr.values()) if corr
                           else None,
        "shared_placement_held": (
            all(len(chips_) == chips for chips_ in
                mux_placer.get("placement", {}).values())
            if mux_placer else None
        ),
        # (b) each model's multiplexed violation rate vs its
        # single-model baseline ceiling
        "per_model_violation_multiplexed": {
            m: mux.get("models", {}).get(m, {}).get("violation_rate")
            for m in models
        },
        "baseline_ceilings": ceilings,
        # (c) zero cross-model loss: in the fault leg, every model the
        # fault does NOT name must complete all its frames OK
        "cross_model_losses": (
            {m: fault_row["models"][m]["errors"] for m in models
             if fault_row is not None
             and f".{m}." not in (cli.zoo_fault or "")}
            if fault_row is not None else None
        ),
    }
    block = {
        "models": models,
        "chips": chips,
        "rate_per_model": rate,
        "period_s": period,
        "duration_s": duration,
        "legs": {k: {kk: v[kk] for kk in
                     ("goodput_rps", "violation_rate", "errors", "n",
                      "p99_ms") if kk in v}
                 for k, v in leg_rows.items()},
        "gates": gates,
    }

    import jax

    payload = {
        "metric": "open_loop_tail_latency",
        "backend": jax.default_backend(),
        "unit": "ms",
        "arrivals": "modulated-poisson",
        "smoke": True,
        "slo_ms": slo_ms,
        "deadline_ms": (deadline_s * 1e3 if deadline_s else 0.0),
        "workers": cli.workers,
        "frame": [w, h],
        "multimodel": block,
        "rows": rows,
    }
    Path(cli.out).write_text(json.dumps(payload, indent=2) + "\n")

    _emit_result({
        "metric": "open_loop_tail_latency",
        "backend": jax.default_backend(),
        "value": (mux.get("p99_ms") or 0.0),
        "unit": "ms",
        "goodput_rps": mux.get("goodput_rps", 0.0),
        "violation_rate": mux.get("violation_rate", 0.0),
        "errors": warm_errors + sum(r["errors"] for r in rows),
        "warm_errors": warm_errors,
        "levels": len(rows),
        "multimodel": block,
        "out": cli.out,
        "smoke": True,
    })


# -- smoke server ------------------------------------------------------------


def boot_smoke_server(slo_ms: float, controller: bool = False,
                      chips: int = 1, decode_workers: int = 0,
                      zoo_models: str = "", zoo_placement: str = "shared",
                      zoo_eager_warm: int = -1,
                      extra_cfg: dict | None = None):
    """An in-process CPU server shaped like tools/metrics_smoke.py's:
    tiny registered model, micro-batching ON (so the dispatcher, the
    flight recorder, and the serving.batch.* fault sites are all in the
    measured path), metrics endpoint on an ephemeral port.

    ``controller=True`` boots the full overload control plane
    (deadline-aware admission + the reactive controller, tightened to
    smoke-scale time constants); False boots the control-off comparison
    leg (FIFO admission, static knobs -- the PR 2 behavior). ``chips``
    routes the dispatch window across that many faked CPU mesh chips
    (the quarantine leg's topology). ``decode_workers`` sizes the ingest
    decode pool (0 = the historical inline decode). ``zoo_models`` /
    ``zoo_placement`` shape the model zoo (serving/zoo.py): every named
    variant is registered into the smoke registry."""
    from robotic_discovery_platform_tpu.utils.platforms import (
        enable_compile_cache,
        force_cpu_platform,
    )

    force_cpu_platform(min_devices=8 if chips > 1 else 1)
    enable_compile_cache()

    from robotic_discovery_platform_tpu.models import (
        variants as variants_lib,
    )
    from robotic_discovery_platform_tpu.serving import (
        replica as replica_lib,
    )
    from robotic_discovery_platform_tpu.serving import server as server_lib
    from robotic_discovery_platform_tpu.utils.config import ServerConfig

    roster = variants_lib.resolve_zoo_models(zoo_models)
    tmp = Path(tempfile.mkdtemp(prefix="rdp-load-bench-"))
    uri = replica_lib.register_tiny_model(
        Path(tmp) / "mlruns", img_size=64, models=roster,
    )
    cfg = ServerConfig(
        address="localhost:0",
        tracking_uri=uri,
        model_img_size=64,
        metrics_csv=str(tmp / "metrics.csv"),
        metrics_flush_every=64,
        calibration_path=str(tmp / "missing.npz"),
        batch_window_ms=2.0,
        max_batch=4,
        metrics_port=-1,
        reload_poll_s=0.0,
        slo_ms=slo_ms,
        # burn must react within a few-second smoke level -- and with a
        # 128-frame window a 1% budget would let two slow frames read as
        # "objective breached"; 5% keeps the smoke's brownout trigger at
        # real overload, not scheduler noise
        slo_window=128,
        slo_budget=0.05,
        serving_mesh=chips if chips > 1 else 0,
        # the comparison legs: full overload control plane vs the PR 2
        # static/FIFO behavior
        admission_policy="deadline" if controller else "fifo",
        controller_enabled=controller,
        controller_interval_s=0.1,
        controller_sustain_s=0.3,
        controller_cooldown_s=0.5,
        chip_breaker_failures=3 if controller or chips > 1 else 0,
        chip_breaker_reset_s=2.0,
        decode_workers=decode_workers,
        zoo_models=zoo_models,
        zoo_placement=zoo_placement,
        # full eager warm per zoo model: the bench measures steady-state
        # multiplexing, not first-burst compile stalls
        zoo_eager_warm=zoo_eager_warm,
        **(extra_cfg or {}),
    )
    # no warmup_shape here on purpose: an armed serving.batch.complete
    # fault would fire inside build_server's warm-up frame and abort the
    # boot; the harness's own warm phase absorbs (and counts) it instead
    server, servicer = server_lib.build_server(cfg)
    port = server.add_insecure_port("localhost:0")
    server.start()
    return server, servicer, f"localhost:{port}"


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true",
                        help="boot an in-process CPU server (tiny model, "
                             "64x64 frames) and run short levels")
    parser.add_argument("--server", default=None,
                        help="address of an already-running server "
                             "(host:port); mutually exclusive with --smoke")
    parser.add_argument("--loads", default=None,
                        help="comma-separated offered loads in frames/sec, "
                             "or capacity multiples suffixed 'x' (1.5x = "
                             "1.5 times measured closed-loop capacity) "
                             "(default: 5,10,20 smoke / 50,100,200 full)")
    parser.add_argument("--controller", choices=("off", "on", "both"),
                        default="off",
                        help="overload-control comparison legs: 'off' = "
                             "FIFO admission + static knobs, 'on' = "
                             "deadline admission + reactive controller, "
                             "'both' = run both legs at the same loads "
                             "(smoke only; rows are tagged per leg)")
    parser.add_argument("--chips", type=int, default=1,
                        help="smoke-server mesh width (faked CPU devices); "
                             ">1 exercises multi-chip routing and the "
                             "serving.chip.<i>.dispatch quarantine path")
    parser.add_argument("--fleet", type=int, default=0, metavar="N",
                        help="boot N replica server subprocesses behind "
                             "the in-process fleet front-end and run the "
                             "1-replica / N-replica / replica-kill legs "
                             "(serving/frontend.py); needs --smoke")
    parser.add_argument("--fleet-fault", default=None, metavar="SPEC",
                        help="RDP_FAULTS spec armed on replica 0 ONLY "
                             "(one degraded member inside a healthy "
                             "fleet), e.g. serving.batch.complete:exc:1")
    parser.add_argument("--models", default=None, metavar="A,B",
                        help="multi-model statistical-multiplexing legs "
                             "(zoo variants, e.g. seg,aux): phase-"
                             "shifted anti-correlated arrivals against "
                             "baseline / multiplexed / dedicated "
                             "placements at equal total chips; needs "
                             "--smoke (chips default 4 here)")
    parser.add_argument("--model-rate", type=float, default=40.0,
                        help="mean per-model offered load (frames/sec) "
                             "for the --models legs; each model bursts "
                             "to ~1.8x this during its active half-"
                             "period")
    parser.add_argument("--period", type=float, default=None,
                        help="burst period (seconds) for the --models "
                             "legs (default: half the level duration)")
    parser.add_argument("--zoo-fault", default=None, metavar="SPEC",
                        help="RDP_FAULTS spec armed for one extra "
                             "--models leg (e.g. serving.model.aux."
                             "dispatch:exc:-1): the named model's "
                             "frames must fail loudly while every "
                             "other model completes clean (zero "
                             "cross-model loss)")
    parser.add_argument("--host-profile", action="store_true",
                        help="host-path before/after profile: run the "
                             "same offered load against the pre-overhaul "
                             "ingest (inline decode, JPEG/PNG wire) and "
                             "the overhauled one (decode pool + raw "
                             "payloads), splitting per-frame microseconds "
                             "into decode/admit/stage-host/H2D/launch/"
                             "device/D2H/encode; needs --smoke")
    parser.add_argument("--decode-workers", type=int, default=None,
                        help="ingest decode-pool width for the smoke "
                             "server ('after' leg of --host-profile, "
                             "default 4 there; other smoke legs default "
                             "to 0 = the historical inline decode)")
    parser.add_argument("--wire-format", default="encoded",
                        choices=("encoded", "raw", "coef"),
                        help="request wire format for the plain smoke "
                             "legs (encoded = JPEG/PNG, raw = zero-copy "
                             "RGB8/z16, coef = split-decode format=2 "
                             "coefficient payloads); --host-profile "
                             "sweeps all three itself")
    parser.add_argument("--deadline-ms", type=float, default=None,
                        help="per-request gRPC deadline (default: the "
                             "SLO itself -- a client with a 250ms "
                             "objective gives up at 250ms) -- the budget "
                             "deadline-aware shedding works against; 0 "
                             "disables")
    parser.add_argument("--duration", type=float, default=None,
                        help="seconds per load level (default: 2.5 smoke "
                             "/ 20 full)")
    parser.add_argument("--trace", default=None,
                        help="replay arrivals from a JSON array of "
                             "inter-arrival milliseconds instead of "
                             "Poisson levels")
    parser.add_argument("--slo-ms", type=float, default=None,
                        help="client-side latency objective for the "
                             "violation-rate column (default: RDP_SLO_MS "
                             "or 250 smoke / 50 full)")
    parser.add_argument("--workers", type=int, default=32,
                        help="max concurrent in-flight requests (the "
                             "simulated client-fleet width)")
    parser.add_argument("--frame-size", type=int, default=None,
                        help="square frame edge (default 64 smoke / 480 "
                             "full; full mode sends 640x480)")
    parser.add_argument("--out", default="LOADBENCH.json",
                        help="result file (default LOADBENCH.json)")
    parser.add_argument("--seed", type=int, default=0)
    cli = parser.parse_args()
    if not cli.smoke and not cli.server:
        parser.error("one of --smoke or --server is required")
    if cli.controller == "both" and not cli.smoke:
        parser.error("--controller both boots one server per leg; it "
                     "needs --smoke")
    if cli.chips > 1 and not cli.smoke:
        parser.error("--chips shapes the smoke server; it needs --smoke")
    if cli.host_profile:
        if not cli.smoke:
            parser.error("--host-profile boots per-leg smoke servers; it "
                         "needs --smoke")
        if cli.fleet or cli.controller != "off":
            parser.error("--host-profile is its own comparison; drop "
                         "--fleet/--controller")
    if cli.models:
        if not cli.smoke:
            parser.error("--models boots per-leg zoo smoke servers; it "
                         "needs --smoke")
        if cli.fleet or cli.host_profile or cli.controller != "off":
            parser.error("--models is its own comparison; drop "
                         "--fleet/--host-profile/--controller")
    if cli.fleet:
        if not cli.smoke:
            parser.error("--fleet boots local CPU replicas; it needs "
                         "--smoke")
        if cli.fleet < 2:
            parser.error("--fleet needs at least 2 replicas (the legs "
                         "compare N vs 1 and kill one mid-run)")
        if cli.controller != "off":
            parser.error("--controller tunes the single-server legs; "
                         "fleet replicas run their own control plane")
    legs = ["off", "on"] if cli.controller == "both" else [cli.controller]

    import grpc

    from robotic_discovery_platform_tpu.io.frames import SyntheticSource
    from robotic_discovery_platform_tpu.serving import client as client_lib
    from robotic_discovery_platform_tpu.serving.proto import vision_grpc

    env_slo = os.environ.get("RDP_SLO_MS", "").strip()
    slo_ms = (cli.slo_ms if cli.slo_ms is not None
              else float(env_slo) if env_slo
              else (250.0 if cli.smoke else 50.0))
    load_spec = (parse_loads(cli.loads) if cli.loads
                 else [(v, False) for v in
                       ([5.0, 10.0, 20.0] if cli.smoke
                        else [50.0, 100.0, 200.0])])
    needs_capacity = any(mult for _, mult in load_spec)
    duration = cli.duration or (2.5 if cli.smoke else 20.0)
    if cli.frame_size:
        w = h = cli.frame_size
    else:
        w, h = (64, 64) if cli.smoke else (640, 480)
    deadline_ms = (cli.deadline_ms if cli.deadline_ms is not None
                   else slo_ms)
    deadline_s = deadline_ms / 1e3 if deadline_ms > 0 else None

    if cli.models:
        run_multimodel_mode(cli, slo_ms, deadline_s,
                            cli.duration or 8.0, (w, h))
        return

    if cli.host_profile:
        run_host_profile(cli, slo_ms, deadline_s, load_spec, duration,
                         (w, h))
        return

    if cli.fleet:
        run_fleet_mode(cli, slo_ms, deadline_s, load_spec, duration,
                       (w, h))
        return

    rng = np.random.default_rng(cli.seed)
    request = None
    rows: list[dict] = []
    legs_summary: dict[str, dict] = {}
    capacity = None
    warm_errors = 0
    quarantines_total = 0
    for leg in legs:
        server = servicer = None
        if cli.smoke:
            server, servicer, address = boot_smoke_server(
                slo_ms, controller=(leg == "on"), chips=cli.chips,
                decode_workers=(cli.decode_workers or 0),
            )
        else:
            address = cli.server
        channel = grpc.insecure_channel(address)
        stub = vision_grpc.VisionAnalysisServiceStub(channel)
        try:
            if request is None:
                source = SyntheticSource(width=w, height=h, seed=cli.seed,
                                         n_frames=1)
                source.start()
                color, depth = source.get_frames()
                source.stop()
                request = client_lib.encode_request(
                    color, depth, fmt=cli.wire_format)
            # warm phase, off the measured window: pays XLA compilation
            # for the single-frame bucket and ABSORBS any armed one-shot
            # fault (CI's graceful-degradation leg) -- errors are
            # counted, not fatal
            for _ in range(3):
                try:
                    resps = list(
                        stub.AnalyzeActuatorPerformance(iter([request]))
                    )
                    if any(r.status.startswith("ERROR") for r in resps):
                        warm_errors += 1
                except Exception:
                    warm_errors += 1
            if servicer is not None:
                # pre-compile every reachable batch bucket so the
                # measured tail reflects serving, not one-off XLA
                # compilation
                servicer.warmup(w, h)
                if cli.wire_format == "coef":
                    # format=2 wire: the coefficient lane has its own
                    # fused decode+analyze graphs per bucket -- warm
                    # them too so the measured tail stays compile-free
                    servicer.warmup_coef(w, h)
            if needs_capacity and capacity is None:
                # anchor 'Nx' loads once, on the FIRST leg's server, so
                # every leg sees the same absolute offered loads
                capacity = measure_capacity(stub, request)
                print(f"# measured capacity ~{capacity:.1f} rps",
                      file=sys.stderr)
            loads = [v * capacity if mult else v for v, mult in load_spec]
            leg_rows: list[dict] = []
            if cli.trace:
                arrivals = trace_arrivals(cli.trace)
                offered = (len(arrivals) / arrivals[-1]
                           if arrivals[-1] else 0.0)
                lat_ms, errors, wall = run_level(
                    stub, request, arrivals, cli.workers, deadline_s)
                leg_rows.append(summarize_level(lat_ms, errors, offered,
                                                wall, slo_ms))
            else:
                for rate in loads:
                    arrivals = poisson_arrivals(rate, duration, rng)
                    if not arrivals:
                        continue
                    lat_ms, errors, wall = run_level(
                        stub, request, arrivals, cli.workers, deadline_s)
                    leg_rows.append(summarize_level(lat_ms, errors, rate,
                                                    wall, slo_ms))
                    print(f"# leg={leg} offered={rate:.1f}rps "
                          f"n={len(lat_ms)} errors={errors} "
                          f"p50={leg_rows[-1]['p50_ms']} "
                          f"p99={leg_rows[-1]['p99_ms']}",
                          file=sys.stderr)
            for row in leg_rows:
                row["controller"] = leg
            rows.extend(leg_rows)
            top = leg_rows[-1] if leg_rows else {}
            summary = {k: top.get(k) for k in (
                "offered_rps", "p99_ms", "goodput_rps", "violation_rate",
                "errors")}
            if servicer is not None:
                dispatcher = servicer.dispatcher
                router = (dispatcher.router
                          if dispatcher is not None else None)
                summary["quarantines"] = (router.quarantines_total
                                          if router is not None else 0)
                quarantines_total += summary["quarantines"]
                if servicer.controller is not None:
                    summary["controller_actions"] = (
                        servicer.controller.actions_total)
                    summary["brownout_level"] = servicer.controller.level
            legs_summary[leg] = summary
        finally:
            channel.close()
            if server is not None:
                server.stop(grace=None)
            if servicer is not None:
                servicer.close()

    if cli.smoke:
        import jax

        backend = jax.default_backend()
    else:
        # --server: this process is a gRPC client beside the one process
        # that holds the chip; it must not open a JAX backend of its own
        backend = "remote"

    payload = {
        "metric": "open_loop_tail_latency",
        "backend": backend,
        "unit": "ms",
        "arrivals": "trace" if cli.trace else "poisson",
        "smoke": bool(cli.smoke),
        "slo_ms": slo_ms,
        "deadline_ms": deadline_ms,
        "workers": cli.workers,
        "frame": [w, h],
        "chips": cli.chips,
        "capacity_rps": (round(capacity, 3) if capacity is not None
                         else None),
        "legs": legs_summary,
        "rows": rows,
    }
    Path(cli.out).write_text(json.dumps(payload, indent=2) + "\n")

    total_errors = warm_errors + sum(r["errors"] for r in rows)
    top = rows[-1] if rows else {}
    p99 = top.get("p99_ms")
    _emit_result({
        "metric": "open_loop_tail_latency",
        "backend": backend,
        # headline: p99 at the highest offered load that was measured
        # (the LAST leg's top row: the controller-on leg under 'both')
        "value": p99 if p99 is not None and math.isfinite(p99) else 0.0,
        "unit": "ms",
        "offered_rps": top.get("offered_rps", 0.0),
        "goodput_rps": top.get("goodput_rps", 0.0),
        "violation_rate": top.get("violation_rate", 0.0),
        "errors": total_errors,
        "warm_errors": warm_errors,
        "levels": len(rows),
        "legs": legs_summary,
        "capacity_rps": (round(capacity, 3) if capacity is not None
                         else None),
        "quarantines": quarantines_total,
        "out": cli.out,
        "smoke": bool(cli.smoke),
    })


if __name__ == "__main__":
    main()
