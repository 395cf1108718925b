"""JAX platform pinning, the accelerator requirement, and the compile cache.

One installation runs everywhere: tests and CPU harnesses pin
``JAX_PLATFORMS=cpu`` with a forced host device count
(:func:`force_cpu_platform`); on a machine with a TPU JAX picks it up by
default. A chip belongs to ONE process at a time -- a parent that has
touched JAX holds it, and a child that needs it then fails or hangs -- so
every entry point that measures or serves on the chip runs in a single
process and calls :func:`require_accelerator` instead of falling back to
the CPU.

:func:`enable_compile_cache` is the single home of the persistent
compilation cache policy. Every entry point (serving/server, serving/
replica, training/__main__, training/supervisor's child, every bench and
chip_smoke.py) calls it first thing.
"""

from __future__ import annotations

import os
import re
import sys
from pathlib import Path

#: ``<checkout>/.jax_cache`` -- the fixed in-checkout cache directory used
#: when ``JAX_COMPILATION_CACHE_DIR`` is unset. The path is part of the
#: cache key's environment, so it must never move between runs (no
#: mkdtemp, pid or timestamp components).
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def force_cpu_platform(min_devices: int = 8) -> None:
    """Pin this process (and its future children) to ``min_devices`` virtual
    CPU devices. Must run before the first JAX backend query: XLA reads the
    flag when the CPU client is first created."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    match = re.search(
        r"--xla_force_host_platform_device_count=(\d+)", flags
    )
    if match is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={min_devices}"
        ).strip()
    elif int(match.group(1)) < min_devices:
        # an inherited smaller count (e.g. from a multihost worker env)
        # must be RAISED, not silently kept -- the caller needs min_devices
        os.environ["XLA_FLAGS"] = (
            flags[: match.start()]
            + f"--xla_force_host_platform_device_count={min_devices}"
            + flags[match.end():]
        )
    # JAX copies JAX_PLATFORMS into its config when it is imported, so a
    # caller that imported jax before pinning needs the config updated too
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def require_accelerator(what: str, platform: str = "tpu"):
    """The first device, or ``SystemExit`` (non-zero) naming the platform
    JAX actually found. Measuring paths call this instead of falling back:
    a number from a CPU run must never be written under a device metric's
    name."""
    import jax

    # inspected, not pinned: the platform of the first device is the test
    device = jax.devices()[0]  # jaxlint: disable=JL006
    if device.platform != platform:
        raise SystemExit(
            f"{what} needs a {platform} device; JAX found platform "
            f"{device.platform!r} ({device.device_kind}, "
            f"{len(jax.devices())} device(s), "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    return device


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when set (placed from outside), else the fixed in-checkout path."""
    return os.environ.get(_CACHE_ENV_VAR) or str(DEFAULT_COMPILE_CACHE_DIR)


def _cpu_pinned() -> bool:
    import jax

    return jax.config.jax_platforms == "cpu"


_JIT_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir_module",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
#: the label values of ``rdp_jit_seconds_total`` / ``rdp_compile_cache_total``
JIT_STAGES = tuple(_JIT_STAGE_EVENTS.values())
CACHE_RESULTS = tuple(_CACHE_EVENTS.values())
_compile_listeners_on = False


def process_age_s() -> float | None:
    """Seconds since this process was started, the interpreter's start-up
    and every import included: ``/proc/self/stat``'s start time against
    ``/proc/uptime``. ``None`` where there is no such file."""
    try:
        # the command's name may hold spaces and brackets; fields count
        # from after its closing one, and ``starttime`` is the 22nd
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def listen_to_compiles() -> None:
    """Feed JAX's own compile telemetry (``jax.monitoring``) into
    ``rdp_jit_seconds_total{stage}`` and ``rdp_compile_cache_total{result}``.
    Registered once a process; JAX keeps listeners for its lifetime.
    :func:`enable_compile_cache` switches it on, and so does every
    ``train_model`` call, whose timeline reads both counters."""
    global _compile_listeners_on
    if _compile_listeners_on:
        return
    _compile_listeners_on = True
    from jax import monitoring

    from robotic_discovery_platform_tpu.observability import (
        instruments as obs,
    )

    def on_duration(event: str, seconds: float, **_) -> None:
        stage = _JIT_STAGE_EVENTS.get(event)
        if stage is not None:
            obs.JIT_SECONDS.labels(stage=stage).inc(seconds)

    def on_event(event: str, **_) -> None:
        result = _CACHE_EVENTS.get(event)
        if result is not None:
            obs.COMPILE_CACHE.labels(result=result).inc()

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no
    directory is set in code; otherwise the cache lives at
    :data:`DEFAULT_COMPILE_CACHE_DIR`. The minimum compile-time and
    entry-size thresholds drop so every program -- the fused analyzers and
    the train step included, whatever their compile time -- is kept.

    A process pinned to the CPU (``JAX_PLATFORMS=cpu``: tests, smokes, the
    local replica harness) is left alone and gets ``None``: the cache
    exists to spare chip runs their compile minutes, XLA:CPU executables
    are tied to the host's CPU features, and tests must not share state
    through the checkout. Reads the config only -- never touches a
    backend -- and is idempotent."""
    import jax

    listen_to_compiles()
    if _cpu_pinned():
        return None
    if not os.environ.get(_CACHE_ENV_VAR):
        jax.config.update(
            "jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE_DIR)
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # A Mosaic kernel rides in its custom call as serialized MLIR with its
    # locations intact, and JAX strips debug info from the OUTER module
    # only before hashing. Python tracebacks ten frames deep in those
    # locations would key every Pallas program on the call stack that
    # traced it; one frame per op keeps the key the same from every entry
    # point. (Not jax_include_full_tracebacks_in_locations=False: JAX 0.9.0
    # then writes every op_name as the bare primitive, and no
    # jax.named_scope reaches a compiled program or a profile.)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    return compile_cache_dir()
