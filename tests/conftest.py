"""Test harness: force an 8-device virtual CPU backend.

This is the standard JAX idiom for exercising multi-chip pjit/shard_map code
paths in CI without TPU hardware (SURVEY.md section 4): the same meshes and
collectives compile and run against N virtual CPU devices. Tests never open
the accelerator: ``force_cpu_platform`` sets ``JAX_PLATFORMS=cpu`` and the
forced host device count before JAX is imported.
"""

from robotic_discovery_platform_tpu.utils.platforms import force_cpu_platform

# Must run before the first device query anywhere in the test session.
force_cpu_platform(min_devices=8)

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()

import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from robotic_discovery_platform_tpu.utils import lockcheck  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _thread_and_lock_hygiene():
    """Thread-leak detector (rdp-racecheck's dynamic sibling): no test
    may leave a NON-daemon thread running (it would outlive pytest's
    interpreter-exit join and hang CI), and -- when RDP_LOCKCHECK has
    instrumented any locks -- none may still be held once the test's
    teardown finishes (a held lock at teardown is a leaked critical
    section: some thread died inside it or someone forgot a release).

    Daemon threads are deliberately out of scope: every long-lived
    platform thread (collector/completer/watchdog, pollers, metric
    servers) is daemon by policy, jaxlint JL012 checks each one has a
    registered join/stop owner, and module-scoped server fixtures
    legitimately keep theirs alive across tests."""
    before = set(threading.enumerate())
    yield

    def leaked():
        return [
            t for t in threading.enumerate()
            if t not in before and t.is_alive() and not t.daemon
        ]

    # grace for teardown stragglers (a joined grpc worker or Timer that
    # is mid-exit), then assert
    deadline = time.monotonic() + 2.0
    while leaked() and time.monotonic() < deadline:
        time.sleep(0.02)
    stragglers = leaked()
    assert not stragglers, (
        f"non-daemon thread(s) leaked by this test: "
        f"{[t.name for t in stragglers]} -- every thread needs a "
        "join/stop owner (jaxlint JL012)"
    )
    deadline = time.monotonic() + 1.0
    held = lockcheck.held_locks()
    while held and time.monotonic() < deadline:
        time.sleep(0.02)
        held = lockcheck.held_locks()
    lockcheck.reset()
    assert not held, (
        f"instrumented lock(s) still held after the test: {held}"
    )
