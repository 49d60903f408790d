"""Test config: force JAX onto a virtual 8-device CPU mesh BEFORE jax import.

The driver benches on one real TPU chip; tests validate multi-chip sharding on
host CPU devices (ref test strategy: SURVEY.md §4 level 2 — hermetic in-process
cluster tests, testkit.CreateMockStore analog).
"""

import os

# force-override: a machine with a chip defaults JAX to it — tests must run
# hermetically on a virtual CPU mesh, whatever the environment presets.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "1"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# runtime lock-order detection for the WHOLE tier-1 suite (defaulted on,
# TIDB_TPU_LOCKCHECK=0 opts out): every threading.Lock/RLock created after
# this point is order-checked, so an acquisition-order inversion raises a
# typed LockOrderError the moment the second edge appears instead of some
# future 2-core CI host hanging forever (the PR 1 _MESH_EXEC_LOCK failure
# mode). Must run BEFORE any tidb_tpu import creates its locks.
os.environ.setdefault("TIDB_TPU_LOCKCHECK", "1")
from tidb_tpu.utils import lockcheck as _lockcheck

_lockcheck.install()

import pytest


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: fast deterministic chaos tests stay in
    # tier-1 (marked `chaos` only); long soak/multi-process topologies add
    # `slow` so they run in the extended lane (see RESILIENCE.md)
    config.addinivalue_line("markers", "chaos: deterministic fault-injection test")
    config.addinivalue_line("markers", "slow: excluded from the tier-1 fast lane")


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop every compiled program when a test module ends. XLA:CPU keeps
    three memory mappings (text, rodata, data) per JIT-linked object for as
    long as its executable lives, and the program caches below pin every
    executable any earlier test compiled: after ~500 tests the one-process
    tier-1 run sat at vm.max_map_count (65530 mappings, ~21.7k of each
    kind), the next compile's mmap failed inside LLVM, and the run died with
    SIGSEGV in backend_compile_and_load (tests/test_mpp.py, wherever the
    count happened to run out)."""
    yield
    import gc

    from tidb_tpu.ops import dag_kernel, window_kernel
    from tidb_tpu.parallel import gather

    with dag_kernel._CACHE_MU:
        dag_kernel._COMPILE_CACHE.clear()
    with window_kernel._MU:
        window_kernel._CACHE.clear()
    with gather._MPP_CACHE_MU:
        gather._MPP_FN_CACHE.clear()
    jax.clear_caches()
    gc.collect()


@pytest.fixture
def thread_hygiene():
    """Owner-keepalive/timer thread-leak guard: yields a ``stray()`` probe
    and asserts at teardown that no ``owner-ka-*`` keepalive or
    ``timer-runtime`` thread survived ``stop_background()``/sweep exit
    (guards the lease-keepalive rework in session._owner_gated). Also flags
    ``cop_``/``rcop_`` threads: cop fan-out runs on the ONE shared
    ``cop-shared`` pool now — a per-request pool thread is a regression.
    ``trace-``-prefixed threads are flagged too: the trace reservoir and the
    sampling coin are deliberately threadless (deposits happen on the
    statement's own thread) — a reservoir/sampler thread appearing would
    mean the observability layer grew background machinery it must not.
    The ``metrics-history`` recorder thread (utils/metricshist) IS allowed
    background machinery, but it is refcounted and must die with
    ``stop_background()`` / ``StoreServer.shutdown()`` — surviving one is a
    leak this fixture flags."""
    import threading
    import time

    def stray():
        return [
            t.name
            for t in threading.enumerate()
            if t.is_alive()
            and (
                t.name.startswith("owner-ka-")
                or t.name == "timer-runtime"
                or t.name.startswith("cop_")
                or t.name.startswith("rcop_")
                or t.name.startswith("trace-")
                or t.name == "metrics-history"
                or t.name == "store-colmerge"
            )
        ]

    yield stray
    deadline = time.time() + 3.0
    while stray() and time.time() < deadline:
        time.sleep(0.02)
    assert not stray(), f"stray background threads survived: {stray()}"
