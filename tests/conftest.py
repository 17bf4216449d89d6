"""Test configuration.

Tests run JAX on a virtual 8-device CPU platform so the sharded propagation
path (parallel/) is exercised on a real multi-device mesh without TPU
hardware. Benchmarks (bench.py) run outside pytest and keep the real TPU.

JAX_PLATFORMS is exported for any subprocesses tests may spawn, and applied
to this process through jax.config via utils.jax_env (an env var alone is
unreliable here — see that module's docstring). XLA_FLAGS is read at lazy
backend-client creation, which has not happened yet at conftest time, so the
host-platform device count takes effect.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Overwrite, not setdefault: tests are defined to run on the virtual CPU
# mesh, whatever platform the environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

from p2pnetwork_tpu.utils.jax_env import apply_platform_env  # noqa: E402

apply_platform_env()

import pytest  # noqa: E402


def pytest_configure(config):
    # Registered here (not only in pyproject) so ad-hoc invocations that
    # bypass pyproject's ini options stay warning-clean in tier-1:
    # `-m analysis` selects the graftlint static-analysis suite.
    config.addinivalue_line(
        "markers",
        "analysis: graftlint static-analysis + retrace_guard tests "
        "(select with -m analysis; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "supervise: supervised execution plane tests — watchdogs, "
        "checkpoint store, crash-tolerant runs (select with -m supervise; "
        "part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "audit: graftaudit IR-level audit tests — jaxpr rules, signature "
        "parity, donation aliasing, cost ratchet (select with -m audit; "
        "part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "buildperf: incremental-build perf ratchet — delta apply vs "
        "from-scratch rebuild ratio at 1M-edge scale (select with "
        "-m buildperf; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "race: graftrace deterministic-concurrency tests — scheduler "
        "replay, HB detector twins, scenario battery, CLI gate (select "
        "with -m race; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "batch: batched message plane tests — lane-packed kernels, "
        "MessageBatch lifecycle, batched-vs-sequential bit parity, the "
        "slow-marked 20x aggregate-throughput ratchet (select with "
        "-m batch; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "ring: comm-seam tests — ppermute vs Pallas ring-DMA halo "
        "backends bit-identical across the sharded protocol sweep and "
        "the lane-word batched path, plus the ICI byte accounting "
        "(select with -m ring; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "scope: graftscope observability tests — flight-recorder parity "
        "+ overhead ratchet, trace-plane span trees / Perfetto export, "
        "history ring + /history endpoint, bench profiler "
        "wiring (select with -m scope; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "query: batched query-lane tests — byte-budgeted non-boolean "
        "carriers, min-plus/DHT/push-sum family identity sweeps, the "
        "query engine loop, and the slow-marked 10x aggregate ratchets "
        "(select with -m query; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "serve: graftserve serving front-end tests — submit/poll/stream "
        "lifecycle, admission pacing, quotas + structured load shedding, "
        "seeded-traffic determinism, preempt/resume bit-identity, the "
        "HTTP endpoints, and the slow-marked 1k-concurrent-lane soak "
        "(select with -m serve; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "quake: graftquake device-plane chaos tests — seeded halo-hop "
        "fault injection (byte-replayable, cross-backend bit-identical), "
        "dispatch chip-loss/wedge faults, integrity checks, RetryPolicy/"
        "Healer recovery bit-identity across engine/sharded/graftserve, "
        "and the slow-marked 100k chaos soak (select with -m quake; "
        "part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "sight: graftsight observability tests — ticket-scoped trace "
        "correlation (Perfetto-per-ticket under chaos), tick-phase "
        "profiler, SLO engine burn-rate alerts + AIMD consumption, "
        "/dashboard + query-param endpoints, tracer-on bit-identity, "
        "and the slow-marked serve-tick overhead ratchet (select with "
        "-m sight; part of the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "churn: graftchurn live-growth tests — bit-identical overlay "
        "growth with O(log K) repads, checkpoint/supervised resume "
        "across a repad, mid-service grow/delta mutations (zero lanes "
        "dropped, untouched tickets bit-identical), sidecar growth "
        "replay, seeded churn storms, and the slow-marked 100k "
        "churn-under-chaos soak (select with -m churn; part of the "
        "default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "mem: graftmem static memory plane tests — analytic liveness "
        "walk vs memory_analysis() parity, membudgets ratchet "
        "arithmetic, capacity-planner extrapolation, SimService "
        "hbm_budget_bytes admission gate (select with -m mem; part of "
        "the default tier-1 run)")
    config.addinivalue_line(
        "markers",
        "dur: graftdur durability tests — write-ahead intent journal "
        "(CRC records, torn-tail fuzz, segment rotation/compaction), "
        "crash-seam resume bit-identity, DurabilityLost shedding, "
        "hot-standby promote + FencedEpoch fencing, and the "
        "slow-marked crash-storm campaign + fsync overhead ratchet "
        "(select with -m dur; part of the default tier-1 run)")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Bound the live compiled-program count across the suite.

    The full suite (680+ tests, most jit-compiling several programs)
    accumulates every compiled executable in one process; past ~600
    tests the XLA CPU compiler has segfaulted inside LLVM on a program
    that compiles fine in isolation (reproduced twice at
    tests/test_walk.py, cleared by exactly this bounding). Cross-module
    cache hits are rare — modules compile their own protocols/shapes —
    so the recompile cost is noise.
    """
    yield
    if "jax" in sys.modules:  # sockets-only runs never import jax
        sys.modules["jax"].clear_caches()
