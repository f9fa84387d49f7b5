"""Test env setup: force the CPU backend with 8 virtual devices so
multi-chip sharding tests run without TPU hardware. JAX reads both
variables when it initializes its backend, hence at conftest import
time, before any test module imports jax."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# the content-addressed verdict/encode caches (tpu/cache.py) are
# process-global by design; tests must not see each other's entries
# (an engine mutated out-of-band — monkeypatched oracle, spied
# device_fn — shares its content key with the unmutated one)
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_perf_caches():
    from kyverno_tpu.cluster.columnar import reset_store
    from kyverno_tpu.tpu.cache import (global_encode_cache,
                                       global_verdict_cache)

    global_verdict_cache.clear()
    global_encode_cache.clear()
    reset_store()  # the columnar store is opt-in; drop any leftover
    yield
    reset_store()


# the policy observatory (observability/analytics.py) accumulates
# process-wide; parity tests assert EXACT per-rule counts, so every
# test starts from an empty accumulator and fresh SLO/starvation state
@pytest.fixture(autouse=True)
def _fresh_observatory():
    from kyverno_tpu.observability.analytics import (global_pattern_cells,
                                                     global_rule_stats,
                                                     global_slo,
                                                     global_starvation)

    global_rule_stats.reset()
    global_starvation.reset()
    global_slo.reset()
    global_pattern_cells.reset()
    yield


# the static-analysis state (analysis/analyzer.py) caches the last
# completed report process-wide for /debug/analysis and the
# /debug/rules correlation; tests seeding anomalies must not leak
# their reports (or lint-run counters) into each other's assertions
@pytest.fixture(autouse=True)
def _fresh_analysis_state():
    from kyverno_tpu.analysis import global_analysis

    global_analysis.reset()
    yield
    global_analysis.reset()


# the flight recorder, shadow verifier, and op log are process-global
# (like the caches); a test that configures a spool dir or a verify
# rate must not leak it into the next test's assertions
@pytest.fixture(autouse=True)
def _fresh_flight_recorder():
    from kyverno_tpu.observability.flightrecorder import global_flight
    from kyverno_tpu.observability.log import global_oplog
    from kyverno_tpu.observability.verification import global_verifier

    global_verifier.reset()
    global_flight.reset()
    global_oplog.reset()
    yield
    global_verifier.reset()
    global_flight.reset()
    global_oplog.reset()


# the incremental report store (reports/store.py) is process-global
# like the columnar store; a test that configures a journal dir must
# not leak report rows into the next test's summaries
@pytest.fixture(autouse=True)
def _fresh_reports():
    from kyverno_tpu.reports import reset_reports

    reset_reports()
    yield
    reset_reports()


# the degraded-storage ladder (resilience/storage.py) is process-
# global per surface: a test that degrades a surface (injected ENOSPC
# etc.) must not leave the next test's durability writes gated
@pytest.fixture(autouse=True)
def _fresh_storage_health():
    from kyverno_tpu.resilience.storage import reset_storage

    reset_storage()
    yield
    reset_storage()


# the fleet manager (fleet/manager.py) is process-global like the
# caches: a test that configures replicas must not leak membership,
# peer breakers, or the verdict-cache fan-out hook into the next test
@pytest.fixture(autouse=True)
def _fresh_fleet():
    yield
    from kyverno_tpu.fleet import get_fleet, reset_fleet
    from kyverno_tpu.tpu.cache import global_verdict_cache

    if get_fleet() is not None:
        reset_fleet()
    global_verdict_cache.on_put = None


@pytest.fixture
def no_verdict_cache():
    """Opt-out for tests that count device dispatches on repeat scans
    of identical content (the cache legitimately skips those)."""
    from kyverno_tpu.tpu.cache import global_verdict_cache

    cap = global_verdict_cache._lru.capacity
    global_verdict_cache.set_capacity(0)
    yield
    global_verdict_cache.set_capacity(cap)
