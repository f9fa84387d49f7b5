"""Resilience layer: circuit breaker, retry/backoff, fault injection,
encode quarantine, shutdown drain, and failurePolicy deadline mapping.

Every failure mode is exercised through the fault registry so the
chaos behavior asserted here is deterministic and replayable."""

import threading
import time

import pytest

from kyverno_tpu.observability.metrics import MetricsRegistry
from kyverno_tpu.resilience import (CLOSED, HALF_OPEN, OPEN, CircuitBreaker,
                                    Deadline, FaultConfigError, FaultInjected,
                                    FaultRegistry, PermanentError, RetryPolicy,
                                    global_faults, retry_call, tpu_breaker)


@pytest.fixture(autouse=True)
def _clean_faults_and_breaker():
    """Faults and the shared TPU breaker are process-global: leave no
    chaos armed for the rest of the suite."""
    global_faults.disarm()
    tpu_breaker().reset()
    yield
    global_faults.disarm()
    tpu_breaker().reset()


# ---------------------------------------------------------------------------
# circuit breaker


def test_breaker_trips_after_consecutive_failures_and_half_open_recovers():
    now = [0.0]
    b = CircuitBreaker(name="t1", failure_threshold=3, reset_timeout_s=5.0,
                       clock=lambda: now[0], metrics=MetricsRegistry())
    assert b.state == CLOSED
    b.record_failure()
    b.record_failure()
    b.record_success()  # success resets the consecutive count
    b.record_failure()
    b.record_failure()
    assert b.state == CLOSED
    b.record_failure()
    assert b.state == OPEN
    assert not b.allow()           # open: no device attempts
    now[0] = 4.9
    assert not b.allow()
    now[0] = 5.1
    assert b.allow()               # one half-open probe admitted
    assert b.state == HALF_OPEN
    assert not b.allow()           # but only one
    b.record_success()
    assert b.state == CLOSED
    assert b.allow()


def test_breaker_bare_reset_restores_constructor_tuning():
    # the process-wide breaker is shared across tests: a bare reset()
    # must restore constructor tuning, or one test's threshold=1 leaks
    # into every later test in the same process
    b = CircuitBreaker(name="t-reset", failure_threshold=3,
                       reset_timeout_s=10.0, metrics=MetricsRegistry())
    b.reset(failure_threshold=1, reset_timeout_s=0.05)
    assert b.failure_threshold == 1 and b.reset_timeout_s == 0.05
    b.record_failure()
    assert b.state == OPEN
    b.reset()
    assert b.state == CLOSED
    assert b.failure_threshold == 3 and b.reset_timeout_s == 10.0


def test_breaker_half_open_failure_reopens():
    now = [0.0]
    b = CircuitBreaker(name="t2", failure_threshold=1, reset_timeout_s=1.0,
                       clock=lambda: now[0], metrics=MetricsRegistry())
    b.record_failure()
    assert b.state == OPEN
    now[0] = 1.5
    assert b.allow()
    b.record_failure()             # probe failed: straight back to OPEN
    assert b.state == OPEN
    assert not b.allow()
    now[0] = 2.4                   # reset timer restarted at reopen
    assert not b.allow()
    now[0] = 2.6
    assert b.allow()


def test_breaker_metrics_state_and_transitions():
    reg = MetricsRegistry()
    b = CircuitBreaker(name="m", failure_threshold=1, reset_timeout_s=0.0,
                       metrics=reg)
    b.record_failure()
    assert b.allow()
    b.record_success()
    text = reg.exposition()
    assert 'kyverno_tpu_breaker_state{breaker="m"} 0' in text
    assert ('kyverno_tpu_breaker_transitions_total'
            '{breaker="m",from="closed",to="open"} 1.0') in text
    assert ('kyverno_tpu_breaker_transitions_total'
            '{breaker="m",from="half_open",to="closed"} 1.0') in text


# ---------------------------------------------------------------------------
# retry / backoff / deadline


def test_retry_recovers_after_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    out = retry_call(flaky, RetryPolicy(max_attempts=3, base_delay_s=0.0),
                     metrics=MetricsRegistry())
    assert out == "ok" and calls["n"] == 3


def test_retry_exhausts_attempts_and_raises_last_error():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise ValueError(f"boom {calls['n']}")

    with pytest.raises(ValueError, match="boom 3"):
        retry_call(always, RetryPolicy(max_attempts=3, base_delay_s=0.0),
                   metrics=MetricsRegistry())
    assert calls["n"] == 3


def test_retry_permanent_error_skips_remaining_attempts():
    # a 404-style deterministic failure must NOT pay 3 backend calls
    # plus backoff on every admission — PermanentError opts out
    calls = {"n": 0}

    class NotFound(PermanentError):
        pass

    def missing():
        calls["n"] += 1
        raise NotFound("no such object")

    with pytest.raises(NotFound):
        retry_call(missing, RetryPolicy(max_attempts=3, base_delay_s=0.0),
                   metrics=MetricsRegistry())
    assert calls["n"] == 1


def test_retry_backoff_is_exponential_with_bounded_jitter():
    policy = RetryPolicy(max_attempts=4, base_delay_s=0.1, max_delay_s=10.0,
                         multiplier=2.0, jitter=0.5, deadline_s=None)
    sleeps = []

    def always():
        raise RuntimeError("x")

    with pytest.raises(RuntimeError):
        retry_call(always, policy, sleep=sleeps.append,
                   metrics=MetricsRegistry())
    assert len(sleeps) == 3
    for i, s in enumerate(sleeps):
        nominal = 0.1 * 2.0 ** i
        assert nominal * 0.5 <= s <= nominal * 1.5


def test_retry_respects_deadline_budget():
    """A backoff the remaining budget cannot cover is not slept: the
    loop fails fast instead of waking up past the caller's deadline."""
    now = [0.0]
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        now[0] += s

    calls = {"n": 0}

    def always():
        calls["n"] += 1
        now[0] += 0.4  # each attempt costs 0.4s of the 1s budget
        raise RuntimeError("slow backend")

    policy = RetryPolicy(max_attempts=10, base_delay_s=0.3, multiplier=2.0,
                         jitter=0.0, deadline_s=1.0)
    with pytest.raises(RuntimeError):
        retry_call(always, policy, clock=lambda: now[0], sleep=sleep,
                   metrics=MetricsRegistry())
    assert calls["n"] == 2  # attempt, 0.3s backoff, attempt, budget gone
    assert sleeps == [0.3]


def test_deadline_remaining_and_expiry():
    now = [0.0]
    d = Deadline(2.0, clock=lambda: now[0])
    assert d.remaining() == pytest.approx(2.0)
    now[0] = 2.5
    assert d.expired()
    assert Deadline(None).remaining() == float("inf")


# ---------------------------------------------------------------------------
# fault registry


def test_fault_registry_count_trigger_then_heals():
    r = FaultRegistry()
    r.arm("gctx.refresh", mode="raise", count=2)
    for _ in range(2):
        with pytest.raises(FaultInjected):
            r.fire("gctx.refresh")
    r.fire("gctx.refresh")  # healed after N triggers
    assert r.armed()["gctx.refresh"].fired == 2


def test_fault_registry_probability_is_seeded_deterministic():
    def run(seed):
        r = FaultRegistry()
        r.arm("tpu.dispatch", mode="raise", p=0.5, seed=seed)
        out = []
        for _ in range(32):
            try:
                r.fire("tpu.dispatch")
                out.append(0)
            except FaultInjected:
                out.append(1)
        return out

    assert run(7) == run(7)      # replayable chaos
    assert 0 < sum(run(7)) < 32  # actually probabilistic


def test_fault_registry_corrupt_mode_mangles_result_shape():
    import numpy as np

    r = FaultRegistry()
    r.arm("tpu.dispatch", mode="corrupt", count=1)
    r.fire("tpu.dispatch")  # corrupt never fires on the raise hook
    table = np.zeros((3, 8))
    assert r.corrupt("tpu.dispatch", table).shape == (3, 7)
    # trigger consumed: the next result passes through untouched
    assert r.corrupt("tpu.dispatch", table).shape == (3, 8)


def test_fault_registry_env_syntax_roundtrip():
    r = FaultRegistry()
    n = r.arm_from_string(
        "tpu.dispatch:corrupt:p=0.3,seed=42; serving.flush:delay:delay_s=0.2;"
        "gctx.refresh:raise:count=3")
    assert n == 3
    armed = r.armed()
    assert armed["tpu.dispatch"].p == 0.3 and armed["tpu.dispatch"].seed == 42
    assert armed["serving.flush"].mode == "delay"
    assert armed["serving.flush"].delay_s == 0.2
    assert armed["gctx.refresh"].count == 3
    with pytest.raises(FaultConfigError):
        r.arm_from_string("not.a.site:raise")
    with pytest.raises(FaultConfigError):
        r.arm_from_string("tpu.dispatch")  # needs site:mode
    with pytest.raises(FaultConfigError):
        r.arm("tpu.dispatch", mode="explode")
    with pytest.raises(FaultConfigError):
        # corrupt only applies where the result is filtered: arming it
        # at a raise/delay-only site would silently inject NOTHING
        r.arm("gctx.refresh", mode="corrupt")


# ---------------------------------------------------------------------------
# TPU engine: breaker-gated dispatch + encode quarantine

POLICY_DOC = {
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "no-priv"},
    "spec": {"validationFailureAction": "Enforce", "rules": [{
        "name": "check-privileged",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "validate": {"message": "privileged denied",
                     "pattern": {"spec": {"containers": [
                         {"=(securityContext)": {"=(privileged)": "false"}}]}}},
    }]},
}


def _pod(name, priv):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [{
                "name": "c", "image": "nginx",
                "securityContext": {"privileged": priv}}]}}


def _mk_engine():
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.tpu.engine import TpuEngine

    return TpuEngine([ClusterPolicy.from_dict(POLICY_DOC)])


def test_tpu_dispatch_fault_trips_breaker_and_verdicts_stay_identical(
        no_verdict_cache, monkeypatch):
    from kyverno_tpu.observability.log import global_oplog

    eng = _mk_engine()
    eng.breaker.reset(failure_threshold=2, reset_timeout_s=60.0)
    resources = [_pod("a", True), _pod("b", False)]
    want = eng.scan(resources).verdicts.tolist()

    events = []
    monkeypatch.setattr(global_oplog, "emit",
                        lambda event, **kw: events.append((event, kw)))
    global_faults.arm("tpu.dispatch", mode="raise", p=1.0)
    assert eng.scan(resources).verdicts.tolist() == want  # failure 1
    assert eng.breaker.state == CLOSED
    # the device error reaches the op log with its message, not only
    # the fallback counter
    failed = [kw for e, kw in events if e == "device_dispatch_failed"]
    assert failed and failed[0]["level"] == "error"
    assert "tpu.dispatch" in failed[0]["error"]
    assert eng.scan(resources).verdicts.tolist() == want  # failure 2: trip
    assert eng.breaker.state == OPEN
    # open: the device is not even attempted, yet verdicts are identical
    fired_before = global_faults.armed()["tpu.dispatch"].fired
    assert eng.scan(resources).verdicts.tolist() == want
    assert global_faults.armed()["tpu.dispatch"].fired == fired_before


def test_tpu_dispatch_corrupt_shape_is_a_device_failure(no_verdict_cache):
    eng = _mk_engine()
    eng.breaker.reset(failure_threshold=1, reset_timeout_s=0.0)
    resources = [_pod("a", True), _pod("b", False)]
    want = eng.scan(resources).verdicts.tolist()
    global_faults.arm("tpu.dispatch", mode="corrupt", count=1)
    assert eng.scan(resources).verdicts.tolist() == want  # mangled -> scalar
    assert eng.breaker.state == OPEN
    # reset_timeout 0: next scan is the half-open probe and succeeds
    assert eng.scan(resources).verdicts.tolist() == want
    assert eng.breaker.state == CLOSED


def test_hostile_resource_is_quarantined_not_fatal():
    """Satellite: a resource that fails encoding must not abort the
    batch — it completes on the scalar engine; the rest of the batch
    still evaluates normally."""
    eng = _mk_engine()
    hostile = {"kind": b"bytes-break-encoding", "metadata": {"name": "h"}}
    result = eng.scan([_pod("a", True), hostile, _pod("b", False)])
    row = result.rules.index(("no-priv", "check-privileged"))
    from kyverno_tpu.tpu.engine import VERDICT_NAMES

    assert VERDICT_NAMES[int(result.verdicts[row, 0])] == "fail"
    assert VERDICT_NAMES[int(result.verdicts[row, 1])] == "not_matched"
    assert VERDICT_NAMES[int(result.verdicts[row, 2])] == "pass"


def test_hostile_resource_scalar_failure_yields_per_rule_error():
    """When even the scalar engine cannot evaluate the quarantined
    resource, every rule reports ERROR — never an exception."""
    eng = _mk_engine()
    hostile = {"kind": b"x", "metadata": "not-a-dict"}
    result = eng.scan([hostile, _pod("ok", False)])
    from kyverno_tpu.tpu.evaluator import ERROR, PASS

    assert (result.verdicts[:, 0] == ERROR).all()
    row = result.rules.index(("no-priv", "check-privileged"))
    assert result.verdicts[row, 1] == PASS


def test_background_scan_survives_hostile_snapshot_resource():
    """Satellite: the scan loop must keep reporting on healthy
    resources when the snapshot holds a resource that breaks
    encoding (NaN metadata.name survives JSON but not the encoder)."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.cluster import (BackgroundScanService, ClusterSnapshot,
                                     PolicyCache, ReportAggregator)

    snap = ClusterSnapshot()
    cache = PolicyCache()
    cache.set(ClusterPolicy.from_dict(POLICY_DOC))
    agg = ReportAggregator()
    svc = BackgroundScanService(snap, cache, agg)
    snap.upsert(_pod("good", True))
    snap.upsert({"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": float("nan"), "namespace": "default",
                              "uid": "hostile-uid"}})
    n = svc.scan_once()
    assert n == 2  # both scanned, nothing aborted
    summary = agg.summary()
    assert summary.get("fail", 0) >= 1  # the good pod's verdict landed


# ---------------------------------------------------------------------------
# context loaders: retry with backoff at the backend sites


def _ctx(resource):
    from kyverno_tpu.engine.context import Context

    ctx = Context()
    ctx.add_resource(resource)
    return ctx


def test_api_call_context_retries_through_transient_faults():
    from kyverno_tpu.engine.contextloaders import (DataSources,
                                                   load_context_entries)

    calls = {"n": 0}

    def backend(spec):
        calls["n"] += 1
        return {"items": [1, 2, 3]}

    # the first two ATTEMPTS fail via the armed site; the third lands
    global_faults.arm("context.api_call", mode="raise", count=2)
    ctx = _ctx(_pod("p", False))
    sources = DataSources(
        api_call=backend,
        retry=RetryPolicy(max_attempts=3, base_delay_s=0.001, deadline_s=2.0))
    load_context_entries(
        ctx, [{"name": "pods", "apiCall": {"urlPath": "/api/v1/pods"}}],
        sources, deferred=False)
    assert ctx.query("pods.items") == [1, 2, 3]
    assert calls["n"] == 1  # fault fired before the backend on 2 attempts


def test_api_call_retries_exhausted_surfaces_error_not_hang():
    from kyverno_tpu.engine.contextloaders import (DataSources,
                                                   load_context_entries)

    global_faults.arm("context.api_call", mode="raise", p=1.0)
    ctx = _ctx(_pod("p", False))
    sources = DataSources(
        api_call=lambda spec: {"x": 1},
        retry=RetryPolicy(max_attempts=3, base_delay_s=0.001, deadline_s=1.0))
    t0 = time.monotonic()
    with pytest.raises(FaultInjected):
        load_context_entries(
            ctx, [{"name": "pods", "apiCall": {"urlPath": "/x"}}],
            sources, deferred=False)
    assert time.monotonic() - t0 < 1.0  # bounded, inside the budget


def test_batch_scoped_backend_poisoning_fails_fast_after_first_exhaust():
    from kyverno_tpu.engine.contextloaders import (ContextLoaderError,
                                                   DataSources,
                                                   load_context_entries)

    calls = {"n": 0}

    def dead_backend(spec):
        calls["n"] += 1
        raise RuntimeError("connection refused")

    sources = DataSources(
        api_call=dead_backend,
        retry=RetryPolicy(max_attempts=3, base_delay_s=0.001, deadline_s=2.0))
    sources.begin_batch()
    entry = [{"name": "pods", "apiCall": {"urlPath": "/api/v1/pods"}}]
    with pytest.raises(RuntimeError):  # first cell pays the retries
        load_context_entries(_ctx(_pod("a", False)), entry, sources,
                             deferred=False)
    assert calls["n"] == 3
    with pytest.raises(ContextLoaderError, match="marked down"):
        load_context_entries(_ctx(_pod("b", False)), entry, sources,
                             deferred=False)
    assert calls["n"] == 3  # poisoned: no further backend calls
    sources.end_batch()  # batch over: loads outside a batch retry again
    with pytest.raises(RuntimeError):
        load_context_entries(_ctx(_pod("c", False)), entry, sources,
                             deferred=False)
    assert calls["n"] == 6


def test_backend_permanent_error_neither_retried_nor_poisoning():
    from kyverno_tpu.engine.contextloaders import (DataSources,
                                                   load_context_entries)

    calls = {"n": 0}

    def backend(spec):
        calls["n"] += 1
        if spec.get("urlPath") == "/missing":
            raise PermanentError("404 not found")
        return {"ok": True}

    sources = DataSources(
        api_call=backend,
        retry=RetryPolicy(max_attempts=3, base_delay_s=0.001, deadline_s=2.0))
    sources.begin_batch()
    with pytest.raises(PermanentError):  # one attempt, no backoff
        load_context_entries(
            _ctx(_pod("a", False)),
            [{"name": "x", "apiCall": {"urlPath": "/missing"}}],
            sources, deferred=False)
    assert calls["n"] == 1
    # a per-cell deterministic failure must NOT poison the site
    ctx = _ctx(_pod("b", False))
    load_context_entries(
        ctx, [{"name": "y", "apiCall": {"urlPath": "/present"}}],
        sources, deferred=False)
    assert ctx.query("y.ok") is True


def test_image_data_context_retries_flaky_backend():
    from kyverno_tpu.engine.contextloaders import (DataSources,
                                                   load_context_entries)

    calls = {"n": 0}

    def image_backend(ref):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("registry 503")
        return {"manifest": {"config": {"digest": "sha256:abc"}}}

    ctx = _ctx(_pod("p", False))
    sources = DataSources(
        image_data=image_backend,
        retry=RetryPolicy(max_attempts=3, base_delay_s=0.001, deadline_s=2.0))
    load_context_entries(
        ctx, [{"name": "img", "imageRegistry": {"reference": "nginx"}}],
        sources, deferred=False)
    assert calls["n"] == 3
    assert ctx.query("img.manifest.config.digest") == "sha256:abc"


# ---------------------------------------------------------------------------
# gctx external-API entry driven through the fault registry (satellite)


def test_gctx_entry_fault_registry_stale_error_recovery_cycle():
    from kyverno_tpu.globalcontext import EntryError, ExternalApiEntry
    from kyverno_tpu.globalcontext.types import ExternalAPICallSpec

    now = [0.0]
    entry = ExternalApiEntry(
        ExternalAPICallSpec(url_path="/x", refresh_interval_s=10),
        lambda spec: {"healthy": True},
        clock=lambda: now[0],
        retry=RetryPolicy(max_attempts=2, base_delay_s=0.0, deadline_s=5.0),
        sleep=lambda s: None)
    assert entry.get() == {"healthy": True}

    # backend fails every attempt for 3 polls (2 retry attempts each)
    global_faults.arm("gctx.refresh", mode="raise", count=6)
    now[0] = 11.0
    assert entry.get() == {"healthy": True}  # stale-served
    now[0] = 22.0
    assert entry.get() == {"healthy": True}  # still inside TTL (30s)
    now[0] = 33.0
    with pytest.raises(EntryError):          # past TTL: error state
        entry.get()
    # fault budget exhausted = backend healed; next poll recovers
    now[0] = 44.0
    assert entry.get() == {"healthy": True}


def test_gctx_concurrent_readers_single_flight_stale_serve():
    """With a stale entry and a slow-failing backend, exactly ONE
    reader pays the refresh; the others serve the cached value
    immediately instead of piling their own retry loops onto a backend
    that is already down."""
    from kyverno_tpu.globalcontext import ExternalApiEntry
    from kyverno_tpu.globalcontext.types import ExternalAPICallSpec

    gate = threading.Event()
    calls = []

    def executor(spec):
        calls.append(1)
        if len(calls) == 1:
            return {"v": 1}
        gate.wait(5.0)  # slow failure: holds the refresh in flight
        raise RuntimeError("backend down")

    entry = ExternalApiEntry(
        ExternalAPICallSpec(url_path="/x", refresh_interval_s=0.01),
        executor,
        retry=RetryPolicy(max_attempts=1, base_delay_s=0.0, deadline_s=5.0),
        stale_ttl_s=60.0)  # keep the refresher inside the stale window
    assert entry.get() == {"v": 1}
    time.sleep(0.02)  # entry is now stale

    results = []
    lock = threading.Lock()

    def reader():
        out = entry.get()
        with lock:
            results.append(out)

    threads = [threading.Thread(target=reader) for _ in range(8)]
    for t in threads:
        t.start()
    # while ONE refresh is wedged on the gate, the other 7 readers must
    # come back with the stale value almost immediately
    deadline = time.monotonic() + 2.0
    while len(results) < 7 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(results) >= 7, "readers blocked behind the in-flight refresh"
    assert all(r == {"v": 1} for r in results)
    assert len(calls) == 2, "more than one refresh ran for one window"
    gate.set()
    for t in threads:
        t.join(timeout=5.0)
    assert len(results) == 8 and all(r == {"v": 1} for r in results)


def test_gctx_cold_entry_wait_is_bounded_when_first_fetch_hangs():
    """A hung executor on the FIRST fetch (no data to stale-serve) must
    not hang every other reader: the cold-entry wait is bounded by the
    retry deadline budget and then surfaces the error state."""
    from kyverno_tpu.globalcontext import EntryError, ExternalApiEntry
    from kyverno_tpu.globalcontext.types import ExternalAPICallSpec

    hang = threading.Event()

    def wedged_executor(spec):
        hang.wait(10.0)  # hung socket, no client timeout
        raise RuntimeError("too late")

    entry = ExternalApiEntry(
        ExternalAPICallSpec(url_path="/x", refresh_interval_s=10),
        wedged_executor,
        retry=RetryPolicy(max_attempts=1, base_delay_s=0.0, deadline_s=0.2))
    def first_reader():
        try:
            entry.get()
        except Exception:
            pass  # the hung fetch eventually errors; not under test

    refresher = threading.Thread(target=first_reader)
    refresher.start()
    time.sleep(0.05)  # let the refresher wedge inside the executor
    t0 = time.monotonic()
    with pytest.raises(EntryError, match="in flight"):
        entry.get()
    assert time.monotonic() - t0 < 5.0  # bounded by deadline_s + 1
    hang.set()
    refresher.join(timeout=5.0)


def test_gctx_store_refresh_all_keeps_polling_through_faults():
    from kyverno_tpu.globalcontext import GlobalContextStore

    store = GlobalContextStore(api_executor=lambda spec: {"v": 1})
    assert store.apply({
        "apiVersion": "kyverno.io/v2alpha1", "kind": "GlobalContextEntry",
        "metadata": {"name": "ext"},
        "spec": {"apiCall": {"urlPath": "/x", "refreshInterval": "1s"}}}) == []
    store.refresh_all()
    assert store["ext"] == {"v": 1}
    global_faults.arm("gctx.refresh", mode="raise", p=1.0)
    store.refresh_all()              # poll fails...
    assert store["ext"] == {"v": 1}  # ...reads serve last-known-good
    global_faults.disarm("gctx.refresh")
    store.refresh_all()
    assert store["ext"] == {"v": 1}


# ---------------------------------------------------------------------------
# serving pipeline: shutdown drain + flush faults


def test_shutdown_with_wedged_evaluator_resolves_queued_waiters():
    """Satellite regression: stop() must leave NO queued future
    unresolved — queued requests resolve via the scalar fallback even
    when the flusher is wedged on a stuck evaluator."""
    from kyverno_tpu.serving import AdmissionPipeline, BatchConfig

    wedged = threading.Event()
    release = threading.Event()

    def stuck(payloads):
        wedged.set()
        release.wait(30)
        return [("batched", p) for p in payloads if p is not None]

    p = AdmissionPipeline(
        stuck, scalar_fallback=lambda payload: ("scalar", payload),
        config=BatchConfig(max_batch_size=1, max_wait_ms=1.0, min_bucket=1,
                           eval_grace_s=0.2))
    results = {}
    threads = [threading.Thread(target=lambda i=i: results.update(
        {i: p.submit(f"r{i}", deadline_ms=60_000)})) for i in range(3)]
    threads[0].start()
    assert wedged.wait(5)          # r0 is in-flight on the stuck evaluator
    threads[1].start()
    threads[2].start()
    time.sleep(0.1)                # r1, r2 are queued behind it
    p.stop()                       # join times out (0.2s), drain kicks in
    release.set()                  # unwedge so r0 also completes
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert results[0] == ("batched", "r0")
    assert results[1] == ("scalar", "r1")
    assert results[2] == ("scalar", "r2")
    assert p.queue.depth() == 0


def test_shutdown_drain_without_fallback_resolves_with_error():
    from kyverno_tpu.serving import AdmissionPipeline, BatchConfig
    from kyverno_tpu.serving.queue import QueuedRequest

    p = AdmissionPipeline(lambda payloads: [], config=BatchConfig())
    p.stop()
    # simulate a stranded entry (wedged-flusher shape) and re-drain
    req = QueuedRequest("r", time.monotonic(), time.monotonic() + 60)
    p.queue._items.append(req)
    for leftover in p.queue.drain_all():
        leftover.resolve(RuntimeError("stopped"))
    assert req.event.is_set()


def test_serving_flush_fault_resolves_per_failure_policy():
    """An injected flush failure must come back as a failurePolicy
    decision (deny on the fail class, allow on ignore) — never an
    unhandled exception out of the webhook handler."""
    from tests.test_serving import _mk_handlers, _pod as s_pod, _review

    handlers = _mk_handlers(batching=True, max_batch_size=4, max_wait_ms=5.0)
    try:
        ok = handlers.validate(_review(s_pod("w", False), "warm"))
        assert ok["response"]["allowed"] is True
        global_faults.arm("serving.flush", mode="raise", p=1.0)
        out = handlers.validate(_review(s_pod("p1", True), "u1"))
        assert out["response"]["allowed"] is False  # "all" fails closed
        assert "evaluation error" in out["response"]["status"]["message"]
        out = handlers.validate(_review(s_pod("p2", True), "u2"), "ignore")
        assert out["response"]["allowed"] is True   # Ignore class allows
        global_faults.disarm("serving.flush")
        out = handlers.validate(_review(s_pod("p3", True), "u3"))
        assert out["response"]["allowed"] is False
        assert "privileged" in out["response"]["status"]["message"]
    finally:
        handlers.pipeline.stop()
        handlers.batcher.stop()


# ---------------------------------------------------------------------------
# webhook deadline budget -> failurePolicy


def test_request_budget_overrun_resolves_per_failure_policy():
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.cluster import PolicyCache
    from kyverno_tpu.webhooks import build_handlers
    from tests.test_serving import _review, _pod as s_pod

    cache = PolicyCache()
    cache.set(ClusterPolicy.from_dict(POLICY_DOC))
    handlers = build_handlers(cache, request_timeout_s=0.0)
    try:
        out = handlers.validate(_review(s_pod("p", True), "u1"))
        assert out["response"]["allowed"] is False
        assert "evaluation error" in out["response"]["status"]["message"]
        out = handlers.validate(_review(s_pod("p", True), "u2"), "ignore")
        assert out["response"]["allowed"] is True
    finally:
        handlers.batcher.stop()


def test_force_failure_policy_ignore_toggle_fails_open():
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.cluster import PolicyCache
    from kyverno_tpu.config import Toggles
    from kyverno_tpu.webhooks import build_handlers
    from tests.test_serving import _review, _pod as s_pod

    cache = PolicyCache()
    cache.set(ClusterPolicy.from_dict(POLICY_DOC))
    handlers = build_handlers(
        cache, request_timeout_s=0.0,
        toggles=Toggles(force_failure_policy_ignore="true"))
    try:
        out = handlers.validate(_review(s_pod("p", True), "u1"))
        assert out["response"]["allowed"] is True  # forced fail-open
    finally:
        handlers.batcher.stop()
