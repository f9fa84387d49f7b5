"""Content-addressed verdict/encode caches + pipelined scan (ISSUE 5).

The contract under test: cached and pipelined scans are BIT-IDENTICAL
to the serial uncached path — under resource mutation, policy-set
revision bumps, ns-label changes, context-dep movement, injected
dispatch faults, and LRU pressure — and a repeat scan of an unchanged
resource set serves >=90% of verdicts from the cache.
"""

import numpy as np
import pytest

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.observability.metrics import global_registry as reg
from kyverno_tpu.tpu.cache import (LruCache, VerdictCache,
                                   enable_xla_compile_cache,
                                   global_encode_cache, global_verdict_cache,
                                   request_digest, resource_content_hash)
from kyverno_tpu.tpu.engine import TpuEngine


def _pol(name="p1", field="privileged", value="false"):
    return ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": name},
        "spec": {"rules": [{
            "name": "r1",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"message": "m", "pattern": {"spec": {"containers": [
                {"=(securityContext)": {f"=({field})": value}}]}}},
        }]}})


def _pods(n, priv_every=3, ns="default"):
    return [{
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"p{i}", "namespace": ns, "uid": f"u-{ns}-{i}"},
        "spec": {"containers": [{
            "name": "c", "image": "nginx",
            **({"securityContext": {"privileged": True}}
               if i % priv_every == 0 else {})}]},
    } for i in range(n)]


def _hits(d=0.0):
    return reg.verdict_cache.value({"outcome": "hit"}) - d


def _misses(d=0.0):
    return reg.verdict_cache.value({"outcome": "miss"}) - d


# ---------------------------------------------------------------------------
# LRU primitive


def test_lru_bound_and_eviction_order():
    lru = LruCache(3)
    for k in "abc":
        lru.put(k, k.upper())
    assert len(lru) == 3 and lru.evictions == 0
    lru.get("a")          # refresh: 'b' is now the oldest
    lru.put("d", "D")
    assert lru.evictions == 1
    assert lru.get("b") is None          # evicted
    assert lru.get("a") == "A" and lru.get("d") == "D"
    lru.set_capacity(1)                  # shrink evicts down to bound
    assert len(lru) == 1 and lru.evictions == 3
    lru.set_capacity(0)                  # 0 disables entirely
    lru.put("x", "X")
    assert lru.get("x") is None and len(lru) == 0


def test_verdict_cache_lru_bound_and_metrics():
    vc = VerdictCache(capacity=4, metrics=reg)
    ev0 = reg.verdict_cache_evictions.value()
    for i in range(8):
        vc.put(("k", i), np.full(3, i, dtype=np.int32))
    assert len(vc) == 4
    assert reg.verdict_cache_evictions.value() - ev0 == 4
    col = vc.get(("k", 7))
    assert col.tolist() == [7, 7, 7]
    col[0] = 99                           # caller copies never alias
    assert vc.get(("k", 7)).tolist() == [7, 7, 7]
    assert vc.get(("k", 0)) is None       # evicted


# ---------------------------------------------------------------------------
# verdict cache: bit-identity + content invalidation


def test_cached_scan_bit_identical_and_hits():
    eng = TpuEngine([_pol()])
    assert eng.cache_eligible
    pods = _pods(12)
    first = eng.scan(pods)
    h0, m0 = _hits(), _misses()
    second = eng.scan(pods)
    assert np.array_equal(first.verdicts, second.verdicts)
    assert _hits(h0) == 12 and _misses(m0) == 0
    # the cached result equals the serial uncached oracle exactly
    oracle = eng._scan_uncached(pods)
    assert np.array_equal(second.verdicts, oracle.verdicts)


def test_resource_mutation_invalidates_only_that_resource():
    eng = TpuEngine([_pol()])
    pods = _pods(10)
    eng.scan(pods)
    mutated = [dict(p) for p in pods]
    mutated[4] = {**pods[4], "spec": {"containers": [{
        "name": "c", "image": "nginx",
        "securityContext": {"privileged": True}}]}}
    h0, m0 = _hits(), _misses()
    res = eng.scan(mutated)
    assert _misses(m0) == 1 and _hits(h0) == 9
    assert np.array_equal(res.verdicts,
                          eng._scan_uncached(mutated).verdicts)


def test_policy_revision_bump_invalidates():
    pods = _pods(6)
    eng1 = TpuEngine([_pol(value="false")])
    eng1.scan(pods)
    # same policy NAME, different content -> different policy-set key
    eng2 = TpuEngine([_pol(value="true")])
    h0, m0 = _hits(), _misses()
    res = eng2.scan(pods)
    assert _misses(m0) == 6 and _hits(h0) == 0
    assert np.array_equal(res.verdicts,
                          eng2._scan_uncached(pods).verdicts)
    # and the original engine's entries are still live (no flush)
    h0 = _hits()
    eng1.scan(pods)
    assert _hits(h0) == 6


def test_ns_label_change_invalidates():
    pods = _pods(5)
    eng = TpuEngine([_pol()])
    eng.scan(pods, namespace_labels={"default": {"team": "a"}})
    h0, m0 = _hits(), _misses()
    eng.scan(pods, namespace_labels={"default": {"team": "b"}})
    assert _misses(m0) == 5 and _hits(h0) == 0
    h0 = _hits()
    eng.scan(pods, namespace_labels={"default": {"team": "a"}})
    assert _hits(h0) == 5


def test_operation_and_userinfo_are_part_of_the_key():
    from kyverno_tpu.engine.match import RequestInfo

    pods = _pods(3)
    eng = TpuEngine([_pol()])
    eng.scan(pods, operations=["CREATE"] * 3)
    m0 = _misses()
    eng.scan(pods, operations=["UPDATE"] * 3)
    assert _misses(m0) == 3
    m0 = _misses()
    eng.scan(pods, operations=["CREATE"] * 3,
             admission_infos=[RequestInfo(username="eve")] * 3)
    assert _misses(m0) == 3


def test_context_dep_movement_rotates_the_policyset_key():
    """A configmap folded into the compiled program at compile time is
    part of the policy-set identity: recompiling after the configmap
    moved yields a different cache key, so stale verdicts are
    unreachable by construction."""
    from kyverno_tpu.engine.contextloaders import DataSources
    from kyverno_tpu.tpu.compiler import compile_policy_set

    pol = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "cm-pol"},
        "spec": {"rules": [{
            "name": "r1",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "context": [{"name": "cm", "configMap": {
                "name": "limits", "namespace": "default"}}],
            "validate": {"message": "m", "deny": {"conditions": {"any": [{
                "key": "{{ cm.data.mode }}",
                "operator": "Equals", "value": "deny"}]}}},
        }]}})

    class _CM:
        def __init__(self, mode):
            self.mode = mode

        def get(self, key):
            return {"apiVersion": "v1", "kind": "ConfigMap",
                    "metadata": {"name": "limits", "namespace": "default"},
                    "data": {"mode": self.mode}}

    cps_a = compile_policy_set([pol],
                               data_sources=DataSources(configmaps=_CM("allow")))
    cps_b = compile_policy_set([pol],
                               data_sources=DataSources(configmaps=_CM("deny")))
    assert cps_a.context_deps and cps_b.context_deps
    assert cps_a.cache_key() != cps_b.cache_key()
    # and with identical content the keys agree (no spurious churn)
    cps_a2 = compile_policy_set([pol],
                                data_sources=DataSources(configmaps=_CM("allow")))
    assert cps_a.cache_key() == cps_a2.cache_key()


def test_dyn_slot_sets_are_cache_ineligible():
    """Rules whose context resolves per request (no compile-time
    folding) do real I/O — they must bypass the verdict cache."""
    pol = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "ctx-pol"},
        "spec": {"rules": [{
            "name": "r1",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "context": [{"name": "cm", "configMap": {
                "name": "limits", "namespace": "default"}}],
            "validate": {"message": "m", "deny": {"conditions": {"any": [{
                "key": "{{ cm.data.mode }}",
                "operator": "Equals", "value": "deny"}]}}},
        }]}})
    eng = TpuEngine([pol])  # no data_sources: rule is host fallback
    assert not eng.cache_eligible
    assert eng.verdict_cache_keys(_pods(2)) is None
    b0 = reg.verdict_cache.value({"outcome": "bypass"})
    eng.scan(_pods(2))
    assert reg.verdict_cache.value({"outcome": "bypass"}) - b0 == 1


def test_unhashable_resource_bypasses_but_still_scans():
    eng = TpuEngine([_pol()])
    hostile = {"kind": b"bytes", "metadata": {"name": "h"}}
    pods = _pods(2) + [hostile]
    res = eng.scan(pods)
    assert res.verdicts.shape[1] == 3
    # repeat: the two clean pods hit, the hostile one re-evaluates
    h0 = _hits()
    res2 = eng.scan(pods)
    assert _hits(h0) == 2
    assert np.array_equal(res.verdicts, res2.verdicts)


# ---------------------------------------------------------------------------
# encode-row cache


def test_encode_row_cache_roundtrip_bit_identical():
    eng = TpuEngine([_pol()])
    pods = _pods(8)
    # force the verdict cache off so the second scan re-encodes (and
    # must restore rows from the encode cache)
    cap = global_verdict_cache._lru.capacity
    global_verdict_cache.set_capacity(0)
    try:
        a = eng.encode(pods)[0]
        eh0 = reg.encode_cache.value({"outcome": "hit"})
        b = eng.encode(pods)[0]
        assert reg.encode_cache.value({"outcome": "hit"}) - eh0 == 8
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
        r1 = eng.scan(pods)
        r2 = eng.scan(pods)
        assert np.array_equal(r1.verdicts, r2.verdicts)
    finally:
        global_verdict_cache.set_capacity(cap)


def test_encode_cache_survives_policy_revision_bump():
    """The encode key covers encode caps + byte paths, NOT policy
    content: a revision bump misses the verdict cache but still skips
    the Python re-encode of unchanged resources."""
    pods = _pods(6)
    eng1 = TpuEngine([_pol(value="false")])
    eng1.scan(pods)
    eng2 = TpuEngine([_pol(value="true")])  # same encode shape, new content
    eh0 = reg.encode_cache.value({"outcome": "hit"})
    eng2.scan(pods)
    assert reg.encode_cache.value({"outcome": "hit"}) - eh0 >= 6


def test_encode_cache_disabled_matches_enabled():
    eng = TpuEngine([_pol()])
    pods = _pods(5)
    enabled = eng.encode(pods)[0]
    cap = global_encode_cache._lru.capacity
    global_encode_cache.set_capacity(0)
    try:
        disabled = eng.encode(pods)[0]
    finally:
        global_encode_cache.set_capacity(cap)
    for k in enabled:
        np.testing.assert_array_equal(enabled[k], disabled[k], err_msg=k)


# ---------------------------------------------------------------------------
# pipelined scan


def _sharded(policies):
    from kyverno_tpu.parallel import ShardedScanner, make_mesh

    return ShardedScanner(policies, mesh=make_mesh())


def test_pipelined_scan_bit_identical_to_serial():
    from kyverno_tpu.tpu.pipeline import PipelinedScanner

    sc = _sharded([_pol()])
    pods = _pods(40) + _pods(10, ns="prod")
    serial = sc.scan(pods)
    pipe = PipelinedScanner(sc)
    out = {}
    stats = pipe.scan_chunks([pods[i:i + 16] for i in range(0, len(pods), 16)],
                             on_result=lambda i, r: out.__setitem__(i, r))
    got = np.concatenate([out[i].verdicts for i in sorted(out)], axis=1)
    assert np.array_equal(serial.verdicts, got)
    assert stats["chunks"] == 4 and stats["resources"] == 50
    assert reg.pipeline_chunks.value({"path": "device"}) > 0


def test_pipelined_scan_bit_identical_under_dispatch_faults(no_verdict_cache):
    from kyverno_tpu.resilience.breaker import tpu_breaker
    from kyverno_tpu.resilience.faults import global_faults
    from kyverno_tpu.tpu.pipeline import PipelinedScanner

    sc = _sharded([_pol()])
    pods = _pods(32)
    serial = sc.scan(pods)
    global_faults.arm("tpu.dispatch", mode="raise", p=0.5, seed=11)
    try:
        pipe = PipelinedScanner(sc)
        out = {}
        pipe.scan_chunks([pods[i:i + 8] for i in range(0, 32, 8)],
                         on_result=lambda i, r: out.__setitem__(i, r))
        got = np.concatenate([out[i].verdicts for i in sorted(out)], axis=1)
        assert np.array_equal(serial.verdicts, got)
    finally:
        global_faults.disarm()
        tpu_breaker().reset()


def test_pipelined_scan_encode_failure_falls_back_not_aborts():
    from kyverno_tpu.tpu.pipeline import PipelinedScanner

    sc = _sharded([_pol()])
    hostile = {"kind": b"bytes-break-encoding", "metadata": {"name": "h"}}
    pods = _pods(8)
    chunks = [pods[:4], pods[4:] + [hostile]]
    pipe = PipelinedScanner(sc)
    out = {}
    stats = pipe.scan_chunks(chunks,
                             on_result=lambda i, r: out.__setitem__(i, r))
    assert stats["encode_fallback_chunks"] == 1
    assert out[1].verdicts.shape[1] == 5
    # clean chunk verdicts match the serial oracle
    serial = sc.scan(pods[:4])
    assert np.array_equal(out[0].verdicts, serial.verdicts)


# ---------------------------------------------------------------------------
# scan service: repeat-scan hit rate + churn invalidation


def test_repeat_scan_serves_90pct_from_cache():
    from kyverno_tpu.cluster import (BackgroundScanService, ClusterSnapshot,
                                     PolicyCache)

    snap = ClusterSnapshot()
    cache = PolicyCache()
    cache.set(_pol())
    svc = BackgroundScanService(snap, cache)
    for p in _pods(30):
        snap.upsert(p)
    assert svc.scan_once(full=True) == 30
    h0, m0 = _hits(), _misses()
    n = svc.scan_once(full=True)
    assert n == 30
    hits = _hits(h0)
    assert hits >= 0.9 * n, f"only {hits}/{n} served from cache"
    assert _misses(m0) == 0
    assert svc.stats["verdict_cache_hits"] >= 27
    # verdicts identical across the cached rescan
    report_a = svc.aggregator.summary()
    svc.scan_once(full=True)
    assert svc.aggregator.summary() == report_a


def test_policy_churn_invalidates_scan_cache():
    from kyverno_tpu.cluster import (BackgroundScanService, ClusterSnapshot,
                                     PolicyCache)

    snap = ClusterSnapshot()
    cache = PolicyCache()
    cache.set(_pol(value="false"))
    svc = BackgroundScanService(snap, cache)
    for p in _pods(10):
        snap.upsert(p)
    svc.scan_once(full=True)
    cache.set(_pol(value="true"))  # revision bump, new content
    h0, m0 = _hits(), _misses()
    svc.scan_once(full=True)
    assert _misses(m0) == 10 and _hits(h0) == 0


# ---------------------------------------------------------------------------
# admission submit-path cache


def test_admission_submit_serves_repeat_manifest_from_cache():
    import time

    from kyverno_tpu.cluster import PolicyCache
    from kyverno_tpu.engine.match import RequestInfo
    from kyverno_tpu.webhooks import build_handlers
    from kyverno_tpu.webhooks.server import AdmissionPayload

    cache = PolicyCache()
    cache.set(_pol())
    h = build_handlers(cache, batching=True)
    h.lifecycle.start()
    try:
        deadline = time.monotonic() + 120
        while h.lifecycle.active is None and time.monotonic() < deadline:
            time.sleep(0.02)
        pod = _pods(1)[0]
        payload = AdmissionPayload(pod, "CREATE", RequestInfo(), "default")
        r1 = h.pipeline.submit(payload)
        r2 = h.pipeline.submit(payload)
        assert list(r1) == list(r2)
        assert r2.revision == r1.revision
        assert h.pipeline.stats.get("cache_hits", 0) == 1
        # a different manifest is a miss, not a false hit
        other = AdmissionPayload(_pods(2)[1], "CREATE", RequestInfo(),
                                 "default")
        h.pipeline.submit(other)
        assert h.pipeline.stats.get("cache_hits", 0) == 1
    finally:
        h.lifecycle.stop()
        h.pipeline.stop()
        h.batcher.stop()


# ---------------------------------------------------------------------------
# key/hash helpers + persistent XLA cache


def test_resource_content_hash_stability():
    a = {"kind": "Pod", "metadata": {"name": "x", "labels": {"a": "1"}}}
    b = {"metadata": {"labels": {"a": "1"}, "name": "x"}, "kind": "Pod"}
    assert resource_content_hash(a) == resource_content_hash(b)
    assert resource_content_hash({"k": b"bytes"}) is None
    # the scan service threads the snapshot's stored hashes into the
    # verdict keys — the two hash functions must agree byte-for-byte
    from kyverno_tpu.cluster.snapshot import resource_hash

    assert resource_content_hash(a) == resource_hash(a)
    assert request_digest({"t": "a"}, "CREATE", None) != \
        request_digest({"t": "b"}, "CREATE", None)
    assert request_digest({}, "", None) != request_digest({}, "CREATE", None)


def _reset_xla_cache():
    """Leave the process-global compile-cache config pristine."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    import kyverno_tpu.tpu.cache as cache_mod

    jax.config.update("jax_compilation_cache_dir", None)
    cache_mod._xla_cache_dir = None
    cc.reset_cache()


def test_xla_compile_cache_dir_populates(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path / "xla")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert enable_xla_compile_cache() == d
    try:
        assert jax.config.jax_compilation_cache_dir == d

        @jax.jit
        def f(x):
            return x * 2 + 1

        f(jnp.arange(8)).block_until_ready()
    finally:
        _reset_xla_cache()
    import os

    assert os.path.isdir(d)


@pytest.mark.parametrize("value", ["false", "0"])
def test_enable_xla_cache_off_by_jax_env(monkeypatch, value):
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", value)
    assert enable_xla_compile_cache() is None


def test_xla_cache_default_is_repo_dir_whatever_the_cwd(tmp_path,
                                                        monkeypatch):
    import os

    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    try:
        assert enable_xla_compile_cache() == os.path.join(repo, ".xla_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".xla_cache")
    finally:
        _reset_xla_cache()


@pytest.mark.parametrize("env_dir", [True, False],
                         ids=["env", "repo_default"])
def test_serve_compile_cache_dir(tmp_path, env_dir):
    """serve uses $JAX_COMPILATION_CACHE_DIR as JAX sees it, else
    <repo>/.xla_cache — never a directory relative to its cwd."""
    import json
    import os
    import socket
    import subprocess
    import sys
    import time
    import urllib.request

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    want = os.path.join(repo, ".xla_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    p = subprocess.Popen(
        [sys.executable, "-m", "kyverno_tpu", "serve",
         os.path.join(repo, "kyverno_tpu", "policies", "pss",
                      "disallow-host-path.yaml"),
         "--engine", "scalar", "--port", "0", "--metrics-port", str(port),
         "--scan-interval", "9999"],
        cwd=tmp_path, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 60
        while True:
            assert p.poll() is None, "serve exited at startup"
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/debug/state",
                        timeout=5) as r:
                    state = json.loads(r.read())
                break
            except OSError:
                assert time.monotonic() < deadline, "serve never answered"
                time.sleep(0.2)
    finally:
        p.terminate()
        p.wait(timeout=30)
    assert state["perf_caches"]["xla_cache_dir"] == want
    assert not (tmp_path / ".xla_cache").exists()
