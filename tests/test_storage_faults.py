"""Storage fault matrix + degraded-storage ladder (ISSUE 19).

Every durability surface routes through the ``resilience/storage``
shim, so one fault grammar (``storage.write:enospc:match=reports``)
can make any surface's disk fail with a REAL ``OSError`` — the same
except-clause a genuinely full, erroring, or read-only disk takes.
The contract under test, per surface:

- no exception escapes to the caller (verdict paths stay correct);
- the surface degrades: ``kyverno_storage_degraded{surface}`` flips
  to 1, errors count by kind, the op-log narrates the transition;
- the surface's memory mode is bit-identical (reports fold digest ==
  an undegraded twin; columnar reads off anonymous arenas == a fresh
  encode);
- disarm + a due re-probe heals: gauge back to 0, heal counted, and
  durability is re-established (reports compact the in-memory state
  to a snapshot a cold reopen recovers completely).

The slow legs drive a REAL serve subprocess: one with the fault armed
ambient through a churn scan (the ISSUE 19 acceptance), one with a
genuine OS failure manufactured via RLIMIT_FSIZE — proving injected
and real disk errors travel the same ladder.
"""

import errno
import http.client
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from kyverno_tpu.observability.metrics import global_registry as reg
from kyverno_tpu.resilience import storage as st
from kyverno_tpu.resilience.faults import FaultConfigError, global_faults


@pytest.fixture(autouse=True)
def _disarm_faults():
    global_faults.disarm()
    yield
    global_faults.disarm()


def _gauge(surface):
    return reg.storage_degraded.value({"surface": surface})


# ---------------------------------------------------------------------------
# the ladder itself


def test_classify_os_error_covers_the_matrix():
    assert st.classify_os_error(OSError(errno.ENOSPC, "x")) == "enospc"
    # EFBIG is how RLIMIT_FSIZE (the real-ENOSPC CI leg) surfaces
    assert st.classify_os_error(OSError(errno.EFBIG, "x")) == "enospc"
    assert st.classify_os_error(OSError(errno.EIO, "x")) == "eio"
    assert st.classify_os_error(OSError(errno.EROFS, "x")) == "erofs"
    assert st.classify_os_error(OSError(errno.EACCES, "x")) == "erofs"
    assert st.classify_os_error(OSError(errno.EPIPE, "x")) == "other"


def test_ladder_degrades_gates_probes_and_heals():
    clock = [0.0]
    h = st.StorageHealth("reports", clock=lambda: clock[0])
    assert h.allow()  # healthy: always
    assert h.record_error(OSError(errno.ENOSPC, "full"), op="write") \
        == "enospc"
    assert h.degraded
    assert _gauge("reports") == 1.0
    # no probe due yet: writes are counted drops
    assert not h.allow()
    assert h.state()["drops"] == 1
    clock[0] += 100.0
    assert h.allow()       # the due probe consumes the slot...
    assert not h.allow()   # ...so a concurrent writer is still dropped
    assert h.record_success() is True   # heal transition, exactly once
    assert h.record_success() is False
    assert not h.degraded
    assert _gauge("reports") == 0.0
    s = h.state()
    assert s["errors"] == 1 and s["heals"] == 1 and s["probes"] == 1
    assert s["last_kind"] == "enospc" and s["last_errno"] == errno.ENOSPC
    assert reg.storage_heals.value({"surface": "reports"}) >= 1


def test_os_error_modes_only_arm_at_storage_sites():
    with pytest.raises(FaultConfigError):
        global_faults.arm("tpu.dispatch", mode="enospc")
    with pytest.raises(FaultConfigError):
        global_faults.arm("reports.journal", mode="eio")
    with pytest.raises(FaultConfigError):
        global_faults.arm("storage.open", mode="short")  # write-only mode
    global_faults.arm("storage.write", mode="short")  # fine


def test_injected_enospc_is_a_real_oserror_scoped_by_surface(tmp_path):
    errors0 = reg.storage_errors.value({"surface": "reports",
                                        "kind": "enospc"})
    global_faults.arm("storage.write", mode="enospc", match="reports")
    fh = st.open_append(str(tmp_path / "j.wal"), st.SURFACE_REPORTS,
                        binary=True)
    with pytest.raises(OSError) as ei:
        st.write_frame(fh, b"x" * 16, st.SURFACE_REPORTS,
                       path=str(tmp_path / "j.wal"))
    fh.close()
    assert ei.value.errno == errno.ENOSPC
    assert st.storage_health(st.SURFACE_REPORTS).degraded
    assert reg.storage_errors.value(
        {"surface": "reports", "kind": "enospc"}) == errors0 + 1
    # match=reports scopes the fault: the oplog surface writes fine
    fh2 = st.open_append(str(tmp_path / "op.jsonl"), st.SURFACE_OPLOG)
    st.write_frame(fh2, "ok\n", st.SURFACE_OPLOG)
    fh2.close()
    assert not st.storage_health(st.SURFACE_OPLOG).degraded


def test_short_write_tears_a_real_prefix_then_raises_eio(tmp_path):
    global_faults.arm("storage.write", mode="short", count=1)
    path = tmp_path / "seg.ndjson"
    fh = st.open_truncate(str(path), st.SURFACE_FLIGHT)
    with pytest.raises(OSError) as ei:
        st.write_frame(fh, "0123456789", st.SURFACE_FLIGHT, path=str(path))
    fh.close()
    assert ei.value.errno == errno.EIO
    assert path.read_text() == "01234"  # the torn half really landed


# ---------------------------------------------------------------------------
# surface: reports — memory-only folding, bit-identical, compact-on-heal


def _put(store, i, mark="a"):
    store.apply(f"u{i}", f"sha-{mark}-{i}", "ps", f"ns{i % 2}", "Pod",
                f"p{i}", [("pol", "r", "fail" if i % 3 == 0 else "pass")])


@pytest.mark.parametrize("kind", ["enospc", "eio", "erofs", "short"])
def test_reports_fold_memory_only_then_heal_recovers_all(tmp_path, kind):
    from kyverno_tpu.reports.store import ReportStore

    d = str(tmp_path / "rep")
    store = ReportStore(directory=d)
    twin = ReportStore(directory=None)  # the undegraded oracle
    _put(store, 0)
    _put(twin, 0)
    global_faults.arm("storage.write", mode=kind, match="reports")
    for i in range(1, 8):  # must not raise: memory-only folding
        _put(store, i)
        _put(twin, i)
    h = st.storage_health(st.SURFACE_REPORTS)
    assert h.degraded
    assert _gauge("reports") == 1.0
    assert reg.storage_errors.value({"surface": "reports", "kind":
                                     "eio" if kind == "short" else kind}) > 0
    # the degraded fold is bit-identical to the never-degraded twin
    assert store.digest() == twin.digest()
    assert store.verify_rebuild()
    # disarm -> due probe -> the next fold lands AND compaction
    # re-establishes durability for every row folded in memory
    global_faults.disarm()
    h.force_probe()
    _put(store, 99)
    _put(twin, 99)
    assert not h.degraded
    assert _gauge("reports") == 0.0
    assert reg.storage_heals.value({"surface": "reports"}) >= 1
    assert store.stats["compactions"] >= 1
    store.close(compact=False)  # dirty close: disk must already be whole
    recovered = ReportStore(directory=d)
    assert recovered.digest() == twin.digest()
    assert recovered.verify_rebuild()
    recovered.close()
    twin.close()


def test_reports_unwritable_dir_at_boot_folds_in_memory(tmp_path):
    from kyverno_tpu.reports.store import ReportStore

    global_faults.arm("storage.open", mode="erofs", match="reports")
    store = ReportStore(directory=str(tmp_path / "ro"))  # must not raise
    _put(store, 1)
    assert st.storage_health(st.SURFACE_REPORTS).degraded
    assert store.state()["resources"] == 1
    assert store.verify_rebuild()
    store.close(compact=False)


# ---------------------------------------------------------------------------
# surface: columnar — anonymous arenas, bit-identical reads, remount


def _pod(i, app="a"):
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"p{i}", "namespace": "default",
                     "uid": f"uid-{i}", "labels": {"app": f"{app}{i % 3}"}},
        "spec": {"containers": [
            {"name": "c", "image": "nginx:1.25",
             "securityContext": {"privileged": i % 2 == 0}}]},
    }


def test_columnar_drops_to_anonymous_arenas_and_remounts(tmp_path):
    from kyverno_tpu.cluster.columnar import ColumnarStore
    from kyverno_tpu.tpu.cache import extract_rows, resource_content_hash
    from kyverno_tpu.tpu.flatten import EncodeConfig, encode_resources

    cfg = EncodeConfig()
    store = ColumnarStore(directory=str(tmp_path / "col"))
    pods = [_pod(i) for i in range(6)]
    for r in pods:
        store.warm(cfg, (), (), r, resource_content_hash(r))
    store.sync()  # healthy: arenas + manifests on disk
    assert not st.storage_health(st.SURFACE_COLUMNAR).degraded

    global_faults.arm("storage.write", mode="eio", match="columnar")
    pods = [_pod(i, app="b") for i in range(6)]  # churn: new rows
    for r in pods:
        store.warm(cfg, (), (), r, resource_content_hash(r))
    store.sync()  # must not raise: tables drop to anonymous arenas
    h = st.storage_health(st.SURFACE_COLUMNAR)
    assert h.degraded
    assert _gauge("columnar") == 1.0
    assert any(t["memory_only"] for t in store.state()["tables"])

    # reads off the anonymous arenas stay bit-identical
    ekey = store.encode_key(cfg, (), ())
    for r in pods:
        e = store.get_entry(ekey, resource_content_hash(r))
        assert e is not None
        ref = extract_rows(encode_resources([r], cfg, (), ()), 0)
        assert e.n_rows == ref.n_rows
        for name in ref.lanes:
            assert np.array_equal(e.lanes[name], ref.lanes[name]), name

    global_faults.disarm()
    h.force_probe()
    store.sync()  # due probe: remount the mmap backing + flush
    assert not h.degraded
    assert _gauge("columnar") == 0.0
    assert all(not t["memory_only"] for t in store.state()["tables"])
    assert all(t["mmap"] for t in store.state()["tables"])
    # the remounted backing survives a cold restart with the rows intact
    reopened = ColumnarStore(directory=str(tmp_path / "col"))
    for r in pods:
        e = reopened.get_entry(ekey, resource_content_hash(r))
        assert e is not None
        ref = extract_rows(encode_resources([r], cfg, (), ()), 0)
        for name in ref.lanes:
            assert np.array_equal(e.lanes[name], ref.lanes[name]), name


# ---------------------------------------------------------------------------
# surfaces: flight spool + divergences — drop-and-count, independent


def test_spool_vs_divergence_surfaces_independent(tmp_path):
    # NB: the test name must not contain a surface name — tmp_path
    # embeds it, and match=<surface> greps the full "<surface>:<path>"
    # payload (that substring semantic is exactly what scopes a chaos
    # run to one surface in production paths)
    from kyverno_tpu.observability.flightrecorder import (global_flight,
                                                          load_capture)

    global_flight.configure(capacity=16, sample_rate=1.0,
                            spool_dir=str(tmp_path / "spool"))
    for i in range(4):
        global_flight.record_admission(
            {"kind": "Pod", "metadata": {"name": f"p{i}"}},
            [(("pol", "r"), 0)], "batched")

    global_faults.arm("storage.write", mode="enospc", match="flight_spool")
    assert global_flight.spool(force=True) is None  # counted drop
    assert st.storage_health(st.SURFACE_FLIGHT).degraded
    assert len(global_flight) == 4  # the in-memory ring keeps recording

    # the divergence surface is its OWN ladder: evidence still lands
    path = global_flight.spool_divergence(
        {"seq": 1, "resource": {"kind": "Pod"}},
        [(("pol", "r"), 0)], [(("pol", "r"), 2)])
    assert path is not None
    assert not st.storage_health(st.SURFACE_DIVERGENCES).degraded
    assert load_capture(path)

    global_faults.disarm()
    st.storage_health(st.SURFACE_FLIGHT).force_probe()
    out = global_flight.spool(force=True)  # the probe spool heals
    assert out is not None
    assert not st.storage_health(st.SURFACE_FLIGHT).degraded
    assert reg.storage_heals.value({"surface": "flight_spool"}) >= 1
    assert len(load_capture(out)) == 4


# ---------------------------------------------------------------------------
# surface: oplog — file sink drop-and-count, stderr untouched, no deadlock


def test_oplog_file_sink_drops_counts_and_heals(tmp_path):
    from kyverno_tpu.observability.log import global_oplog

    path = tmp_path / "op.jsonl"
    global_oplog.configure(path=str(path), stderr=False)
    global_oplog.emit("healthy")
    global_faults.arm("storage.write", mode="eio", match="oplog")
    for _ in range(5):
        global_oplog.emit("sick")  # must not raise, must not deadlock
    h = st.storage_health(st.SURFACE_OPLOG)
    assert h.degraded
    assert _gauge("oplog") == 1.0
    assert h.state()["drops"] > 0

    global_faults.disarm()
    h.force_probe()
    global_oplog.emit("healed")
    assert not h.degraded
    events = [json.loads(ln)["event"]
              for ln in path.read_text().splitlines() if ln.strip()]
    assert "healthy" in events and "healed" in events
    assert "sick" not in events          # dropped, not torn
    assert "storage_healed" in events    # the ladder narrates itself
    global_oplog.reset()


def test_oplog_unopenable_sink_degrades_instead_of_raising(tmp_path):
    from kyverno_tpu.observability.log import global_oplog

    global_faults.arm("storage.open", mode="erofs", match="oplog")
    global_oplog.configure(path=str(tmp_path / "op.jsonl"), stderr=False)
    assert st.storage_health(st.SURFACE_OPLOG).degraded
    global_oplog.emit("while-down")  # no raise
    global_faults.disarm()
    st.storage_health(st.SURFACE_OPLOG).force_probe()
    global_oplog.emit("back")  # the probe retries the open itself
    assert not st.storage_health(st.SURFACE_OPLOG).degraded
    assert os.path.exists(tmp_path / "op.jsonl")
    global_oplog.reset()


# ---------------------------------------------------------------------------
# surface: trace_export — exporter born degraded reopens on probe


def test_trace_exporter_degrades_at_birth_and_reopens(tmp_path):
    from kyverno_tpu.observability.tracing import (OTLPJsonFileExporter,
                                                   Tracer)

    path = str(tmp_path / "trace.otlp.jsonl")
    global_faults.arm("storage.open", mode="erofs", match="trace_export")
    tr = Tracer(exporter=OTLPJsonFileExporter(path))  # must not raise
    with tr.span("while-down"):
        pass
    h = st.storage_health(st.SURFACE_TRACE)
    assert h.degraded
    assert _gauge("trace_export") == 1.0

    global_faults.disarm()
    h.force_probe()
    with tr.span("after-heal"):
        pass
    assert not h.degraded
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    names = [ln["resourceSpans"][0]["scopeSpans"][0]["spans"][0]["name"]
             for ln in lines]
    assert names == ["after-heal"]  # dropped span dropped, healed span real


# ---------------------------------------------------------------------------
# surface: xla_cache — unwritable dir disables the cache, never a compile


def test_xla_cache_unwritable_dir_disables_persistent_cache(tmp_path,
                                                            monkeypatch):
    import jax

    import kyverno_tpu.tpu.cache as cache_mod
    from kyverno_tpu.observability.log import global_oplog

    monkeypatch.setattr(cache_mod, "_xla_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    seen = []
    monkeypatch.setattr(global_oplog, "emit",
                        lambda event, **kw: seen.append(event))
    # makedirs(exist_ok=True) succeeds on an existing dir even on a
    # read-only mount — only the probe-file write catches this
    global_faults.arm("storage.write", mode="erofs", match="xla_cache")
    was = jax.config.jax_enable_compilation_cache
    try:
        assert cache_mod.enable_xla_compile_cache() is None
        assert cache_mod.xla_cache_dir() is None
        # JAX itself stops using the directory it was given
        assert jax.config.jax_enable_compilation_cache is False
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    h = st.storage_health(st.SURFACE_XLA_CACHE)
    assert h.degraded
    assert "xla_cache_disabled" in seen
    global_faults.disarm()
    h.force_probe()
    st.probe_writable(str(tmp_path), st.SURFACE_XLA_CACHE)
    assert not h.degraded
    assert reg.storage_heals.value({"surface": "xla_cache"}) >= 1


# ---------------------------------------------------------------------------
# the /debug/state + /readyz surfaces


def test_debug_state_and_readyz_carry_storage_advisory():
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.cluster.policycache import PolicyCache
    from kyverno_tpu.cluster.snapshot import ClusterSnapshot
    from kyverno_tpu.webhooks.server import Handlers, handle_debug_path

    cache = PolicyCache()
    cache.set(ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "storage-dbg"},
        "spec": {"validationFailureAction": "Enforce", "rules": [{
            "name": "named",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"message": "m",
                         "pattern": {"metadata": {"name": "?*"}}},
        }]}}))
    h = Handlers(cache, ClusterSnapshot(), batching=True)
    try:
        st.storage_health(st.SURFACE_REPORTS).record_error(
            OSError(errno.ENOSPC, "full"), op="write")
        status, body, _ = handle_debug_path("/debug/state", h)
        assert status == 200
        doc = json.loads(body)
        assert doc["storage"]["reports"]["state"] == "degraded"
        assert doc["storage"]["reports"]["last_kind"] == "enospc"
        assert st.global_storage.degraded_surfaces() == ["reports"]
        ok, detail = h.ready()
        assert ok  # degraded storage NEVER flips readiness
        assert detail["storage_degraded"] == ["reports"]
        st.storage_health(st.SURFACE_REPORTS).record_success()
        _, detail = h.ready()
        assert "storage_degraded" not in detail
    finally:
        h.pipeline.stop()
        h.batcher.stop()


# ---------------------------------------------------------------------------
# slow legs: a REAL serve process under ambient + genuine disk failure


N_PODS = 60


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _post(port, path, doc, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(doc),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _serve_pods(n, mark="a"):
    return [{
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": f"ns{i % 4}",
                     "uid": f"u-{i}", "labels": {"rev": mark}},
        "spec": {"containers": [{
            "name": "c", "image": "nginx",
            **({"securityContext": {"privileged": True}}
               if i % 3 == 0 else {})}]},
    } for i in range(n)]


def _metric(text, name, **labels):
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest and rest[0] not in ("{", " "):
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            try:
                total += float(line.split(" # ")[0].rsplit(" ", 1)[-1])
            except ValueError:
                pass
    return total


def _policy_yaml(tmp_path):
    import yaml

    policy_file = tmp_path / "policy.yaml"
    policy_file.write_text(yaml.safe_dump({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "storage-chaos"},
        "spec": {"validationFailureAction": "Enforce", "rules": [{
            "name": "no-privileged",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"message": "no privileged",
                         "pattern": {"spec": {"containers": [
                             {"=(securityContext)":
                              {"=(privileged)": "false"}}]}}},
        }]}}))
    return policy_file


@pytest.fixture
def serve_procs():
    procs = []
    yield procs
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=15)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait(timeout=5)


def _wait_ready(p, metrics_port, timeout=300):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if p.poll() is not None:
            raise AssertionError(
                "serve died at boot:\n" + (p.stderr.read() or "")[-3000:])
        try:
            status, _ = _get(metrics_port, "/healthz", timeout=2)
            if status == 200:
                return
        except OSError:
            pass
        time.sleep(0.3)
    raise AssertionError("serve never became healthy")


@pytest.mark.slow
def test_ambient_enospc_churn_scan_degrades_heals_bit_identical(
        tmp_path, serve_procs):
    """ISSUE 19 acceptance: storage.write:enospc armed ambient on the
    reports surface through a churn scan — zero escaped exceptions,
    zero verdict divergence at shadow-verify 1.0, the degraded gauge
    raised while sick, then (the injected fault exhausts its count=5
    budget against the capped re-probes) the store heals, compacts,
    and the offline --rebuild-check recovers bit-identically."""
    policy_file = _policy_yaml(tmp_path)
    reports_dir = tmp_path / "reports"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla"),
                # fires on the first 5 matched storage writes (the
                # first journal append + the next 4 re-probes), then
                # the disk "recovers" — the heal path needs no disarm
                # endpoint, exactly like space being freed
                "KYVERNO_TPU_FAULTS":
                    "storage.write:enospc:match=reports,count=5"})
    metrics_port = _free_port()
    p = subprocess.Popen(
        [sys.executable, "-m", "kyverno_tpu", "serve", str(policy_file),
         "--port", "0", "--metrics-port", str(metrics_port),
         "--scan-interval", "9999", "--batching",
         "--reports-dir", str(reports_dir),
         "--shadow-verify-rate", "1.0",
         "--flight-sample-rate", "1.0"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    serve_procs.append(p)
    _wait_ready(p, metrics_port)

    for pod in _serve_pods(N_PODS):
        status, _ = _post(metrics_port, "/snapshot/upsert", pod)
        assert status == 200
    status, body = _post(metrics_port, "/scan", {"full": True})
    assert status == 200
    assert json.loads(body)["scanned"] == N_PODS

    # the first journal append degraded the surface; readiness is
    # NEVER flipped by sick storage (it is an advisory)
    status, body = _get(metrics_port, "/metrics")
    text = body.decode()
    assert _metric(text, "kyverno_storage_degraded", surface="reports") == 1
    assert _metric(text, "kyverno_storage_errors_total",
                   surface="reports", kind="enospc") >= 1
    status, body = _get(metrics_port, "/readyz")
    assert status == 200
    detail = json.loads(body)
    assert detail.get("storage_degraded") == ["reports"]
    status, body = _get(metrics_port, "/debug/state")
    assert json.loads(body)["storage"]["reports"]["state"] == "degraded"

    # churn: mutate every pod + rescan to keep folds (and re-probes)
    # flowing until the fault budget exhausts and a probe append heals
    healed = False
    deadline = time.monotonic() + 120
    rev = 0
    while time.monotonic() < deadline:
        rev += 1
        for pod in _serve_pods(N_PODS, mark=f"r{rev}"):
            _post(metrics_port, "/snapshot/upsert", pod)
        status, _ = _post(metrics_port, "/scan", {"full": True})
        assert status == 200  # zero exceptions escape throughout
        _, body = _get(metrics_port, "/metrics")
        text = body.decode()
        if _metric(text, "kyverno_storage_degraded", surface="reports") == 0 \
                and _metric(text, "kyverno_storage_heals_total",
                            surface="reports") >= 1:
            healed = True
            break
        time.sleep(2.0)
    assert healed, "reports surface never healed after the fault budget"

    # shadow verification at rate 1.0 saw zero divergence end to end
    def matches():
        _, b = _get(metrics_port, "/metrics")
        return _metric(b.decode(), "kyverno_verification_checks_total",
                       result="match")

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and matches() == 0:
        time.sleep(0.5)
    _, body = _get(metrics_port, "/metrics")
    text = body.decode()
    assert _metric(text, "kyverno_verification_divergence_total") == 0
    assert _metric(text, "kyverno_verification_checks_total",
                   result="match") > 0
    status, body = _get(metrics_port, "/readyz")
    assert status == 200
    assert "storage_degraded" not in json.loads(body)

    p.terminate()
    p.wait(timeout=15)

    # heal-time compaction made the in-memory folds durable: the
    # offline oracle recovers every row bit-identically
    cli_env = dict(env)
    cli_env.pop("KYVERNO_TPU_FAULTS")
    cli = subprocess.run(
        [sys.executable, "-m", "kyverno_tpu", "report", str(reports_dir),
         "--rebuild-check", "--json"],
        env=cli_env, capture_output=True, text=True, timeout=120)
    assert cli.returncode == 0, cli.stderr[-2000:]
    doc = json.loads(cli.stdout)
    assert doc["rebuild_identical"] is True
    assert doc["state"]["resources"] == N_PODS


@pytest.mark.slow
def test_real_enospc_via_rlimit_fsize_shares_the_injected_path(
        tmp_path, serve_procs):
    """No fault armed at all: the child's RLIMIT_FSIZE makes the
    journal writes genuinely fail (EFBIG, SIGXFSZ ignored) once the
    WAL crosses the limit — and the SAME ladder the injected tests
    exercised absorbs it: degraded+counted (kind=enospc), serving and
    readiness stay green, zero divergence."""
    policy_file = _policy_yaml(tmp_path)
    reports_dir = tmp_path / "reports"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                # persistent XLA cache writes would trip the rlimit too
                "JAX_ENABLE_COMPILATION_CACHE": "false"})
    env.pop("KYVERNO_TPU_FAULTS", None)
    metrics_port = _free_port()
    bootstrap = (
        "import resource, signal, sys, runpy;"
        "signal.signal(signal.SIGXFSZ, signal.SIG_IGN);"
        "resource.setrlimit(resource.RLIMIT_FSIZE, (8192, 8192));"
        "sys.argv = ['kyverno_tpu'] + sys.argv[1:];"
        "runpy.run_module('kyverno_tpu', run_name='__main__')")
    p = subprocess.Popen(
        [sys.executable, "-c", bootstrap, "serve", str(policy_file),
         "--port", "0", "--metrics-port", str(metrics_port),
         "--scan-interval", "9999", "--batching",
         "--reports-dir", str(reports_dir),
         "--shadow-verify-rate", "1.0",
         "--flight-sample-rate", "1.0"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    serve_procs.append(p)
    _wait_ready(p, metrics_port)

    for pod in _serve_pods(N_PODS):
        status, _ = _post(metrics_port, "/snapshot/upsert", pod)
        assert status == 200
    # ~60 journaled folds blow through the 8 KiB cap mid-scan
    status, body = _post(metrics_port, "/scan", {"full": True})
    assert status == 200
    assert json.loads(body)["scanned"] == N_PODS

    _, body = _get(metrics_port, "/metrics")
    text = body.decode()
    assert _metric(text, "kyverno_storage_degraded", surface="reports") == 1
    # EFBIG classifies as the space-exhaustion kind: one code path
    assert _metric(text, "kyverno_storage_errors_total",
                   surface="reports", kind="enospc") >= 1
    status, body = _get(metrics_port, "/readyz")
    assert status == 200  # advisory only, never flips readiness
    assert json.loads(body).get("storage_degraded") == ["reports"]

    # the engine keeps serving scans correctly on the sick disk
    status, body = _post(metrics_port, "/scan", {"full": True})
    assert status == 200
    assert json.loads(body)["scanned"] == N_PODS

    def matches():
        _, b = _get(metrics_port, "/metrics")
        return _metric(b.decode(), "kyverno_verification_checks_total",
                       result="match")

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and matches() == 0:
        time.sleep(0.5)
    _, body = _get(metrics_port, "/metrics")
    text = body.decode()
    assert _metric(text, "kyverno_verification_divergence_total") == 0
    assert _metric(text, "kyverno_verification_checks_total",
                   result="match") > 0
    assert p.poll() is None, "serve must survive a genuinely full disk"
