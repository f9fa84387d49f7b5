"""ControlPlane (`serve`): admission + snapshot + scan + metrics
round-trip over HTTP."""

import http.client
import json

import pytest

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.cli.serve import ControlPlane

POLICY = ClusterPolicy.from_dict({
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "no-privileged"},
    "spec": {
        "validationFailureAction": "Enforce",
        "rules": [{
            "name": "privileged",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {
                "message": "privileged is forbidden",
                "pattern": {"spec": {"containers": [
                    {"=(securityContext)": {"=(privileged)": "false"}}]}},
            },
        }],
    },
})


def pod(name, priv):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [{
                "name": "c", "image": "nginx",
                "securityContext": {"privileged": priv}}]}}


@pytest.fixture(scope="module")
def cp():
    plane = ControlPlane([POLICY], port=0, metrics_port=0)
    plane.start(scan_interval=3600)  # scans driven explicitly below
    yield plane
    plane.stop()


def _req(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request(method, path, json.dumps(body) if body is not None else None,
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_snapshot_scan_reports_metrics(cp):
    mport = cp.metrics_server.server_address[1]
    for i, priv in enumerate([True, False, False]):
        status, _ = _req(mport, "POST", "/snapshot/upsert", pod(f"p{i}", priv))
        assert status == 200
    status, data = _req(mport, "POST", "/scan", {})
    out = json.loads(data)
    assert status == 200 and out["scanned"] == 3
    assert out["summary"]["fail"] == 1 and out["summary"]["pass"] == 2
    status, data = _req(mport, "GET", "/reports")
    reports = json.loads(data)
    assert reports["default"]["summary"]["fail"] == 1
    status, data = _req(mport, "GET", "/metrics")
    assert status == 200 and b"# TYPE" in data


def test_admission_alongside_scan(cp):
    review = {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
              "request": {"uid": "u", "operation": "CREATE",
                          "namespace": "default", "object": pod("adm", True)}}
    status, data = _req(cp.admission.port, "POST", "/validate", review)
    out = json.loads(data)
    assert status == 200 and out["response"]["allowed"] is False


MUTATE = ClusterPolicy.from_dict({
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "add-team"},
    "spec": {"rules": [{
        "name": "team",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "mutate": {"patchStrategicMerge": {
            "metadata": {"labels": {"+(team)": "core"}}}},
    }]},
})


@pytest.mark.parametrize("batching,mutate,want", [
    # --max-batch-size 40 -> bucket 64
    (True, False, {"scan": [16, 32, 64]}),
    # the plain MicroBatcher flushes up to 256
    (False, False, {"scan": [16, 32, 64, 128, 256]}),
    # --mutate-batching: the triage program's buckets too
    (True, True, {"scan": [16, 32, 64], "triage": [16, 32, 64]}),
])
def test_ready_only_after_every_admission_bucket_compiled(
        monkeypatch, batching, mutate, want):
    """/readyz goes ready only once every batch shape an admission
    flush can dispatch has been built: none compiles on the request
    path, inside a request's budget."""
    from kyverno_tpu.serving import BatchConfig
    from kyverno_tpu.tpu.engine import TpuEngine

    warmed = {}
    for kind, name in (("scan", "_scan_uncached"),
                       ("triage", "_triage_uncached")):
        def record(self, resources, *a, _real=getattr(TpuEngine, name),
                   _kind=kind, **kw):
            warmed.setdefault(_kind, []).append(len(resources))
            return _real(self, resources, *a, **kw)

        monkeypatch.setattr(TpuEngine, name, record)
    plane = ControlPlane([POLICY] + ([MUTATE] if mutate else []),
                         port=0, metrics_port=0, batching=batching,
                         mutate_batching=mutate,
                         batch_config=BatchConfig(max_batch_size=40))
    plane.start(scan_interval=3600)
    try:
        ok, detail = plane.handlers.ready()
        assert ok, detail
        assert warmed == want
    finally:
        plane.stop()


def test_chip_smoke_fails_without_a_tpu():
    """chip_smoke.py is the chip's proof: with JAX held to the CPU it
    exits nonzero and never prints its ok line."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, os.path.join(repo, "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_bench_fails_when_a_leg_raises_or_a_ladder_fires(monkeypatch, capsys):
    """bench.py's run fails when a leg raised or when a breaker,
    quarantine, shed or expiry ladder answered in the device's place,
    outside the legs that drive that ladder by design."""
    import os

    from kyverno_tpu.observability.metrics import Counter, global_registry

    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    shed = Counter("kyverno_serving_shed_total", "test")
    monkeypatch.setattr(global_registry, "serving_shed_total", shed)
    monkeypatch.setattr("kyverno_tpu.tpu.cache.enable_xla_compile_cache",
                        lambda: None)
    monkeypatch.setattr(bench, "bench_scan", lambda: {"value": 1.0})
    monkeypatch.setattr(bench, "mixed_corpus_coverage", lambda: {})

    def sheds():
        shed.inc({"outcome": "scalar"})
        return {}

    def raises():
        raise RuntimeError("leg broke")

    monkeypatch.setitem(bench.FNS, "match", raises)
    monkeypatch.setitem(bench.FNS, "overlay", sheds)
    monkeypatch.setitem(bench.FNS, "mixed_traffic", sheds)
    monkeypatch.setenv("BENCH_CONFIGS", "match,overlay,mixed_traffic")
    with pytest.raises(SystemExit) as e:
        bench.run_all(None)
    assert e.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] is False and out["failed_legs"] == ["match"]
    assert out["ladders_fired"] == {"overlay": {"shed": 1.0}}
