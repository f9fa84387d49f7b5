#!/bin/bash
# Observability gate: tier-1 must hold, then a smoke leg drives the
# serve control plane under concurrent admission load WITH a
# tpu.dispatch fault armed, hitting /metrics and /debug/state on every
# iteration — asserting the Prometheus exposition stays parseable
# under load and that the trace of a scalar-fallback batch records the
# breaker state that caused it.
#
# Usage: ./scripts_obs_check.sh
set -o pipefail
cd "$(dirname "$0")"
rc=0

echo "=== leg 1/5: tier-1 (faults disarmed) ==="
KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 870 \
  python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
  -p no:cacheprovider -p no:xdist -p no:randomly || rc=1

echo "=== leg 2/5: /metrics + /debug/* smoke under load, tpu.dispatch armed ==="
KYVERNO_TPU_FAULTS="tpu.dispatch:raise:p=1.0" JAX_PLATFORMS=cpu \
  timeout -k 10 300 python - <<'EOF' || rc=1
import http.client
import json
import re
import sys
import threading

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.cli.serve import ControlPlane

POLICY = ClusterPolicy.from_dict({
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "obs-smoke"},
    "spec": {"validationFailureAction": "Enforce", "rules": [{
        "name": "named",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "validate": {"message": "m",
                     "pattern": {"metadata": {"name": "?*"}}},
    }]}})

REVIEW = json.dumps({
    "apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
    "request": {"uid": "u1", "operation": "CREATE",
                "object": {"apiVersion": "v1", "kind": "Pod",
                           "metadata": {"name": "p", "namespace": "d"},
                           "spec": {"containers": [
                               {"name": "c", "image": "nginx"}]}}}})

METRIC_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9.eE+-]+|NaN"
    r"( # \{[^{}]*\} [0-9.eE+-]+( [0-9.eE+-]+)?)?$")


def get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", path, body,
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = resp.read()
    conn.close()
    return resp.status, out


cp = ControlPlane([POLICY], port=0, metrics_port=0, batching=True)
cp.start(scan_interval=3600.0)
adm, met = cp.admission.port, cp.metrics_server.server_address[1]
failures = []
try:
    def worker(n):
        for _ in range(n):
            status, out = post(adm, "/validate", REVIEW)
            if status != 200:
                failures.append(f"/validate -> {status}")
                return
            if "response" not in json.loads(out):
                failures.append("validate response missing body")
                return

    threads = [threading.Thread(target=worker, args=(10,))
               for _ in range(8)]
    for t in threads:
        t.start()
    # scrape WHILE the load runs: exposition must parse mid-flight
    scrapes = 0
    while any(t.is_alive() for t in threads):
        status, body = get(met, "/metrics")
        assert status == 200, status
        for line in body.decode().splitlines():
            if line.startswith("#") or not line:
                continue
            assert METRIC_LINE.match(line), f"unparseable: {line!r}"
        status, body = get(met, "/debug/state")
        assert status == 200, status
        json.loads(body)  # must be valid JSON under load
        scrapes += 1
    for t in threads:
        t.join()
    assert not failures, failures
    assert scrapes > 0

    # the armed fault forces every device dispatch to fail -> breaker
    # trips -> batches complete via the scalar ladder; the TRACES must
    # say so: admission.batch spans on the scalar fallback, carrying
    # the breaker state
    status, body = get(met, "/debug/traces")
    assert status == 200
    traces = json.loads(body)["traces"]
    fallback_spans = [s for t in traces for s in t["spans"]
                      if s["name"] == "admission.batch"
                      and s["attributes"].get("engine") == "scalar_fallback"]
    assert fallback_spans, "no scalar-fallback batch traced under faults"
    assert all("breaker" in s["attributes"] for s in fallback_spans), \
        "fallback batch span lacks breaker state"
    state = json.loads(get(met, "/debug/state")[1])
    assert state["breaker"]["state"] in ("open", "half_open", "closed")
    assert state["faults_armed"].get("tpu.dispatch", {}).get("fired", 0) > 0
    text = get(met, "/metrics")[1].decode()
    assert "kyverno_tpu_breaker_fallback_total" in text

    # verdict-cache metrics under admission load: repeated identical
    # reviews must produce hit-labeled lookups on /metrics, and the
    # pipelined background scan must publish its overlap gauge. Two
    # full scans of an unchanged snapshot: the second must be >=90%
    # cache-served (the repeat-scan amortization acceptance)
    POD = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": "scanme", "namespace": "d", "uid": "u-scan"},
           "spec": {"containers": [{"name": "c", "image": "nginx"}]}}
    assert post(met, "/snapshot/upsert", json.dumps(POD))[0] == 200
    assert post(met, "/scan", json.dumps({"full": True}))[0] == 200
    assert post(met, "/scan", json.dumps({"full": True}))[0] == 200
    text = get(met, "/metrics")[1].decode()
    assert 'kyverno_tpu_verdict_cache_total{outcome="hit"}' in text, \
        "verdict cache hit counter missing from /metrics"
    assert 'kyverno_tpu_verdict_cache_total{outcome="miss"}' in text
    assert "kyverno_tpu_pipeline_overlap_ratio" in text and \
        "kyverno_tpu_pipeline_chunks_total" in text, \
        "pipeline metrics missing from /metrics"
    perf = json.loads(get(met, "/debug/state")[1])["perf_caches"]
    assert perf["verdict"]["hits"] >= 1
    print(f"OBS SMOKE OK: {scrapes} live scrapes, "
          f"{len(fallback_spans)} fallback spans, "
          f"breaker={state['breaker']['state']}, "
          f"verdict_cache={perf['verdict']}")
finally:
    cp.stop()
EOF

echo "=== leg 3/5: policy observatory (rule analytics + starvation + SLO) ==="
KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 300 python - <<'EOF' || rc=1
import http.client
import json

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.cli.serve import ControlPlane

# one rule that fires on the workload, one that can never fire (no
# Gateway in the snapshot) — the never-fired report is the on-ramp to
# shadow/dead-rule analysis
POLICY = ClusterPolicy.from_dict({
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "observatory"},
    "spec": {"validationFailureAction": "Enforce", "rules": [
        {"name": "hot",
         "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
         "validate": {"message": "m",
                      "pattern": {"metadata": {"name": "?*"}}}},
        {"name": "cold",
         "match": {"any": [{"resources": {"kinds": ["Gateway"]}}]},
         "validate": {"message": "m",
                      "pattern": {"metadata": {"name": "?*"}}}},
    ]}})


def get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", path, body, {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = resp.read()
    conn.close()
    return resp.status, out


def review(i):
    return json.dumps({
        "apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
        "request": {"uid": f"u{i}", "operation": "CREATE",
                    "object": {"apiVersion": "v1", "kind": "Pod",
                               "metadata": {"name": f"p{i}",
                                            "namespace": "d"},
                               "spec": {"containers": [
                                   {"name": "c", "image": "nginx"}]}}}})


cp = ControlPlane([POLICY], port=0, metrics_port=0, batching=True)
cp.start(scan_interval=3600.0)
adm, met = cp.admission.port, cp.metrics_server.server_address[1]
try:
    # drive admissions + a full background scan
    for i in range(12):
        status, out = post(adm, "/validate", review(i))
        assert status == 200, status
    for i in range(6):
        pod = json.loads(review(i))["request"]["object"]
        assert post(met, "/snapshot/upsert", json.dumps(pod))[0] == 200
    assert post(met, "/scan", json.dumps({"full": True}))[0] == 200

    # /debug/rules: the known-hot rule ranks, the known-never-fired
    # rule is reported with an age
    status, body = get(met, "/debug/rules?top=10")
    assert status == 200, status
    doc = json.loads(body)
    hot = {(r["policy"], r["rule"]) for r in doc["top"]}
    never = {(r["policy"], r["rule"]): r for r in doc["never_fired"]}
    assert ("observatory", "hot") in hot, doc["top"]
    assert ("observatory", "cold") in never, doc["never_fired"]
    assert never[("observatory", "cold")]["age_s"] >= 0

    # starvation gauge present and in [0,1]; SLO gauges on /metrics
    text = get(met, "/metrics")[1].decode()
    line = [l for l in text.splitlines()
            if l.startswith("kyverno_tpu_feed_starvation_ratio")]
    assert line, "starvation gauge missing"
    ratio = float(line[0].rsplit(" ", 1)[1])
    assert 0.0 <= ratio <= 1.0, ratio
    for fam in ("kyverno_slo_admission_burn_rate",
                "kyverno_slo_scan_freshness_seconds",
                "kyverno_slo_device_coverage_ratio",
                "kyverno_rule_evals_total"):
        assert fam in text, f"{fam} missing from /metrics"

    # /debug/utilization answers with the starvation + SLO state
    status, body = get(met, "/debug/utilization")
    assert status == 200
    util = json.loads(body)
    assert 0.0 <= util["feed_starvation"]["ratio"] <= 1.0
    assert "windows" in util["slo"]["admission"]

    # /readyz carries the SLO block
    ready = json.loads(get(met, "/readyz")[1])
    assert "slo" in ready, ready
    print(f"OBSERVATORY OK: starvation={ratio}, "
          f"hot={len(doc['top'])}, never_fired={len(doc['never_fired'])}, "
          f"slo_breached={util['slo']['breached']}")
finally:
    cp.stop()
EOF

echo "=== leg 4/5: device-side string matching (pattern metrics + /scan device cells) ==="
KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 300 python - <<'EOF' || rc=1
import http.client
import json
import re

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.cli.serve import ControlPlane

# a pattern-heavy set: glob operands + a matches() CEL expression —
# BOTH must evaluate on the device path (pattern_cells path="device")
POLICIES = [ClusterPolicy.from_dict({
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "pattern-globs"},
    "spec": {"validationFailureAction": "Audit", "rules": [{
        "name": "image-glob",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "validate": {"message": "m", "pattern": {"spec": {"containers": [
            {"image": "nginx-* | redis-?*"}]}}},
    }]}}), ClusterPolicy.from_dict({
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "pattern-matches"},
    "spec": {"validationFailureAction": "Audit", "rules": [{
        "name": "re2-name",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "validate": {"cel": {"expressions": [
            {"expression": "object.metadata.name.matches('^[a-z][a-z0-9-]*$')"}]}},
    }]}})]

METRIC_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9.eE+-]+|NaN"
    r"( # \{[^{}]*\} [0-9.eE+-]+( [0-9.eE+-]+)?)?$")


def get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", path, body, {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = resp.read()
    conn.close()
    return resp.status, out


cp = ControlPlane(POLICIES, port=0, metrics_port=0, batching=True)
cp.start(scan_interval=3600.0)
met = cp.metrics_server.server_address[1]
try:
    for i in range(6):
        pod = {"apiVersion": "v1", "kind": "Pod",
               "metadata": {"name": f"pat-{i}", "namespace": "d",
                            "uid": f"u{i}"},
               "spec": {"containers": [
                   {"name": "c", "image": f"nginx-{i}"}]}}
        assert post(met, "/snapshot/upsert", json.dumps(pod))[0] == 200
    assert post(met, "/scan", json.dumps({"full": True}))[0] == 200

    text = get(met, "/metrics")[1].decode()
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        assert METRIC_LINE.match(line), f"unparseable: {line!r}"
    for fam in ("kyverno_tpu_pattern_cells_total",
                "kyverno_tpu_dfa_tables", "kyverno_tpu_dfa_states",
                "kyverno_tpu_dfa_table_bytes"):
        assert fam in text, f"{fam} missing from /metrics"
    dev = [l for l in text.splitlines()
           if l.startswith('kyverno_tpu_pattern_cells_total{path="device"}')]
    assert dev, "no device-path pattern cells after a pattern-heavy /scan"
    assert float(dev[0].rsplit(" ", 1)[1]) > 0, dev

    state = json.loads(get(met, "/debug/state")[1])
    pat = state["patterns"]
    assert pat["totals"]["device"] > 0, pat
    assert pat["bank"]["tables"] >= 2, pat
    util = json.loads(get(met, "/debug/utilization")[1])
    assert "patterns" in util
    rules = json.loads(get(met, "/debug/rules")[1])
    with_cells = [p for p in rules["policies"] if "pattern_cells" in p]
    assert with_cells, rules["policies"]
    print(f"PATTERNS OK: cells={pat['totals']}, bank={pat['bank']}")
finally:
    cp.stop()
EOF

echo "=== leg 5/5: flight recorder + continuous shadow verification ==="
KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 300 python - <<'EOF' || rc=1
import json
import os
import tempfile

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.cli.serve import ControlPlane

POLICY = ClusterPolicy.from_dict({
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "flight-smoke"},
    "spec": {"validationFailureAction": "Enforce", "rules": [{
        "name": "named",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "validate": {"message": "m",
                     "pattern": {"metadata": {"name": "?*"}}},
    }]}})


def get(port, path):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def post(port, path, body):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", path, body, {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = resp.read()
    conn.close()
    return resp.status, out


def review(i):
    return json.dumps({
        "apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
        "request": {"uid": f"u{i}", "operation": "CREATE",
                    "object": {"apiVersion": "v1", "kind": "Pod",
                               "metadata": {"name": f"fp{i}",
                                            "namespace": "d"},
                               "spec": {"containers": [
                                   {"name": "c", "image": "nginx"}]}}}})


def counter_value(text, family):
    # strip any exemplar suffix (" # {...} v ts") BEFORE taking the
    # sample value; sum all matching series
    vals = [float(l.split(" # ")[0].rsplit(" ", 1)[1])
            for l in text.splitlines()
            if l.startswith(family) and not l.startswith("#")]
    return sum(vals)


spool = tempfile.mkdtemp(prefix="flight-gate-")
cp = ControlPlane([POLICY], port=0, metrics_port=0, batching=True,
                  flight_sample_rate=1.0, flight_dir=spool,
                  shadow_verify_rate=1.0)
cp.start(scan_interval=3600.0)
adm, met = cp.admission.port, cp.metrics_server.server_address[1]
try:
    from kyverno_tpu.observability.verification import global_verifier

    # drive admissions + a background scan with verification at 100%
    for i in range(8):
        status, _ = post(adm, "/validate", review(i))
        assert status == 200, status
    for i in range(4):
        pod = json.loads(review(i))["request"]["object"]
        pod["metadata"]["uid"] = f"fu{i}"
        assert post(met, "/snapshot/upsert", json.dumps(pod))[0] == 200
    assert post(met, "/scan", json.dumps({"full": True}))[0] == 200
    assert global_verifier.drain(timeout=30.0)

    # /debug/flight returns the recorded decisions
    status, body = get(met, "/debug/flight?last=50")
    assert status == 200, status
    doc = json.loads(body)
    assert len(doc["records"]) >= 8, len(doc["records"])
    kinds = {r["kind"] for r in doc["records"]}
    assert "admission" in kinds and "scan" in kinds, kinds
    assert all(r["verdicts"] for r in doc["records"])

    # clean run: checks happened, divergence counter is 0
    text = get(met, "/metrics")[1].decode()
    assert "kyverno_verification_checks_total" in text
    assert counter_value(text, "kyverno_verification_checks_total"
                         '{result="match"}') >= 8
    assert counter_value(
        text, "kyverno_verification_divergence_total") == 0.0

    # arm a corrupt flip fault: shape-valid WRONG verdicts served —
    # only the shadow verifier can catch it
    from kyverno_tpu.resilience.faults import global_faults

    global_faults.arm("tpu.dispatch", mode="corrupt", flip=True)
    try:
        for i in range(8, 12):
            status, _ = post(adm, "/validate", review(i))
            assert status == 200, status
    finally:
        global_faults.disarm()
    assert global_verifier.drain(timeout=30.0)
    text = get(met, "/metrics")[1].decode()
    div = counter_value(text, "kyverno_verification_divergence_total")
    assert div >= 1, "corrupt dispatch not caught as divergence"
    # the full record + both verdicts landed in the spool
    div_file = os.path.join(spool, "divergences.ndjson")
    assert os.path.exists(div_file), os.listdir(spool)
    lines = [json.loads(l) for l in open(div_file)]
    assert lines and lines[0]["kind"] == "divergence"
    assert lines[0]["record"]["resource"] is not None
    # verdict-integrity SLO rides /readyz (advisory)
    ready = json.loads(get(met, "/readyz")[1])
    assert "verdict_integrity" in ready["slo"]["breached"], ready["slo"]
    print(f"FLIGHT OK: {len(doc['records'])} records, "
          f"divergences={div}, spool={sorted(os.listdir(spool))}")
finally:
    cp.stop()
EOF

if [ "$rc" -eq 0 ]; then
  echo "OBS GATE: all legs passed"
else
  echo "OBS GATE: FAILURES (see above)"
fi
exit $rc
