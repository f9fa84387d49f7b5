#!/bin/bash
# Fleet gate (ISSUE 15): the multi-replica story proven end-to-end
# through REAL serve processes.
#
# Leg 1 boots two fleet-wired replicas, warms one with an admission
# review, and asserts the COLD replica answers the identical review
# from the fleet cache (peer fetch hit counted, no local compute) with
# a bit-identical response, and that the kyverno_fleet_* families pass
# the exposition surface. Leg 2 is the telemetry-aggregation
# acceptance (ISSUE 18): three replicas under ambient tpu.dispatch
# corruption with shadow verification at rate 1.0 — the leader's
# fleet divergence aggregate must equal the SUM of the replicas'
# ground-truth divergence counters, one deliberately corrupted
# telemetry snapshot must be rejected-and-counted (never merged), and
# after a SIGKILL the rollup must drop the dead replica within the
# lease TTL while keeping its folded work. Leg 3 is the chaos
# acceptance: three replicas, one SIGKILLed mid-scan, shard takeover
# within the lease TTL, the scan completing with the exact expected
# verdict split across survivors, and zero shadow-verification
# divergence at rate 1.0. Leg 4 runs the fleet unit/integration suite
# under the dynamic lock-order sanitizer and asserts zero cycles.
# Leg 5 is tier-1.
#
# Usage: ./scripts_fleet_gate.sh
set -o pipefail
cd "$(dirname "$0")"
rc=0

echo "=== leg 1/5: cold replica answers from the fleet cache ==="
KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 600 python - <<'EOF' || rc=1
import http.client
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import yaml

POLICY = {
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "fleet-gate"},
    "spec": {"validationFailureAction": "Enforce", "rules": [{
        "name": "no-privileged",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "validate": {"message": "no privileged",
                     "pattern": {"spec": {"containers": [
                         {"=(securityContext)":
                          {"=(privileged)": "false"}}]}}},
    }]}}

REVIEW = {"request": {
    "uid": "gate-1", "operation": "CREATE", "namespace": "default",
    "object": {"apiVersion": "v1", "kind": "Pod",
               "metadata": {"name": "gate-pod", "namespace": "default"},
               "spec": {"containers": [{"name": "c", "image": "nginx"}]}},
}}


def free_port():
    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close(); return port


def get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse(); body = resp.read(); conn.close()
    return resp.status, body


def post(port, path, doc, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, json.dumps(doc),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse(); body = resp.read(); conn.close()
    return resp.status, body


def metric(text, name, **labels):
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


tmp = tempfile.mkdtemp(prefix="fleet-gate-")
pol_file = os.path.join(tmp, "policy.yaml")
with open(pol_file, "w") as f:
    yaml.safe_dump(POLICY, f)
env = dict(os.environ)
env.update({"JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "xla")})
fleet = [free_port(), free_port()]
adm = [free_port(), free_port()]
met = [free_port(), free_port()]
procs = []
try:
    for i in range(2):
        peers = f"http://127.0.0.1:{fleet[1 - i]}"
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "kyverno_tpu", "serve", pol_file,
             "--port", str(adm[i]), "--metrics-port", str(met[i]),
             "--scan-interval", "9999", "--batching",
             "--fleet-listen", str(fleet[i]), "--fleet-peers", peers,
             "--replica-id", f"gate{i}", "--fleet-lease-s", "2.0"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
        # serialize boots so replica 1 reads replica 0's warm XLA cache
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            try:
                if get(met[i], "/healthz", timeout=2)[0] == 200:
                    break
            except OSError:
                time.sleep(0.3)
        else:
            raise AssertionError(f"replica {i} never became healthy")
    # converge to 2 live replicas
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            views = [json.loads(get(fleet[i], "/fleet/state", 2)[1])
                     for i in range(2)]
            if all(len(v["membership"]["live"]) == 2 for v in views):
                break
        except OSError:
            pass
        time.sleep(0.3)
    else:
        raise AssertionError("fleet never converged")

    # warm replica 0 with the review (computes + caches the column)
    status, body = post(adm[0], "/validate", REVIEW)
    assert status == 200, status
    warm = json.loads(body)["response"]
    # give the async gossip a beat, then ALSO verify fetch-on-miss by
    # hitting the cold replica: whether the column arrived by push or
    # is pulled now, the cold replica must answer from the FLEET cache
    status, body = get(met[1], "/metrics")
    before_fetch = metric(body.decode(), "kyverno_fleet_peer_fetch_total",
                          outcome="hit")
    before_gossip = metric(body.decode(), "kyverno_fleet_gossip_total",
                           outcome="received")
    status, body = post(adm[1], "/validate", REVIEW)
    assert status == 200, status
    cold = json.loads(body)["response"]
    assert cold["allowed"] == warm["allowed"], (cold, warm)
    status, body = get(met[1], "/metrics")
    text = body.decode()
    after_fetch = metric(text, "kyverno_fleet_peer_fetch_total",
                         outcome="hit")
    after_gossip = metric(text, "kyverno_fleet_gossip_total",
                          outcome="received")
    assert (after_fetch > before_fetch or after_gossip >= 1), \
        "cold replica neither fetched nor received the warm column"
    # exposition surface: every fleet family TYPE'd and present
    for fam in ("kyverno_fleet_replicas", "kyverno_fleet_is_leader",
                "kyverno_fleet_epoch", "kyverno_fleet_shards_owned",
                "kyverno_fleet_heartbeats_total",
                "kyverno_fleet_shard_reassignments_total"):
        assert f"# TYPE {fam} " in text, fam
    assert metric(text, "kyverno_fleet_replicas") == 2
    # /debug/fleet rides the metrics port debug router too
    status, body = get(met[1], "/debug/fleet")
    doc = json.loads(body)
    assert doc["enabled"] and doc["membership"]["replica_id"] == "gate1"
    print(f"cold-peer admission OK (fetch {after_fetch - before_fetch:+.0f}, "
          f"gossip received {after_gossip:.0f}); families scrapeable")
finally:
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
EOF

echo "=== leg 2/5: telemetry aggregation — divergence rollup bit-exact ==="
KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 700 python - <<'EOF' || rc=1
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import yaml

POLICY = {
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "agg-gate"},
    "spec": {"validationFailureAction": "Enforce", "rules": [{
        "name": "no-privileged",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "validate": {"message": "no privileged",
                     "pattern": {"spec": {"containers": [
                         {"=(securityContext)":
                          {"=(privileged)": "false"}}]}}},
    }]}}


def review(name):
    return {"request": {
        "uid": f"agg-{name}", "operation": "CREATE",
        "namespace": "default",
        "object": {"apiVersion": "v1", "kind": "Pod",
                   "metadata": {"name": name, "namespace": "default"},
                   "spec": {"containers": [{"name": "c",
                                            "image": f"img-{name}"}]}},
    }}


def free_port():
    s = socket.socket(); s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]; s.close(); return port


def get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse(); body = resp.read(); conn.close()
    return resp.status, body


def post(port, path, doc, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, json.dumps(doc),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse(); body = resp.read(); conn.close()
    return resp.status, body


def metric(text, name, **labels):
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


tmp = tempfile.mkdtemp(prefix="fleet-agg-gate-")
pol_file = os.path.join(tmp, "policy.yaml")
with open(pol_file, "w") as f:
    yaml.safe_dump(POLICY, f)
N = 3
fleet = [free_port() for _ in range(N)]
adm = [free_port() for _ in range(N)]
met = [free_port() for _ in range(N)]
# ambient faults, per replica: agg1/agg2 flip device dispatch results
# (shadow verification at rate 1.0 turns each flipped admission into a
# counted divergence); agg1 ALSO corrupts exactly ONE outgoing
# telemetry snapshot, which the leader must reject-and-count
faults = {
    1: "tpu.dispatch:corrupt:flip=1,count=40;"
       "fleet.telemetry:corrupt:count=1",
    2: "tpu.dispatch:corrupt:flip=1,count=40",
}
procs = []
try:
    for i in range(N):
        peers = ",".join(f"http://127.0.0.1:{fleet[j]}"
                         for j in range(N) if j != i)
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "xla"),
                    "KYVERNO_TPU_FAULTS": faults.get(i, "")})
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "kyverno_tpu", "serve", pol_file,
             "--port", str(adm[i]), "--metrics-port", str(met[i]),
             "--scan-interval", "9999", "--batching",
             "--shadow-verify-rate", "1.0",
             # the bit-exact check needs EVERY admission on the faulted
             # device path, audited: burn-shed off (cold-start SLO burn
             # would reroute posts to the scalar path — no dispatch, no
             # divergence) and flight capture at 1.0 (the default 0.01
             # sample would hide batched records from the verifier)
             "--shed-burn-default", "0", "--shed-burn-bulk", "0",
             "--flight-sample-rate", "1.0",
             "--fleet-listen", str(fleet[i]), "--fleet-peers", peers,
             "--replica-id", f"agg{i}", "--fleet-lease-s", "2.0"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
        # serialize boots on the shared warm XLA cache
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            try:
                if get(met[i], "/healthz", timeout=2)[0] == 200:
                    break
            except OSError:
                time.sleep(0.3)
        else:
            raise AssertionError(f"replica {i} never became healthy")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            views = [json.loads(get(fleet[i], "/fleet/state", 2)[1])
                     for i in range(N)]
            if all(len(v["membership"]["live"]) == N for v in views):
                break
        except OSError:
            pass
        time.sleep(0.3)
    else:
        raise AssertionError("fleet never converged")
    # agg0 is the lexicographically smallest live id = the leader
    assert json.loads(get(fleet[0], "/fleet/state", 2)[1]
                      )["membership"]["is_leader"]

    # drive DISTINCT admissions through the faulted replicas (distinct
    # manifests so every review dispatches instead of hitting a cache)
    for i in (1, 2):
        for k in range(4):
            status, _ = post(adm[i], "/validate", review(f"r{i}-{k}"))
            assert status == 200, status

    # converge: leader's fleet divergence aggregate == SUM of the
    # per-replica ground-truth counters, nonzero (delta-fold is exact,
    # so once the per-replica counters settle, equality is bit-exact)
    deadline = time.monotonic() + 60
    agg_total = truth = -1
    while time.monotonic() < deadline:
        texts = [get(met[i], "/metrics")[1].decode() for i in range(N)]
        truth = sum(metric(t, "kyverno_verification_divergence_total")
                    for t in texts)
        agg_total = metric(texts[0], "kyverno_fleet_agg_divergence_total")
        if truth > 0 and agg_total == truth:
            break
        time.sleep(0.5)
    else:
        raise AssertionError(
            f"aggregate {agg_total} != sum of replica truths {truth}")
    print(f"fleet divergence aggregate bit-exact: {agg_total:.0f} == "
          f"sum of per-replica truths")

    leader_text = get(met[0], "/metrics")[1].decode()
    # exactly one poisoned snapshot was rejected-and-counted, and the
    # leader kept folding agg1 afterwards (it appears in the rollup)
    rejects = metric(leader_text, "kyverno_fleet_telemetry_rejects_total",
                     reason="checksum")
    assert rejects == 1, f"expected 1 checksum reject, saw {rejects}"
    roll = json.loads(get(met[0], "/debug/fleet")[1]
                      )["telemetry"]["rollup"]
    assert set(roll["replicas"]) == {"agg0", "agg1", "agg2"}, roll["replicas"]
    assert roll["degraded"] is True
    assert roll["totals"]["verification_divergences"] == truth
    # the rollup GOSSIPS BACK: a follower answers /debug/fleet with it
    f_roll = json.loads(get(met[2], "/debug/fleet")[1]
                        )["telemetry"]["rollup"]
    assert f_roll and f_roll["computed_by"] == "agg0"
    # /readyz carries the advisory degraded bit without failing ready
    status, body = get(met[0], "/readyz")
    ready = json.loads(body)
    assert ready["slo"]["fleet"]["degraded"] is True
    assert "fleet_divergence" in ready["slo"]["breached"]
    print("poisoned snapshot rejected-and-counted; rollup gossiped; "
          "readyz advisory degraded")

    # SIGKILL agg2: the rollup must drop it within the lease TTL while
    # keeping its already-folded divergences in the totals
    procs[2].send_signal(signal.SIGKILL)
    t_kill = time.monotonic()
    deadline = t_kill + 20
    while time.monotonic() < deadline:
        roll = json.loads(get(met[0], "/debug/fleet")[1]
                          )["telemetry"]["rollup"]
        if set(roll["replicas"]) == {"agg0", "agg1"}:
            break
        time.sleep(0.2)
    else:
        raise AssertionError(f"rollup never dropped agg2: "
                             f"{sorted(roll['replicas'])}")
    took = time.monotonic() - t_kill
    assert roll["totals"]["verification_divergences"] == truth, \
        "a dead replica's folded work must stay in the totals"
    leader_text = get(met[0], "/metrics")[1].decode()
    assert metric(leader_text, "kyverno_fleet_agg_replicas_reporting") == 2
    print(f"SIGKILLed replica left the rollup in {took:.1f}s "
          f"(lease 2.0s + pull cadence); folded work retained")
finally:
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
EOF

echo "=== leg 3/5: SIGKILL chaos — takeover + zero divergence ==="
KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 900 \
  python -m pytest tests/test_fleet_chaos.py -q -p no:cacheprovider || rc=1

echo "=== leg 4/5: fleet suite under the lock-order sanitizer ==="
rm -f /tmp/_san_fleet.json
KYVERNO_TPU_SANITIZE=1 KYVERNO_TPU_SANITIZE_REPORT=/tmp/_san_fleet.json \
  KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 900 \
  python -m pytest tests/test_fleet.py tests/test_fleet_telemetry.py \
  -q -p no:cacheprovider || rc=1
python - <<'EOF' || rc=1
import json
doc = json.load(open("/tmp/_san_fleet.json"))
assert doc["cycles"] == [], f"LOCK-ORDER CYCLES: {doc['cycles']}"
assert doc["dispatch_violations"] == [], \
    f"locks held across dispatch: {doc['dispatch_violations']}"
print(f"fleet clean under sanitizer: {doc['locks_tracked']} locks, "
      f"{doc['edges']} edges, 0 cycles")
EOF

echo "=== leg 5/5: tier-1 ==="
KYVERNO_TPU_FAULTS= JAX_PLATFORMS=cpu timeout -k 10 870 \
  python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
  -p no:cacheprovider -p no:xdist -p no:randomly || rc=1

if [ $rc -eq 0 ]; then echo "FLEET GATE: PASS"; else echo "FLEET GATE: FAIL"; fi
exit $rc
