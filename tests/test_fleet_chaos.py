"""Fleet chaos leg (ISSUE 15 acceptance): SIGKILL a replica mid-scan.

Three REAL serve processes (process-level replicas — the fleet story
on CPU), one logical cluster: every replica holds the same snapshot,
scans only its rendezvous-owned shards, runs the shadow verifier at
rate 1.0, and gossips verdict columns. The test SIGKILLs one replica
while its scan is in flight and asserts:

- the survivors detect the death within the lease TTL and the shard
  map re-covers the whole keyspace;
- the next scan completes: the union of survivor reports covers EVERY
  resource with exactly the expected pass/fail split (cross-replica
  verdict identity, not just per-replica consistency);
- zero shadow-verification divergences anywhere (rate 1.0 — every
  captured verdict re-checked against the scalar oracle);
- the kyverno_fleet_* families are live and scrapeable on survivors.

Marked slow: boots three Python processes and pays one XLA build
(amortized through a shared persistent cache dir).
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import pytest
import yaml

pytestmark = pytest.mark.slow

N_PODS = 120
LEASE_S = 2.0


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


def _pods(n):
    return [{
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": f"ns{i % 4}",
                     "uid": f"u-{i}"},
        "spec": {"containers": [{
            "name": "c", "image": "nginx",
            **({"securityContext": {"privileged": True}}
               if i % 3 == 0 else {})}]},
    } for i in range(n)]


def _metric(text, name, **labels):
    """Sum the series of ``name`` matching the given labels."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest and rest[0] not in ("{", " "):
            continue  # a longer family sharing the prefix
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            try:
                # strip an OpenMetrics exemplar suffix before parsing
                total += float(line.split(" # ")[0].rsplit(" ", 1)[-1])
            except ValueError:
                pass
    return total


@pytest.fixture
def fleet_procs(tmp_path):
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


def test_sigkill_mid_scan_fails_over_with_zero_divergence(tmp_path,
                                                          fleet_procs):
    policy_file = tmp_path / "policy.yaml"
    policy_file.write_text(yaml.safe_dump({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "fleet-chaos"},
        "spec": {"validationFailureAction": "Enforce", "rules": [{
            "name": "no-privileged",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"message": "no privileged",
                         "pattern": {"spec": {"containers": [
                             {"=(securityContext)":
                              {"=(privileged)": "false"}}]}}},
        }]}}))
    xla_cache = tmp_path / "xla"
    fleet_ports = [_free_port() for _ in range(3)]
    metrics_ports = [_free_port() for _ in range(3)]
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": str(xla_cache)})

    def boot(i):
        peers = ",".join(f"http://127.0.0.1:{fleet_ports[j]}"
                         for j in range(3) if j != i)
        p = subprocess.Popen(
            [sys.executable, "-m", "kyverno_tpu", "serve",
             str(policy_file),
             "--port", "0", "--metrics-port", str(metrics_ports[i]),
             "--scan-interval", "9999", "--batching",
             "--fleet-listen", str(fleet_ports[i]),
             "--fleet-peers", peers,
             "--replica-id", f"rep{i}",
             "--fleet-lease-s", str(LEASE_S),
             "--shadow-verify-rate", "1.0",
             "--flight-sample-rate", "1.0"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            text=True)
        fleet_procs.append(p)
        return p

    def wait_ready(i, timeout=300):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if fleet_procs[i].poll() is not None:
                raise AssertionError(
                    f"replica {i} died at boot:\n"
                    + (fleet_procs[i].stderr.read() or "")[-2000:])
            try:
                status, _ = _get(metrics_ports[i], "/healthz", timeout=2)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.3)
        raise AssertionError(f"replica {i} never became healthy")

    # replica 0 pays the XLA build into the shared cache; 1 and 2 boot
    # against the warm directory
    boot(0)
    wait_ready(0)
    boot(1)
    boot(2)
    wait_ready(1)
    wait_ready(2)

    # fleet converges to 3 live replicas on every view
    def live_count(i):
        try:
            _, body = _get(fleet_ports[i], "/fleet/state", timeout=2)
            return len(json.loads(body)["membership"]["live"])
        except OSError:
            return 0

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if all(live_count(i) == 3 for i in range(3)):
            break
        time.sleep(0.3)
    assert all(live_count(i) == 3 for i in range(3)), \
        [live_count(i) for i in range(3)]

    # one logical snapshot: every replica sees every resource
    pods = _pods(N_PODS)
    for pod in pods:
        for i in range(3):
            status, _ = _post(metrics_ports[i], "/snapshot/upsert", pod)
            assert status == 200

    # first scan wave: each replica covers exactly its owned shards
    scanned = []
    for i in range(3):
        status, body = _post(metrics_ports[i], "/scan", {"full": True})
        assert status == 200
        scanned.append(json.loads(body)["scanned"])
    assert sum(scanned) == N_PODS, (scanned, "shards must partition")
    assert all(n > 0 for n in scanned), scanned

    # SIGKILL replica 1 MID-SCAN: fire a /scan at it and kill the
    # process while the request is in flight
    victim = fleet_procs[1]
    import threading

    def fire_scan():
        try:
            _post(metrics_ports[1], "/scan", {"full": True}, timeout=10)
        except OSError:
            pass  # the kill races the response; either is fine

    t = threading.Thread(target=fire_scan, daemon=True)
    t.start()
    time.sleep(0.05)
    os.kill(victim.pid, signal.SIGKILL)
    t_kill = time.monotonic()
    victim.wait(timeout=10)

    # survivors detect the death within the lease TTL (+ slack) and
    # the shard map re-covers the whole keyspace
    survivors = [0, 2]

    def fleet_state(i):
        _, body = _get(fleet_ports[i], "/fleet/state", timeout=2)
        return json.loads(body)

    deadline = time.monotonic() + LEASE_S + 8
    while time.monotonic() < deadline:
        states = [fleet_state(i) for i in survivors]
        if all(len(s["membership"]["live"]) == 2 for s in states):
            owned = set()
            for s in states:
                owned.update(s["shards"]["owned"])
            if owned == set(range(64)):
                break
        time.sleep(0.2)
    detect_s = time.monotonic() - t_kill
    states = [fleet_state(i) for i in survivors]
    assert all(len(s["membership"]["live"]) == 2 for s in states), states
    owned = set()
    for s in states:
        owned.update(s["shards"]["owned"])
    assert owned == set(range(64)), "keyspace not re-covered"
    assert detect_s < LEASE_S + 8, detect_s

    # takeover scan wave: survivors rescan their gained shards; the
    # scan COMPLETES (no wedge on the dead peer)
    for i in survivors:
        status, body = _post(metrics_ports[i], "/scan", {})
        assert status == 200

    # union of survivor reports covers EVERY resource with the exact
    # expected pass/fail split — cross-replica verdict identity
    names = set()
    n_fail = n_pass = 0
    for i in survivors:
        _, body = _get(metrics_ports[i], "/reports")
        for report in json.loads(body).values():
            for result in report["results"]:
                for res in result["resources"]:
                    key = (res["namespace"], res["name"])
                    names.add(key)
                    if result["result"] == "fail":
                        n_fail += 1
                    elif result["result"] == "pass":
                        n_pass += 1
    assert len(names) == N_PODS, f"only {len(names)}/{N_PODS} reported"
    expected_fail = sum(1 for i in range(N_PODS) if i % 3 == 0)
    assert n_fail == expected_fail, (n_fail, expected_fail)
    assert n_pass == N_PODS - expected_fail, (n_pass,)

    # zero divergence at shadow-verify rate 1.0, with real checks run,
    # and the fleet families scrapeable on every survivor. The
    # verifier runs on a background thread: wait for its queue to
    # actually produce match results before judging.
    def checks(i):
        _, body = _get(metrics_ports[i], "/metrics")
        return _metric(body.decode(), "kyverno_verification_checks_total",
                       result="match")

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if all(checks(i) > 0 for i in survivors):
            break
        time.sleep(0.5)
    for i in survivors:
        _, body = _get(metrics_ports[i], "/metrics")
        text = body.decode()
        assert _metric(text, "kyverno_verification_divergence_total") == 0
        assert _metric(text, "kyverno_verification_checks_total",
                       result="match") > 0, f"replica {i} verified nothing"
        for fam in ("kyverno_fleet_replicas", "kyverno_fleet_shards_owned",
                    "kyverno_fleet_heartbeats_total",
                    "kyverno_fleet_shard_reassignments_total"):
            assert f"# TYPE {fam} " in text, (i, fam)
        assert _metric(text, "kyverno_fleet_replicas") == 2
        assert _metric(text, "kyverno_fleet_shard_reassignments_total") > 0
