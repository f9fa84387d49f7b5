"""Report-store crash leg (ISSUE 17 acceptance): SIGKILL mid-fold.

One REAL serve process journaling reports to disk, with a delay fault
armed at ``reports.fold`` (via KYVERNO_TPU_FAULTS) so every fold holds
the window open. The test fires a /scan and SIGKILLs the process while
folds are in flight, then asserts the crash-consistency contract:

- ``kyverno-tpu report <dir> --rebuild-check`` (offline recovery)
  exits 0 and reports delta-state == rebuild() bit-identity;
- a serve RESTART on the same directory recovers, counts the replay on
  ``kyverno_reports_recoveries_total``, and serves the recovered rows
  on ``/reports?source=store``;
- after a fresh full scan the store agrees with the live aggregator
  and the shadow verifier (rate 1.0) logs zero divergences.

Marked slow: boots two serve processes (amortized through a shared
persistent XLA cache dir).
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest
import yaml

pytestmark = pytest.mark.slow

N_PODS = 80


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


def test_sigkill_mid_fold_recovers_bit_identical(tmp_path, serve_procs):
    policy_file = tmp_path / "policy.yaml"
    policy_file.write_text(yaml.safe_dump({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "reports-chaos"},
        "spec": {"validationFailureAction": "Enforce", "rules": [{
            "name": "no-privileged",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"message": "no privileged",
                         "pattern": {"spec": {"containers": [
                             {"=(securityContext)":
                              {"=(privileged)": "false"}}]}}},
        }]}}))
    reports_dir = tmp_path / "reports"
    xla_cache = tmp_path / "xla"
    base_env = dict(os.environ)
    base_env.update({"JAX_PLATFORMS": "cpu",
                     "JAX_COMPILATION_CACHE_DIR": str(xla_cache)})
    base_env.pop("KYVERNO_TPU_FAULTS", None)

    def boot(metrics_port, fold_delay_s=None):
        env = dict(base_env)
        if fold_delay_s:
            # every fold sleeps: the SIGKILL lands inside the window
            # between journal-append and derived-count update
            env["KYVERNO_TPU_FAULTS"] = \
                f"reports.fold:delay:delay_s={fold_delay_s},p=1.0"
        p = subprocess.Popen(
            [sys.executable, "-m", "kyverno_tpu", "serve",
             str(policy_file),
             "--port", "0", "--metrics-port", str(metrics_port),
             "--scan-interval", "9999", "--batching",
             "--reports-dir", str(reports_dir),
             "--shadow-verify-rate", "1.0",
             "--flight-sample-rate", "1.0"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            text=True)
        serve_procs.append(p)
        return p

    def wait_ready(p, metrics_port, timeout=300):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if p.poll() is not None:
                raise AssertionError(
                    "serve died at boot:\n" + (p.stderr.read() or "")[-2000:])
            try:
                status, _ = _get(metrics_port, "/healthz", timeout=2)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.3)
        raise AssertionError("serve never became healthy")

    port1 = _free_port()
    victim = boot(port1, fold_delay_s=0.02)
    wait_ready(victim, port1)

    for pod in _pods(N_PODS):
        status, _ = _post(port1, "/snapshot/upsert", pod)
        assert status == 200

    # fire the scan and SIGKILL while folds are in flight: 80 pods at
    # >=20ms of injected fold delay each keeps the scan alive well past
    # the kill point
    def fire_scan():
        try:
            _post(port1, "/scan", {"full": True}, timeout=30)
        except OSError:
            pass  # the kill races the response; either is fine

    t = threading.Thread(target=fire_scan, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if os.path.isdir(reports_dir) and os.path.exists(
                os.path.join(reports_dir, "journal.wal")) and \
                os.path.getsize(os.path.join(reports_dir, "journal.wal")) > 0:
            break
        time.sleep(0.02)
    time.sleep(0.1)  # a few more folds mid-flight
    os.kill(victim.pid, signal.SIGKILL)
    victim.wait(timeout=10)

    jpath = os.path.join(reports_dir, "journal.wal")
    assert os.path.getsize(jpath) > 0, "no deltas journaled before the kill"

    # offline recovery oracle: the CLI replays the journal and asserts
    # delta state == rebuild() bit-identity (exit 1 on mismatch)
    cli = subprocess.run(
        [sys.executable, "-m", "kyverno_tpu", "report", str(reports_dir),
         "--rebuild-check", "--json"],
        env=base_env, capture_output=True, text=True, timeout=120)
    assert cli.returncode == 0, cli.stderr[-2000:]
    doc = json.loads(cli.stdout)
    assert doc["rebuild_identical"] is True
    assert doc["state"]["resources"] > 0
    recovered_resources = doc["state"]["resources"]
    recovered_summary = doc["summary"]

    # restart on the SAME directory (no fault this time): the replay
    # recovery is counted and the recovered rows are served
    port2 = _free_port()
    survivor = boot(port2)
    wait_ready(survivor, port2)

    status, body = _get(port2, "/metrics")
    assert status == 200
    text = body.decode()
    assert _metric(text, "kyverno_reports_recoveries_total") > 0, \
        "unclean shutdown must be counted as a recovery"
    assert _metric(text, "kyverno_reports_resources") \
        == recovered_resources

    status, body = _get(port2, "/reports?source=store")
    assert status == 200
    served = json.loads(body)
    served_rows = sum(len(r.get("results", [])) for r in served.values())
    assert served_rows == sum(recovered_summary.values())

    # a fresh full scan over the same snapshot-fed pods converges the
    # store on the live truth; shadow verifier at rate 1.0 throughout
    for pod in _pods(N_PODS):
        status, _ = _post(port2, "/snapshot/upsert", pod)
        assert status == 200
    status, body = _post(port2, "/scan", {"full": True})
    assert status == 200
    assert json.loads(body)["scanned"] == N_PODS

    status, body = _get(port2, "/debug/state")
    assert status == 200
    dbg = json.loads(body)
    assert dbg["reports"]["enabled"] is True
    assert dbg["reports"]["resources"] == N_PODS

    def checks():
        _, b = _get(port2, "/metrics")
        return _metric(b.decode(), "kyverno_verification_checks_total",
                       result="match")

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if checks() > 0:
            break
        time.sleep(0.5)
    _, body = _get(port2, "/metrics")
    text = body.decode()
    assert _metric(text, "kyverno_verification_divergence_total") == 0
    assert _metric(text, "kyverno_verification_checks_total",
                   result="match") > 0
    for fam in ("kyverno_reports_resources", "kyverno_reports_fold_ops_total",
                "kyverno_reports_journal_records_total",
                "kyverno_reports_recoveries_total"):
        assert f"# TYPE {fam} " in text, fam
