#!/usr/bin/env python
"""Proof that kyverno-tpu's main path runs on one TPU chip.

Default run: this process stays off JAX and drives ONE child,
``python -m kyverno_tpu serve kyverno_tpu/policies/pss --batching``,
over HTTP, the way a user would:

1. device attach + cold compile: the child attaches the chip and
   compiles the PSS set (all 66 rules must lower to the device);
2. admission: concurrent AdmissionReviews (CREATE of seeded Pods) on
   the admission port, so micro-batches hold several requests;
3. snapshot load: a 100,000-Pod snapshot (BASELINE config #2) through
   ``POST /snapshot/upsert``;
4. scan: ``POST /scan {"full": true}``, then ``GET /reports``.

Admission verdicts, the admission report rows and a seeded sample of
the scan's report rows must equal the scalar engine's
(kyverno_tpu/engine). Nothing may have been answered anywhere but the
device: no breaker fallback, no quarantined policy, no shed, expired,
hedged or timed-out request, no fallback scan chunk, and the native
encoder loaded.

``--multichip`` runs only the sharded data-parallel scan
(parallel/sharding.py) over the same snapshot on a 4-chip mesh and on
a 1-chip mesh in this one process, and compares them bit for bit.

Each phase prints its wall time on a ``chip_smoke:`` line. The last
line is ``{"ok": true, "device": {...}}`` only when every check held;
any failure, and any run without a TPU, exits nonzero without it.
"""

from __future__ import annotations

import argparse
import functools
import http.client
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

PLATFORM = "tpu"
PSS_DIR = os.path.join("kyverno_tpu", "policies", "pss")
# every rule of the autogen-expanded PSS set lowers to the device
PSS_DEVICE_RULES = 66
ADMISSION_REQUESTS = 64
# the background-scan snapshot: BASELINE config #2
SNAPSHOT_PODS = 100_000
SCALAR_SAMPLE = 2000
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(failures: list, cond: bool, what: str) -> None:
    if not cond:
        failures.append(what)
        say(f"FAIL {what}")


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def http_call(port: int, method: str, path: str, body=None,
              timeout: float = 60.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        if body is not None and not isinstance(body, bytes):
            body = json.dumps(body).encode()
        conn.request(method, path, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(port: int, path: str, timeout: float = 60.0):
    status, data = http_call(port, "GET", path, timeout=timeout)
    if status != 200:
        raise SmokeFailure(f"GET {path} -> {status}: {data[:300]!r}")
    return json.loads(data)


def metric(text: str, name: str, **labels) -> float:
    """Sum of every series of ``name`` whose labels include ``labels``."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest and rest[0] not in ("{", " "):
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total += float(line.split(" # ")[0].rsplit(" ", 1)[-1])
    return total


def pss_policies():
    from kyverno_tpu.policies import load_pss_policies
    from kyverno_tpu.policy.autogen import expand_policy

    return [expand_policy(p) for p in load_pss_policies()]


@functools.lru_cache(maxsize=1)
def scalar_engine():
    from kyverno_tpu.engine.engine import Engine

    return Engine(background=True)


def scalar_results(policies, resource, operation: str = "",
                   info=None) -> dict:
    """{(policy, rule): result} from the scalar engine, in the report's
    vocabulary (not-matched rules have no row)."""
    from kyverno_tpu.engine.policycontext import build_scan_context

    out = {}
    for policy in policies:
        pctx = build_scan_context(policy, resource, {}, operation, info)
        for rr in scalar_engine().validate(pctx).policy_response.rules:
            out[(policy.name, rr.name)] = rr.status
    return out


def report_index(reports: dict) -> dict:
    """(namespace, name) -> {(policy, rule): result} over every report."""
    index: dict = {}
    for rep in reports.values():
        for r in rep["results"]:
            res = r["resources"][0]
            index.setdefault((res["namespace"], res["name"]), {})[
                (r["policy"], r["rule"])] = r["result"]
    return index


def make_pods(n: int, seed: int, prefix: str = ""):
    from bench import make_snapshot

    pods = make_snapshot(n, seed=seed)
    if prefix:
        for i, p in enumerate(pods):
            p["metadata"]["name"] = f"{prefix}-{i}"
    return pods


# ---------------------------------------------------------------------------
# the default path: one serve child over HTTP


def start_serve(adm_port: int, met_port: int):
    os.makedirs(OUT_DIR, exist_ok=True)
    oplog = os.path.join(OUT_DIR, "serve_oplog.jsonl")
    if os.path.exists(oplog):
        os.remove(oplog)
    cmd = [sys.executable, "-m", "kyverno_tpu", "serve", PSS_DIR,
           "--port", str(adm_port), "--metrics-port", str(met_port),
           # serve's own admission budgets (10 s request, 5 s queue):
           # every batch bucket compiles before /readyz goes ready
           "--batching", "--scan-interval", "86400",
           "--log-file", oplog]
    log = open(os.path.join(OUT_DIR, "serve.log"), "wb")
    child = subprocess.Popen(cmd, cwd=HERE, stdout=log,
                             stderr=subprocess.STDOUT,
                             start_new_session=True)
    return child, log, oplog


def stop_serve(child) -> None:
    if child.poll() is None:
        child.terminate()
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait()


def wait_for(child, port: int, path: str, deadline: float) -> dict:
    while True:
        if child.poll() is not None:
            raise SmokeFailure(f"serve exited with {child.returncode} "
                               f"before {path} answered")
        try:
            status, data = http_call(port, "GET", path, timeout=600)
            if status == 200:
                return json.loads(data) if data[:1] in (b"{", b"[") else {}
        except (OSError, http.client.HTTPException):
            pass
        if time.perf_counter() > deadline:
            raise SmokeFailure(f"{path} did not answer 200 in time")
        time.sleep(0.2)


def review(pod: dict, i: int) -> dict:
    return {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
            "request": {
                "uid": f"chip-smoke-{i}", "operation": "CREATE",
                "kind": {"group": "", "version": "v1", "kind": "Pod"},
                "resource": {"group": "", "version": "v1",
                             "resource": "pods"},
                "namespace": pod["metadata"]["namespace"],
                "name": pod["metadata"]["name"],
                "userInfo": {"username": "chip-smoke"},
                "object": pod}}


def phase_admission(args, adm_port: int, policies, failures) -> list:
    from kyverno_tpu.engine.match import RequestInfo

    pods = make_pods(ADMISSION_REQUESTS, args.seed + 1, prefix="admission")
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(pods)) as ex:
        answers = list(ex.map(
            lambda ip: http_call(adm_port, "POST", "/validate",
                                 review(ip[1], ip[0]), timeout=60),
            enumerate(pods)))
    wall = time.perf_counter() - t0
    say(f"phase=admission wall_s={wall} requests={len(pods)}")
    enforce = {p.name for p in policies if (
        p.spec.validation_failure_action or "Audit").lower().startswith(
            "enforce")}
    info = RequestInfo(username="chip-smoke")
    for i, ((status, data), pod) in enumerate(zip(answers, pods)):
        check(failures, status == 200, f"admission {i}: HTTP {status}")
        if status != 200:
            continue
        resp = json.loads(data)["response"]
        msg = (resp.get("status") or {}).get("message", "")
        check(failures, "evaluation error" not in msg,
              f"admission {i} answered by an error path: {msg[:200]}")
        want = scalar_results(policies, pod, "CREATE", info)
        allowed = not any(r in ("fail", "error") and p in enforce
                          for (p, _), r in want.items())
        check(failures, resp.get("uid") == f"chip-smoke-{i}"
              and resp.get("allowed") is allowed,
              f"admission {i}: allowed={resp.get('allowed')} "
              f"scalar={allowed}")
    return pods


def phase_load(args, met_port: int) -> list:
    t0 = time.perf_counter()
    pods = make_pods(SNAPSHOT_PODS, args.seed)
    say(f"phase=generate wall_s={time.perf_counter() - t0} pods={len(pods)}")
    bodies = [json.dumps(p).encode() for p in pods]

    def upsert(lo_hi):
        bad = 0
        for body in bodies[lo_hi[0]:lo_hi[1]]:
            status, _ = http_call(met_port, "POST", "/snapshot/upsert", body)
            bad += status != 200
        return bad

    workers = 16
    step = (len(bodies) + workers - 1) // workers
    t0 = time.perf_counter()
    with ThreadPoolExecutor(workers) as ex:
        bad = sum(ex.map(upsert, [(i, i + step)
                                  for i in range(0, len(bodies), step)]))
    say(f"phase=snapshot_load wall_s={time.perf_counter() - t0} "
        f"pods={len(bodies)} rejected={bad}")
    if bad:
        raise SmokeFailure(f"{bad} snapshot upserts were rejected")
    return pods


def run_serve_path(args) -> dict:
    # the chip belongs to the serve child: this process must never
    # have loaded JAX (it would hold the chip and the child would hang)
    assert "jax" not in sys.modules, "chip_smoke parent imported jax"
    failures: list = []
    policies = pss_policies()
    adm_port, met_port = free_port(), free_port()
    t_spawn = time.perf_counter()
    child, log, oplog = start_serve(adm_port, met_port)
    try:
        wait_for(child, met_port, "/healthz", t_spawn + 600)
        state = get_json(met_port, "/debug/state")
        device = state.get("device") or {}
        say(f"phase=device_attach wall_s={device.get('attach_s')} "
            f"platform={device.get('platform')} kind={device.get('kind')!r} "
            f"count={device.get('count')}")
        if device.get("platform") != PLATFORM:
            raise SmokeFailure(f"serve dispatches to "
                               f"{device.get('platform')!r}, not {PLATFORM}")
        t_up = time.perf_counter()
        wait_for(child, met_port, "/readyz", t_up + 900)
        say(f"phase=cold_compile wall_s={time.perf_counter() - t_up} "
            f"(policy-set compile + XLA build at every admission bucket; serve "
            f"start to ready {time.perf_counter() - t_spawn})")
        state = get_json(met_port, "/debug/state")
        say(f"xla_cache_dir={state['perf_caches']['xla_cache_dir']}")
        cc = state["compile_cache"][0]
        say(f"device_rules={cc['device_rules']} total_rules="
            f"{cc['total_rules']}")
        check(failures, cc["device_rules"] == cc["total_rules"]
              == PSS_DEVICE_RULES,
              f"device rules {cc['device_rules']}/{cc['total_rules']}, "
              f"want {PSS_DEVICE_RULES}/{PSS_DEVICE_RULES}")

        adm_pods = phase_admission(args, adm_port, policies, failures)
        pods = phase_load(args, met_port)

        before = phase_seconds(met_port)
        t0 = time.perf_counter()
        status, data = http_call(met_port, "POST", "/scan", {"full": True},
                                 timeout=1800)
        if status != 200:
            raise SmokeFailure(f"POST /scan -> {status}: {data[:300]!r}")
        scanned = json.loads(data)["scanned"]
        say(f"phase=scan wall_s={time.perf_counter() - t0} "
            f"scanned={scanned}")
        # the engine's own per-phase split of that scan (profiling.py)
        split = {k: v - before.get(k, 0.0)
                 for k, v in phase_seconds(met_port).items()}
        say("scan_split " + " ".join(f"{k}_s={v}" for k, v in split.items()))
        check(failures, scanned == len(pods),
              f"scan covered {scanned} of {len(pods)} pods")

        t0 = time.perf_counter()
        index = report_index(get_json(met_port, "/reports", timeout=1800))
        say(f"phase=reports wall_s={time.perf_counter() - t0} "
            f"resources={len(index)}")
        check(failures, len(index) == len(pods) + len(adm_pods),
              f"reports hold {len(index)} resources, want "
              f"{len(pods) + len(adm_pods)}")

        # per-rule results vs the scalar engine: every admission pod and
        # a seeded sample of the scan
        t0 = time.perf_counter()
        sample = random.Random(args.seed).sample(
            range(len(pods)), min(SCALAR_SAMPLE, len(pods)))
        mismatched = 0
        for pod, op in ([(p, "CREATE") for p in adm_pods]
                        + [(pods[i], "") for i in sample]):
            meta = pod["metadata"]
            got = index.get((meta["namespace"], meta["name"]), {})
            if got != scalar_results(policies, pod, op):
                mismatched += 1
                if mismatched <= 5:
                    say(f"mismatch {meta['namespace']}/{meta['name']}")
        say(f"phase=scalar_check wall_s={time.perf_counter() - t0} "
            f"checked={len(adm_pods) + len(sample)} mismatched={mismatched}")
        check(failures, mismatched == 0,
              f"{mismatched} resources differ from the scalar engine")

        check_no_fallback(met_port, failures)
        if failures:
            _print_oplog_errors(oplog)
            raise SmokeFailure(f"{len(failures)} checks failed")
        return {"platform": device["platform"], "kind": device["kind"],
                "count": device["count"]}
    finally:
        stop_serve(child)
        log.close()


def phase_seconds(met_port: int) -> dict:
    return {k: v["seconds"] for k, v in
            get_json(met_port, "/debug/state")["phase_breakdown"].items()}


def check_no_fallback(met_port: int, failures: list) -> None:
    """Nothing but the device answered: every rung of the breaker,
    quarantine, shed and hedge ladders stayed untouched."""
    state = get_json(met_port, "/debug/state")
    _, body = http_call(met_port, "GET", "/metrics")
    text = body.decode()
    native = state.get("native_encoder") or {}
    say(f"native_encoder_loaded={native.get('loaded')} "
        f"so={native.get('so')} error={native.get('error')}")
    check(failures, native.get("loaded") is True,
          "native encoder did not load")
    counts = {
        "breaker_fallback": metric(text, "kyverno_tpu_breaker_fallback_total"),
        "quarantined": metric(text, "kyverno_policyset_quarantined"),
        "shed": metric(text, "kyverno_serving_shed_total"),
        "deadline_expired": metric(
            text, "kyverno_serving_deadline_expired_total"),
        "hedged": metric(text, "kyverno_serving_hedge_total"),
        "fallback_chunks": metric(text, "kyverno_tpu_pipeline_chunks_total",
                                  path="fallback"),
        "encode_fallback_chunks": metric(
            text, "kyverno_tpu_pipeline_chunks_total",
            path="encode_fallback"),
    }
    say("fallbacks " + " ".join(f"{k}={v}" for k, v in counts.items()))
    for k, v in counts.items():
        check(failures, v == 0, f"{k} = {v}")
    check(failures, state["breaker"]["state"] == "closed",
          f"breaker {state['breaker']['state']}")
    device_chunks = metric(text, "kyverno_tpu_pipeline_chunks_total",
                           path="device")
    batches = metric(text, "kyverno_serving_batch_size_count")
    singles = metric(text, "kyverno_serving_batch_size_bucket", le="1")
    say(f"device_scan_chunks={device_chunks} admission_batches={batches} "
        f"single_request_batches={singles}")
    check(failures, device_chunks > 0, "no scan chunk ran on the device")
    check(failures, batches > singles,
          "no admission micro-batch held more than one request")


def _print_oplog_errors(path: str) -> None:
    try:
        with open(path) as f:
            events = [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return
    for e in [e for e in events if e.get("level") in ("warn", "error")][-10:]:
        say(f"oplog {json.dumps(e)[:600]}")


# ---------------------------------------------------------------------------
# --multichip: the sharded data-parallel scan, 4 chips vs 1


def run_multichip(args) -> dict:
    import jax
    import numpy as np

    from kyverno_tpu.parallel import ShardedScanner, make_mesh

    failures: list = []
    t0 = time.perf_counter()
    devices = jax.devices()
    say(f"phase=device_attach wall_s={time.perf_counter() - t0} "
        f"platform={devices[0].platform} kind={devices[0].device_kind!r} "
        f"count={len(devices)}")
    if devices[0].platform != PLATFORM:
        raise SmokeFailure(f"JAX found {devices[0].platform}, not {PLATFORM}")
    if len(devices) < 4:
        raise SmokeFailure(f"--multichip needs 4 chips, found {len(devices)}")
    policies = pss_policies()
    pods = make_pods(SNAPSHOT_PODS, args.seed)
    tile = 8192
    results = {}
    for n in (4, 1):
        scanner = ShardedScanner(policies, mesh=make_mesh(devices[:n]))
        t0 = time.perf_counter()
        scanner.scan_stream(pods[:tile], tile=tile)  # compile this shape
        t1 = time.perf_counter()
        result, stats = scanner.scan_stream(pods, tile=tile)
        t2 = time.perf_counter()
        say(f"phase=scan_{n}chip compile_wall_s={t1 - t0} wall_s={t2 - t1} "
            f"pods={len(pods)} tiles={stats['tiles']} "
            f"encode_s={stats['encode_s']} device_s={stats['device_s']} "
            f"host_s={stats['host_s']}")
        # one tile's device output stays resident for the placement check
        batch, _ = scanner.encode(pods[:tile])
        verdicts, counts = scanner._step(scanner.put(batch))
        verdicts.block_until_ready()
        if n == 4:
            placed = verdicts.sharding.device_set
            in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                      for d in devices[:4]]
            say(f"verdict_devices={len(placed)} bytes_in_use={in_use}")
            check(failures, len(placed) == 4,
                  f"verdicts live on {len(placed)} devices, want 4")
            check(failures, all(b > 0 for b in in_use),
                  f"a chip holds no data: {in_use}")
        results[n] = (result, np.asarray(verdicts), np.asarray(counts))
    (r4, v4, c4), (r1, v1, c1) = results[4], results[1]
    same_v = np.array_equal(r4.verdicts, r1.verdicts)
    same_c = r4.counts() == r1.counts()
    same_tile = np.array_equal(v4, v1) and np.array_equal(c4, c1)
    say(f"bit_identical verdicts={same_v} counts={same_c} "
        f"device_tile={same_tile} counts_4chip={r4.counts()}")
    check(failures, same_v and same_c and same_tile,
          "4-chip scan differs from 1-chip scan")
    # and the 1-chip scan agrees with the scalar engine on a sample
    from kyverno_tpu.tpu.engine import NOT_MATCHED, VERDICT_NAMES

    rows = r1.rules
    sample = random.Random(args.seed).sample(range(len(pods)),
                                             min(500, len(pods)))
    mismatched = 0
    for i in sample:
        got = {rows[r]: VERDICT_NAMES.get(int(r1.verdicts[r, i]), "?")
               for r in range(len(rows))
               if int(r1.verdicts[r, i]) != NOT_MATCHED}
        mismatched += got != scalar_results(policies, pods[i])
    say(f"scalar_check checked={len(sample)} mismatched={mismatched}")
    check(failures, mismatched == 0,
          f"{mismatched} resources differ from the scalar engine")
    if failures:
        raise SmokeFailure(f"{len(failures)} checks failed")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the 4-chip vs 1-chip sharded scan")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # a SIGTERM from a time limit still runs the finally that stops
    # the serve child
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    platforms = os.environ.get("JAX_PLATFORMS")
    try:
        if platforms and PLATFORM not in platforms.split(","):
            raise SmokeFailure(f"JAX_PLATFORMS={platforms} rules out the "
                               f"{PLATFORM}")
        device = run_multichip(args) if args.multichip \
            else run_serve_path(args)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
