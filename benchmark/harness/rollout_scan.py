"""Generator ``rollout_scan``: rolling updates fed to serve's snapshot API,
each wave followed by one ``POST /scan``, in a closed loop.

Set-up loads the cluster in waves of the window's size, each followed by
a scan, so that it compiles only the window's shapes. The window then
runs waves back to back and closes at the first wave
boundary after ``--seconds``. ``rollout_pods_per_s`` is every Pod
replaced in the window over the whole window.

Checks, once the window has closed: the per-rule report rows of every
Pod the window created, and of a seeded sample of the others, equal the
plain reference's; no Pod the window deleted has rows; every Pod of the
cluster has rows; every feed and scan call answered 200; and nothing but
the device answered.
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

from . import steps
from .gen import Cluster, Rollout, stream
from .serve import HarnessError, call


def _feed(port: int, ops: List[Tuple[str, bytes]], workers: int) -> int:
    """POST every (path, body) with ``workers`` clients; the number of
    calls that did not answer 200."""
    def part(chunk):
        bad = 0
        for path, body in chunk:
            try:
                status, _ = call(port, "POST", path, body)
                bad += status != 200
            except OSError:
                bad += 1
        return bad

    step = (len(ops) + workers - 1) // workers
    with ThreadPoolExecutor(workers) as ex:
        return sum(ex.map(part, [ops[i:i + step] for i in range(0, len(ops), step)]))


def _delete_body(pod: dict) -> bytes:
    m = pod["metadata"]
    return json.dumps({"apiVersion": "v1", "kind": "Pod", "metadata": {
        "name": m["name"], "namespace": m["namespace"], "uid": m["uid"]}}).encode()


def _wave_ops(wave) -> List[Tuple[str, bytes]]:
    ops = []
    for old, new in wave:
        ops.append(("/snapshot/delete", _delete_body(old)))
        ops.append(("/snapshot/upsert", json.dumps(new).encode()))
    return ops


def _scan(port: int) -> int:
    """One incremental scan: every Pod changed since the last one."""
    status, data = call(port, "POST", "/scan", {"full": False}, timeout=1800)
    if status != 200:
        raise HarnessError(f"POST /scan -> {status}: {data[:300]!r}")
    return json.loads(data)["scanned"]


def report_index(reports: dict) -> Dict[Tuple[str, str], Dict]:
    """(namespace, name) -> {(policy, rule): result} over every report."""
    index: Dict = {}
    for rep in reports.values():
        for r in rep["results"]:
            res = r["resources"][0]
            index.setdefault((res["namespace"], res["name"]), {})[
                (r["policy"], r["rule"])] = r["result"]
    return index


def _key(pod: dict) -> Tuple[str, str]:
    return pod["metadata"]["namespace"], pod["metadata"]["name"]


def drive(run) -> None:
    from .core import say

    cfg, traffic = run.config, run.traffic
    workers = traffic["feed_clients"]
    serve = steps.start(run)
    try:
        t_gen = time.perf_counter()
        cluster = Cluster(run.seed, cfg["cluster"], cfg["cluster"]["pods"])
        rollout = Rollout(cluster, traffic)
        bodies = [("/snapshot/upsert", json.dumps(p).encode())
                  for p in cluster.pods()]
        say(f"generated {len(bodies)} pods in "
            f"{len(cluster.deployments)} deployments in "
            f"{time.perf_counter() - t_gen} s")
        steps.wait_ready(run, serve)
        port = serve.met_port

        # the cluster arrives in waves of the window's size, each
        # scanned: the only shapes compiled are the window's own
        t0 = time.perf_counter()
        failed_setup = 0
        step = traffic["wave_pods"]
        for i in range(0, len(bodies), step):
            failed_setup += _feed(port, bodies[i:i + step], workers)
            _scan(port)
        setup_s = time.perf_counter() - serve.t_spawn
        say(f"loaded and scanned {len(bodies)} pods in "
            f"{time.perf_counter() - t0} s; setup_s={setup_s}")

        before = steps.counters(serve)
        created: Dict[str, dict] = {}
        deleted: List[dict] = []
        feed_s = scan_s = 0.0
        pods = waves = failed = 0
        traced_pods = 0
        t_trace0 = t_trace1 = None
        t_w0, wall_w0 = time.perf_counter(), time.time()
        wave_walls = []
        while True:
            if run.trace and waves == 1 and t_trace0 is None:
                t_trace0 = steps.start_trace(serve, run)
            wave = rollout.next_wave()
            ta, wall_a = time.perf_counter(), time.time()
            failed += _feed(port, _wave_ops(wave), workers)
            tb = time.perf_counter()
            n = _scan(port)
            tc = time.perf_counter()
            wave_walls.append((wall_a, time.time()))
            feed_s += tb - ta
            scan_s += tc - tb
            waves += 1
            pods += len(wave)
            for old, new in wave:
                created.pop(old["metadata"]["uid"], None)
                created[new["metadata"]["uid"]] = new
                deleted.append(old)
            say(f"wave {waves}: {len(wave)} pods feed {tb - ta} s scan {tc - tb} s "
                f"scanned {n}")
            if t_trace0 is not None and t_trace1 is None:
                traced_pods += len(wave)
                if waves - 1 >= traffic["trace_waves"]:
                    t_trace1 = steps.stop_trace(serve)
            if tc - t_w0 >= run.seconds:
                break
        window = time.perf_counter() - t_w0
        wall_w1 = time.time()
        if t_trace0 is not None and t_trace1 is None:
            t_trace1 = steps.stop_trace(serve)
        after = steps.counters(serve)
        moved = steps.delta(after, before)
        run.metrics["rollout_pods_per_s"] = pods / window
        run.metrics["setup_s"] = setup_s
        run.attempted, run.failed = pods, failed
        run.spans.update(window_s=window, waves=waves, pods=pods,
                         feed_s=feed_s, scan_s=scan_s, traced_pods=traced_pods,
                         window_wall=(wall_w0, wall_w1), wave_walls=wave_walls)
        run.counters.update(moved)
        say(f"window {window} s: {waves} waves, {pods} pods, "
            f"{pods / window} pods/s; feed {feed_s} s, scan {scan_s} s; "
            f"serve cpu {moved['serve_cpu_s']} s; verdict cache hits "
            f"{moved['verdict_cache_hits']} misses {moved['verdict_cache_misses']}; "
            f"phases " + " ".join(f"{k[8:]}={v:.3f}" for k, v in moved.items()
                                  if k.startswith("phase_s.")))

        # the comparison, once the window has closed
        t3 = time.perf_counter()
        index = report_index(serve.get("/reports", timeout=600))
        current = {_key(p): p for p in cluster.pods()}
        ref, policies = run.reference_policies()
        rest = [p for p in current.values() if p["metadata"]["uid"] not in created]
        sample = stream(run.seed, "check").sample(
            rest, min(traffic["check_sample"], len(rest)))
        compared = list(created.values()) + sample
        mismatched = 0
        for pod in compared:
            want = ref.rule_results(policies, pod)
            got = index.get(_key(pod), {})
            if got != want:
                mismatched += 1
                if mismatched <= 3:
                    diff = {f"{p}/{r}": [got.get((p, r)), want.get((p, r))]
                            for (p, r) in set(got) | set(want)
                            if got.get((p, r)) != want.get((p, r))}
                    say(f"mismatch {_key(pod)} [served, reference]: {diff}")
        say(f"compared {len(compared)} pods ({len(created)} from the window) "
            f"in {time.perf_counter() - t3} s")
        run.checks.add("failed_calls", failed + failed_setup, 0)
        run.checks.add("mismatched_pods", mismatched, 0)
        run.checks.add("missing_pods", sum(k not in index for k in current), 0)
        run.checks.add("deleted_with_rows",
                       sum(_key(p) in index for p in deleted
                           if _key(p) not in current), 0)
        run.checks.add("unexpected_pods", sum(k not in current for k in index), 0)
        steps.device_checks(run, moved, after)
    finally:
        steps.finish(run, serve)
