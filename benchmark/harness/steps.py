"""Steps every generator shares: start serve and wait until it is ready,
read its counters, check that the device answered, and stop it."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict

from .serve import ROOT, HarnessError, Serve, metric

READY_S = 1100.0


def start(run) -> Serve:
    """Spawn serve on the configuration's policies, as served. The fault
    ``audit`` serves them under Audit whatever the configuration states
    (the admission control); any other fault goes to the child."""
    args = list(run.config["serve_args"])
    if run.fault == "audit":
        return Serve(run.policy_dir("Audit"), args, run.workdir)
    return Serve(run.policy_dir(), args, run.workdir, run.fault)


def wait_ready(run, serve: Serve) -> None:
    """Wait for /readyz, record the device serve dispatches to, and check
    that every rule of the set lowered to the device."""
    from .core import say

    serve.wait_for("/healthz", READY_S)
    serve.wait_for("/readyz", READY_S)
    state = serve.get("/debug/state")
    dev = state.get("device") or {}
    run.device = {"platform": dev.get("platform"), "kind": dev.get("kind"),
                  "count": dev.get("count")}
    cc = (state.get("compile_cache") or [{}])[0]
    say(f"ready after {time.perf_counter() - serve.t_spawn} s on {run.device}; "
        f"device rules {cc.get('device_rules')}/{cc.get('total_rules')}; "
        f"native encoder {state.get('native_encoder')}")
    run.checks.add("host_rules", (cc.get("total_rules") or 0)
                   - (cc.get("device_rules") or 0), 0)
    run.checks.add("rules_missing", abs(run.config["rules"]
                                        - (cc.get("total_rules") or 0)), 0)


def counters(serve: Serve) -> Dict[str, float]:
    """The program's counters that the checks and the readers use."""
    text = serve.metrics_text()
    state = serve.get("/debug/state")
    out = {
        "breaker_fallback": metric(text, "kyverno_tpu_breaker_fallback_total"),
        "quarantined": metric(text, "kyverno_policyset_quarantined"),
        "fallback_chunks": metric(text, "kyverno_tpu_pipeline_chunks_total",
                                  path="fallback"),
        "encode_fallback_chunks": metric(
            text, "kyverno_tpu_pipeline_chunks_total", path="encode_fallback"),
        "device_chunks": metric(text, "kyverno_tpu_pipeline_chunks_total",
                                path="device"),
        "shed": metric(text, "kyverno_serving_shed_total"),
        "deadline_expired": metric(text, "kyverno_serving_deadline_expired_total"),
        "hedged": metric(text, "kyverno_serving_hedge_total"),
        "review_s_sum": metric(text, "kyverno_admission_review_duration_seconds_sum"),
        "review_count": metric(text, "kyverno_admission_review_duration_seconds_count"),
        "batch_size_sum": metric(text, "kyverno_serving_batch_size_sum"),
        "batch_count": metric(text, "kyverno_serving_batch_size_count"),
        "breaker_open": float(state["breaker"]["state"] != "closed"),
        "verdict_cache_hits": state["perf_caches"]["verdict"]["hits"],
        "verdict_cache_misses": state["perf_caches"]["verdict"]["misses"],
        "serve_cpu_s": serve.cpu_seconds(),
    }
    for phase, v in (state.get("phase_breakdown") or {}).items():
        out[f"phase_s.{phase}"] = v["seconds"]
    return out


def delta(after: Dict[str, float], before: Dict[str, float]) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def device_checks(run, moved: Dict[str, float], after: Dict[str, float]) -> None:
    """Nothing but the device answered in the window: no breaker or
    pipeline fallback, no quarantine, and no review shed, expired or
    hedged to the scalar engine."""
    for name in ("breaker_fallback", "quarantined", "fallback_chunks",
                 "encode_fallback_chunks", "shed", "deadline_expired", "hedged"):
        run.checks.add(name, moved[name], 0)
    run.checks.add("breaker_open", after["breaker_open"], 0)


def gc_seconds(events, start: float, end: float) -> float:
    """Seconds of the full collections ``[wall start, seconds, freed]``
    that fall between the wall-clock times ``start`` and ``end``."""
    return sum(max(0.0, min(t + d, end) - max(t, start)) for t, d, _ in events)


def start_trace(serve: Serve, run) -> float:
    path = "/debug/xla/start?dir=" + os.path.join(run.workdir, "trace")
    serve.get(path)
    return time.perf_counter()


def stop_trace(serve: Serve) -> float:
    serve.get("/debug/xla/stop", timeout=600)
    return time.perf_counter()


def finish(run, serve: Serve) -> None:
    """Stop serve, then read the device's peak memory and, in a traced
    run, reduce the trace in a process of its own."""
    from .core import say

    peak = serve.stop()
    run.device["memory_peak_bytes"] = peak.get("memory_peak_bytes")
    run.gc_full = peak.get("gc_full") or []
    window = run.spans.get("window_wall")
    if window:
        waves = [round(gc_seconds(run.gc_full, *w), 3)
                 for w in run.spans.get("wave_walls", [])]
        say(f"full collections in the window: "
            f"{gc_seconds(run.gc_full, *window)} s; per wave {waves}")
    trace_dir = os.path.join(run.workdir, "trace")
    if not run.trace or not os.path.isdir(trace_dir):
        return
    out = os.path.join(run.workdir, "trace_summary.json")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "harness", "trace.py"),
           trace_dir, out]
    t0 = time.perf_counter()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    if proc.returncode != 0:
        raise HarnessError(f"trace reduction failed: {proc.stderr[-2000:]}")
    with open(out) as f:
        run.trace_summary = json.load(f)
    say(f"trace reduced in {time.perf_counter() - t0} s: "
        f"busy_s={run.trace_summary.get('busy_s')} "
        f"window_s={run.trace_summary.get('window_s')} "
        f"module_s={run.trace_summary.get('module_s')}")
