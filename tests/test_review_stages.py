"""Per-review stage accounting inside serve: one trace per review cut
into contiguous stage spans (and ``review.<stage>`` phases), the batch's
engine spans under one live ``admission.batch`` span, XLA compiles
counted where they happen, full collections as spans, and the program's
spans written on a profiler session's clock."""

import gc
import glob
import http.client
import json
import os
import time

import pytest

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.cluster import ClusterSnapshot, PolicyCache
from kyverno_tpu.observability.metrics import global_registry
from kyverno_tpu.observability.profiling import global_profiler
from kyverno_tpu.observability.tracing import global_tracer
from kyverno_tpu.webhooks import AdmissionServer, build_handlers

POLICY = {
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
}

STAGES = ("read", "admit", "queue_wait", "flush", "wake", "decide",
          "respond")


def _review(name, uid):
    pod = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": name, "namespace": "default"},
           "spec": {"containers": [{"name": "c", "image": "nginx"}]}}
    return {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
            "request": {"uid": uid, "operation": "CREATE",
                        "namespace": "default", "object": pod,
                        "userInfo": {"username": "alice"}}}


def _handlers():
    cache = PolicyCache()
    cache.set(ClusterPolicy.from_dict(POLICY))
    return build_handlers(cache, ClusterSnapshot(), batching=True,
                          max_wait_ms=2.0)


def _post(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/validate", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = json.loads(resp.read())
    conn.close()
    return data


def _calls(name):
    return global_profiler.breakdown().get(name, {}).get("calls", 0)


def test_validate_review_is_one_trace_of_contiguous_stages():
    handlers = _handlers()
    handlers._engine()  # compile before the timed review
    walls = []
    validate = handlers.validate

    def timed(*a, **kw):
        t0 = time.monotonic()
        try:
            return validate(*a, **kw)
        finally:
            walls.append(time.monotonic() - t0)

    handlers.validate = timed
    srv = AdmissionServer(handlers, port=0)
    srv.start()
    before = {s: _calls("review." + s) for s in STAGES}
    try:
        out = _post(srv.port, _review("stages-pod", "u-stages"))
    finally:
        srv.stop()
    assert out["response"]["uid"] == "u-stages"
    assert out["response"]["allowed"] is True
    root = [s for s in global_tracer.finished("admission.review")
            if s.attributes.get("path") == "validate"][-1]
    trace = {s.name: s for s in global_tracer.trace(root.trace_id)}
    stages = {s: trace["admission." + s] for s in STAGES}
    assert trace["admission.submit"].parent_span_id == root.span_id
    for s in ("read", "admit", "wake", "decide", "respond"):
        assert stages[s].parent_span_id == root.span_id, s
    # admit .. decide meet end to start and cover validate's wall time
    inner = [stages[s] for s in STAGES[1:-1]]
    for a, b in zip(inner, inner[1:]):
        assert a.end == b.start, (a.name, b.name)
    assert abs(sum(s.duration for s in inner) - walls[0]) < 1e-3
    assert root.start == stages["read"].start
    assert stages["respond"].end <= root.end
    # the flush names the shared batch span, whose trace holds the
    # engine's encode, dispatch and assemble
    batch_id = stages["flush"].attributes["batch_span_id"]
    batch = [s for s in global_tracer.finished("admission.batch")
             if s.span_id == batch_id][0]
    assert batch.attributes["engine"] == "device"
    children = {s.name for s in global_tracer.trace(batch.trace_id)
                if s.parent_span_id == batch.span_id}
    assert {"tpu.encode", "tpu.dispatch", "tpu.assemble"} <= children
    assert batch.end == stages["flush"].end
    # one review.<stage> phase entry per stage
    assert {s: _calls("review." + s) - before[s] for s in STAGES} == \
        {s: 1 for s in STAGES}


def _dispatch_under(name, run):
    """Run ``run`` under a span of its own; its tpu.dispatch child."""
    with global_tracer.span(name) as parent:
        run()
    return [s for s in global_tracer.trace(parent.trace_id)
            if s.name == "tpu.dispatch"][0]


def test_jit_at_new_shape_counts_compile_on_its_dispatch_span():
    from kyverno_tpu.tpu.compiler import compile_policy_set
    from kyverno_tpu.tpu.engine import TpuEngine

    eng = TpuEngine(cps=compile_policy_set([ClusterPolicy.from_dict(POLICY)]))
    series = global_registry.xla_compiles.series
    before = sum(v for _, v in series())
    dispatch = _dispatch_under(
        "first", lambda: eng._scan_uncached([{}] * 16, live_n=0))
    assert sum(v for _, v in series()) > before
    assert {labels["source"] for labels, _ in series()} <= {"compile", "cache"}
    events = [e for e in dispatch.events if e.name == "xla_compile"]
    assert events and events[0].attributes["seconds"] > 0
    assert events[0].attributes["source"] in ("compile", "cache")
    # a second call at the same shape compiles nothing
    before = sum(v for _, v in series())
    dispatch = _dispatch_under(
        "second", lambda: eng._scan_uncached([{}] * 16, live_n=0))
    assert sum(v for _, v in series()) == before
    assert not [e for e in dispatch.events if e.name == "xla_compile"]


def test_profiler_session_writes_program_spans_on_the_trace_clock(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from kyverno_tpu.webhooks.server import handle_debug_path

    def kyverno_probe(x):
        return (x * 3).sum()

    probe = jax.jit(kyverno_probe)
    probe(jnp.ones(64)).block_until_ready()
    out = str(tmp_path / "trace")
    code, body, _ = handle_debug_path(f"/debug/xla/start?dir={out}")
    assert code == 200, body
    with global_tracer.span("probe.call") as span:
        probe(jnp.ones(64)).block_until_ready()
    code, body, _ = handle_debug_path("/debug/xla/stop")
    assert code == 200, body
    with open(os.path.join(out, "program_spans.json")) as f:
        doc = json.load(f)
    names = [s["name"] for s in doc["spans"]]
    assert {"probe.call", "debug.xla_start", "debug.xla_stop"} <= set(names)
    a0, a1 = doc["anchor"]["mono_ns"]
    assert 0 < a1 - a0 < 50_000_000
    rec = [s for s in doc["spans"] if s["span_id"] == span.span_id][0]
    assert rec["start_ns"] == int(span.start * 1e9)
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"][0]
    events = [e for line in host.lines for e in line.events]
    anchor = [e for e in events if e.name == doc["anchor"]["name"]]
    assert len(anchor) == 1
    offset = anchor[0].start_ns - (a0 + a1) / 2
    s, e = rec["start_ns"] + offset, rec["end_ns"] + offset
    lo = min(ev.start_ns for ev in events)
    hi = max(ev.start_ns + ev.duration_ns for ev in events)
    assert lo <= s < e <= hi
    # the jitted call the span wraps lies inside the mapped span
    calls = [ev for ev in events if ev.name == "PjitFunction(kyverno_probe)"]
    assert calls
    assert any(s - 1e5 <= ev.start_ns and
               ev.start_ns + ev.duration_ns <= e + 1e5 for ev in calls)
    # a second stop has no session to end
    assert handle_debug_path("/debug/xla/stop")[0] == 500


def test_forced_full_collection_is_one_span():
    from kyverno_tpu.observability.profiling import FullCollections

    hook = FullCollections()
    count = global_registry.python_gc_full.value()
    seconds = global_registry.python_gc_full_seconds.value()
    hook.install()
    try:
        gc.collect()
        assert len(hook.pending) == 1
        t0, t1, _ = hook.pending[0]
    finally:
        hook.uninstall()
    spans = [s for s in global_tracer.finished("python.gc_full")
             if (s.start, s.end) == (t0, t1)]
    assert len(spans) == 1 and spans[0].duration > 0
    assert global_registry.python_gc_full.value() >= count + 1
    assert global_registry.python_gc_full_seconds.value() > seconds
    assert hook not in gc.callbacks


def test_serve_ready_line_splits_setup(tmp_path, monkeypatch):
    from kyverno_tpu.observability import profiling
    from kyverno_tpu.observability.log import global_oplog

    handlers = _handlers()
    handlers._engine()
    assert handlers.setup_stages["lowering_s"] > 0
    handlers._warm_bucket("validate", 16, lambda b: time.sleep(0.01))
    warm = handlers.setup_stages["warm"][-1]
    assert warm["bucket"] == 16 and warm["xla_compile_s"] == 0.0
    assert warm["execute_s"] >= 0.01
    span = global_tracer.finished("setup.warm_bucket")[-1]
    assert span.attributes["execute_s"] == warm["execute_s"]
    monkeypatch.setattr(profiling, "_serve_ready_at", None)
    log = tmp_path / "oplog.jsonl"
    global_oplog.configure(path=str(log))
    try:
        assert handlers.ready()[0] is True
        assert handlers.ready()[0] is True
    finally:
        global_oplog.reset()
        handlers.pipeline.stop()
        handlers.batcher.stop()
    lines = [json.loads(x) for x in log.read_text().splitlines()]
    ready = [x for x in lines if x["event"] == "serve_ready"]
    assert len(ready) == 1 and profiling.serve_is_ready()
    r = ready[0]
    assert r["seconds"] > 0 and r["buckets"][-1]["bucket"] == 16
    assert r["lowering_s"] == handlers.setup_stages["lowering_s"]
    assert r["unattributed_s"] == pytest.approx(
        r["seconds"] - r["lowering_s"] - r["warm_s"] - r["attach_s"])
