"""End-to-end tracing, per-phase profiling, exemplars, debug endpoints.

The PR 3 observability layer: span-ID context propagation (including
across the serving queue's thread handoff), OTLP-JSON export, the
Prometheus exposition validator, per-phase profiling hooks, and the
/healthz /readyz /debug/* introspection surface.
"""

import json
import re
import threading
import time

import pytest

from kyverno_tpu.observability.metrics import MetricsRegistry
from kyverno_tpu.observability.profiling import PhaseProfiler
from kyverno_tpu.observability.tracing import (OTLPJsonFileExporter,
                                               SpanContext, Tracer)

# ---------------------------------------------------------------------------
# tracer core


def test_span_ids_are_real_identifiers():
    tr = Tracer()
    with tr.span("root") as root:
        pass
    assert re.fullmatch(r"[0-9a-f]{32}", root.trace_id)  # 128-bit
    assert re.fullmatch(r"[0-9a-f]{16}", root.span_id)   # 64-bit


def test_same_name_nested_spans_keep_distinct_parents():
    """The former name-keyed parent stack corrupted exactly this shape:
    retry wrappers nest a span inside a SAME-NAMED span."""
    tr = Tracer()
    with tr.span("attempt") as outer:
        with tr.span("attempt") as inner:
            with tr.span("leaf") as leaf:
                pass
    assert inner.parent_span_id == outer.span_id
    assert leaf.parent_span_id == inner.span_id
    assert leaf.parent_span_id != outer.span_id
    assert {outer.trace_id, inner.trace_id, leaf.trace_id} == {outer.trace_id}
    # and the thread-local stack fully unwound
    assert tr.current_context() is None


def test_sibling_threads_do_not_inherit_each_others_parents():
    tr = Tracer()
    errors = []

    def worker(i):
        try:
            with tr.span(f"w{i}") as s:
                time.sleep(0.01)
                assert s.parent_span_id is None  # no cross-thread leak
        except AssertionError as e:  # pragma: no cover
            errors.append(e)

    with tr.span("main"):
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors


def test_explicit_parent_crosses_threads():
    """The serving-queue pattern: capture a SpanContext on the
    submitting thread, start children from another thread."""
    tr = Tracer()
    ctx_box = {}

    with tr.span("request") as root:
        ctx_box["ctx"] = root.context

        def flusher():
            with tr.span("flush", parent=ctx_box["ctx"]):
                pass

        t = threading.Thread(target=flusher)
        t.start()
        t.join()
    spans = {s.name: s for s in tr.finished()}
    assert spans["flush"].trace_id == spans["request"].trace_id
    assert spans["flush"].parent_span_id == spans["request"].span_id


def test_record_span_retroactive_and_trace_grouping():
    tr = Tracer()
    with tr.span("root") as root:
        pass
    t0 = time.monotonic() - 0.25
    s = tr.record_span("queue_wait", t0, t0 + 0.2, parent=root.context,
                       flush_reason="timer")
    assert abs(s.duration - 0.2) < 1e-6
    assert s.trace_id == root.trace_id
    trace = tr.trace(root.trace_id)
    assert {x.name for x in trace} == {"root", "queue_wait"}
    # recent_traces filter: the whole trace spans >= 200ms
    assert tr.recent_traces(min_duration_s=0.1)
    assert not tr.recent_traces(min_duration_s=3600.0)


def test_span_events_and_status():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom") as s:
            tr.add_event("fault_injected", site="tpu.dispatch")
            raise ValueError("injected")
    assert s.status == "error"
    assert "injected" in s.status_message
    assert s.events and s.events[0].name == "fault_injected"
    assert s.events[0].attributes["site"] == "tpu.dispatch"


def test_otlp_json_file_exporter(tmp_path):
    path = str(tmp_path / "trace.otlp.jsonl")
    tr = Tracer(exporter=OTLPJsonFileExporter(path))
    with tr.span("outer", engine="tpu") as outer:
        outer.add_event("breaker_transition", to_state="open")
        with tr.span("inner"):
            pass
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert len(lines) == 2
    spans = [l["resourceSpans"][0]["scopeSpans"][0]["spans"][0] for l in lines]
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parentSpanId"] == by_name["outer"]["spanId"]
    assert by_name["inner"]["traceId"] == by_name["outer"]["traceId"]
    assert int(by_name["outer"]["endTimeUnixNano"]) >= \
        int(by_name["outer"]["startTimeUnixNano"])
    ev = by_name["outer"]["events"][0]
    assert ev["name"] == "breaker_transition"
    # a broken exporter must never break the traced path
    tr.add_exporter(lambda s: (_ for _ in ()).throw(RuntimeError("bad")))
    with tr.span("still-works"):
        pass
    assert tr.finished("still-works")


# ---------------------------------------------------------------------------
# per-phase profiler


def test_phase_profiler_accumulates_and_renders():
    prof = PhaseProfiler()
    with prof.phase("encode"):
        time.sleep(0.002)
    with prof.phase("encode"):
        pass
    prof.add("dispatch", 0.5)
    bd = prof.breakdown()
    assert bd["encode"]["calls"] == 2
    assert bd["encode"]["seconds"] > 0.0
    assert list(bd) == ["encode", "dispatch"]  # canonical order
    table = prof.render_table()
    assert "encode" in table and "dispatch" in table and "total" in table
    prof.reset()
    assert prof.breakdown() == {}


# ---------------------------------------------------------------------------
# Prometheus exposition validator (every line of every instrument)

_METRIC_LINE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^{}]*\})?"
    r" (?P<value>[0-9.eE+-]+|NaN)"
    r"(?P<exemplar> # \{[^{}]*\} [0-9.eE+-]+( [0-9.eE+-]+)?)?$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\["\\n])*)"')


def _parse_labels(block):
    assert block.startswith("{") and block.endswith("}")
    body = block[1:-1]
    out = {}
    consumed = 0
    for m in _LABEL.finditer(body):
        out[m.group(1)] = m.group(2)
        consumed += len(m.group(0))
    # everything except separating commas must be well-formed pairs
    assert consumed + max(0, len(out) - 1) == len(body), body
    return out


def test_exposition_format_is_scrapeable():
    """Parse EVERY line of the exposition: HELP/TYPE pairing, label
    escaping, histogram bucket monotonicity, +Inf == _count agreement,
    exemplar syntax. New instruments that emit unparseable text fail
    here, not in a scrape loop at 3am."""
    import numpy as np

    from kyverno_tpu.observability.analytics import (RuleIdent,
                                                     RuleStatsAccumulator,
                                                     SloTracker)

    reg = MetricsRegistry()
    # exercise the interesting encodings, including label escaping
    reg.policy_results.inc({"policy": 'we"ird\\pol\nicy', "status": "fail"})
    reg.admission_duration.observe(0.003, {"path": "validate"})
    reg.admission_duration.observe(
        0.07, {"path": "validate"}, exemplar={"trace_id": "ab" * 16})
    reg.serving_request_latency.observe(
        99.0, exemplar={"trace_id": "cd" * 16})  # +Inf bucket exemplar
    reg.serving_queue_depth.set(7)
    # admission scheduling families: per-class depth/outcomes, hedged
    # race winners, class+reason-labeled sheds, class-split SLO gauges
    reg.serving_class_queue_depth.set(2, {"class": "bulk"})
    reg.serving_class_requests.inc({"class": "critical",
                                    "outcome": "batched"})
    reg.serving_hedge.inc({"winner": "device"})
    reg.serving_shed_total.inc({"outcome": "rejected", "class": "bulk",
                                "reason": "burn"})
    # the observatory families: rule analytics (scrape-time collector,
    # label-escaping policy names included) + SLO/starvation gauges
    acc = RuleStatsAccumulator(clock=lambda: 0.0)
    acc.ingest_counts([RuleIdent("h1", 'po"l\\one', "r1", True),
                       RuleIdent("h2", "pol-two", "r2", False)],
                      np.array([[3, 0, 1, 2, 0, 0], [0, 0, 0, 4, 0, 0]]))
    reg.rule_stats.accumulator = acc
    slo = SloTracker(metrics=reg)
    slo.record_admission(0.004)
    slo.record_admission(0.2, cls="bulk")  # class-labeled SLO series
    slo.record_scan(coverage=0.97)
    # verdict-integrity: one diverged check drives the divergence
    # gauge + breached flag; the counter exemplar carries the trace id
    slo.record_verification(True)
    reg.feed_starvation.set(0.25)
    reg.flight_records.inc({"outcome": "fallback"})
    reg.flight_sampled_out.inc()
    reg.flight_ring_size.set(3)
    reg.flight_spools.inc({"reason": "breaker-tpu-closed-open"})
    reg.verification_checks.inc({"result": "diverge"})
    reg.verification_divergence.inc(exemplar={"trace_id": "ef" * 16})
    reg.verification_queue_depth.set(0)
    # static-analysis families (analysis/): run outcomes, last report's
    # anomaly counts by kind, corpus size, per-phase wall
    reg.analysis_runs.inc({"outcome": "ok"})
    reg.analysis_anomalies.set(2, {"kind": "shadow"})
    reg.analysis_witnesses.set(46)
    reg.analysis_wall_seconds.set(0.5, {"phase": "evaluate"})
    # fleet families (fleet/): membership, shard ownership, per-peer
    # heartbeat/fetch outcomes, receive-verification rejects, gossip
    reg.fleet_replicas.set(3)
    reg.fleet_is_leader.set(1)
    reg.fleet_epoch.set(4)
    reg.fleet_shards_owned.set(21)
    reg.fleet_shard_reassignments.inc({"reason": "membership"}, value=17)
    reg.fleet_shard_staleness.set(2.5)
    reg.fleet_heartbeats.inc({"peer": "r1", "outcome": "ok"})
    reg.fleet_peer_fetch.inc({"peer": "r1", "outcome": "hit"})
    reg.fleet_peer_rejects.inc({"reason": "checksum"})
    reg.fleet_gossip.inc({"outcome": "sent"}, value=8)
    # fleet telemetry plane: leader pull outcomes, trust-ladder
    # rejects, delta-folded fleet aggregates, fleet burn/health gauges
    reg.fleet_telemetry_pulls.inc({"peer": "r1", "outcome": "ok"})
    reg.fleet_telemetry_rejects.inc({"reason": "checksum"})
    reg.fleet_agg_admissions.inc(value=12)
    reg.fleet_agg_admission_slow.inc(value=1)
    reg.fleet_agg_scan_ticks.inc(value=3)
    reg.fleet_agg_verification_checked.inc(value=5)
    reg.fleet_agg_divergence.inc()
    reg.fleet_agg_burn.set(0.4, {"window": "5m"})
    reg.fleet_agg_replicas_reporting.set(3)
    reg.fleet_agg_snapshot_age.set(0.2, {"replica": "r1"})
    reg.fleet_agg_degraded.set(1)
    # degraded-storage ladder: per-surface error/heal counters + gauge
    reg.storage_errors.inc({"surface": "reports", "kind": "enospc"})
    reg.storage_degraded.set(1, {"surface": "reports"})
    reg.storage_heals.inc({"surface": "reports"})
    # multi-stride + approximate-reduction pattern engine (tpu/dfa.py)
    reg.dfa_stride_tables.set(5, {"stride": "4"})
    reg.dfa_stride_tables.set(2, {"stride": "1"})
    reg.dfa_stride_bytes.set(4096)
    reg.dfa_approx_states_merged.set(11)
    reg.dfa_approx_error_max.set(0.004)
    reg.dfa_top_collapse.inc({"reason": "error_ceiling"})
    reg.dfa_confirm_cells.inc(value=3)

    text = reg.exposition()
    # every new family is present (cardinality guard has its own test)
    for fam in ("kyverno_rule_evals_total", "kyverno_rule_fired_total",
                "kyverno_rule_fail_total", "kyverno_rule_never_fired",
                "kyverno_policy_device_coverage",
                "kyverno_slo_admission_latency_p99_seconds",
                "kyverno_slo_admission_burn_rate",
                "kyverno_slo_scan_freshness_seconds",
                "kyverno_slo_device_coverage_ratio", "kyverno_slo_breached",
                "kyverno_tpu_feed_starvation_ratio",
                "kyverno_flight_records_total",
                "kyverno_flight_sampled_out_total",
                "kyverno_flight_ring_records", "kyverno_flight_spools_total",
                "kyverno_verification_checks_total",
                "kyverno_verification_divergence_total",
                "kyverno_verification_queue_depth",
                "kyverno_slo_verification_divergences",
                "kyverno_analysis_runs_total", "kyverno_analysis_anomalies",
                "kyverno_analysis_witnesses",
                "kyverno_analysis_wall_seconds",
                "kyverno_serving_class_queue_depth",
                "kyverno_serving_class_requests_total",
                "kyverno_serving_hedge_total",
                "kyverno_fleet_replicas", "kyverno_fleet_is_leader",
                "kyverno_fleet_epoch", "kyverno_fleet_shards_owned",
                "kyverno_fleet_shard_reassignments_total",
                "kyverno_fleet_shard_staleness_seconds",
                "kyverno_fleet_heartbeats_total",
                "kyverno_fleet_peer_fetch_total",
                "kyverno_fleet_peer_rejects_total",
                "kyverno_fleet_gossip_total",
                "kyverno_fleet_telemetry_pulls_total",
                "kyverno_fleet_telemetry_rejects_total",
                "kyverno_fleet_agg_admission_requests_total",
                "kyverno_fleet_agg_admission_slow_total",
                "kyverno_fleet_agg_scan_ticks_total",
                "kyverno_fleet_agg_verification_checked_total",
                "kyverno_fleet_agg_divergence_total",
                "kyverno_fleet_agg_admission_burn_rate",
                "kyverno_fleet_agg_replicas_reporting",
                "kyverno_fleet_agg_snapshot_age_seconds",
                "kyverno_fleet_agg_degraded",
                "kyverno_storage_errors_total", "kyverno_storage_degraded",
                "kyverno_storage_heals_total",
                "kyverno_dfa_stride_tables",
                "kyverno_dfa_stride_table_bytes",
                "kyverno_dfa_approx_states_merged",
                "kyverno_dfa_approx_error_max",
                "kyverno_dfa_top_collapse_total",
                "kyverno_dfa_confirm_cells_total"):
        assert f"# TYPE {fam} " in text, fam
    # per-class SLO burn series render alongside the aggregate ones
    assert 'kyverno_slo_admission_burn_rate{class="bulk",window=' in text
    # the divergence counter line carries its trace-id exemplar
    assert any(l.startswith("kyverno_verification_divergence_total")
               and " # {" in l for l in text.splitlines())
    assert text.endswith("\n")
    helped, typed = set(), {}
    hist_series = {}
    for line in text.splitlines():
        assert line == line.rstrip(), f"trailing whitespace: {line!r}"
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram"), line
            typed[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = _METRIC_LINE.match(line)
        assert m, f"unparseable metric line: {line!r}"
        name, labels = m.group("name"), m.group("labels")
        parsed = _parse_labels(labels) if labels else {}
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        owner = base if base in typed else name
        assert owner in typed, f"sample before TYPE: {line!r}"
        assert owner in helped, f"sample without HELP: {line!r}"
        if m.group("exemplar"):
            # OpenMetrics: exemplars attach to histogram buckets and to
            # counter samples (the divergence counter carries the
            # diverging record's trace id) — never to gauges
            assert typed[owner] in ("histogram", "counter"), line
            if typed[owner] == "histogram":
                assert name.endswith("_bucket"), line
        if typed.get(base) == "histogram" and name.endswith("_bucket"):
            assert "le" in parsed, line
            key = (base, tuple(sorted((k, v) for k, v in parsed.items()
                                      if k != "le")))
            le = float("inf") if parsed["le"] == "+Inf" else float(parsed["le"])
            hist_series.setdefault(key, []).append(
                (le, float(m.group("value"))))
        if typed.get(base) == "histogram" and name.endswith("_count"):
            key = (base, tuple(sorted(parsed.items())))
            hist_series.setdefault(key, []).append(
                ("count", float(m.group("value"))))
    # escaped label value round-trips
    assert 'policy="we\\"ird\\\\pol\\nicy"' in text
    # bucket monotonicity + the +Inf bucket equals _count
    for key, samples in hist_series.items():
        buckets = sorted((le, v) for le, v in samples if le != "count")
        counts = [v for le, v in samples if le == "count"]
        if not buckets:
            continue
        values = [v for _, v in buckets]
        assert values == sorted(values), f"non-monotonic buckets: {key}"
        assert buckets[-1][0] == float("inf"), f"missing +Inf: {key}"
        assert counts and counts[0] == buckets[-1][1], \
            f"+Inf != _count: {key}"
    # the exemplar itself parses and carries the trace id
    assert f'# {{trace_id="{"ab" * 16}"}} 0.07' in text
    assert f'trace_id="{"cd" * 16}"' in text


def test_fleet_replica_label_cardinality_tracks_live_set():
    """The per-replica snapshot-age gauge must not accumulate a series
    for every replica that EVER reported — prune() removes the series
    when a replica leaves, so replica-label cardinality is bounded by
    the live population."""
    from kyverno_tpu.fleet.telemetry import (TELEMETRY_SCHEMA_VERSION,
                                             TelemetryAggregator,
                                             snapshot_checksum)

    def snap(rid):
        doc = {"schema_version": TELEMETRY_SCHEMA_VERSION,
               "replica_id": rid, "boot_id": "b1", "seq": 1, "epoch": 1,
               "at": time.time(),
               "counters": {"admission_requests": 1},
               "slo_windows": {}, "gauges": {}}
        doc["sha"] = snapshot_checksum(doc)
        return doc

    reg = MetricsRegistry()
    agg = TelemetryAggregator(metrics=reg, max_age_s=30.0)
    fleet = [f"r{i}" for i in range(5)]
    for rid in fleet:
        assert agg.ingest(snap(rid)) == ""
    agg.publish_gauges()
    text = reg.exposition()
    for rid in fleet:
        assert f'kyverno_fleet_agg_snapshot_age_seconds{{replica="{rid}"}}' \
            in text
    # three replicas leave: their matrix rows AND gauge series go, the
    # already-folded totals stay (work that happened, happened)
    agg.prune({"r0", "r1"})
    agg.publish_gauges()
    text = reg.exposition()
    for rid in ("r0", "r1"):
        assert f'kyverno_fleet_agg_snapshot_age_seconds{{replica="{rid}"}}' \
            in text
    for rid in ("r2", "r3", "r4"):
        assert f'replica="{rid}"' not in text
    assert agg.totals()["admission_requests"] == 5.0
    assert reg.fleet_agg_replicas_reporting.value() == 2.0


# ---------------------------------------------------------------------------
# event generator accounting


def test_event_generator_counters_locked_and_exported():
    from kyverno_tpu.observability.events import Event, EventGenerator

    reg = MetricsRegistry()
    slow = threading.Event()

    def sink(e):
        slow.wait(2.0)

    gen = EventGenerator(sink=sink, workers=1, max_queued=2, metrics=reg)
    gen.add(Event(reason="PolicyViolation", message="m0"))
    time.sleep(0.05)  # worker parks in the slow sink
    # queue (cap 2) fills; further adds drop
    for i in range(5):
        gen.add(Event(reason="PolicyViolation", message=f"m{i + 1}"))
    assert gen.dropped >= 3
    slow.set()
    gen.flush()
    gen.stop(timeout=2.0)
    for w in gen._workers:
        assert not w.is_alive(), "stop() must join worker threads"
    text = reg.exposition()
    assert "kyverno_events_dropped_total" in text
    assert "kyverno_events_emitted_total" in text
    assert gen.emitted + gen.dropped == 6


# ---------------------------------------------------------------------------
# the acceptance path: one admission request -> one connected trace


def _eval_fn(padded):
    time.sleep(0.005)  # measurable dispatch time
    return ["allow" for p in padded if p is not None]


def test_single_request_yields_one_connected_trace():
    from kyverno_tpu.observability.metrics import global_registry
    from kyverno_tpu.observability.tracing import global_tracer
    from kyverno_tpu.serving import AdmissionPipeline, BatchConfig

    pipeline = AdmissionPipeline(
        _eval_fn, config=BatchConfig(max_batch_size=4, max_wait_ms=5.0))
    try:
        t0 = time.monotonic()
        out = pipeline.submit({"r": 1})
        latency = time.monotonic() - t0
    finally:
        pipeline.stop()
    assert out == "allow"
    submits = [s for s in global_tracer.finished("admission.submit")]
    root = submits[-1]
    trace = {s.name: s for s in global_tracer.trace(root.trace_id)}
    # the request's own trace: submit, queue wait, flush — the shared
    # evaluation is ONE admission.batch span the flush names, not a
    # per-request copy
    assert set(trace) == {"admission.submit", "admission.queue_wait",
                          "admission.flush"}
    # every span hangs off the submit root's trace, children point at it
    assert trace["admission.queue_wait"].parent_span_id == root.span_id
    assert trace["admission.flush"].parent_span_id == root.span_id
    batch_id = trace["admission.flush"].attributes["batch_span_id"]
    batch = [s for s in global_tracer.finished("admission.batch")
             if s.span_id == batch_id]
    assert len(batch) == 1 and batch[0].attributes["batch_size"] == 1
    assert batch[0].attributes["engine"] == "device"
    # queue wait then flush, contiguous, inside the measured latency;
    # the batch span covers the evaluation and ends with the flush
    qw, fl = trace["admission.queue_wait"], trace["admission.flush"]
    assert qw.end == fl.start and fl.end == batch[0].end
    assert qw.duration + fl.duration <= latency + 0.05, latency
    assert batch[0].duration >= 0.004  # the sleep is visible
    # the latency histogram carries the trace id as an exemplar
    text = global_registry.exposition()
    assert "kyverno_serving_request_latency_seconds" in text
    assert f'trace_id="{root.trace_id}"' in text


def test_trace_records_scalar_fallback_with_breaker_state():
    """A batch the engine resolved on the scalar fallback (marked via the
    dispatch-path thread-local) says so once, on its admission.batch
    span, with the breaker state beside it."""
    from kyverno_tpu.observability.profiling import (PATH_SCALAR_FALLBACK,
                                                     set_dispatch_path)
    from kyverno_tpu.observability.tracing import global_tracer
    from kyverno_tpu.serving import AdmissionPipeline, BatchConfig

    def scalar_eval(padded):
        set_dispatch_path(PATH_SCALAR_FALLBACK)
        return ["ok" for p in padded if p is not None]

    pipeline = AdmissionPipeline(
        scalar_eval, config=BatchConfig(max_batch_size=4, max_wait_ms=2.0))
    try:
        pipeline.submit({"r": 2})
    finally:
        pipeline.stop()
    root = global_tracer.finished("admission.submit")[-1]
    trace = {s.name: s for s in global_tracer.trace(root.trace_id)}
    assert "admission.scalar_fallback" not in trace
    batch_id = trace["admission.flush"].attributes["batch_span_id"]
    fb = [s for s in global_tracer.finished("admission.batch")
          if s.span_id == batch_id][0]
    assert fb.attributes["engine"] == PATH_SCALAR_FALLBACK
    assert fb.attributes["breaker"] in ("closed", "open", "half_open",
                                        "unknown")


# ---------------------------------------------------------------------------
# debug introspection endpoints


def _get(port, path):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    ctype = resp.getheader("Content-Type", "")
    conn.close()
    return resp.status, body, ctype


def test_health_ready_and_debug_endpoints():
    from kyverno_tpu.cluster import PolicyCache
    from kyverno_tpu.webhooks import AdmissionServer, build_handlers

    handlers = build_handlers(PolicyCache(), batching=True)
    srv = AdmissionServer(handlers, port=0, enable_debug=True)
    srv.start()
    try:
        status, body, _ = _get(srv.port, "/healthz")
        assert (status, body) == (200, b"ok")
        status, body, _ = _get(srv.port, "/readyz")
        detail = json.loads(body)
        assert status == 200 and detail["ready"] is True
        assert detail["breaker"] in ("closed", "half_open")
        # generate one traced request so /debug/traces has content
        handlers.pipeline.submit(
            __import__("kyverno_tpu.webhooks.server",
                       fromlist=["AdmissionPayload"]).AdmissionPayload(
                {"kind": "Pod", "metadata": {"name": "p"}}, "CREATE",
                None, ""))
        status, body, _ = _get(srv.port, "/debug/traces?min_ms=0")
        traces = json.loads(body)["traces"]
        assert status == 200 and traces
        assert any(s["name"] == "admission.submit"
                   for t in traces for s in t["spans"])
        # min_ms filter actually filters
        status, body, _ = _get(srv.port, "/debug/traces?min_ms=3600000")
        assert json.loads(body)["traces"] == []
        status, body, _ = _get(srv.port, "/debug/state")
        state = json.loads(body)
        assert status == 200
        assert state["breaker"]["state"] in ("closed", "open", "half_open")
        assert "pipeline" in state and "queue_depth" in state["pipeline"]
        assert "compile_cache" in state and "phase_breakdown" in state
        # where device batches run, as JAX reports it (CPU under tests)
        assert state["device"]["platform"] == "cpu"
        assert state["device"]["count"] == 8 and state["device"]["kind"]
        assert state["native_encoder"]["loaded"] in (True, False, None)
    finally:
        srv.stop()


def test_readyz_not_ready_when_breaker_open():
    from kyverno_tpu.cluster import PolicyCache
    from kyverno_tpu.resilience.breaker import tpu_breaker
    from kyverno_tpu.webhooks import build_handlers

    handlers = build_handlers(PolicyCache())
    breaker = tpu_breaker()
    breaker.reset()
    try:
        for _ in range(breaker.failure_threshold):
            breaker.record_failure()
        assert breaker.state == "open"
        ok, detail = handlers.ready()
        assert ok is False and detail["breaker"] == "open"
    finally:
        breaker.reset()
        handlers.batcher.stop()


def test_serve_metrics_port_serves_debug_surface():
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.cli.serve import ControlPlane

    pol = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "t"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"pattern": {"metadata": {"name": "?*"}}},
        }]},
    })
    cp = ControlPlane([pol], port=0, metrics_port=0)
    cp.start(scan_interval=3600.0)
    try:
        port = cp.metrics_server.server_address[1]
        assert _get(port, "/healthz")[:2] == (200, b"ok")
        status, body, _ = _get(port, "/readyz")
        assert status == 200 and json.loads(body)["ready"] is True
        status, body, _ = _get(port, "/debug/state")
        assert status == 200 and "breaker" in json.loads(body)
        status, body, _ = _get(port, "/debug/traces")
        assert status == 200 and "traces" in json.loads(body)
        status, body, ctype = _get(port, "/metrics")
        assert status == 200 and b"kyverno_" in body
        # exemplars are OpenMetrics: the endpoint must declare the
        # format and terminate with '# EOF' so scrapers pick the parser
        # that understands the exemplar suffixes
        assert "openmetrics-text" in ctype
        assert body.decode().rstrip().endswith("# EOF")
    finally:
        cp.stop()


# ---------------------------------------------------------------------------
# engine profiling hooks + compile-cache attribution


def test_scan_records_phases_and_compile_cache_outcomes(no_verdict_cache):
    # cache off: the second scan must reach packed_fn() for the
    # compile-cache "hit" outcome this test asserts — the verdict
    # cache would legitimately answer it without dispatching
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.observability.metrics import global_registry
    from kyverno_tpu.observability.profiling import global_profiler
    from kyverno_tpu.tpu.engine import TpuEngine

    pol = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "t"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"pattern": {"metadata": {"name": "?*"}}},
        }]},
    })
    res = [{"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "p", "namespace": "d"}, "spec": {}}]
    global_profiler.reset()
    miss0 = global_registry.compile_cache._values.get(
        (("outcome", "miss"),), 0.0)
    hit0 = global_registry.compile_cache._values.get(
        (("outcome", "hit"),), 0.0)
    eng = TpuEngine([pol])
    eng.scan(res)
    eng.scan(res)
    bd = global_profiler.breakdown()
    for phase in ("encode", "compile", "dispatch", "readback"):
        assert phase in bd, (phase, bd)
    assert global_registry.compile_cache._values[
        (("outcome", "miss"),)] == miss0 + 1
    assert global_registry.compile_cache._values[
        (("outcome", "hit"),)] >= hit0 + 1


def test_apply_profile_prints_breakdown(tmp_path, capsys):
    from kyverno_tpu.cli.__main__ import main

    pol = tmp_path / "p.yaml"
    pol.write_text("""
apiVersion: kyverno.io/v1
kind: ClusterPolicy
metadata: {name: named}
spec:
  rules:
    - name: has-name
      match: {any: [{resources: {kinds: [Pod]}}]}
      validate: {message: m, pattern: {metadata: {name: "?*"}}}
""")
    res = tmp_path / "r.yaml"
    res.write_text("""
apiVersion: v1
kind: Pod
metadata: {name: ok, namespace: default}
spec: {containers: [{name: c, image: nginx}]}
""")
    rc = main(["apply", str(pol), "-r", str(res), "--profile"])
    captured = capsys.readouterr()
    assert rc == 0
    assert "per-phase latency breakdown" in captured.err
    assert "dispatch" in captured.err
