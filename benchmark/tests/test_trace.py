"""The trace reduction, checked on a small piece of a trace recorded on
one TPU v5e in a traced run of ``pss-restricted.scan-rollout``
(``data/trace_sample.json``, written by ``harness/trace.py``'s
``sample``)."""

import json
import os

from harness import trace

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "trace_sample.json")


def _sample():
    with open(SAMPLE) as f:
        return json.load(f)


def _busy_by_sweep(ops):
    """Busy time by an event sweep: +1 at each start, -1 at each end."""
    edges = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops])
    busy, active, last = 0.0, 0, None
    for t, step in edges:
        if active > 0:
            busy += t - last
        active += step
        last = t
    return busy


def test_busy_and_idle_match_an_independent_sweep():
    ex = _sample()
    out = trace.reduce_extract(ex)
    ops = ex["devices"][0]["ops"]
    assert len(ops) > 100
    assert abs(out["busy_s"] - _busy_by_sweep(ops) / 1e9) < 1e-9
    t0, t1 = ex["span_ns"]
    assert out["window_s"] == (t1 - t0) / 1e9
    assert 0 < out["busy_s"] <= out["window_s"]
    longest = max(g for _, g in out["idle_gaps"])
    merged = trace.merge([(s, s + d) for _, s, d in ops])
    between = [b[0] - a[1] for a, b in zip(merged, merged[1:])]
    edges = [merged[0][0] - t0, t1 - merged[-1][1]]
    assert abs(longest - max(between + edges) / 1e9) < 1e-9


def test_device_ops_and_modules_add_up():
    ex = _sample()
    out = trace.reduce_extract(ex)
    total = sum(d for _, _, d in ex["devices"][0]["ops"])
    assert abs(sum(v for _, v in out["device_ops"]) - total / 1e9) <= total / 1e9
    assert out["device_ops"] == sorted(out["device_ops"], key=lambda kv: -kv[1])
    assert len(out["device_ops"]) <= trace.TOP and len(out["idle_gaps"]) <= trace.TOP
    mods = sum(d for _, _, d in ex["devices"][0]["modules"])
    assert abs(sum(out["module_s"].values()) - mods / 1e9) < 1e-9


def test_gaps_are_named_by_overlapping_host_events():
    host = [["outer", 0, 100], ["inner", 10, 40], ["elsewhere", 200, 5]]
    assert trace.name_gap((12, 45), host) == "inner"
    assert trace.name_gap((0, 100), host) == "outer"
    assert trace.name_gap((150, 190), host) == "no host event"
    assert trace.merge([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]
    assert trace.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


def test_no_device_plane_gives_nothing_to_read():
    assert trace.reduce_extract({"span_ns": [0, 10], "devices": [], "host": []}) == {}
