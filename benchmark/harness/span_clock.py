"""The program's spans on the device trace's clock: how long the device
sat idle inside each served review, and in which of the review's stages.

serve's profiler session (``/debug/xla/start``/``stop``) writes
``program_spans.json`` beside the ``.xplane.pb``: every span finished in
the session, in ``time.monotonic_ns()``, and the ``time.monotonic_ns()``
bracket of one ``TraceAnnotation``, the clock anchor, that the trace
records on its own clock. The anchor's start in the host plane minus the
bracket's midpoint maps every span onto the trace's timeline.

``reduce_spans`` works on plain lists, so that the test can check it on
a hand-made trace. ``extract`` reads the ``.xplane.pb`` and needs
jaxlib; the harness's reader runs this file in a process of its own,
under ``JAX_PLATFORMS=cpu``, so that the benchmark's parent never loads
JAX:

    python benchmark/harness/span_clock.py <trace dir> <out json>
"""

from __future__ import annotations

import bisect
import json
import os
import sys
from typing import Dict, List, Sequence, Tuple

try:
    from . import trace
except ImportError:  # run as a script: its own directory leads sys.path
    import trace  # type: ignore[no-redef]

PROGRAM_SPANS = "program_spans.json"
REVIEW = "admission.review"
STAGES = ("admission.read", "admission.admit", "admission.queue_wait",
          "admission.flush", "admission.wake", "admission.decide",
          "admission.respond")
# spans outside a review's trace that can hold the host up inside it
STALLS = ("python.gc_full", "debug.xla_start", "debug.xla_stop")
DISPATCH = "tpu.dispatch"
EVALUATOR_MODULE = "jit_run"


def overlap(intervals: Sequence[Tuple[float, float]], s: float, e: float
            ) -> float:
    """Length of [s, e) covered by the disjoint ``intervals``."""
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in intervals)


def _inside(busy: List[Tuple[float, float]], starts: List[float],
            s: float, e: float) -> List[Tuple[float, float]]:
    """The merged ``busy`` intervals that overlap [s, e)."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    out = []
    while i < len(busy) and busy[i][0] < e:
        if busy[i][1] > s:
            out.append(busy[i])
        i += 1
    return out


def offset_ns(ex: Dict, doc: Dict) -> float:
    """Trace time minus monotonic time, from the clock anchor."""
    a0, a1 = doc["anchor"]["mono_ns"]
    return ex["anchor_ns"] - (a0 + a1) / 2.0


def reduce_spans(ex: Dict, doc: Dict) -> Dict:
    """Idle device time inside each review span lying wholly within the
    traced span, and how it splits across the review's stage spans and
    the stalls beside it; and how far each ``tpu.dispatch`` span sits
    from the evaluator's device execution it launched.

    ``ex``: ``{"span_ns": [t0, t1], "anchor_ns": a, "devices": [{"ops":
    [[start_ns, dur_ns]...], "modules": [[name, start_ns, dur_ns]...]}]}``
    (the first device's ops and modules are read); ``doc``: the
    ``program_spans.json`` document."""
    if not ex.get("devices") or ex.get("anchor_ns") is None:
        return {}
    off = offset_ns(ex, doc)
    t0, t1 = ex["span_ns"]
    dev = ex["devices"][0]
    busy = trace.merge([(s, s + d) for s, d in dev["ops"]])
    starts = [s for s, _ in busy]
    spans = [dict(sp, s=sp["start_ns"] + off, e=sp["end_ns"] + off)
             for sp in doc["spans"]]
    by_trace: Dict[str, List[Dict]] = {}
    for sp in spans:
        by_trace.setdefault(sp["trace_id"], []).append(sp)
    stalls = [sp for sp in spans if sp["name"] in STALLS]
    reviews = [sp for sp in spans if sp["name"] == REVIEW
               and sp["attributes"].get("path", "validate") == "validate"
               and t0 <= sp["s"] and sp["e"] <= t1]
    split = {name: 0.0 for name in STAGES + STALLS}
    idle_total = review_total = 0.0
    for rv in reviews:
        idle = trace.gaps(_inside(busy, starts, rv["s"], rv["e"]),
                          rv["s"], rv["e"])
        idle_total += sum(e - s for s, e in idle)
        review_total += rv["e"] - rv["s"]
        for sp in by_trace[rv["trace_id"]]:
            if sp["name"] in STAGES:
                split[sp["name"]] += overlap(idle, sp["s"], sp["e"])
        for sp in stalls:
            split[sp["name"]] += overlap(idle, sp["s"], sp["e"])
    n = len(reviews)
    out = {"reviews": n, "offset_ns": off}
    if n:
        out["idle_ms_per_review"] = idle_total / n / 1e6
        out["review_ms"] = review_total / n / 1e6
        out["idle_split_ms"] = {k: v / n / 1e6 for k, v in split.items()}
        out["idle_split_ms"]["no stage span"] = (
            idle_total - sum(split[k] for k in STAGES)) / n / 1e6
    out["dispatch"] = dispatch_alignment(spans, dev["modules"], t0, t1)
    return out


def dispatch_alignment(spans: List[Dict], modules: Sequence, t0: float,
                       t1: float) -> Dict:
    """For each ``tpu.dispatch`` span inside the trace, whether an
    evaluator execution (``jit_run`` module) overlaps it, and the worst
    distance by which one lies outside its span: a module cannot start
    before the host launched it, nor end after the host read it back, so
    any distance is the two clocks' misalignment."""
    mods = sorted((s, s + d) for name, s, d in modules
                  if EVALUATOR_MODULE in name)
    n = hit = 0
    worst = 0.0
    for sp in spans:
        if sp["name"] != DISPATCH or not (t0 <= sp["s"] and sp["e"] <= t1):
            continue
        n += 1
        inside = [(a, b) for a, b in mods if a < sp["e"] and b > sp["s"]]
        if inside:
            hit += 1
            worst = max(worst, max(max(sp["s"] - a, b - sp["e"], 0.0)
                                   for a, b in inside))
        elif mods:
            worst = max(worst, min(max(a - sp["e"], sp["s"] - b)
                                   for a, b in mods))
    return {"spans": n, "overlapping": hit, "worst_outside_us": worst / 1e3}


# ---------------------------------------------------------------------------
# reading the .xplane.pb


def extract(path: str, anchor: str) -> Dict:
    """The anchor's start on the host plane, every TPU plane's op
    intervals and modules, and the trace's span (as ``trace.extract``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, anchor_ns = [], None
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    dev["ops"] = [[e.start_ns, e.duration_ns]
                                  for e in line.events]
                elif line.name == trace.MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
            for s, d in dev["ops"]:
                lo, hi = min(lo, s), max(hi, s + d)
            for _, s, d in dev["modules"]:
                lo, hi = min(lo, s), max(hi, s + d)
            devices.append(dev)
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    lo = min(lo, e.start_ns)
                    hi = max(hi, e.start_ns + e.duration_ns)
                    if anchor_ns is None and e.name == anchor:
                        anchor_ns = e.start_ns
    return {"span_ns": [lo, hi], "anchor_ns": anchor_ns, "devices": devices}


def main(argv=None) -> int:
    """span_clock.py <trace dir> <out json>"""
    argv = sys.argv[1:] if argv is None else argv
    with open(os.path.join(argv[0], PROGRAM_SPANS)) as f:
        doc = json.load(f)
    ex = extract(trace.find_xplane(argv[0]), doc["anchor"]["name"])
    with open(argv[1], "w") as f:
        json.dump(reduce_spans(ex, doc), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
