"""Reduction of a profiler trace to device busy time, kernel time and the
longest idle gaps, each gap named by what the host was doing in it.

``reduce_extract`` works on plain lists, so that the test can check it
against a small recorded trace. ``extract`` reads the ``.xplane.pb``
that ``jax.profiler`` wrote, and needs jaxlib; the harness runs it in a
process of its own (``python benchmark/harness/trace.py``) once serve
has exited, so that the benchmark's parent never loads JAX. Given a
third path, it also writes a small ``sample`` of the extracted trace
there; the test's recorded trace is made so:

    python benchmark/harness/trace.py <trace dir> <summary.json> <sample.json>
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Sequence, Tuple

TOP = 10
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: List[Tuple[float, float]], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [t0, t1) outside the merged ``busy`` list."""
    out, t = [], t0
    for s, e in busy:
        if s > t:
            out.append((t, min(s, t1)))
        t = max(t, e)
    if t < t1:
        out.append((t, t1))
    return [(s, e) for s, e in out if e > s]


def name_gap(gap: Tuple[float, float], host: Sequence[Tuple[str, float, float]]) -> str:
    """The most specific host event that covers most of the gap: of the
    events overlapping it by at least half, the shortest; else the one
    overlapping it most; else "no host event"."""
    s, e = gap
    best, best_cover = None, 0.0
    covering = []
    for name, hs, hd in host:
        cover = min(e, hs + hd) - max(s, hs)
        if cover <= 0:
            continue
        if cover >= 0.5 * (e - s):
            covering.append((hd, name))
        if cover > best_cover:
            best, best_cover = name, cover
    if covering:
        return min(covering)[1]
    return best or "no host event"


def reduce_extract(ex: Dict) -> Dict:
    """Busy, per-module and gap figures of one extracted trace.

    ``ex``: ``{"span_ns": [t0, t1], "devices": [{"name", "ops":
    [[name, start_ns, dur_ns]...], "modules": [...]}], "host":
    [[name, start_ns, dur_ns]...]}``. Busy and module time are averaged
    over the devices; the gaps are the first device's.
    """
    t0, t1 = ex["span_ns"]
    devices = ex["devices"]
    if not devices:
        return {}
    busy_ns, op_ns, module_ns = [], {}, {}
    first_busy = None
    for dev in devices:
        merged = merge([(s, s + d) for _, s, d in dev["ops"]])
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        for name, _, d in dev["ops"]:
            op_ns[name] = op_ns.get(name, 0.0) + d
        for name, _, d in dev["modules"]:
            module_ns[name] = module_ns.get(name, 0.0) + d
    idle = sorted(gaps(first_busy, t0, t1), key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "module_s": {k: v / len(devices) / 1e9 for k, v in module_ns.items()},
        "device_ops": [[n, v / 1e9] for n, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[name_gap(g, ex["host"]), (g[1] - g[0]) / 1e9]
                      for g in idle],
    }


# ---------------------------------------------------------------------------
# reading the .xplane.pb


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str) -> Dict:
    """Device ops and modules of every TPU plane, and the host events that
    overlap the first device's longest idle gaps."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host_lines = [], []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            host_lines = list(plane.lines)
    for line in host_lines:
        for e in line.events:
            lo, hi = min(lo, e.start_ns), max(hi, e.start_ns + e.duration_ns)
    for dev in devices:
        for _, s, d in dev["ops"] + dev["modules"]:
            lo, hi = min(lo, s), max(hi, s + d)
    ex = {"span_ns": [lo, hi], "devices": devices, "host": []}
    if devices:
        busy = merge([(s, s + d) for _, s, d in devices[0]["ops"]])
        top = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
        ex["host"] = [[e.name, e.start_ns, e.duration_ns]
                      for line in host_lines for e in line.events
                      if e.duration_ns > 0 and any(
                          e.start_ns < ge and e.start_ns + e.duration_ns > gs
                          for gs, ge in top)]
    return ex


def sample(ex: Dict, device_events: int = 1500) -> Dict:
    """A small piece of an extracted trace, for the reduction's test: the
    span of the first device's first ``device_events`` ops, with the
    events of every plane inside it."""
    ops = sorted(ex["devices"][0]["ops"], key=lambda e: e[1])[:device_events]
    t0 = ops[0][1]
    t1 = max(s + d for _, s, d in ops)

    def inside(events):
        return [e for e in events if e[1] >= t0 and e[1] + e[2] <= t1]

    def overlapping(events):
        return [e for e in events if e[1] < t1 and e[1] + e[2] > t0]

    return {"span_ns": [t0, t1],
            "devices": [{"name": d["name"], "ops": inside(d["ops"]),
                         "modules": overlapping(d["modules"])}
                        for d in ex["devices"]],
            "host": inside(ex["host"])}


def main(argv=None) -> int:
    """trace.py <trace dir> <out json> [<sample out json>]"""
    argv = sys.argv[1:] if argv is None else argv
    ex = extract(find_xplane(argv[0]))
    with open(argv[1], "w") as f:
        json.dump(reduce_extract(ex), f)
    if len(argv) > 2 and ex["devices"]:
        with open(argv[2], "w") as f:
            json.dump(sample(ex), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
