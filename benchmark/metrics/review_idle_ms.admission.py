"""Mean milliseconds the device sat idle inside each served review that
lies wholly within the traced span: serve's program spans mapped onto
the profiler trace through its clock anchor (``harness/span_clock.py``,
run in a process of its own under ``JAX_PLATFORMS=cpu``). Prints how
that idle time splits across the review's stage spans and the full
collections and profiler start and stop beside them."""

import json
import os
import subprocess
import sys

from harness.core import say
from harness.serve import ROOT

HELPER = os.path.join(ROOT, "benchmark", "harness", "span_clock.py")


def read(run):
    trace_dir = os.path.join(run.workdir, "trace")
    if not os.path.isfile(os.path.join(trace_dir, "program_spans.json")):
        return None
    out = os.path.join(run.workdir, "span_clock.json")
    proc = subprocess.run([sys.executable, HELPER, trace_dir, out], cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        say(f"span clock failed: {proc.stderr[-2000:]}")
        return None
    with open(out) as f:
        summary = json.load(f)
    say(f"span clock: {summary.get('reviews')} reviews inside the trace, "
        f"mean review {summary.get('review_ms')} ms, idle "
        f"{summary.get('idle_ms_per_review')} ms; idle split (ms per "
        f"review) {summary.get('idle_split_ms')}; dispatch alignment "
        f"{summary.get('dispatch')}")
    return summary.get("idle_ms_per_review")
