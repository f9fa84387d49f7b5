"""Share of the window's wall time in which serve ran a full (generation
2) garbage collection, from the collections that ``serve_child.py``
records through ``gc.callbacks``."""

from harness.steps import gc_seconds


def read(run):
    window = run.spans.get("window_wall")
    if not window:
        return None
    start, end = window
    return 100.0 * gc_seconds(run.gc_full, start, end) / (end - start)
