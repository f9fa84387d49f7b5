"""Harness span around each wave's POST /scan, per Pod replaced in the
window (background scan: cluster/scanner.py -> tpu/pipeline.py -> report fold)."""


def read(run):
    pods = run.spans.get("pods")
    return run.spans["scan_s"] * 1e3 / pods if pods else None
