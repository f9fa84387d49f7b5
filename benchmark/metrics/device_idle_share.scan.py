"""Share of the traced span in which no operation ran on the device:
1 - union of the device's op intervals / traced span (profiler trace)."""


def read(run):
    ts = run.trace_summary
    if not ts or not ts.get("window_s"):
        return None
    return 100.0 * (1.0 - ts["busy_s"] / ts["window_s"])
