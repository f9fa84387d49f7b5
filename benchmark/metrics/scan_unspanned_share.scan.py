"""Share of the window's POST /scan wall time that no profiler phase of
the program covers: 1 - (moved phase_breakdown seconds in /debug/state)
/ (scan wall). Phases of pipelined chunks can overlap, so this is a
lower bound on the unattributed share."""


def read(run):
    scan_s = run.spans.get("scan_s")
    if not scan_s:
        return None
    covered = sum(v for k, v in run.counters.items() if k.startswith("phase_s."))
    return 100.0 * (1.0 - covered / scan_s)
