"""Launch ``kyverno_tpu serve`` in this process, as the benchmark's child.

    python benchmark/serve_child.py --peak-file F [--fault NAME] -- <serve args>

Runs the program's own CLI entry (``python -m kyverno_tpu serve ...``)
in-process. When serve returns (SIGTERM), it writes to ``--peak-file``
the device's peak memory, as JAX reports it for this process, and the
interpreter's full (generation 2) garbage collections: the wall-clock
start, seconds and objects freed of each, as ``gc.callbacks`` sees them.

``--fault`` breaks the served path underneath, for the benchmark's own
tests of its comparison; the benchmark's runs never pass it:

- ``stale``: an incremental ``POST /scan`` answers without scanning, so
  reports keep their state from before the change;
- ``half``: every second report fold and every second AdmissionReview
  is left out (the review is allowed unevaluated);
- ``flip``: every tenth report fold has its first result inverted, and
  every tenth AdmissionReview its ``allowed``.
"""

from __future__ import annotations

import argparse
import gc
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _every(n: int):
    counter = itertools.count(1)
    return lambda: next(counter) % n == 0


def apply_fault(name: str) -> None:
    from kyverno_tpu.cluster import reports, scanner
    from kyverno_tpu.webhooks import server

    if name == "stale":
        scan_once = scanner.BackgroundScanService.scan_once

        def stale_scan(self, full: bool = False) -> int:
            return scan_once(self, full=True) if full else 0

        scanner.BackgroundScanService.scan_once = stale_scan
        return
    n = {"half": 2, "flip": 10}.get(name)
    if n is None:
        raise SystemExit(f"unknown fault {name!r}")
    put = reports.ReportAggregator.put
    validate = server.Handlers.validate
    response = server._response
    put_hit, review_hit = _every(n), _every(n)

    def faulty_put(self, uid, results, *a, **kw):
        if put_hit() and results:
            if name == "half":
                return None
            first = results[0]
            first.result = "pass" if first.result != "pass" else "fail"
        return put(self, uid, results, *a, **kw)

    def faulty_validate(self, review, *a, **kw):
        if name == "half" and review_hit():
            return response(review.get("request") or {}, True, "")
        out = validate(self, review, *a, **kw)
        if name == "flip" and review_hit():
            out["response"]["allowed"] = not out["response"]["allowed"]
        return out

    reports.ReportAggregator.put = faulty_put
    server.Handlers.validate = faulty_validate


class FullCollections:
    """Every generation-2 collection of this process: [wall start, seconds,
    objects freed]."""

    def __init__(self):
        self.events = []
        self._t0 = self._wall0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._wall0, self._t0 = time.time(), time.perf_counter()
        else:
            self.events.append([self._wall0, time.perf_counter() - self._t0,
                                info["collected"]])


def device_peak() -> dict:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return {"memory_peak_bytes": max(peaks) if peaks else None}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--" not in argv:
        raise SystemExit("usage: serve_child.py --peak-file F [--fault N] -- <serve args>")
    cut = argv.index("--")
    ap = argparse.ArgumentParser()
    ap.add_argument("--peak-file", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv[:cut])
    sys.path.insert(0, ROOT)
    if args.fault:
        apply_fault(args.fault)
    from kyverno_tpu.cli.__main__ import main as cli_main

    full = FullCollections()
    gc.callbacks.append(full)
    rc = cli_main(["serve", *argv[cut + 1:]])
    gc.callbacks.remove(full)
    with open(args.peak_file, "w") as f:
        json.dump(dict(device_peak(), gc_full=full.events), f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
