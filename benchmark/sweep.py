#!/usr/bin/env python3
"""Find the knee of an open-loop admission cell: one serve, rising rates.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --rates 50,100,200 [--step-seconds 10]

Starts serve as the cell does, warms it at the first rate, then offers
each rate for ``--step-seconds`` and prints one JSON line per step: the
latency p50/p95/max from when each request was due, how late the
generator ran, the shed and expired counts, and the backlog signs (the
p50 of the step's last quarter against its first, and how long the last
answers came after the last send). The knee is the highest rate with no
shed, no expiry and no growing backlog; the cell's traffic file takes
0.8 times it as a number. Stops after the first step that is not clean.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import core, steps  # noqa: E402
from harness.open_loop_admission import OpenLoop, percentile  # noqa: E402
from harness.gen import AdmissionStream  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = core.Cell(args.workload)
    run = core.Run(cell, args.seed, args.step_seconds, False)
    rates = [float(r) for r in args.rates.split(",")]
    serve = steps.start(run)
    try:
        steps.wait_ready(run, serve)
        loop = OpenLoop(serve.adm_port, cell.traffic["clients"])
        warm = AdmissionStream(args.seed, cell.config["cluster"], cell.traffic, "warm")
        offsets = warm.schedule(rates[0], cell.traffic["warm_seconds"])
        loop.send([b for _, b in warm.requests(len(offsets))], offsets)
        for rate in rates:
            gen = AdmissionStream(args.seed, cell.config["cluster"], cell.traffic,
                                  f"sweep-{rate}")
            offsets = gen.schedule(rate, args.step_seconds)
            bodies = [b for _, b in gen.requests(len(offsets))]
            before = steps.counters(serve)
            due, sent, done, answers = loop.send(bodies, offsets)
            moved = steps.delta(steps.counters(serve), before)
            lat = [d - u for d, u in zip(done, due)]
            q = max(1, len(lat) // 4)
            errors = sum(1 for a in answers if a is None or a[0] != 200
                         or b"evaluation error" in a[1])
            step = {"rate": rate, "requests": len(lat),
                    "p50_ms": percentile(lat, 50) * 1e3,
                    "p95_ms": percentile(lat, 95) * 1e3,
                    "max_ms": max(lat) * 1e3,
                    "p50_first_quarter_ms": percentile(lat[:q], 50) * 1e3,
                    "p50_last_quarter_ms": percentile(lat[-q:], 50) * 1e3,
                    "drain_ms": (max(done) - max(due)) * 1e3,
                    "late_p95_ms": percentile([s - u for s, u in zip(sent, due)], 95) * 1e3,
                    "shed": moved["shed"], "expired": moved["deadline_expired"],
                    "hedged": moved["hedged"], "errors": errors,
                    "batches": moved["batch_count"],
                    "mean_batch": (moved["batch_size_sum"] / moved["batch_count"]
                                   if moved["batch_count"] else None)}
            print(json.dumps(step), flush=True)
            if step["shed"] or step["expired"] or errors:
                break
            time.sleep(1.0)
    finally:
        steps.finish(run, serve)
        run.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
