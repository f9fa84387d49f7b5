"""Cell resolution, the run's checks, and its result line.

A cell in ``BENCHMARK.json`` names a configuration and a traffic mix;
the harness finds ``benchmark/configs/<config>.json``,
``benchmark/traffic/<traffic>.json`` and, for every per-layer metric
that lists the cell, ``benchmark/metrics/<metric>.py`` by those names.
The traffic file's ``generator`` names the general generator that reads
it, ``benchmark/harness/<generator>.py``.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, Optional

import yaml

from .serve import ROOT

BENCH = os.path.join(ROOT, "benchmark")


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(held: bool = False) -> dict:
    """``BENCHMARK.json``; with ``held``, also the entries of each cell held
    out of it (``benchmark/held/<cell>.json``), which the benchmark's own
    tests still run."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if held:
        for path in sorted(glob.glob(os.path.join(BENCH, "held", "*.json"))):
            extra = load_json(path)
            for key in ("configs", "workloads", "end_to_end", "per_layer"):
                spec[key] = spec[key] + extra[key]
    return spec


class Cell:
    """One entry of ``workloads`` with everything it refers to."""

    def __init__(self, name: str, spec: Optional[dict] = None,
                 bench_dir: str = BENCH):
        spec = spec or load_spec()
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.spec = spec
        self.entry = cells[name]
        self.name = name
        self.chips = self.entry["chips"]
        self.dir = bench_dir
        self.config = load_json(os.path.join(bench_dir, "configs",
                                             self.entry["config"] + ".json"))
        self.traffic = load_json(os.path.join(bench_dir, "traffic",
                                              self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]

    def generator(self) -> Callable:
        """``drive(run)`` of ``harness/<generator>.py``, the general
        generator that the traffic file names."""
        name = self.traffic["generator"]
        path = os.path.join(self.dir, "harness", name + ".py")
        return load_module(path, f"{__package__}.{name}").drive

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.dir, "metrics", metric + ".py")
        return load_module(path, "metric_" + metric.replace(".", "_")).read

    def reference(self):
        return load_module(os.path.join(self.dir, "configs",
                                        self.config["reference"] + ".py"),
                           self.config["reference"])


class Checks:
    """Every number compared, beside its limit. ``correct`` is their
    conjunction and nothing else."""

    def __init__(self):
        self.items: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": value, "limit": limit}
        say(f"check {name} value={value} limit={limit} "
            f"{'ok' if value <= limit else 'FAIL'}")

    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.items.values())

    def print_last(self) -> None:
        for name, c in self.items.items():
            print(f"check {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr, flush=True)
        print(f"correct: {self.correct()}", file=sys.stderr, flush=True)


class Run:
    """State of one run of one cell, shared by the generator and the metric
    readers: ``counters`` and ``spans`` from the window, ``trace`` from
    the profiler."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 fault: Optional[str] = None):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.fault = fault
        self.checks = Checks()
        self.metrics: Dict[str, float] = {}
        self.spans: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self.trace_summary: Dict = {}
        self.attempted = 0
        self.failed = 0
        self.device: Dict = {}
        self.gc_full: list = []
        self.workdir = tempfile.mkdtemp(prefix="kyverno-bench-")

    def policy_dir(self, failure_action: Optional[str] = None) -> str:
        """The configuration's policies, as served, in the run's own
        directory."""
        out = os.path.join(self.workdir, "policies")
        os.makedirs(out, exist_ok=True)
        ref = self.cell.reference()
        action = failure_action or self.config["validationFailureAction"]
        for i, doc in enumerate(ref.load_policies(
                os.path.join(ROOT, self.config["policies"]), action)):
            with open(os.path.join(out, f"{i:02d}-{doc['metadata']['name']}.yaml"),
                      "w") as f:
                yaml.safe_dump(doc, f, sort_keys=False)
        return out

    def reference_policies(self):
        """The plain reference and the configuration's policies as the
        configuration states them."""
        ref = self.cell.reference()
        return ref, ref.load_policies(os.path.join(ROOT, self.config["policies"]),
                                      self.config["validationFailureAction"])

    def cleanup(self) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             fault: Optional[str] = None) -> Run:
    run = Run(cell, seed, seconds, trace, fault)
    errors = 0
    try:
        cell.generator()(run)
    except Exception:  # noqa: BLE001 - every failure is a named check
        errors = 1
        say("exception:\n" + traceback.format_exc())
    run.checks.add("exceptions", errors, 0)
    if trace and run.trace_summary:
        ts = run.trace_summary
        run.device["busy_s"] = ts["busy_s"]
        run.device["window_s"] = ts["window_s"]
    return run


def result_line(run: Run) -> Dict:
    """The run's last line. ``metrics`` are the cell's end-to-end metrics,
    or with ``--trace 1`` its per-layer ones; ``checks`` comes last."""
    metrics = {}
    if run.trace:
        for m in run.cell.per_layer:
            value = run.cell.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in run.cell.end_to_end:
            if m["name"] in run.metrics:
                metrics[m["name"]] = {"value": run.metrics[m["name"]],
                                      "unit": m["unit"]}
    out = {"correct": run.checks.correct(), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": run.device}
    if run.trace and run.trace_summary:
        out["breakdown"] = {"device_ops": run.trace_summary["device_ops"],
                            "idle_gaps": run.trace_summary["idle_gaps"]}
    out["checks"] = run.checks.items
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None,
                    help="break the served path (the benchmark's own tests "
                         "and control runs; see serve_child.py)")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    t0 = time.perf_counter()
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace), args.fault)
    try:
        line = result_line(run)
    except Exception:  # noqa: BLE001
        say("exception while reading metrics:\n" + traceback.format_exc())
        run.checks.add("metric_exceptions", 1, 0)
        line = None
    finally:
        run.cleanup()
    say(f"run wall_s={time.perf_counter() - t0}")
    run.checks.print_last()
    dev = run.device
    if dev.get("platform") != "tpu" or (dev.get("count") or 0) < cell.chips:
        print(f"no TPU with {cell.chips} chip(s) found (device: {dev}): "
              "no result", file=sys.stderr, flush=True)
        return 3
    if line is None:
        return 1
    print(json.dumps(line), flush=True)
    return 0
