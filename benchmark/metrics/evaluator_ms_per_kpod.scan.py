"""Device time of the evaluator program (tpu/evaluator.py via
parallel/sharding.py and tpu/compiler.py) in the trace, per thousand Pods
scanned in the traced waves."""

# XLA module names of the evaluator program: the sharded scan step
# (parallel/sharding.py) and the dense batch program (tpu/compiler.py)
MODULES = ("jit_step", "jit_run")


def read(run):
    ts = run.trace_summary
    pods = run.spans.get("traced_pods")
    if not ts or not pods:
        return None
    s = sum(v for k, v in ts.get("module_s", {}).items() if k.startswith(MODULES))
    return s * 1e3 / (pods / 1e3) if s > 0 else None
