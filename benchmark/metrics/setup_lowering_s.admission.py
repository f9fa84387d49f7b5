"""Seconds serve spent lowering the policy set before it was ready: the
``lowering_s`` of its ``serve_ready`` op-log line (the policy_set_compile
span), in the run's ``oplog.jsonl``; None where serve logged none."""

import json
import os


def read(run):
    try:
        with open(os.path.join(run.workdir, "oplog.jsonl")) as f:
            lines = [json.loads(x) for x in f if '"serve_ready"' in x]
    except (OSError, ValueError):
        return None
    ready = [x for x in lines if x.get("event") == "serve_ready"]
    return ready[0].get("lowering_s") if ready else None
