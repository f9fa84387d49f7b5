"""Mean milliseconds of a served review's front-end stages over the
window: read, admit, decide and respond, from the moved ``review.<stage>``
entries of serve's phase breakdown (webhooks/server.py) over the moved
count of kyverno_admission_review_duration_seconds."""

STAGES = ("read", "admit", "decide", "respond")


def read(run):
    n = run.counters.get("review_count")
    keys = [f"phase_s.review.{s}" for s in STAGES]
    if not n or not any(k in run.counters for k in keys):
        return None
    return sum(run.counters.get(k, 0.0) for k in keys) * 1e3 / n
