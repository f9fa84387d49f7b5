"""Mean milliseconds of a served review's flush over the window: drained
from the queue to the batch's verdicts back from the evaluator, from the
moved ``review.flush`` entry of serve's phase breakdown over the moved
review count."""


def read(run):
    n = run.counters.get("review_count")
    key = "phase_s.review.flush"
    if not n or key not in run.counters:
        return None
    return run.counters[key] * 1e3 / n
