"""Mean milliseconds a served review waits on admission batching over
the window: its queue wait and its wake-up once the batch's verdicts are
back, from the moved ``review.queue_wait`` and ``review.wake`` entries of
serve's phase breakdown (serving/batcher.py, webhooks/server.py) over
the moved review count."""

STAGES = ("queue_wait", "wake")


def read(run):
    n = run.counters.get("review_count")
    keys = [f"phase_s.review.{s}" for s in STAGES]
    if not n or not any(k in run.counters for k in keys):
        return None
    return sum(run.counters.get(k, 0.0) for k in keys) * 1e3 / n
