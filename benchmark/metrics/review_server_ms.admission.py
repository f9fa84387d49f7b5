"""Mean server-side AdmissionReview time over the window, from
kyverno_admission_review_duration_seconds (webhooks/server.py)."""


def read(run):
    n = run.counters.get("review_count")
    return run.counters["review_s_sum"] * 1e3 / n if n else None
