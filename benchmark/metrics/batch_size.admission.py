"""Mean requests per admission micro-batch over the window, from
kyverno_serving_batch_size (serving/)."""


def read(run):
    n = run.counters.get("batch_count")
    return run.counters["batch_size_sum"] / n if n else None
