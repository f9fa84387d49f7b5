"""The program's encode phase (cluster/columnar.py, tpu/flatten.py, the
native encoder) over the window, per Pod replaced."""


def read(run):
    pods = run.spans.get("pods")
    encode = run.counters.get("phase_s.encode")
    return encode * 1e3 / pods if pods and encode is not None else None
