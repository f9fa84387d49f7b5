"""The readers of a served review's stages and of serve's set-up, each on
a synthetic run, and the span clock (``harness/span_clock.py``) on a
hand-made trace and, through the helper as the reader runs it, on a
profiler session made on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from harness import core, span_clock

CELL = "pss-restricted-enforce.admission-steady"
ROOT = os.path.dirname(core.BENCH)


@pytest.fixture
def run():
    r = core.Run(core.Cell(CELL), 1, 1.0, True)
    yield r
    r.cleanup()


def _read(run, metric):
    return run.cell.reader(metric)(run)


def test_stage_readers_divide_moved_phases_by_reviews(run):
    names = ("review_front_ms.admission", "review_wait_ms.admission",
             "review_flush_ms.admission")
    run.counters.update(review_count=4.0)
    # a program without the stage phases (the parent) reads nothing
    assert [_read(run, m) for m in names] == [None, None, None]
    run.counters.update({
        "phase_s.review.read": 0.004, "phase_s.review.admit": 0.008,
        "phase_s.review.queue_wait": 0.02, "phase_s.review.flush": 0.1,
        "phase_s.review.wake": 0.004, "phase_s.review.decide": 0.002,
        "phase_s.review.respond": 0.002})
    assert _read(run, names[0]) == pytest.approx(4.0)
    assert _read(run, names[1]) == pytest.approx(6.0)
    assert _read(run, names[2]) == pytest.approx(25.0)
    run.counters["review_count"] = 0.0
    assert [_read(run, m) for m in names] == [None, None, None]


def test_setup_readers_take_serve_ready_from_the_op_log(run):
    names = ("setup_lowering_s.admission", "setup_xla_s.admission")
    assert [_read(run, m) for m in names] == [None, None]
    with open(os.path.join(run.workdir, "oplog.jsonl"), "w") as f:
        f.write(json.dumps({"event": "xla_compile", "seconds": 20.0}) + "\n")
        assert [_read(run, m) for m in names] == [None, None]
        f.write(json.dumps({"event": "serve_ready", "seconds": 80.0,
                            "lowering_s": 0.5, "warm_s": 70.0}) + "\n")
    assert [_read(run, m) for m in names] == [0.5, 70.0]


def test_review_idle_reads_nothing_without_program_spans(run):
    assert _read(run, "review_idle_ms.admission") is None
    os.makedirs(os.path.join(run.workdir, "trace"))
    assert _read(run, "review_idle_ms.admission") is None


def _doc(spans, anchor=(1000, 1010)):
    return {"anchor": {"name": "kyverno.clock_anchor", "mono_ns": list(anchor)},
            "spans": [{"name": n, "trace_id": t, "span_id": f"s{i}",
                       "parent": None, "start_ns": s, "end_ns": e,
                       "attributes": a}
                      for i, (n, t, s, e, a) in enumerate(spans)]}


def test_span_clock_on_a_hand_made_trace():
    # anchor at 100 in the trace, bracket midpoint 1005 on the monotonic
    # clock: trace time = monotonic - 905
    ex = {"span_ns": [0, 1000], "anchor_ns": 100,
          "devices": [{"ops": [[200, 100], [250, 100], [600, 50]],
                       "modules": [["jit_run(x)", 190, 180]]}]}
    m = 905
    doc = _doc([
        ("admission.review", "t1", 150 + m, 700 + m, {"path": "validate"}),
        ("admission.admit", "t1", 150 + m, 250 + m, {}),
        ("admission.queue_wait", "t1", 250 + m, 400 + m, {}),
        ("admission.flush", "t1", 400 + m, 650 + m, {}),
        ("admission.decide", "t1", 650 + m, 700 + m, {}),
        ("python.gc_full", "t9", 380 + m, 420 + m, {}),
        # outside the traced span: not counted
        ("admission.review", "t2", 900 + m, 1100 + m, {"path": "validate"}),
        ("tpu.dispatch", "t3", 180 + m, 380 + m, {}),
        ("tpu.dispatch", "t4", 560 + m, 640 + m, {}),
    ])
    out = span_clock.reduce_spans(ex, doc)
    assert out["offset_ns"] == -m and out["reviews"] == 1
    assert out["review_ms"] == pytest.approx(550 / 1e6)
    # idle in [150, 700]: [150, 200] + [350, 600] + [650, 700]
    assert out["idle_ms_per_review"] == pytest.approx(350 / 1e6)
    split = {k: v * 1e6 for k, v in out["idle_split_ms"].items()}
    assert split["admission.admit"] == pytest.approx(50)
    assert split["admission.queue_wait"] == pytest.approx(50)
    assert split["admission.flush"] == pytest.approx(200)
    assert split["admission.decide"] == pytest.approx(50)
    assert split["python.gc_full"] == pytest.approx(40)
    assert split["no stage span"] == pytest.approx(0)
    # the first dispatch holds its module; the second is 190 ns from it
    assert out["dispatch"] == {"spans": 2, "overlapping": 1,
                               "worst_outside_us": pytest.approx(0.19)}
    # no device plane, or no anchor: nothing to read
    assert span_clock.reduce_spans(dict(ex, devices=[]), doc) == {}
    assert span_clock.reduce_spans(dict(ex, anchor_ns=None), doc) == {}


def test_span_clock_reads_a_profiler_session_with_no_device(tmp_path):
    """A CPU session through the helper as the reader runs it: with no
    TPU plane it writes an empty summary, so the reader reads nothing."""
    code = f"""
import sys
sys.path.insert(0, {ROOT!r})
import jax.numpy as jnp
from kyverno_tpu.observability.profiling import start_xla_session, stop_xla_session
from kyverno_tpu.observability.tracing import global_tracer
start_xla_session({str(tmp_path / "trace")!r})
with global_tracer.span("admission.review", path="validate"):
    jnp.ones(8).sum().block_until_ready()
stop_xla_session()
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=300)
    out = tmp_path / "summary.json"
    subprocess.run([sys.executable, os.path.join(core.BENCH, "harness",
                                                 "span_clock.py"),
                    str(tmp_path / "trace"), str(out)],
                   check=True, env=env, timeout=300)
    assert json.loads(out.read_text()) == {}
