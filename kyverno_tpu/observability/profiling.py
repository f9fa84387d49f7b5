"""Per-phase profiling — where did the scan's wall time actually go?

SURVEY §5 splits accelerator scan cost into encode / device / host
phases; tuning any of them requires attribution first. The profiler
accumulates (seconds, calls) per named phase process-wide; the engine
hot paths mark ``encode`` / ``compile`` / ``dispatch`` / ``readback`` /
``host_complete``, and consumers (``apply --profile``, ``bench.py
--phases``, ``/debug/state``) read the breakdown without re-timing
anything.

Served admission adds one entry per review and stage
(``review.read`` ... ``review.respond``, webhooks/server.py), taken
from the timestamps of the stage's span.

Also here: the thread-local dispatch-path marker (device vs scalar
fallback — the serving flusher reads it to name its batch span's
engine honestly), the one-shot ``jax.profiler`` capture latch behind
``KYVERNO_TPU_XLA_TRACE_DIR``, the profiler session behind
``/debug/xla/start``/``stop`` that writes the program's spans on the
device trace's clock, and the process-wide counts of what stalls the
host without a phase of its own: XLA compiles (a ``jax.monitoring``
listener) and full garbage collections (a ``gc.callbacks`` hook).
"""

from __future__ import annotations

import collections
import gc
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Deque, Dict, List, Optional, Tuple

PHASE_ENCODE = "encode"
PHASE_COMPILE = "compile"
PHASE_DISPATCH = "dispatch"
PHASE_READBACK = "readback"
PHASE_HOST_COMPLETE = "host_complete"
# pipelined-scan consumer idle time: the main loop blocked on the
# encode queue with nothing in flight — the device was starving
# (observability/analytics.py StarvationTracker owns the windowed view)
PHASE_ENCODE_WAIT = "encode_wait"

# canonical print order; unknown phases sort after these
PHASE_ORDER = (PHASE_ENCODE, PHASE_ENCODE_WAIT, PHASE_COMPILE,
               PHASE_DISPATCH, PHASE_READBACK, PHASE_HOST_COMPLETE)


class PhaseProfiler:
    """Thread-safe accumulator of per-phase wall time."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seconds: Dict[str, float] = {}
        self._calls: Dict[str, int] = {}

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._seconds[name] = self._seconds.get(name, 0.0) + seconds
            self._calls[name] = self._calls.get(name, 0) + 1

    def breakdown(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "seconds": round(self._seconds[name], 6),
                    "calls": self._calls[name],
                    "mean_ms": round(
                        self._seconds[name] / self._calls[name] * 1e3, 4),
                }
                for name in self._ordered_names()
            }

    def _ordered_names(self):
        known = [p for p in PHASE_ORDER if p in self._seconds]
        extra = sorted(n for n in self._seconds if n not in PHASE_ORDER)
        return known + extra

    def render_table(self, title: str = "per-phase latency breakdown") -> str:
        """Aligned text table (the `apply --profile` output)."""
        bd = self.breakdown()
        if not bd:
            return f"{title}: no phases recorded"
        total = sum(v["seconds"] for v in bd.values())
        rows = [("phase", "seconds", "calls", "mean_ms", "share")]
        for name, v in bd.items():
            share = (v["seconds"] / total * 100.0) if total else 0.0
            rows.append((name, f"{v['seconds']:.4f}", str(v["calls"]),
                         f"{v['mean_ms']:.3f}", f"{share:5.1f}%"))
        rows.append(("total", f"{total:.4f}", "", "", "100.0%"))
        widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
        lines = [title]
        for i, r in enumerate(rows):
            lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
            if i == 0:
                lines.append("  ".join("-" * w for w in widths))
        return "\n".join(lines)

    def reset(self) -> None:
        with self._lock:
            self._seconds.clear()
            self._calls.clear()


global_profiler = PhaseProfiler()


# -- dispatch-path marker ---------------------------------------------------
# guarded_dispatch records HOW the last batch on this thread resolved
# (device vs scalar fallback); the serving flusher — which runs the
# evaluator inline on its own thread — reads it to name the request's
# dispatch span. Thread-local, so concurrent scanners don't cross-talk.

_tls = threading.local()

PATH_DEVICE = "device"
PATH_SCALAR_FALLBACK = "scalar_fallback"


def set_dispatch_path(path: str) -> None:
    _tls.dispatch_path = path


def last_dispatch_path(default: str = PATH_DEVICE) -> str:
    return getattr(_tls, "dispatch_path", default)


# -- optional XLA profiler capture ------------------------------------------

XLA_TRACE_ENV = "KYVERNO_TPU_XLA_TRACE_DIR"
_xla_latch_lock = threading.Lock()
_xla_captured = False


@contextmanager
def maybe_xla_trace(out_dir: Optional[str] = None):
    """Capture ONE ``jax.profiler`` trace of the wrapped region when the
    flag is set (``KYVERNO_TPU_XLA_TRACE_DIR`` or an explicit dir); a
    one-shot latch keeps steady-state dispatches unperturbed after the
    first capture. No flag -> zero-cost passthrough."""
    global _xla_captured
    target = out_dir or os.environ.get(XLA_TRACE_ENV, "")
    if not target:
        yield False
        return
    with _xla_latch_lock:
        if _xla_captured:
            yield False
            return
        _xla_captured = True
    started = False
    try:
        import jax

        jax.profiler.start_trace(target)
        started = True
    except Exception:
        pass  # profiler unavailability must not fail the dispatch
    try:
        yield started
    finally:
        if started:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass


def reset_xla_trace_latch() -> None:
    """Re-arm the one-shot capture (tests / repeated profile runs)."""
    global _xla_captured
    with _xla_latch_lock:
        _xla_captured = False


# -- serve readiness and process age -----------------------------------------

_ready_lock = threading.Lock()
_serve_ready_at: Optional[float] = None
_module_loaded_at = time.monotonic()


def mark_serve_ready() -> bool:
    """Record that serve first answered /readyz 200; True only for the
    first call (the one that logs ``serve_ready``)."""
    global _serve_ready_at
    with _ready_lock:
        if _serve_ready_at is not None:
            return False
        _serve_ready_at = time.monotonic()
        return True


def serve_is_ready() -> bool:
    return _serve_ready_at is not None


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), else since
    this module loaded."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return time.monotonic() - _module_loaded_at


# -- XLA compiles, counted where they happen ---------------------------------
# jax.jit(...) is lazy: the XLA build (or the load from the persistent
# compile cache) runs inside the first call at each shape. JAX reports
# it on the calling thread as a duration event; a persistent-cache hit
# reports its retrieval first, on the same thread.

XLA_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
XLA_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# before the build, JAX traces the function to a jaxpr and lowers it
# to an MLIR module, on the host, at every new shape (cache hit or not)
JAX_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")
_xla_listener_lock = threading.Lock()
_xla_listening = False


def install_xla_compile_listener() -> None:
    """Register the compile listener once per process (idempotent)."""
    global _xla_listening
    with _xla_listener_lock:
        if _xla_listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_xla_duration)
        _xla_listening = True


def thread_xla_seconds() -> Dict[str, float]:
    """Seconds on this thread so far of JAX tracing and lowering
    (``trace``), XLA builds (``compile``) and persistent-cache loads
    (``cache``)."""
    return dict(_thread_xla_acc())


def _thread_xla_acc() -> Dict[str, float]:
    acc = getattr(_tls, "xla_seconds", None)
    if acc is None:
        acc = _tls.xla_seconds = {"trace": 0.0, "compile": 0.0, "cache": 0.0}
    return acc


def _on_xla_duration(event: str, seconds: float, **kwargs: Any) -> None:
    try:
        if event in JAX_TRACE_EVENTS:
            _thread_xla_acc()["trace"] += seconds
            return
        if event == XLA_CACHE_LOAD_EVENT:
            _tls.xla_cache_hit = True
            return
        if event != XLA_COMPILE_EVENT:
            return
        source = "cache" if getattr(_tls, "xla_cache_hit", False) \
            else "compile"
        _tls.xla_cache_hit = False
        _thread_xla_acc()[source] += seconds
        from .log import global_oplog
        from .metrics import global_registry
        from .tracing import global_tracer

        global_registry.xla_compiles.inc({"source": source})
        global_registry.xla_compile_seconds.inc({"source": source}, seconds)
        fun = str(kwargs.get("fun_name", ""))
        global_tracer.add_event("xla_compile", seconds=seconds,
                                source=source, fun=fun)
        global_oplog.emit("xla_compile", seconds=seconds, source=source,
                          fun=fun, ready=serve_is_ready())
    except Exception:
        pass  # accounting must never fail the compile it watches


# -- full garbage collections ------------------------------------------------


class FullCollections:
    """``gc.callbacks`` hook: each generation-2 collection becomes a
    ``python.gc_full`` span on the tracer's clock and feeds
    ``kyverno_python_gc_full_total``/``_seconds_total``. The callback
    runs wherever the collector interrupts, possibly inside a lock the
    tracer or the registry holds, so it only takes its two timestamps
    and appends; ``drain`` turns the records into spans and counts
    (on every /metrics scrape and when a profiler session ends)."""

    def __init__(self) -> None:
        self.pending: Deque[Tuple[float, float, int]] = collections.deque()
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            self.pending.append((self._t0, time.monotonic(),
                                 info.get("collected", 0)))

    def install(self) -> None:
        from .metrics import global_registry

        if self not in gc.callbacks:
            gc.callbacks.append(self)
        global_registry.add_collect_hook(self.drain)

    def uninstall(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)
        self.drain()

    def drain(self) -> None:
        from .metrics import global_registry
        from .tracing import global_tracer

        while True:
            try:
                t0, t1, freed = self.pending.popleft()
            except IndexError:
                return
            global_tracer.record_span("python.gc_full", t0, t1,
                                      collected=freed)
            global_registry.python_gc_full.inc()
            global_registry.python_gc_full_seconds.inc(value=t1 - t0)


global_gc_full = FullCollections()


# -- profiler session on the device trace's clock ----------------------------

PROGRAM_SPANS_FILE = "program_spans.json"


class XlaSession:
    """One ``jax.profiler`` session with the program's spans beside it.

    ``start`` opens the trace, then one ``TraceAnnotation`` (the clock
    anchor) bracketed by ``time.monotonic_ns()`` reads, and attaches an
    in-memory span exporter. ``stop`` ends the trace, records the
    profiler's own start and stop as ``debug.xla_start``/``_stop``
    spans, and writes ``<dir>/program_spans.json`` once. The anchor's
    ``start_ns`` in the trace's host plane minus the bracket's midpoint
    maps every span onto the trace's timeline."""

    def __init__(self, out_dir: str) -> None:
        from .tracing import SpanSession

        self.out_dir = out_dir
        self.spans = SpanSession()
        self.anchor_ns: List[int] = []
        self.started: Tuple[float, float] = (0.0, 0.0)

    def start(self) -> None:
        import jax

        from .tracing import CLOCK_ANCHOR, global_tracer

        t0 = time.monotonic()
        jax.profiler.start_trace(self.out_dir)
        a0 = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(CLOCK_ANCHOR):
            pass
        a1 = time.monotonic_ns()
        self.anchor_ns = [a0, a1]
        self.started = (t0, time.monotonic())
        global_tracer.add_exporter(self.spans)

    def stop(self) -> str:
        import jax

        from .tracing import global_tracer

        t0 = time.monotonic()
        try:
            jax.profiler.stop_trace()
        finally:
            t1 = time.monotonic()
            global_tracer.record_span("debug.xla_start", *self.started,
                                      dir=self.out_dir)
            global_tracer.record_span("debug.xla_stop", t0, t1,
                                      dir=self.out_dir)
            global_gc_full.drain()
            global_tracer.remove_exporter(self.spans)
        path = os.path.join(self.out_dir, PROGRAM_SPANS_FILE)
        with open(path, "w") as f:
            json.dump(self.spans.to_dict(self.anchor_ns), f, default=str)
        return path


_xla_session_lock = threading.Lock()
_xla_session: Optional[XlaSession] = None


def start_xla_session(out_dir: str) -> None:
    """``/debug/xla/start?dir=D``; raises when a session is open or the
    profiler cannot start."""
    global _xla_session
    with _xla_session_lock:
        if _xla_session is not None:
            raise RuntimeError("an XLA profiler session is already open")
        session = XlaSession(out_dir)
        session.start()
        _xla_session = session


def stop_xla_session() -> str:
    """``/debug/xla/stop``: the path of the written program spans."""
    global _xla_session
    with _xla_session_lock:
        session, _xla_session = _xla_session, None
    if session is None:
        raise RuntimeError("no XLA profiler session is open")
    return session.stop()
