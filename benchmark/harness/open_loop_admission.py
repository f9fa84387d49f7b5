"""Generator ``open_loop_admission``: Pod CREATE AdmissionReviews to serve's
``POST /validate`` at open-loop Poisson arrivals, steady or in the rate
phases that the traffic file gives (``gen.modulate``).

Set-up builds every request body and warms the path with a short
stream at the cell's rate. The window sends ``round(rate * seconds)``
requests on their schedule, whatever the server does; each is timed
from when it was due to the response. The percentiles are over every
request of the window.

Checks, once every request has answered or timed out: each response
carries its request's ``uid``, and its ``allowed`` and, for a denial,
its denying (policy, rule) pairs equal the plain reference's; every
request got an AdmissionReview from the policy path (not an HTTP error,
a timeout, or an ``evaluation error``); and nothing but the device
answered: no review was shed, expired or hedged. Unanswered, shed and
expired requests count in ``failed``.
"""

from __future__ import annotations

import json
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

from . import steps
from .gen import AdmissionStream
from .serve import call

CLIENT_TIMEOUT_S = 70.0


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def denied_rules(message: str) -> set:
    """{(policy, rule)} of a denial message ``"p/r: fail; p2/r2: fail"``."""
    out = set()
    for part in message.split("; "):
        head = part.rsplit(": ", 1)[0]
        if "/" in head:
            out.add(tuple(head.split("/", 1)))
    return out


class OpenLoop:
    """Sends each body at its offset from a common start, from a pool of
    client threads large enough that a slow server does not delay the
    schedule; records each request's due time, send time and answer."""

    def __init__(self, port: int, clients: int):
        self.port = port
        self.clients = clients

    def send(self, bodies: List[bytes], offsets: List[float]):
        n = len(bodies)
        due = [0.0] * n
        sent = [0.0] * n
        done = [math.inf] * n
        answers: List[Optional[Tuple[int, bytes]]] = [None] * n
        lock = threading.Lock()

        def one(i: int) -> None:
            sent[i] = time.perf_counter()
            try:
                ans = call(self.port, "POST", "/validate", bodies[i],
                           timeout=CLIENT_TIMEOUT_S)
            except OSError as e:
                ans = (0, repr(e).encode())
            t = time.perf_counter()
            with lock:
                answers[i], done[i] = ans, t

        with ThreadPoolExecutor(self.clients) as ex:
            t0 = time.perf_counter()
            futures = []
            for i, off in enumerate(offsets):
                due[i] = t0 + off
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                futures.append(ex.submit(one, i))
            for f in futures:
                f.result()
        return due, sent, done, answers


def drive(run) -> None:
    from .core import say

    cfg, traffic = run.config, run.traffic
    rate = traffic["rate_per_s"]
    serve = steps.start(run)
    try:
        t_gen = time.perf_counter()
        gen = AdmissionStream(run.seed, cfg["cluster"], traffic, "window")
        offsets = gen.schedule(rate, run.seconds)
        window = gen.requests(len(offsets))
        warm_gen = AdmissionStream(run.seed, cfg["cluster"], traffic, "warm")
        warm_offsets = warm_gen.schedule(rate, traffic["warm_seconds"])
        warm = warm_gen.requests(len(warm_offsets))
        say(f"generated {len(window)} window and {len(warm)} warm-up reviews "
            f"in {time.perf_counter() - t_gen} s")
        steps.wait_ready(run, serve)
        loop = OpenLoop(serve.adm_port, traffic["clients"])
        loop.send([b for _, b in warm], warm_offsets)
        setup_s = time.perf_counter() - serve.t_spawn
        say(f"setup_s={setup_s}")

        before = steps.counters(serve)
        t_trace0 = None
        if run.trace:
            t_trace0 = steps.start_trace(serve, run)
            stopper = threading.Timer(traffic["trace_seconds"],
                                      lambda: steps.stop_trace(serve))
            stopper.start()
        t_w0 = time.perf_counter()
        due, sent, done, answers = loop.send([b for _, b in window], offsets)
        window_s = time.perf_counter() - t_w0
        if t_trace0 is not None:
            stopper.join()
        after = steps.counters(serve)
        moved = steps.delta(after, before)

        latency = [min(d - u, CLIENT_TIMEOUT_S) for d, u in zip(done, due)]
        late = [s - u for s, u in zip(sent, due)]
        run.metrics["admission_p50_ms"] = percentile(latency, 50) * 1e3
        run.metrics["admission_p95_ms"] = percentile(latency, 95) * 1e3
        run.metrics["setup_s"] = setup_s
        run.spans.update(window_s=window_s, requests=len(window))
        run.counters.update(moved)
        say(f"window {window_s} s: {len(window)} reviews at {rate}/s offered; "
            f"p50 {run.metrics['admission_p50_ms']} ms, "
            f"p95 {run.metrics['admission_p95_ms']} ms, "
            f"max {max(latency) * 1e3} ms; generator late p95 "
            f"{percentile(late, 95) * 1e3} ms, max {max(late) * 1e3} ms; "
            f"shed {moved['shed']}, expired {moved['deadline_expired']}, "
            f"hedged {moved['hedged']}, batches {moved['batch_count']}")

        ref, policies = run.reference_policies()
        mismatched = unanswered = 0
        for i, ((pod, body), ans) in enumerate(zip(window, answers)):
            uid = json.loads(body)["request"]["uid"]
            status, data = ans if ans is not None else (0, b"")
            resp = None
            if status == 200:
                resp = json.loads(data).get("response") or {}
                msg = (resp.get("status") or {}).get("message", "")
                if msg.startswith("evaluation error"):
                    resp = None
            if resp is None:
                unanswered += 1
                if unanswered <= 3:
                    say(f"request {i} not answered by the policy path: "
                        f"HTTP {status} {data[:300]!r}")
                continue
            allowed, denied = ref.admission_verdict(policies, pod)
            got_denied = set() if resp.get("allowed") else denied_rules(msg)
            if (resp.get("uid") != uid or resp.get("allowed") is not allowed
                    or got_denied != denied):
                mismatched += 1
                if mismatched <= 3:
                    say(f"mismatch request {i}: served allowed="
                        f"{resp.get('allowed')} denied={sorted(got_denied)}; "
                        f"reference allowed={allowed} denied={sorted(denied)}")
        run.attempted = len(window)
        run.failed = min(len(window), unanswered + int(moved["shed"])
                         + int(moved["deadline_expired"]))
        run.checks.add("unanswered_reviews", unanswered, 0)
        run.checks.add("mismatched_reviews", mismatched, 0)
        steps.device_checks(run, moved, after)
    finally:
        steps.finish(run, serve)
