"""Serving pipeline load test (slow tier): 64 simultaneous admission
threads through the batching pipeline must produce verdicts identical
to the scalar path, with real batch amortization (mean batch size > 4)
and ZERO XLA recompiles after warmup — flushes within one shape bucket
reuse the compiled program."""

import concurrent.futures
import threading

import pytest

from kyverno_tpu.serving import BatchConfig
from tests.test_serving import (DEVICE_POLICY, HOST_POLICY, _cm, _mk_handlers,
                                _pod, _review)

pytestmark = pytest.mark.slow

N_THREADS = 64
REQUESTS_PER_THREAD = 3


def _requests():
    out = []
    for i in range(N_THREADS * REQUESTS_PER_THREAD):
        if i % 8 == 7:
            res = _cm(f"cm{i}", "forbidden" if i % 16 == 7 else "ok")
        else:
            res = _pod(f"p{i}", i % 2 == 0)
        out.append(_review(res, f"u{i}"))
    return out


def test_load_batched_equals_scalar_without_recompile(no_verdict_cache):
    # cache off: this test measures COALESCING (mean batch size, jit
    # cache stability) — the verdict cache would legitimately answer
    # repeat reviews at submit() and starve the queue it is probing
    from kyverno_tpu.webhooks.server import _payload_from_request

    batched = _mk_handlers(batching=True, max_batch_size=32, max_wait_ms=20.0)
    reviews = _requests()

    # warmup: dispatch once at every bucket the pipeline can produce
    # (16 and 32) so the measured phase runs against a warm jit cache
    _, eng = batched._engine()
    payload = _payload_from_request(reviews[0]["request"])
    for bucket in (16, 32):
        batched._evaluate_padded([payload] + [None] * (bucket - 1))
    fn = eng.cps.packed_fn()
    if not hasattr(fn, "_cache_size"):
        pytest.skip("jax jit cache introspection unavailable")
    compiles_after_warmup = fn._cache_size()
    assert compiles_after_warmup <= 2

    barrier = threading.Barrier(N_THREADS)
    results = {}
    res_lock = threading.Lock()

    def worker(tid):
        barrier.wait()  # all 64 threads hit the pipeline simultaneously
        local = {}
        for r in reviews[tid::N_THREADS]:
            local[r["request"]["uid"]] = batched.validate(r)
        with res_lock:
            results.update(local)

    with concurrent.futures.ThreadPoolExecutor(max_workers=N_THREADS) as ex:
        list(ex.map(worker, range(N_THREADS)))
    stats = dict(batched.pipeline.stats)
    mean_batch = batched.pipeline.mean_batch_size()
    compiles_after_load = fn._cache_size()
    batched.pipeline.stop()
    batched.batcher.stop()

    scalar = _mk_handlers(batching=False, engine="scalar")
    want = {r["request"]["uid"]: scalar.validate(r) for r in reviews}
    scalar.batcher.stop()

    assert len(results) == len(reviews)
    for uid, got in results.items():
        assert got["response"]["allowed"] == want[uid]["response"]["allowed"], uid
        assert got["response"].get("status") == want[uid]["response"].get("status"), uid

    # real coalescing happened, and shape bucketing kept the jit cache
    # frozen: repeated flushes within a bucket never recompiled
    assert stats["shed"] == 0 and stats["expired"] == 0
    assert mean_batch > 4, stats
    assert sum(stats["flushes_by_bucket"].values()) >= 2
    assert compiles_after_load == compiles_after_warmup, stats
