"""The packed transfer of a dispatch: the lane dict crosses as one
buffer per dtype (evaluator.pack_lanes) into the packed entry
(CompiledPolicySet.packed_fn), which answers the verdict table and the
per-rule counts as one int32 array. The packed entry must answer
bit-identically to the lane-dict entry (the same program jitted over
the lane dict, built here as the reference), and an admission flush
must make one put and one readback."""

import importlib.util
import os
import random

import jax
import numpy as np
import pytest

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.observability.metrics import global_registry as reg
from kyverno_tpu.observability.tracing import global_tracer
from kyverno_tpu.resilience.breaker import CircuitBreaker
from kyverno_tpu.tpu.engine import DeviceResultError, TpuEngine
from kyverno_tpu.tpu.evaluator import (HOST, NUM_VERDICT_CLASSES,
                                       build_program, pack_lanes,
                                       unpack_lanes)

SEED = 4100000059


def _bench_gen():
    """The admission cell's Pod generator (benchmark/harness/gen.py,
    stdlib only), loaded by path."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "harness", "gen.py")
    spec = importlib.util.spec_from_file_location("bench_gen", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cell_pods(n, seed=SEED):
    """``n`` Pod CREATEs from restricted Deployment templates, every
    violation kind the cell draws among them, the rest compliant."""
    gen = _bench_gen()
    rng = random.Random(seed)
    kinds = list(gen.VIOLATIONS) + [None] * max(0, n - len(gen.VIOLATIONS))
    rng.shuffle(kinds)
    pods = []
    for i, violation in enumerate(kinds[:n]):
        dep = gen.Deployment(f"deploy-{i:05d}", f"team-{i % 5:02d}", 1,
                             gen.draw_restricted_template(rng, violation))
        pods.append(dep.make_pod(rng))
    return pods


@pytest.fixture(scope="module")
def pss_engine():
    from kyverno_tpu.policies import load_pss_policies
    from kyverno_tpu.policy.autogen import expand_policy

    return TpuEngine([expand_policy(p) for p in load_pss_policies()])


@pytest.fixture(scope="module")
def pss_reference(pss_engine):
    return dict_entry(pss_engine.cps)


def dict_entry(cps):
    """The reference: the packed entry's program jitted over the lane
    dict itself, one array per lane."""
    return jax.jit(build_program(cps.device_programs,
                                 cps.encode_cfg.max_instances,
                                 with_counts=True, dfa=cps.dfa))


def _both_entries(eng, batch, reference=None):
    """(dict-entry table, dict-entry counts, packed-entry output)."""
    reference = reference or dict_entry(eng.cps)
    table, counts = reference(jax.device_put(batch))
    buffers, layout = pack_lanes(batch)
    out = eng.cps.packed_fn()(jax.device_put(buffers), layout)
    return np.asarray(table), np.asarray(counts), np.asarray(out)


@pytest.mark.parametrize("bucket,live", [(16, 13), (32, 29), (64, 50)])
def test_packed_entry_matches_dict_entry_on_pss(pss_engine, pss_reference,
                                                bucket, live):
    """Seeded cell Pods, pad columns included, at every admission
    bucket: the packed entry's table and counts are the dict entry's,
    bit for bit."""
    pods = _cell_pods(live) + [{}] * (bucket - live)
    batch, _, _ = pss_engine.encode(pods)
    buffers, _ = pack_lanes(batch)
    # every PSS lane leads with the resources: one slab per dtype
    assert [b.shape[0] for b in buffers] == [bucket] * 4
    table, counts, out = _both_entries(pss_engine, batch, pss_reference)
    assert out.dtype == np.int32
    assert out.shape == (table.shape[0], bucket + NUM_VERDICT_CLASSES)
    np.testing.assert_array_equal(out[:, :bucket], table)
    np.testing.assert_array_equal(out[:, bucket:], counts)
    # the violating Pods really fail some rule: the comparison is not
    # between two all-pass tables
    assert (table[:, :live] == 2).any()


def _dyn_policy():
    """A per-request apiCall operand: lowers to a device rule fed by
    host-resolved dyn lanes (int8 among their dtypes)."""
    return ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "ns-quota"},
        "spec": {"rules": [{
            "name": "max-containers",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "context": [{"name": "quota", "apiCall": {"urlPath":
                "/api/v1/namespaces/{{ request.object.metadata.namespace }}"
                "/quota"}}],
            "validate": {"message": "over quota", "deny": {"conditions": {
                "any": [{"key": "{{ quota.max }}", "operator": "LessThan",
                         "value": 2}]}}},
        }]}})


def test_packed_entry_matches_dict_entry_with_dyn_slots():
    """A set with dyn slots adds the int8 group, and its lanes lead
    with the slots, not the resources: every lane of the dict survives
    the packing bit for bit, and both entries answer alike."""
    from kyverno_tpu.engine.contextloaders import DataSources

    def quota(spec):
        ns = spec["urlPath"].split("/")[4]
        return {"max": int(ns[-2:]) % 4}

    eng = TpuEngine([_dyn_policy()],
                    data_sources=DataSources(api_call=quota))
    assert eng.coverage() == (1, 1) and eng.cps.dyn_slots
    pods = _cell_pods(11) + [{}] * 5
    batch, _, _ = eng.encode(pods)
    dtypes = {np.asarray(v).dtype.name for v in batch.values()}
    assert {"float32", "int32", "uint32", "uint8", "int8"} <= dtypes

    buffers, layout = pack_lanes(batch)
    # one slab per (dtype, leading extent), in key order
    keys = sorted({(np.asarray(v).dtype.name, np.shape(v)[0])
                   for v in batch.values()})
    assert [(b.dtype.name, b.shape[0]) for b in buffers] == keys
    assert all(b.ndim == 2 for b in buffers)
    assert {k[1] for k in keys} == {16, len(eng.cps.dyn_slots)}
    # the order the encoder filled the dict in makes no new layout
    assert pack_lanes(dict(reversed(list(batch.items()))))[1] == layout
    lanes = jax.jit(unpack_lanes, static_argnums=1)(buffers, layout)
    assert set(lanes) == set(batch)
    for name, want in batch.items():
        got = np.asarray(lanes[name])
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert got.tobytes() == np.ascontiguousarray(want).tobytes(), name

    table, counts, out = _both_entries(eng, batch)
    np.testing.assert_array_equal(out[:, :16], table)
    np.testing.assert_array_equal(out[:, 16:], counts)
    verdicts = table[0, :11]
    assert (verdicts == 0).any() and (verdicts == 2).any()


POLICY = ClusterPolicy.from_dict({
    "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
    "metadata": {"name": "no-privileged"},
    "spec": {"rules": [{
        "name": "privileged",
        "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
        "validate": {"message": "m", "pattern": {"spec": {"containers": [
            {"=(securityContext)": {"=(privileged)": "false"}}]}}},
    }]}})


class _CountedReadback:
    """A device result that counts its conversions to numpy."""

    def __init__(self, arr, seen):
        self._arr, self._seen = arr, seen

    def __array__(self, dtype=None, copy=None):
        self._seen.append(1)
        return np.asarray(self._arr, dtype=dtype)


def _transfers():
    return {d: (reg.device_transfers.value({"direction": d}),
                reg.device_transfer_bytes.value({"direction": d}))
            for d in ("h2d", "d2h")}


def test_admission_flush_is_one_put_and_one_readback(monkeypatch,
                                                     no_verdict_cache):
    """One flush through TpuEngine._dispatch: one device_put of one
    buffer per dtype, one readback, and the transfer counters and the
    tpu.dispatch span read those same counts."""
    eng = TpuEngine([POLICY], breaker=CircuitBreaker(name="packed-test"))
    pods = _cell_pods(2)
    eng.scan(pods)  # compile outside the counted flush

    puts, readbacks = [], []
    real_put, real_fn = jax.device_put, eng.cps.packed_fn()

    def counting_put(x, *a, **kw):
        puts.append(x)
        return real_put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(eng.cps, "packed_fn", lambda: (
        lambda buffers, layout: _CountedReadback(real_fn(buffers, layout),
                                                 readbacks)))
    before = _transfers()
    batch, _, _ = eng.encode(pods + [{}] * 14)
    dtypes = {np.asarray(v).dtype.name for v in batch.values()}
    res = eng.scan(pods)
    after = _transfers()

    assert len(puts) == 1 and len(readbacks) == 1
    buffers = puts[0]
    assert len(buffers) == len(dtypes) == 4
    assert all(isinstance(b, np.ndarray) and b.shape[0] == 16
               and b.ndim == 2 for b in buffers)
    span = global_tracer.finished("tpu.dispatch")[-1]
    assert span.attributes["buffers_in"] == len(buffers)
    assert span.attributes["bytes_in"] == sum(b.nbytes for b in buffers)
    assert span.attributes["buffers_out"] == 1
    h2d = [after["h2d"][i] - before["h2d"][i] for i in (0, 1)]
    d2h = [after["d2h"][i] - before["d2h"][i] for i in (0, 1)]
    assert h2d == [len(buffers), sum(b.nbytes for b in buffers)]
    assert d2h == [1, 4 * 1 * (16 + NUM_VERDICT_CLASSES)]
    assert res.verdicts.shape == (1, 2)


def test_dispatch_failure_returns_all_host_table(no_verdict_cache):
    """A packed entry that raises goes through the breaker ladder: the
    dispatch answers an all-HOST table, counted as a breaker fallback,
    and the scalar oracle completes the scan with the device's
    verdicts."""
    eng = TpuEngine([POLICY], breaker=CircuitBreaker(name="packed-fail"))
    pods = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p{i}", "namespace": "d"},
             "spec": {"containers": [{"name": "c", "image": "nginx",
                                      "securityContext": {
                                          "privileged": i == 0}}]}}
            for i in range(2)]
    want = eng.scan(pods).verdicts
    batch, _, _ = eng.encode(pods + [{}] * 14)

    def broken(buffers, layout):
        raise RuntimeError("device lost")

    eng.cps.packed_fn = lambda: broken
    fb0 = reg.breaker_fallback.value({"reason": "error"})
    table = eng._dispatch(batch, 16, 2)
    assert table.shape == (1, 16) and (table == HOST).all()
    assert reg.breaker_fallback.value({"reason": "error"}) == fb0 + 1
    np.testing.assert_array_equal(eng.scan(pods).verdicts, want)
    assert list(want[0]) == [2, 0]


def test_dispatch_refuses_a_result_of_the_wrong_width(no_verdict_cache):
    """A packed entry answering a table without the counts' columns is
    refused as a device result error before it is split: all-HOST
    table, breaker fallback, scalar completion."""
    eng = TpuEngine([POLICY], breaker=CircuitBreaker(name="packed-width"))
    pods = _cell_pods(2)
    want = eng.scan(pods).verdicts
    batch, _, _ = eng.encode(pods + [{}] * 14)
    real_fn = eng.cps.packed_fn()
    eng.cps.packed_fn = lambda: (
        lambda buffers, layout: real_fn(buffers, layout)[:, :16])
    errors = []
    real_record = eng._record_dispatch_failure
    eng._record_dispatch_failure = lambda e: (errors.append(e),
                                              real_record(e))
    fb0 = reg.breaker_fallback.value({"reason": "error"})
    table = eng._dispatch(batch, 16, 2)
    assert table.shape == (1, 16) and (table == HOST).all()
    assert [type(e) for e in errors] == [DeviceResultError]
    assert "want (1, 23)" in str(errors[0])
    assert reg.breaker_fallback.value({"reason": "error"}) == fb0 + 1
    np.testing.assert_array_equal(eng.scan(pods).verdicts, want)
