"""The main path's device programs compile for a TPU v5e.

No chip is attached here: JAX describes a v5e:2x2 topology and the
TPU compiler builds for it, which refuses what the chip would refuse
(unsupported ops or dtypes, programs that do not fit the device's
memory, shardings that cannot be partitioned). Nothing runs, so these
say nothing about verdicts or times: chip_smoke.py does that on the
chip. The topology is described only inside the fixture below, never
at import time, so every xdist worker collects the same tests.
"""

import numpy as np
import pytest

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these silent
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _pods(n):
    out = []
    for i in range(n):
        sc = {"privileged": i % 3 == 0, "runAsNonRoot": i % 2 == 0}
        out.append({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"app-{i:05d}-x", "namespace": "default",
                         "labels": {"tier-0": "frontend"}},
            "spec": {"containers": [{
                "name": "c", "image": "registry.corp/api:v3",
                "imagePullPolicy": "Always", "securityContext": sc}]},
        })
    return out


def _shapes(batch, n, sharding, replicated=None, repl=None):
    """The encoded batch's lanes as shapes, every per-resource lane
    resized to ``n`` resources (they all lead with that axis);
    ``replicated`` lanes keep their shape and take ``repl``."""
    import jax

    def one(k, v):
        if replicated is not None and replicated(k):
            return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=repl)
        return jax.ShapeDtypeStruct((n,) + v.shape[1:], v.dtype,
                                    sharding=sharding)

    return {k: one(k, v) for k, v in batch.items()}


def _pss():
    from kyverno_tpu.policies import load_pss_policies
    from kyverno_tpu.policy.autogen import expand_policy

    return [expand_policy(p) for p in load_pss_policies()]


@pytest.mark.parametrize("n", [64, 8192])
def test_pss_evaluator_compiles_for_v5e(one_chip, n):
    """The program serve dispatches (TpuEngine -> cps.device_fn()) at
    an admission bucket and at a scan tile."""
    from kyverno_tpu.tpu.engine import TpuEngine

    eng = TpuEngine(_pss())
    assert eng.coverage() == (66, 66)
    batch, _, _ = eng.encode(_pods(16))
    compiled = eng.cps.device_fn().lower(
        _shapes(batch, n, one_chip)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem


def _pattern_policies():
    from kyverno_tpu.api.policy import ClusterPolicy

    def P(name, rule):
        return ClusterPolicy.from_dict({
            "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
            "metadata": {"name": name}, "spec": {"rules": [rule]}})

    pods = {"any": [{"resources": {"kinds": ["Pod"]}}]}
    return [
        P("pat-image-globs", {
            "name": "registry-globs", "match": pods,
            "validate": {"message": "m", "pattern": {"spec": {"containers": [
                {"image": "nginx-* | redis-?* | registry.corp/*"}]}}}}),
        P("pat-name-glob", {
            "name": "names", "match": {"any": [{"resources": {
                "kinds": ["Pod"], "names": ["app-*", "job-?????-*"]}}]},
            "validate": {"message": "m",
                         "pattern": {"metadata": {"name": "?*"}}}}),
        P("pat-wild-labels", {
            "name": "team-label", "match": pods,
            "validate": {"message": "m", "pattern": {"metadata": {"labels": {
                "tier-*": "frontend | backend | cache"}}}}}),
        P("pat-vap-matches", {
            "name": "re2-names", "match": pods,
            "validate": {"cel": {"expressions": [
                {"expression":
                 "object.metadata.name.matches('^[a-z][a-z0-9-]{0,62}$')"},
                {"expression":
                 "!object.metadata.name.matches('^(tmp|scratch)-')"}]}}}),
    ]


def test_dfa_bank_compiles_for_v5e(one_chip):
    """The DFA bank (uint8/uint16 tables, the byte-lane lax.scan)
    inside a pattern-heavy set's evaluator."""
    from kyverno_tpu.tpu.engine import TpuEngine

    eng = TpuEngine(_pattern_policies())
    dev, total = eng.coverage()
    assert eng.cps.dfa is not None and dev == total
    batch, _, _ = eng.encode(_pods(16))
    compiled = eng.cps.device_fn().lower(
        _shapes(batch, 1024, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES


def test_sharded_scan_step_compiles_for_4_chips(topo):
    """ShardedScanner._step on a 4-chip mesh: resources shard, the
    vocabulary replicates, and the per-rule counts reduce across
    chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from kyverno_tpu.parallel import ShardedScanner

    mesh = Mesh(np.array(topo.devices), ("data",))
    scanner = ShardedScanner(_pss(), mesh=mesh)
    batch, _ = scanner.encode(_pods(16))
    compiled = scanner._step.lower(_shapes(
        batch, 8192, NamedSharding(mesh, P(scanner.axes)),
        replicated=scanner._replicated_key,
        repl=NamedSharding(mesh, P()))).compile()
    assert "all-reduce" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes < V5E_HBM_BYTES
