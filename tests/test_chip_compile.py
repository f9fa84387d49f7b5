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


# (entry, policy set, n) -> memory_analysis(), shared by the compile
# checks and the memory comparison so each program compiles once
_COMPILED = {}


def _memory(eng, entry, n, sharding, key):
    """The evaluator of ``eng`` compiled for the described chip at
    ``n`` resources: ``packed`` is the entry TpuEngine dispatches
    (cps.packed_fn over pack_lanes' slabs), ``dict`` the same program
    jitted over the lane dict, the reference the packing must not
    outgrow."""
    import jax

    from kyverno_tpu.tpu.evaluator import build_program, pack_lanes

    if (entry, key, n) not in _COMPILED:
        batch, _, _ = eng.encode(_pods(16))
        if entry == "packed":
            buffers, layout = pack_lanes(
                {k: np.broadcast_to(np.zeros((), v.dtype), (n,) + v.shape[1:])
                 for k, v in batch.items()})
            compiled = eng.cps.packed_fn().lower(
                [jax.ShapeDtypeStruct(b.shape, b.dtype, sharding=sharding)
                 for b in buffers], layout).compile()
        else:
            cps = eng.cps
            compiled = jax.jit(build_program(
                cps.device_programs, cps.encode_cfg.max_instances,
                with_counts=True, dfa=cps.dfa)).lower(
                    _shapes(batch, n, sharding)).compile()
        _COMPILED[entry, key, n] = compiled.memory_analysis()
    return _COMPILED[entry, key, n]


@pytest.fixture(scope="module")
def pss_engine():
    from kyverno_tpu.tpu.engine import TpuEngine

    eng = TpuEngine(_pss())
    assert eng.coverage() == (66, 66)
    return eng


@pytest.mark.parametrize("entry, n", [
    pytest.param("packed", 64, id="64"),
    pytest.param("dict", 8192, id="8192"),
    pytest.param("packed", 8192, id="packed-8192")])
def test_pss_evaluator_compiles_for_v5e(one_chip, pss_engine, entry, n):
    """The PSS evaluator: the entry TpuEngine dispatches
    (cps.packed_fn()) at the largest admission bucket and at a scan
    tile, and the lane-dict reference at the tile."""
    mem = _memory(pss_engine, entry, n, one_chip, "pss")
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


@pytest.fixture(scope="module")
def pattern_engine():
    from kyverno_tpu.tpu.engine import TpuEngine

    eng = TpuEngine(_pattern_policies())
    dev, total = eng.coverage()
    assert eng.cps.dfa is not None and dev == total
    return eng


def test_dfa_bank_compiles_for_v5e(one_chip, pattern_engine):
    """The DFA bank (uint8/uint16 tables, the byte-lane lax.scan)
    inside a pattern-heavy set's evaluator (lane-dict reference)."""
    mem = _memory(pattern_engine, "dict", 1024, one_chip, "pattern")
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_dfa_bank_packed_entry_compiles_for_v5e(one_chip, pattern_engine):
    """The same pattern-heavy set through the entry TpuEngine
    dispatches."""
    mem = _memory(pattern_engine, "packed", 1024, one_chip, "pattern")
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("key, n", [pytest.param("pss", 8192, id="pss-8192"),
                                    pytest.param("pattern", 1024,
                                                 id="dfa-1024")])
def test_packed_entry_memory_tracks_dict_entry_for_v5e(
        request, one_chip, key, n):
    """The packed entry holds each lane twice at most, as its slab and
    as the lane the evaluator reads, and otherwise runs the reference's
    program: its temporaries stay within the reference's, 10% of
    compiler slack and twice its own arguments (tile padding). Slicing
    the lanes inside the evaluator's loops instead doubled them (v5e:
    1.24 GB against 0.52 GB at 1024 PSS resources)."""
    eng = request.getfixturevalue(f"{key}_engine")
    packed = _memory(eng, "packed", n, one_chip, key)
    ref = _memory(eng, "dict", n, one_chip, key)
    assert packed.temp_size_in_bytes <= (
        1.1 * ref.temp_size_in_bytes + 2 * packed.argument_size_in_bytes), (
            packed, ref)


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
