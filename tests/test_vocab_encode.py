"""Vocabulary-encoding parity: densify(encode_resources_vocab(...))
must reproduce every lane of the dense encode_resources(...) output.

The vocab form is the transferable representation (row dedup + device
gather, flatten.py "Vocabulary encoding"); the dense form is the
oracle. Any divergence is a wrong-verdict bug, so the comparison is
exact, lane by lane, over adversarial resource shapes.
"""

import numpy as np
import pytest

from kyverno_tpu.tpu.evaluator import batch_to_host, densify
from kyverno_tpu.tpu.flatten import (
    EncodeConfig,
    encode_resources,
    encode_resources_vocab,
)
from kyverno_tpu.tpu.hashing import hash_path
from kyverno_tpu.tpu.metadata import encode_metadata


def _assert_parity(resources, cfg=None, byte_paths=(), key_byte_paths=()):
    cfg = cfg or EncodeConfig()
    dense = encode_resources(resources, cfg, byte_paths, key_byte_paths)
    vocab = encode_resources_vocab(resources, cfg, byte_paths, key_byte_paths)
    meta = encode_metadata(resources)
    want = batch_to_host(dense, meta)
    got = {k: np.asarray(v) for k, v in
           densify(vocab.to_host(meta, v_bucket=None)).items()}
    assert set(got) == set(want)
    for k in sorted(want):
        assert np.array_equal(got[k], np.asarray(want[k])), (
            f"lane {k} diverges:\n{np.asarray(want[k])}\nvs\n{got[k]}")


def _pods(n):
    out = []
    for i in range(n):
        out.append({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"p{i}", "namespace": "ns",
                         "labels": {"app": f"a{i % 3}"}},
            "spec": {
                "hostNetwork": i % 4 == 0,
                "containers": [
                    {"name": f"c{j}", "image": "nginx:1.25",
                     "securityContext": {"privileged": j % 2 == 0},
                     "resources": {"limits": {"memory": "1Gi"}}}
                    for j in range(1 + i % 3)
                ],
            },
        })
    return out


def test_parity_pods():
    _assert_parity(_pods(17))


def test_parity_empty_and_scalars():
    _assert_parity([
        {},
        {"a": None, "b": True, "c": False, "d": 0, "e": -1.5, "f": "s"},
        {"nums": [1, 2.5, "3", "1e3", "0x10", "10Mi", "3h2m", "-0.0"]},
        {"zero": 0.0, "negzero": -0.0, "big": 2**40},
    ])


def test_parity_nested_arrays_scopes():
    _assert_parity([
        {"spec": {"containers": [
            {"env": [{"name": "A", "value": "x"}, {"name": "B"}]},
            {"env": [{"name": "A", "value": "x"}]},
        ]}},
        {"matrix": [[1, 2], [3, [4, 5]]]},
    ])


def test_parity_glob_values_and_wild_keys():
    _assert_parity([
        {"metadata": {"annotations": {"k*y": "v?l", "plain": "x"}}},
        {"v": "has*glob", "w": "q?mark"},
    ])


def test_parity_byte_pool():
    bp = {hash_path(("spec", "image"))}
    kbp = {hash_path(("metadata", "annotations"))}
    res = [
        {"spec": {"image": "nginx:latest"}},
        {"spec": {"image": "nginx:latest"},
         "metadata": {"annotations": {"a": "runtime/default", "b": "localhost/x"}}},
        {"spec": {"image": "other"}, "metadata": {"annotations": {}}},
    ]
    _assert_parity(res, byte_paths=bp, key_byte_paths=kbp)


def test_parity_row_cap_fallback():
    cfg = EncodeConfig(max_rows=8)
    res = [{"a": {f"k{i}": i for i in range(20)}}, {"b": 1}]
    _assert_parity(res, cfg=cfg)
    vb = encode_resources_vocab(res, cfg)
    assert vb.fallback[0] == 1 and vb.fallback[1] == 0


def test_parity_instance_overflow():
    cfg = EncodeConfig(max_instances=2)
    res = [
        {"spec": {"containers": [{"n": i} for i in range(4)]}},   # depth0 overflow
        {"spec": {"containers": [{"env": [{"v": i} for i in range(4)]}]}},  # depth1
    ]
    _assert_parity(res, cfg=cfg)


def test_parity_pool_overflow_marks_fallback():
    cfg = EncodeConfig(byte_pool_slots=1, byte_pool_width=4)
    bp = {hash_path(("a",)), hash_path(("b",))}
    _assert_parity([{"a": "xy", "b": "zw"}, {"a": "toolongvalue"}],
                   cfg=cfg, byte_paths=bp)


def test_vocab_dedup_is_effective():
    res = _pods(64)
    vb = encode_resources_vocab(res)
    n_rows_total = int(vb.n_rows.sum())
    assert vb.vocab_size < n_rows_total / 4, (
        f"vocab {vb.vocab_size} rows vs {n_rows_total} total — dedup ineffective")


def test_native_encoder_keyed_by_source_hash(tmp_path):
    """The loaded binary is named by a hash of fastencode.c + the build
    command: an edited source keys a new file, so a stale or copied
    binary never loads."""
    import os
    import shutil

    from kyverno_tpu import native

    if native.load() is None:
        pytest.skip(f"native toolchain unavailable: {native.status()}")
    src = os.path.join(os.path.dirname(native.__file__), "fastencode.c")
    key = native._build_key(src, os.environ.get("CC", "gcc"),
                            __import__("sysconfig").get_paths()["include"])
    assert os.path.basename(native.status()["so"]) == \
        f"_fastencode-{key}.so"
    edited = tmp_path / "fastencode.c"
    shutil.copy(src, edited)
    with open(edited, "a") as f:
        f.write("\n/* edited */\n")
    assert native._build_key(str(edited), "gcc", "inc") != \
        native._build_key(src, "gcc", "inc")


def test_native_encoder_parity():
    """The C walk (native/fastencode.c) must agree with the Python
    vocab encoder on densified output, n_rows and fallback for every
    adversarial shape (vocab internals may order rows differently)."""
    from kyverno_tpu.native import load
    from kyverno_tpu.tpu import flatten as F

    native = load()
    if native is None:
        pytest.skip("native toolchain unavailable")

    def py_encode(res, cfg, bp, kbp):
        enc = F._FastEncoder(F._CfgShell(cfg), set(bp), set(kbp))
        vb = F.VocabBatch(len(res), cfg)
        for i, r in enumerate(res):
            enc.begin(i)
            enc.walk(r, F._ROOT_REC, 0, 0, -1, -1, 0)
            vb.n_rows[i] = enc.row
            vb.fallback[i] = 0 if enc.ok else 1
        F._finish_vocab(enc, vb)
        return vb

    cases = [
        (_pods(23), EncodeConfig(), (), ()),
        ([{}, {"a": None, "b": True, "c": 0, "d": -1.5, "e": "s",
               "n": [1, "2", "10Mi", "3h", "0x10", "-0.0", 2**40, 1e20]}],
         EncodeConfig(), (), ()),
        ([{1: "intkey", "m": {2.5: "floatkey"}}], EncodeConfig(), (), ()),
        ([{"metadata": {"annotations": {"k*y": "v?l", "a": "runtime/default"}}},
          {"v": "g*b"}], EncodeConfig(),
         {hash_path(("v",))}, {hash_path(("metadata", "annotations"))}),
        ([{"a": {f"k{i}": i for i in range(20)}}, {"b": 1}],
         EncodeConfig(max_rows=8), (), ()),
        ([{"spec": {"containers": [{"n": i} for i in range(4)]}},
          {"spec": {"containers": [{"env": [{"v": i} for i in range(4)]}]}}],
         EncodeConfig(max_instances=2), (), ()),
        ([{"a": "xy", "b": "zw"}, {"a": "toolongvalue"}],
         EncodeConfig(byte_pool_slots=1, byte_pool_width=4),
         {hash_path(("a",)), hash_path(("b",))}, ()),
        # memo tables grow mid-call: entries must stay pointer-stable
        # (regression for use-after-free on scalar/path table growth)
        ([{"x": f"u{i}"} for i in range(9000)], EncodeConfig(), (), ()),
        ([{"arr": [{f"uniquekey{i}": 1} for i in range(600)]}],
         EncodeConfig(max_rows=2048, max_instances=1024), (), ()),
    ]
    for res, cfg, bp, kbp in cases:
        nat = F._encode_vocab_native(native, list(res), cfg, bp, kbp)
        pyv = py_encode(res, cfg, bp, kbp)
        assert np.array_equal(nat.n_rows, pyv.n_rows)
        assert np.array_equal(nat.fallback, pyv.fallback)
        meta = encode_metadata(res)
        got = {k: np.asarray(v) for k, v in densify(nat.to_host(meta)).items()}
        want = {k: np.asarray(v) for k, v in densify(pyv.to_host(meta)).items()}
        assert set(got) == set(want)
        for k in sorted(want):
            assert np.array_equal(got[k], want[k]), f"lane {k} diverges"


def test_bucket_padding_shapes():
    res = _pods(5)
    vb = encode_resources_vocab(res)
    meta = encode_metadata(res)
    host = vb.to_host(meta, v_bucket=4096, s_bucket=512)
    assert host["vocab_norm_hi"].shape == (4096,)
    assert host["pool_svocab"].shape[0] == 512
    # padded vocab rows are invalid and scope lanes keep the -1 default
    assert host["vocab_valid"][vb.vocab_size:].sum() == 0
    assert (host["vocab_scope1"][vb.vocab_size:] == -1).all()
    with pytest.raises(ValueError):
        vb.to_host(meta, v_bucket=2)
