"""IR -> JAX batch program (trace-time specialization).

The policy set compiles into ONE jitted function evaluating every
device rule against every resource in a batch:

    fn(batch: dict[str, jnp.ndarray]) -> (num_rules, N) int32 verdicts

Design choices (tpu-first):
- trace-time unrolling over rules and pattern nodes: the policy set is
  static per compiled artifact, so the tree walk happens at trace time
  and the device program is pure vector ops — no dynamic control flow,
  no string handling, static shapes throughout;
- per-instance anchor semantics inside arrays-of-maps are masked
  reductions over row tables, aggregated with one-hot einsums over the
  scope index (MXU-friendly int8/f32 matmuls instead of scatters);
- string comparisons are canonical-hash equalities; glob operands run
  a bit-parallel NFA (lax.scan over padded byte tensors) against the
  policy-aware byte pool;
- the three-valued outcome algebra {PASS, SKIP, FAIL} reproduces the
  reference's anchor fail/skip classification (validate.go:36-53,
  anchor/handlers.go) exactly, including phase-1/phase-2 ordering.

Verdict codes: 0 PASS, 1 SKIP, 2 FAIL, 3 NOT_MATCHED, 4 ERROR,
5 HOST (resource exceeded encode caps -> host fallback), 6 CONFIRM
(a pattern evaluated through an over-approximating DFA — or over bytes
whose codepoint semantics can differ — hit this cell: the device
verdict is a maybe, the scalar oracle confirms it; see tpu/dfa.py).
"""

from __future__ import annotations

import functools
from fractions import Fraction
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine import pattern as patternpkg
from ..engine.operator import Operator
from ..utils.duration import parse_duration
from ..utils.quantity import parse_quantity
from .flatten import T_ARR, T_BOOL, T_MAP, T_NULL, T_NUM, T_STR, RowBatch, go_sprint
from .hashing import (
    ARRAY_SEG,
    canon_duration,
    canon_number,
    canon_quantity,
    hash_path,
    hash_str,
    split32,
)
from .dfa import DfaBank, bank_match, nonascii_mask
from .ir import (
    AnchorChild,
    ArrayMapsNode,
    ArrayScalarNode,
    BoolLeaf,
    CelAnd,
    CelConst,
    CelHas,
    CelMatches,
    CelNot,
    CelOr,
    CelStrCmp,
    CondIR,
    CondTreeIR,
    Cmp,
    ElementCollect,
    ExistenceNode,
    FilterIR,
    LeafNode,
    LiteralKey,
    MapNode,
    MatchIR,
    Node,
    NullLeaf,
    NumLeaf,
    DynKey,
    DynValueRef,
    OpKey,
    UserInfoKey,
    PathCollect,
    PathState,
    RuleProgram,
    StrLeaf,
    Unsupported,
)
from .metadata import MetaBatch, OP_CODES

PASS, SKIP, FAIL, NOT_MATCHED, ERROR, HOST, CONFIRM = 0, 1, 2, 3, 4, 5, 6
NUM_VERDICT_CLASSES = 7


# ---------------------------------------------------------------------------
# batch assembly


def batch_to_host(rows: RowBatch, meta: MetaBatch) -> Dict[str, Any]:
    """Assemble the flat lane dict on host (numpy views — no copy, no
    transfer). Device placement happens in ONE ``jax.device_put`` on the
    whole dict: per-array ``jnp.asarray`` pays a round-trip per lane
    over the host-to-device link and was the dominant
    scan cost (~30x slower than a single batched put)."""
    out = dict(rows.arrays())
    for k, v in meta.arrays().items():
        out["meta_" + k] = v
    return out


def batch_to_device(rows: RowBatch, meta: MetaBatch, sharding=None) -> Dict[str, jnp.ndarray]:
    host = batch_to_host(rows, meta)
    return jax.device_put(host, sharding) if sharding is not None else jax.device_put(host)


class LaneView:
    """Lazy dense view over a vocabulary batch (flatten.VocabBatch
    .to_host): dense (n, max_rows) lanes materialize on first access
    via device-side gathers — the embedding-lookup layout that keeps
    H2D transfer at ~1KB/resource. Laziness matters twice: unused
    lanes are never gathered (XLA sees no use), and recording which
    lanes a program touches (see ``record`` / used_keys) lets callers
    PRUNE untouched lanes from the host dict before transfer.

    A vocabulary lane absent from the (pruned) batch densifies to
    zeros — sound only because pruning is driven by a recording trace
    of the same program, which by construction never reads them."""

    def __init__(self, batch: Dict[str, jnp.ndarray], record: bool = False):
        from .flatten import _ROW_LANE_DTYPES, _ROW_LANES

        self._b = batch
        self._cache: Dict[str, jnp.ndarray] = {}
        self._row_lanes = set(_ROW_LANES)
        self._dtypes = _ROW_LANE_DTYPES
        self.used_keys: Optional[set] = set() if record else None
        self._shape = batch["row_idx"].shape  # (n, max_rows)

    def _note(self, *keys: str) -> None:
        if self.used_keys is not None:
            self.used_keys.update(keys)

    def __getitem__(self, name: str) -> jnp.ndarray:
        out = self._cache.get(name)
        if out is not None:
            return out
        b = self._b
        if name in self._row_lanes:
            vkey = "vocab_" + name
            self._note("row_idx", vkey)
            if vkey in b:
                out = jnp.take(b[vkey], b["row_idx"].astype(jnp.int32), axis=0)
            else:
                out = jnp.zeros(self._shape, dtype=self._dtypes[name])
        elif name == "pool":
            self._note("pool_sidx", "pool_svocab")
            out = jnp.take(b["pool_svocab"], b["pool_sidx"].astype(jnp.int32), axis=0)
        elif name == "pool_len":
            self._note("pool_sidx", "pool_slen")
            out = jnp.take(b["pool_slen"], b["pool_sidx"].astype(jnp.int32), axis=0)
        else:  # meta_*, n_rows, fallback — pass through
            self._note(name)
            out = b[name]
        self._cache[name] = out
        return out

    def __contains__(self, name: str) -> bool:
        return (name in self._row_lanes or name in ("pool", "pool_len")
                or name in self._b)

    def items(self):
        """Materialize every lane (dense-dict compatibility for tests)."""
        from .flatten import _ROW_LANES

        names = list(_ROW_LANES) + ["pool", "pool_len", "n_rows", "fallback"]
        names += [k for k in self._b if k.startswith("meta_")]
        return [(n, self[n]) for n in names]


def densify(batch: Dict[str, jnp.ndarray], record: bool = False):
    """Dense batches pass through; vocabulary batches wrap in a lazy
    LaneView (gather-on-access, see LaneView docstring)."""
    if "row_idx" not in batch:
        return batch
    return LaneView(batch, record=record)


# ---------------------------------------------------------------------------
# trace-time context with memoization


class Ctx:
    def __init__(self, batch: Dict[str, jnp.ndarray], max_instances: int,
                 dfa: Optional[DfaBank] = None):
        self.b = batch
        self.I = max_instances
        self.dfa = dfa if (dfa is not None and dfa.trans is not None
                           and len(dfa)) else None
        n, r = batch["norm_hi"].shape
        self.N, self.R = n, r
        self._row_masks: Dict[Tuple[int, str], jnp.ndarray] = {}
        self._glob_cache: Dict[Tuple[str, str], jnp.ndarray] = {}
        self._oh: Optional[jnp.ndarray] = None
        self._oh2: Optional[jnp.ndarray] = None
        self._valid = batch["valid"].astype(bool)
        # per-rule host-fallback masks appended during trace (nested
        # instance-join overflow); eval_rule drains them
        self.host_acc: List[jnp.ndarray] = []
        # per-rule oracle-confirmation masks (approximate-DFA hits,
        # non-ASCII subjects under byte-sensitive patterns)
        self.confirm_acc: List[jnp.ndarray] = []

    # -- row masks

    def rows_at(self, path: Tuple[str, ...]) -> jnp.ndarray:
        return self._mask(hash_path(path), "norm")

    def rows_with_parent(self, path: Tuple[str, ...]) -> jnp.ndarray:
        return self._mask(hash_path(path), "parent")

    def _mask(self, h: int, lane: str) -> jnp.ndarray:
        key = (h, lane)
        if key not in self._row_masks:
            hi, lo = split32(h)
            m = (
                (self.b[lane + "_hi"] == np.uint32(hi))
                & (self.b[lane + "_lo"] == np.uint32(lo))
                & self._valid
            )
            self._row_masks[key] = m
        return self._row_masks[key]

    def heq(self, lane: str, h: int) -> jnp.ndarray:
        hi, lo = split32(h)
        return (self.b[lane + "_hi"] == np.uint32(hi)) & (self.b[lane + "_lo"] == np.uint32(lo))

    def hset(self, lane: str, hashes: Sequence[int]) -> jnp.ndarray:
        if not hashes:
            return jnp.zeros((self.N, self.R), dtype=bool)
        acc = self.heq(lane, hashes[0])
        for h in hashes[1:]:
            acc = acc | self.heq(lane, h)
        return acc

    def type_is(self, t: int) -> jnp.ndarray:
        return self.b["type_tag"] == np.uint8(t)

    @property
    def onehot(self) -> jnp.ndarray:
        """(N, R, I) f32 one-hot of scope1 — shared by all instance
        aggregations; einsum against it is an MXU matmul."""
        if self._oh is None:
            s1 = self.b["scope1"]
            oh = (s1[:, :, None] == jnp.arange(self.I, dtype=np.int32)[None, None, :])
            self._oh = (oh & self._valid[:, :, None]).astype(jnp.float32)
        return self._oh

    @property
    def onehot2(self) -> jnp.ndarray:
        """(N, R, I) f32 one-hot of scope2 (second-level array index)."""
        if self._oh2 is None:
            s2 = self.b["scope2"]
            oh = (s2[:, :, None] == jnp.arange(self.I, dtype=np.int32)[None, None, :])
            self._oh2 = (oh & self._valid[:, :, None]).astype(jnp.float32)
        return self._oh2

    # -- pattern matching over byte lanes. With a compiled DFA bank
    # (tpu/dfa.py) every pattern of a lane family is stepped through
    # the packed tables in ONE shared lax.scan; without one (legacy
    # callers, bank-capacity overflow) each glob falls back to the
    # per-pattern bit-parallel NFA below.

    _FAMILY_LANES = {
        "pool": ("pool", "pool_len"),
        "name": ("meta_name_bytes", "meta_name_len"),
        "ns": ("meta_ns_bytes", "meta_ns_len"),
        "user": ("meta_user_bytes", "meta_user_len"),
        "labels_kb": ("meta_labels_kb", "meta_labels_kb_len"),
        "labels_vb": ("meta_labels_vb", "meta_labels_vb_len"),
    }

    def _family_tensors(self, family: str):
        byte_lane, len_lane = self._FAMILY_LANES[family]
        return self.b[byte_lane], self.b[len_lane]

    def _bank_lookup(self, kind: str, pattern: str, family: str):
        """(accept plane, Dfa) for one bank pattern on one lane family
        — the family's FULL accept tensor is computed once and cached,
        so N patterns on a family cost one scan, not N. None when the
        pattern is not in the bank (legacy NFA path)."""
        bank = self.dfa
        if bank is None:
            return None
        ids = bank.families.get(family)
        pid = (bank.glob_ids if kind == "glob" else bank.re2_ids).get(pattern)
        if pid is None or not ids or pid not in ids:
            return None
        key = ("\x00bank", family)
        if key not in self._glob_cache:
            byt, lens = self._family_tensors(family)
            self._glob_cache[key] = bank_match(bank, ids, byt, lens)
        return self._glob_cache[key][..., ids.index(pid)], bank.patterns[pid]

    def _family_nonascii(self, family: str) -> jnp.ndarray:
        key = ("\x00nonascii", family)
        if key not in self._glob_cache:
            byt, lens = self._family_tensors(family)
            self._glob_cache[key] = nonascii_mask(byt, lens)
        return self._glob_cache[key]

    def _accept_confirm(self, kind: str, pattern: str, family: str):
        """(accepts, confirm-needed | None) over a lane family. The
        confirm plane marks positions whose device verdict is a maybe:
        over-approximating tables on a HIT (miss stays definitive),
        byte-sensitive patterns on non-ASCII subjects (either way)."""
        got = self._bank_lookup(kind, pattern, family)
        if got is None:
            if kind == "re2":
                return None, None  # no bank => caller routes to host
            byt, lens = self._family_tensors(family)
            acc = glob_match(pattern, byt, lens)
            if "?" in pattern:
                # legacy NFA consumes one BYTE per '?': confirm
                # non-ASCII subjects exactly like the bank path
                return acc, self._family_nonascii(family)
            return acc, None
        acc, dfa = got
        conf = None
        if not dfa.exact:
            conf = acc
        if dfa.confirm_nonascii:
            na = self._family_nonascii(family)
            conf = na if conf is None else (conf | na)
        return acc, conf

    def _accept_confirm_cached(self, kind: str, pattern: str, family: str):
        """One (accepts, confirm) pair per (kind, pattern, family) —
        the legacy NFA fallback traces a full scan per pattern, so the
        pair MUST be computed once, not once per consumer."""
        key = ("\x00ac", kind, pattern, family)
        if key not in self._glob_cache:
            self._glob_cache[key] = self._accept_confirm(
                kind, pattern, family)
        return self._glob_cache[key]

    def glob_pool(self, pattern: str) -> jnp.ndarray:
        return self._accept_confirm_cached("glob", pattern, "pool")[0]

    def _pool_confirm(self, kind: str, pattern: str):
        return self._accept_confirm_cached(kind, pattern, "pool")[1]

    def glob_meta(self, pattern: str, which: str) -> jnp.ndarray:
        """which: name | ns | user. Returns (N,) accepts; confirm-needy
        cells accumulate into confirm_acc."""
        acc, conf = self._accept_confirm_cached("glob", pattern, which)
        if conf is not None:
            self.confirm_acc.append(conf)
        return acc

    def _rows_from_pool(self, plane: jnp.ndarray,
                        lane: str = "byte_slot") -> jnp.ndarray:
        """Gather a (N, K) pool-slot plane to (N, R) rows via the
        row->slot lane (False when the row has no slot)."""
        slot = self.b[lane]
        safe = jnp.clip(slot, 0, plane.shape[1] - 1)
        got = jnp.take_along_axis(
            plane, safe.reshape(self.N, -1), axis=1).reshape(slot.shape)
        return got & (slot >= 0)

    def glob_rows(self, pattern: str, lane: str = "byte_slot") -> jnp.ndarray:
        """(N, R) glob accept per row via its byte-pool slot (False when
        the row has no slot)."""
        acc = self._rows_from_pool(self.glob_pool(pattern), lane)
        conf = self._pool_confirm("glob", pattern)
        if conf is not None:
            self.confirm_acc.append(
                self._rows_from_pool(conf, lane).any(axis=-1))
        return acc

    def glob_key_rows(self, pattern: str) -> jnp.ndarray:
        """(N, R) glob accept of each row's map KEY bytes."""
        return self.glob_rows(pattern, "key_byte_slot")


def glob_match(pattern: str, bytes_: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
    """Glob (*/?) NFA over padded byte tensors. bytes_: (..., B) uint8,
    lens: (...) int32 -> (...) bool. Pad bytes are 0 and match nothing;
    acceptance is read at position len."""
    # collapse runs of '*'
    chars: List[str] = []
    for c in pattern:
        if c == "*" and chars and chars[-1] == "*":
            continue
        chars.append(c)
    m = len(chars)
    lead = bytes_.shape[:-1]
    B = bytes_.shape[-1]

    def closure(dp_cols: List[jnp.ndarray]) -> List[jnp.ndarray]:
        # epsilon moves: '*' at j-1 lets dp[j-1] flow into dp[j]
        out = list(dp_cols)
        for j in range(1, m + 1):
            if chars[j - 1] == "*":
                out[j] = out[j] | out[j - 1]
        return out

    dp0 = [jnp.ones(lead, dtype=bool)] + [jnp.zeros(lead, dtype=bool)] * m
    dp0 = closure(dp0)

    def step(dp, c):
        cols = [jnp.moveaxis(dp, -1, 0)[j] for j in range(m + 1)]
        new = [jnp.zeros(lead, dtype=bool)]
        for j in range(1, m + 1):
            pc = chars[j - 1]
            if pc == "*":
                new.append(cols[j])  # self-loop; epsilon handled in closure
            elif pc == "?":
                new.append(cols[j - 1] & (c != 0))
            else:
                new.append(cols[j - 1] & (c == np.uint8(ord(pc) & 0xFF)))
        new = closure(new)
        out = jnp.stack(new, axis=-1)
        return out, new[m]

    seq = jnp.moveaxis(bytes_, -1, 0)  # (B, ...)
    _, accepts = jax.lax.scan(step, jnp.stack(dp0, axis=-1), seq)
    all_accepts = jnp.concatenate([dp0[m][None], accepts], axis=0)  # (B+1, ...)
    sel = jnp.arange(B + 1, dtype=np.int32).reshape((B + 1,) + (1,) * len(lead)) == lens[None]
    return jnp.sum(all_accepts & sel, axis=0).astype(bool)


# ---------------------------------------------------------------------------
# scopes: depth-0 (per-resource) vs instance (per array element)


class Depth0:
    shape_suffix = ()

    def any(self, rowpred: jnp.ndarray) -> jnp.ndarray:
        return rowpred.any(axis=-1)

    def count(self, rowpred: jnp.ndarray) -> jnp.ndarray:
        return rowpred.sum(axis=-1)


class InstScope:
    def __init__(self, ctx: Ctx):
        self.ctx = ctx

    def any(self, rowpred: jnp.ndarray) -> jnp.ndarray:
        return self.count(rowpred) > 0.5

    def count(self, rowpred: jnp.ndarray) -> jnp.ndarray:
        return jnp.einsum("nr,nri->ni", rowpred.astype(jnp.float32), self.ctx.onehot)


class Inst2Scope:
    """Second-level instance scope: joins rows by (scope1, scope2) pairs
    for nested arrays-of-maps (containers[].ports[]); reductions land in
    (N, I, J). The double one-hot contraction is a batched matmul."""

    def __init__(self, ctx: Ctx):
        self.ctx = ctx

    def any(self, rowpred: jnp.ndarray) -> jnp.ndarray:
        return self.count(rowpred) > 0.5

    def count(self, rowpred: jnp.ndarray) -> jnp.ndarray:
        return jnp.einsum(
            "nr,nri,nrj->nij",
            rowpred.astype(jnp.float32), self.ctx.onehot, self.ctx.onehot2,
        )


# ---------------------------------------------------------------------------
# leaf row predicates (pattern.Validate lowering, pattern.go:26)


def leaf_row_pred(ctx: Ctx, leaf: Any) -> jnp.ndarray:
    if isinstance(leaf, BoolLeaf):
        return ctx.type_is(T_BOOL) & (ctx.b["bool_val"] == np.uint8(1 if leaf.value else 0))
    if isinstance(leaf, NumLeaf):
        canon = canon_number(leaf.value)
        num_eq = ctx.heq("num", canon)
        grammar = ctx.b["str_goint" if leaf.is_int else "str_gofloat"] == 1
        return (ctx.type_is(T_NUM) & num_eq) | (ctx.type_is(T_STR) & grammar & num_eq)
    if isinstance(leaf, NullLeaf):
        return (
            ctx.type_is(T_NULL)
            | (ctx.type_is(T_BOOL) & (ctx.b["bool_val"] == 0))
            | (ctx.type_is(T_NUM) & (ctx.b["num_val"] == 0))
            | (ctx.type_is(T_STR) & ctx.heq("repr", hash_str("", tag="s")))
        )
    if isinstance(leaf, StrLeaf):
        pred = ctx.type_is(T_STR) & ctx.heq("repr", hash_str(leaf.full, tag="s"))
        for units in leaf.alternatives:
            conj = None
            for unit in units:
                disj = None
                for c in unit:
                    p = _cmp_pred(ctx, c)
                    disj = p if disj is None else (disj | p)
                if disj is None:  # unmatched range -> always false
                    disj = jnp.zeros((ctx.N, ctx.R), dtype=bool)
                conj = disj if conj is None else (conj & disj)
            if conj is not None:
                pred = pred | conj
        return pred
    raise Unsupported(f"leaf {type(leaf).__name__}")


_ORD_OPS = {
    Operator.EQUAL: "eq", Operator.NOT_EQUAL: "ne", Operator.MORE: "gt",
    Operator.LESS: "lt", Operator.MORE_EQUAL: "ge", Operator.LESS_EQUAL: "le",
}


def _ord_cmp(val: jnp.ndarray, const: float, canon_eq: jnp.ndarray, op: Operator) -> jnp.ndarray:
    """Ordered compare on f32 lanes; equality points are exact via the
    canonical hash, strict compares are exact except in the final ulp."""
    kind = _ORD_OPS[op]
    c = np.float32(const)
    if kind == "eq":
        return canon_eq
    if kind == "ne":
        return ~canon_eq
    if kind == "gt":
        return (val > c) & ~canon_eq
    if kind == "lt":
        return (val < c) & ~canon_eq
    if kind == "ge":
        return (val > c) | canon_eq
    return (val < c) | canon_eq


def _cmp_pred(ctx: Ctx, c: Cmp) -> jnp.ndarray:
    """One operator+operand term (pattern.go:207 validateString trial
    order: duration, then quantity, then string)."""
    if c.op not in _ORD_OPS:
        return jnp.zeros((ctx.N, ctx.R), dtype=bool)
    res: Optional[jnp.ndarray] = None
    processed: Optional[jnp.ndarray] = None
    if c.dur_ns is not None:
        has = ctx.b["has_dur"] == 1
        r = _ord_cmp(ctx.b["dur_val"], c.dur_ns / 1e9, ctx.heq("dur", canon_duration(c.dur_ns)), c.op)
        res, processed = jnp.where(has, r, False), has
    if c.qty is not None:
        has_q = (ctx.b["has_qty"] == 1)
        if processed is not None:
            has_q = has_q & ~processed
        r = _ord_cmp(ctx.b["qty_val"], float(c.qty), ctx.heq("qty", canon_quantity(c.qty)), c.op)
        if res is None:
            res, processed = jnp.where(has_q, r, False), has_q
        else:
            res = jnp.where(has_q, r, res)
            processed = processed | has_q
    # string branch (only Equal / NotEqual ever succeed, pattern.go:272)
    if c.op in (Operator.EQUAL, Operator.NOT_EQUAL):
        has_repr = ctx.b["has_repr"] == 1
        if c.operand == "*":
            m = jnp.ones((ctx.N, ctx.R), dtype=bool)
        elif c.is_glob:
            m = ctx.glob_rows(c.operand)
        else:
            m = ctx.heq("repr", hash_str(c.operand, tag="s"))
        s = has_repr & (~m if c.op is Operator.NOT_EQUAL else m)
    else:
        s = jnp.zeros((ctx.N, ctx.R), dtype=bool)
    if res is None:
        return s
    return jnp.where(processed, res, s)


# ---------------------------------------------------------------------------
# pattern node evaluation


def _leaf_missing_cls(leaf: Any) -> int:
    """validate(None, pattern) is a compile-time constant."""
    if isinstance(leaf, NullLeaf):
        return PASS
    if isinstance(leaf, (BoolLeaf, NumLeaf)):
        return FAIL
    if isinstance(leaf, StrLeaf):
        return PASS if patternpkg.validate(None, leaf.full) else FAIL
    return FAIL


def _first_nonpass(classes: List[jnp.ndarray], shape) -> jnp.ndarray:
    res = jnp.full(shape, PASS, dtype=jnp.int32)
    taken = jnp.zeros(shape, dtype=bool)
    for cls in classes:
        take = (~taken) & (cls != PASS)
        res = jnp.where(take, cls, res)
        taken = taken | (cls != PASS)
    return res


def eval_node(ctx: Ctx, scope, node: Node) -> jnp.ndarray:
    if isinstance(node, LeafNode):
        return _eval_leaf(ctx, scope, node)
    if isinstance(node, MapNode):
        return _eval_map(ctx, scope, node)
    if isinstance(node, ArrayMapsNode):
        return _eval_array_maps(ctx, scope, node)
    if isinstance(node, ArrayScalarNode):
        return _eval_array_scalar(ctx, scope, node)
    raise Unsupported(f"node {type(node).__name__}")


def _eval_leaf(ctx: Ctx, scope, node: LeafNode) -> jnp.ndarray:
    mask = ctx.rows_at(node.path)
    exists = scope.any(mask)
    is_arr = scope.any(mask & ctx.type_is(T_ARR))
    pred = leaf_row_pred(ctx, node.leaf)
    scalar_ok = scope.any(mask & pred & ~ctx.type_is(T_ARR))
    elem_mask = ctx.rows_at(node.path + (ARRAY_SEG,))
    n_elem = scope.count(elem_mask)
    n_ok = scope.count(elem_mask & pred)
    arr_ok = n_elem == n_ok  # every element matches; empty array passes
    ok = jnp.where(is_arr, arr_ok, scalar_ok)
    missing = _leaf_missing_cls(node.leaf)
    cls = jnp.where(ok, PASS, FAIL)
    return jnp.where(exists, cls, jnp.full_like(cls, missing))


def _eval_wildcard_anchor(ctx: Ctx, wc, kind: str, literal_cls: jnp.ndarray) -> jnp.ndarray:
    """ExpandInMetadata select (wildcards.go:62, via engine/wildcards.py):
    when the labels/annotations map exists with all-string values and a
    resource key matches the glob, the anchor applies to the FIRST
    matching key's value (oracle dict order = row order); otherwise the
    literal glob-key behavior stands. Depth-0 only (compile-enforced)."""
    P = wc.map_path
    map_rows = ctx.rows_at(P)
    children = ctx.rows_with_parent(P)
    is_map = (map_rows & ctx.type_is(T_MAP)).any(axis=-1)
    nonstring = (children & ~ctx.type_is(T_STR)).any(axis=-1)
    accept = children & ctx.glob_key_rows(wc.glob)
    has = accept.any(axis=-1)
    idx = jnp.argmax(accept, axis=-1)  # first matching row
    pred = leaf_row_pred(ctx, wc.leaf)
    val_ok = jnp.take_along_axis(pred, idx[:, None], axis=-1)[:, 0]
    if kind == "condition":
        m_cls = jnp.where(val_ok, PASS, SKIP)
    elif kind == "negation":  # expanded key exists -> negation fails
        m_cls = jnp.full(val_ok.shape, FAIL, dtype=jnp.int32)
    else:  # equality / plain: key exists, value must match the leaf
        m_cls = jnp.where(val_ok, PASS, FAIL)
    use = is_map & ~nonstring & has
    return jnp.where(use, m_cls, literal_cls)


def _eval_map(ctx: Ctx, scope, node: MapNode) -> jnp.ndarray:
    mask = ctx.rows_at(node.path)
    exists = scope.any(mask)
    is_map = scope.any(mask & ctx.type_is(T_MAP))

    anchor_cls: List[jnp.ndarray] = []
    for a in node.anchors:
        cpath = node.path + (a.key,)
        cexists = scope.any(ctx.rows_at(cpath))
        if a.kind == "negation":
            cls = jnp.where(cexists, FAIL, PASS)
        elif a.kind == "condition":
            ch = eval_node(ctx, scope, a.child)
            cls = jnp.where(cexists & (ch == PASS), PASS, SKIP)
        elif a.kind == "equality":
            ch = eval_node(ctx, scope, a.child)
            cls = jnp.where(cexists, ch, PASS)
        else:  # existence
            cls = _eval_existence(ctx, scope, a.child, cexists)
        if a.wildcard is not None:
            cls = _eval_wildcard_anchor(ctx, a.wildcard, a.kind, cls)
        anchor_cls.append(cls)

    shape = exists.shape
    if anchor_cls:
        any_fail = functools.reduce(jnp.logical_or, [c == FAIL for c in anchor_cls])
        all_skip = functools.reduce(jnp.logical_and, [c == SKIP for c in anchor_cls])
    else:
        any_fail = jnp.zeros(shape, dtype=bool)
        all_skip = jnp.zeros(shape, dtype=bool)

    p2_cls: List[jnp.ndarray] = []
    for c in node.phase2:
        cpath = node.path + (c.key,)
        cmask = ctx.rows_at(cpath)
        cexists = scope.any(cmask)
        if c.is_star and not c.is_global:
            # "*" under a plain key: present and non-null (handlers.go:128)
            non_null = scope.any(cmask & ~ctx.type_is(T_NULL))
            cls = jnp.where(cexists & non_null, PASS, FAIL)
        elif c.is_global:
            ch = eval_node(ctx, scope, c.child)
            cls = jnp.where(cexists, jnp.where(ch == PASS, PASS, SKIP), PASS)
        else:
            cls = eval_node(ctx, scope, c.child)
        if c.wildcard is not None:
            cls = _eval_wildcard_anchor(ctx, c.wildcard, "plain", cls)
        p2_cls.append(cls)

    phase2 = _first_nonpass(p2_cls, shape)
    cls = jnp.where(any_fail, FAIL, jnp.where(all_skip, SKIP, phase2))
    return jnp.where(exists & is_map, cls, jnp.full(shape, FAIL, dtype=jnp.int32))


def _eval_existence(ctx: Ctx, scope, node: ExistenceNode, cexists: jnp.ndarray) -> jnp.ndarray:
    if not isinstance(scope, Depth0):
        raise Unsupported("existence anchor in array scope")
    mask = ctx.rows_at(node.path)
    is_arr = (mask & ctx.type_is(T_ARR)).any(axis=-1)
    inst = InstScope(ctx)
    valid_i = inst.any(ctx.rows_at(node.path + (ARRAY_SEG,)))
    sat = jnp.ones(cexists.shape, dtype=bool)
    for pm in node.elements:
        cls_i = eval_node(ctx, inst, pm)  # (N, I)
        sat = sat & (valid_i & (cls_i == PASS)).any(axis=-1)
    cls = jnp.where(is_arr, jnp.where(sat, PASS, FAIL), FAIL)
    return jnp.where(cexists, cls, PASS)


def _eval_array_maps(ctx: Ctx, scope, node: ArrayMapsNode) -> jnp.ndarray:
    if isinstance(scope, Depth0):
        mask = ctx.rows_at(node.path)
        exists = mask.any(axis=-1)
        is_arr = (mask & ctx.type_is(T_ARR)).any(axis=-1)
        inst = InstScope(ctx)
        valid_i = inst.any(ctx.rows_at(node.path + (ARRAY_SEG,)))
        elem = eval_node(ctx, inst, node.element)  # (N, I)
    elif isinstance(scope, InstScope):
        # nested array-of-maps (containers[].ports[]): join elements by
        # (scope1, scope2); classes land in (N, I, J), reduced over J
        mask = ctx.rows_at(node.path)
        exists = scope.any(mask)
        is_arr = scope.any(mask & ctx.type_is(T_ARR))
        inst2 = Inst2Scope(ctx)
        valid_i = inst2.any(ctx.rows_at(node.path + (ARRAY_SEG,)))  # (N, I, J)
        elem = eval_node(ctx, inst2, node.element)
        # second-level joins cap at max_instances; overflowing arrays
        # route the resource to host for this rule
        over = (mask & (ctx.b["s2_overflow"] == 1)).any(axis=-1)
        ctx.host_acc.append(over)
    else:
        raise Unsupported("array-of-maps nested beyond two levels")
    any_fail = (valid_i & (elem == FAIL)).any(axis=-1)
    any_pass = (valid_i & (elem == PASS)).any(axis=-1)
    nonempty = valid_i.any(axis=-1)
    cls = jnp.where(
        any_fail, FAIL, jnp.where(any_pass, PASS, jnp.where(nonempty, SKIP, PASS))
    )
    return jnp.where(exists & is_arr, cls, jnp.full(cls.shape, FAIL, dtype=jnp.int32))


def _eval_array_scalar(ctx: Ctx, scope, node: ArrayScalarNode) -> jnp.ndarray:
    mask = ctx.rows_at(node.path)
    exists = scope.any(mask)
    is_arr = scope.any(mask & ctx.type_is(T_ARR))
    pred = leaf_row_pred(ctx, node.leaf)
    elem_mask = ctx.rows_at(node.path + (ARRAY_SEG,))
    all_ok = scope.count(elem_mask) == scope.count(elem_mask & pred)
    cls = jnp.where(all_ok, PASS, FAIL)
    bad = jnp.full(cls.shape, FAIL, dtype=jnp.int32)
    return jnp.where(exists & is_arr, cls, bad)


# ---------------------------------------------------------------------------
# condition evaluation (deny / preconditions)


def _op_canon(op: str) -> str:
    op = op.lower()
    return {"equal": "equals", "notequal": "notequals"}.get(op, op)


_IN_MODES = {"anyin": "any_in", "allin": "all_in",
             "anynotin": "any_not_in", "allnotin": "all_not_in",
             # deprecated In/NotIn (in.go): scalar keys behave like
             # AnyIn/AnyNotIn; list keys are strict (all-in / any-not-
             # in with non-string elements forcing false — see the
             # strict handling in _eval_path_cond)
             "in": "in_strict", "notin": "notin_strict"}
_NUM_OPS = {"greaterthan": "gt", "greaterthanorequals": "ge",
            "lessthan": "lt", "lessthanorequals": "le"}


def _cond_shape(ctx: Ctx, scope) -> Tuple[int, ...]:
    return (ctx.N, ctx.I) if isinstance(scope, InstScope) else (ctx.N,)


def _expand(ctx: Ctx, scope, r: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a per-resource (N,) result into the element scope."""
    if isinstance(scope, InstScope) and r.ndim == 1:
        return jnp.broadcast_to(r[:, None], (ctx.N, ctx.I))
    return r


def eval_cond_tree(
    ctx: Ctx, tree: Optional[CondTreeIR], scope=None, prefix: Tuple[str, ...] = ()
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (ok, err) bools — (N,) at resource scope, (N, I) inside a
    foreach element scope."""
    scope = scope if scope is not None else Depth0()
    shape = _cond_shape(ctx, scope)
    ok = jnp.ones(shape, dtype=bool)
    err = jnp.zeros(shape, dtype=bool)
    if tree is None:
        return ok, err
    for any_list, all_list in tree.blocks:
        if any_list:
            acc = jnp.zeros(shape, dtype=bool)
            for c in any_list:
                p, e = eval_cond(ctx, c, scope, prefix)
                acc = acc | p
                err = err | e
            ok = ok & acc
        for c in all_list:
            p, e = eval_cond(ctx, c, scope, prefix)
            ok = ok & p
            err = err | e
    return ok, err


def eval_cond(
    ctx: Ctx, ir: CondIR, scope=None, prefix: Tuple[str, ...] = ()
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scope = scope if scope is not None else Depth0()
    shape = _cond_shape(ctx, scope)
    zero_err = jnp.zeros(shape, dtype=bool)
    op = _op_canon(ir.op)
    if isinstance(ir.key, OpKey):
        return _expand(ctx, scope, _eval_op_cond(ctx, ir.key, op, ir.value)), zero_err
    if isinstance(ir.key, UserInfoKey):
        return _expand(ctx, scope,
                       _eval_userinfo_cond(ctx, ir.key, op, ir.value)), zero_err
    if isinstance(ir.key, DynKey):
        res, errs = _eval_dyn_key_cond(ctx, ir.key, op, ir.value)
        return _expand(ctx, scope, res), _expand(ctx, scope, errs)
    if isinstance(ir.value, DynValueRef):
        res, errs = _eval_path_vs_dyn_list(ctx, ir.key, op, ir.value, prefix)
        return _expand(ctx, scope, res), _expand(ctx, scope, errs)
    if isinstance(ir.key, LiteralKey):
        if isinstance(ir.value, ElementCollect):
            return _eval_literal_vs_collect(ctx, scope, prefix, ir.key.value, op, ir.value)
        # literal key + literal value: constant-fold via the scalar oracle
        from ..engine.conditions import evaluate_condition_values

        const = bool(evaluate_condition_values(ir.key.value, ir.op, ir.value))
        return jnp.full(shape, const, dtype=bool), zero_err
    if isinstance(ir.key, ElementCollect):
        return _eval_path_cond(ctx, ir.key, op, ir.value, scope, prefix)
    # PathCollect keys always resolve at resource scope
    res, err = _eval_path_cond(ctx, ir.key, op, ir.value, Depth0(), ())
    return _expand(ctx, scope, res), _expand(ctx, scope, err)


def _eval_literal_vs_collect(
    ctx: Ctx, scope, prefix: Tuple[str, ...], key_val: Any, op: str, ec: ElementCollect
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """LiteralKey membership against an {{element...}} collected list
    (the capabilities-strict `key: ALL` shape): hit iff any collected
    element go_sprint-equals the key (conditions.py _key_exists_in_array
    list branch). Null / empty / []-default collections all yield
    hit=False, so no explicit presence gating is needed."""
    shape = _cond_shape(ctx, scope)
    err = _keys_errors(ctx, ec.keys_error_states, scope, prefix)
    mode = _IN_MODES[op]
    ks = go_sprint(key_val)
    if ks is None:
        hit = jnp.zeros(shape, dtype=bool)
    else:
        rows = jnp.zeros((ctx.N, ctx.R), dtype=bool)
        for st in ec.states:
            if st.mode == "keys":
                m = ctx.rows_with_parent(prefix + st.segs) & ctx.heq("key", hash_str(ks, tag="k"))
            else:
                m = ctx.rows_at(prefix + st.segs)
                if st.no_arr:
                    m = m & ~ctx.type_is(T_ARR)
                if st.no_null:
                    m = m & ~ctx.type_is(T_NULL)
                m = m & ctx.heq("sprint", hash_str(ks, tag="s"))
            rows = rows | m
        hit = scope.any(rows)
    return (hit if mode in ("any_in", "all_in") else ~hit), err


def _eval_dyn_key_cond(ctx: Ctx, key: DynKey, op: str,
                       value: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Host-resolved context operand vs literal. Lanes carry the
    value's canonical forms (type / bool / go-parsed number / sprint
    hash); load failures surface as rule ERROR exactly like the scalar
    engine's context-load errors. Returns ((N,) res, (N,) err)."""
    s = key.slot
    t = ctx.b["dyn_type"][s]
    err = t == 0
    # host-flagged cells (value shapes hash lanes can't express)
    ctx.host_acc.append((ctx.b["dyn_host"][s] == 1) & ~err)
    if op in ("equals", "notequals"):
        if isinstance(value, bool):
            eq = (t == 2) & (ctx.b["dyn_bool"][s] == (1 if value else 0))
        elif isinstance(value, (int, float)):
            # numeric equality go-coerces number strings (equal.go)
            eq = (ctx.b["dyn_has_num"][s] == 1) \
                & (ctx.b["dyn_num"][s] == np.float32(value))
        elif isinstance(value, str):
            hi, lo = split32(hash_str(value, tag="s"))
            sh = ctx.b["dyn_sprint"][s]
            eq = (t == 4) & (sh[:, 0] == np.uint32(hi)) \
                & (sh[:, 1] == np.uint32(lo))
        else:  # None — Equals never matches nil (equal.go)
            eq = jnp.zeros(t.shape, dtype=bool)
        if op == "notequals":
            # nil/err/composite keys are False, not negated-True
            typed = (t == 2) | (t == 3) | (t == 4)
            return typed & ~eq, err
        return eq, err
    if op in _NUM_OPS:
        kind = _NUM_OPS[op]
        num = ctx.b["dyn_num"][s]
        has = ctx.b["dyn_has_num"][s] == 1
        c = np.float32(value)
        res = {"gt": num > c, "ge": num >= c,
               "lt": num < c, "le": num <= c}[kind]
        return has & res, err
    return jnp.zeros(t.shape, dtype=bool), err


def _dyn_in_set(ctx: Ctx, slot: int, mask: jnp.ndarray) -> jnp.ndarray:
    """Row-hash membership against a per-resource list operand."""
    ln = ctx.b["dyn_list_n"][slot]
    lh = ctx.b["dyn_list_h"][slot]       # (N, L, 2)
    shi, slo = ctx.b["sprint_hi"], ctx.b["sprint_lo"]
    in_set = jnp.zeros((ctx.N, ctx.R), dtype=bool)
    for l in range(lh.shape[1]):
        live = (jnp.asarray(l, dtype=np.int32) < ln)[:, None]
        eq = (shi == lh[:, l, 0][:, None]) & (slo == lh[:, l, 1][:, None])
        in_set = in_set | (live & eq)
    return in_set & mask


def _eval_path_vs_dyn_list(ctx: Ctx, pc, op: str, ref: DynValueRef,
                           prefix: Tuple[str, ...]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Path-chain / projection keys against a host-resolved operand:
    list membership (_set_in semantics, hash equality) or scalar
    equality. Glob/unit-bearing values and list overflow were flagged
    host-side. ((N,) res, (N,) err)."""
    scope = Depth0()
    s = ref.slot
    t = ctx.b["dyn_type"][s]
    err = t == 0
    # flagged cells complete on host (per-cell HOST verdict)
    ctx.host_acc.append((ctx.b["dyn_host"][s] == 1) & ~err)
    if isinstance(pc, LiteralKey):
        # constant key vs per-resource list (scalar keys: the strict
        # In/NotIn modes behave like AnyIn/AnyNotIn)
        from .flatten import go_sprint

        ks = go_sprint(pc.value)
        ln = ctx.b["dyn_list_n"][s]
        lh = ctx.b["dyn_list_h"][s]
        hit = jnp.zeros((ctx.N,), dtype=bool)
        if ks is not None:
            hi, lo = split32(hash_str(ks, tag="s"))
            for l in range(lh.shape[1]):
                live = jnp.asarray(l, dtype=np.int32) < ln
                hit = hit | (live & (lh[:, l, 0] == np.uint32(hi))
                             & (lh[:, l, 1] == np.uint32(lo)))
        is_list = t == 5
        mode = _IN_MODES[op]
        pos = mode in ("any_in", "all_in", "in_strict")
        return is_list & hit if pos else is_list & ~hit, err
    mask = jnp.zeros((ctx.N, ctx.R), dtype=bool)
    for st in pc.states:
        m = ctx.rows_at(prefix + st.segs)
        if st.no_arr:
            m = m & ~ctx.type_is(T_ARR)
        if st.no_null:
            m = m & ~ctx.type_is(T_NULL)
        mask = mask | m
    if op in ("equals", "notequals"):
        # scalar chain key vs scalar operand
        sh = ctx.b["dyn_sprint"][s]
        eq_str = scope.any(mask & ctx.type_is(T_STR)
                           & (ctx.b["sprint_hi"] == sh[:, 0][:, None])
                           & (ctx.b["sprint_lo"] == sh[:, 1][:, None]))
        nh = ctx.b["dyn_num_h"][s]
        has_num = (ctx.b["dyn_has_num"][s] == 1)[:, None]
        eq_num = scope.any(mask & ctx.type_is(T_NUM) & has_num
                           & (ctx.b["num_hi"] == nh[:, 0][:, None])
                           & (ctx.b["num_lo"] == nh[:, 1][:, None]))
        ab = ctx.b["dyn_as_bool"][s]
        eq_bool = scope.any(mask & ctx.type_is(T_BOOL)
                            & (ab < 2)[:, None]
                            & (ctx.b["bool_val"] == ab[:, None]))
        eq = eq_str | eq_num | eq_bool
        if op == "notequals":
            null_or_missing = (~scope.any(mask)) \
                | scope.any(mask & ctx.type_is(T_NULL))
            return ~eq & ~null_or_missing, err
        return eq, err
    # membership. value shapes: a real list (t==5), or a STRING that
    # JSON-decodes to a string array (strict In/NotIn + the AnyIn
    # family both decode; other string forms keep oracle-only
    # semantics and route to host / evaluate false)
    mode = _IN_MODES[op]
    strict = mode in ("in_strict", "notin_strict")
    is_list = t == 5
    json_list = (t == 4) & (ctx.b["dyn_json_list"][s] == 1)
    if strict:
        usable = is_list | json_list
        # raw singleton-equality (the wildcard arm of keyExistsInArray)
        # is exact equality for non-glob values; non-decodable string
        # values keep oracle-only edge semantics -> host
        ctx.host_acc.append((t == 4) & ~json_list & ~err)
        sh = ctx.b["dyn_sprint"][s]
        raw_eq = scope.any(mask & ctx.type_is(T_STR)
                           & (ctx.b["sprint_hi"] == sh[:, 0][:, None])
                           & (ctx.b["sprint_lo"] == sh[:, 1][:, None]))
    else:
        usable = is_list
        raw_eq = jnp.zeros((ctx.N,), dtype=bool)
        # AnyIn-family string values have singleton/range semantics
        # hash lanes don't model: those cells complete on host
        ctx.host_acc.append((t == 4) & ~err)
    in_set = _dyn_in_set(ctx, s, mask)
    if pc.is_projection:
        present = _list_exists(ctx, pc, scope, prefix)
        any_in = scope.any(in_set)
        any_not_in = scope.any(mask & ~in_set)
        res = {
            "any_in": any_in,
            "all_in": ~any_not_in,
            "any_not_in": any_not_in,
            "all_not_in": ~any_in,
            "in_strict": ~any_not_in,
            "notin_strict": any_not_in,
        }[mode]
        return present & usable & res, err
    # scalar chain key: scalar vs array semantics as in the static
    # membership branch
    st = pc.states[0]
    is_scalar = scope.any(mask & (ctx.type_is(T_STR) | ctx.type_is(T_NUM)
                                  | ctx.type_is(T_BOOL)))
    is_arr = scope.any(mask & ctx.type_is(T_ARR))
    hit = scope.any(in_set) | raw_eq
    em = ctx.rows_at(prefix + st.segs + (ARRAY_SEG,))
    e_in = _dyn_in_set(ctx, s, em)
    e_any_in = scope.any(e_in)
    e_any_not = scope.any(em & ~e_in)
    e_nonstr = scope.any(em & ~ctx.type_is(T_STR))
    if strict and pc is not None:
        # strict array keys vs string values mix decode rules — host
        ctx.host_acc.append(is_arr & (t == 4) & ~err)
    res = {
        "any_in": jnp.where(is_arr, e_any_in, is_scalar & hit),
        "all_in": jnp.where(is_arr, ~e_any_not, is_scalar & hit),
        "any_not_in": jnp.where(is_arr, e_any_not, is_scalar & ~hit),
        "all_not_in": jnp.where(is_arr, ~e_any_in, is_scalar & ~hit),
        "in_strict": jnp.where(is_arr, ~e_any_not & ~e_nonstr,
                               is_scalar & hit),
        "notin_strict": jnp.where(is_arr, e_any_not & ~e_nonstr,
                                  is_scalar & ~hit),
    }[mode]
    return usable & res, err


def _eval_userinfo_cond(ctx: Ctx, key: UserInfoKey, op: str,
                        value: Any) -> jnp.ndarray:
    """{{ request.userInfo.<field> }} membership against a literal
    string list — per-lane hash equality over the RBAC identity lanes,
    mirroring conditions.py _set_in for glob-free values (vacuous
    truths on empty identity lists included)."""
    lane, n_lane, tag = {
        "groups": ("groups_h", "groups_n", "u"),
        "roles": ("roles_h", "roles_n", "r"),
        "clusterRoles": ("croles_h", "croles_n", "r"),
    }[key.field]
    arr = ctx.b["meta_" + lane]          # (N, L, 2)
    n = ctx.b["meta_" + n_lane]
    L = arr.shape[1]
    live = jnp.arange(L, dtype=np.int32)[None, :] < n[:, None]
    hit = jnp.zeros(arr.shape[:2], dtype=bool)
    for v in value:
        hi, lo = split32(hash_str(v, tag=tag))
        hit = hit | ((arr[..., 0] == np.uint32(hi))
                     & (arr[..., 1] == np.uint32(lo)))
    mode = _IN_MODES[op]
    if mode == "any_in":
        return (live & hit).any(-1)
    if mode == "all_in":
        return (~live | hit).all(-1)
    if mode == "any_not_in":
        return (live & ~hit).any(-1)
    return (~live | ~hit).all(-1)  # all_not_in


def _eval_op_cond(ctx: Ctx, key: OpKey, op: str, value: Any) -> jnp.ndarray:
    """request.operation comparisons: a per-resource vocabulary-code
    compare. The vocab covers the four admission ops plus any literal
    strings appearing in this condition."""
    vocab: Dict[str, int] = {}

    def code(s: str) -> int:
        if s not in vocab:
            vocab[s] = len(vocab)
        return vocab[s]

    for s in OP_CODES:
        code(s)
    op_lane = ctx.b["meta_op_code"]  # 0..4 per OP_CODES order of insertion
    present = op_lane != 0
    if key.default is not None:
        key_code = jnp.where(present, op_lane, np.int32(code(key.default)))
        key_present = jnp.ones_like(present)
    else:
        key_code = op_lane
        key_present = present
    if op in ("equals", "notequals"):
        if not isinstance(value, str):
            eq = jnp.zeros((ctx.N,), dtype=bool)
        else:
            eq = key_present & (key_code == np.int32(code(value)))
        return ~eq if op == "notequals" else eq
    if op in _IN_MODES:
        vals = value if isinstance(value, list) else [value]
        vcodes = [code(v) for v in vals if isinstance(v, str)]
        hit = jnp.zeros((ctx.N,), dtype=bool)
        for vc in vcodes:
            hit = hit | (key_code == np.int32(vc))
        hit = key_present & hit
        mode = _IN_MODES[op]
        # request.operation is a scalar string: deprecated In/NotIn
        # behave exactly like AnyIn/AnyNotIn on it
        if mode in ("any_in", "all_in", "in_strict"):
            return hit
        return key_present & ~hit
    # numeric on operation strings never succeeds
    return jnp.zeros((ctx.N,), dtype=bool)


def _collect_masks(
    ctx: Ctx, pc: PathCollect, literals: List[Any], prefix: Tuple[str, ...] = ()
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(mask, in_set) over rows for a projection collect; literal
    membership per row uses the sprint lane (value states) or key lane
    (keys() states). Works for ElementCollect too (same field shape)
    with the element prefix prepended."""
    lits = [go_sprint(v) for v in literals]
    lits = [l for l in lits if l is not None]
    sprint_set = [hash_str(l, tag="s") for l in lits]
    key_set = [hash_str(l, tag="k") for l in lits]
    mask = jnp.zeros((ctx.N, ctx.R), dtype=bool)
    in_set = jnp.zeros((ctx.N, ctx.R), dtype=bool)
    for st in pc.states:
        if st.mode == "keys":
            m = ctx.rows_with_parent(prefix + st.segs)
            s = ctx.hset("key", key_set)
        else:
            m = ctx.rows_at(prefix + st.segs)
            if st.no_arr:
                m = m & ~ctx.type_is(T_ARR)
            if st.no_null:
                m = m & ~ctx.type_is(T_NULL)
            s = ctx.hset("sprint", sprint_set)
        mask = mask | m
        in_set = in_set | (m & s)
    return mask, in_set


def _list_exists(ctx: Ctx, pc: PathCollect, scope, prefix: Tuple[str, ...] = ()) -> jnp.ndarray:
    """Projection result is a list (vs null) when any root produces one."""
    ex = jnp.zeros(_cond_shape(ctx, scope), dtype=bool)
    for segs, kind in pc.array_roots:
        m = ctx.rows_at(prefix + segs)
        if kind == "array":
            ex = ex | scope.any(m & ctx.type_is(T_ARR))
        else:  # mselect: any non-null input yields a literal list
            ex = ex | scope.any(m & ~ctx.type_is(T_NULL))
    return ex


def _keys_errors(
    ctx: Ctx, states: List[PathState], scope, prefix: Tuple[str, ...] = ()
) -> jnp.ndarray:
    """keys(@) on a non-object element is a JMESPath error -> rule ERROR."""
    err = jnp.zeros(_cond_shape(ctx, scope), dtype=bool)
    for st in states:
        m = ctx.rows_at(prefix + st.segs)
        bad = ctx.type_is(T_BOOL) | ctx.type_is(T_NUM) | ctx.type_is(T_STR)
        if not st.no_arr:
            bad = bad | ctx.type_is(T_ARR)
        if not st.no_null:
            bad = bad | ctx.type_is(T_NULL)
        err = err | scope.any(m & bad)
    return err


def _scalar_falsy(ctx: Ctx, mask: jnp.ndarray, scope) -> jnp.ndarray:
    """JMESPath falsy for a scalar path value: missing/null/''/false/
    empty map/empty list."""
    exists = scope.any(mask)
    null = scope.any(mask & ctx.type_is(T_NULL))
    empty_str = scope.any(mask & ctx.type_is(T_STR) & ctx.heq("repr", hash_str("", tag="s")))
    false_b = scope.any(mask & ctx.type_is(T_BOOL) & (ctx.b["bool_val"] == 0))
    empty_cont = scope.any(
        mask & (ctx.type_is(T_MAP) | ctx.type_is(T_ARR)) & (ctx.b["arr_len"] == 0)
    )
    return (~exists) | null | empty_str | false_b | empty_cont


def _scalar_membership_const(default: Any, literals: List[Any], mode: str) -> bool:
    """Host-computed membership result when the || default kicks in
    (exact conditions.py semantics via the scalar oracle)."""
    from ..engine.conditions import _deprecated_in, _membership
    from .ir import _NullDefault

    if isinstance(default, _NullDefault):
        default = None
    if mode in ("in_strict", "notin_strict"):
        return _deprecated_in(default, list(literals),
                              not_in=(mode == "notin_strict"))
    return _membership(default, literals, mode)


def _eval_path_cond(
    ctx: Ctx, pc: PathCollect, op: str, value: Any, scope=None, prefix: Tuple[str, ...] = ()
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scope = scope if scope is not None else Depth0()
    shape = _cond_shape(ctx, scope)
    err = _keys_errors(ctx, pc.keys_error_states, scope, prefix)
    # a bare {{ request.object... }} chain with NO || default raises
    # VariableNotFoundError when the path is absent (forked go-jmespath
    # behavior pinned by the reference corpus) -> rule ERROR. A null
    # VALUE is a present row (T_NULL) and does not error. not_null()
    # keys never error: the function absorbs missing paths.
    if (pc.default is None and pc.default_collect is None
            and not pc.default_null_only and not pc.is_projection
            and len(pc.states) == 1 and pc.states[0].mode == "value"):
        exists = scope.any(ctx.rows_at(prefix + pc.states[0].segs))
        err = err | ~exists
    if op in _IN_MODES:
        mode = _IN_MODES[op]
        literals = value if isinstance(value, list) else [value]
        if pc.is_projection:
            mask, in_set = _collect_masks(ctx, pc, literals, prefix)
            count = scope.count(mask)
            present = _list_exists(ctx, pc, scope, prefix)
            any_in = scope.any(in_set)
            any_not_in = scope.any(mask & ~in_set)
            res = {
                "any_in": any_in,
                "all_in": ~any_not_in,
                "any_not_in": any_not_in,
                "all_not_in": ~any_in,
            }[mode]
            res = present & res
            if pc.default is not None:
                falsy = (~present) | (count == 0)
                const = _scalar_membership_const(pc.default, literals, mode)
                res = jnp.where(falsy, const, res)
            return res, err
        # scalar chain key
        st = pc.states[0]
        mask = ctx.rows_at(prefix + st.segs)
        lits = [go_sprint(v) for v in (value if isinstance(value, list) else [value])]
        sset = [hash_str(l, tag="s") for l in lits if l is not None]
        in_set = ctx.hset("sprint", sset)
        is_scalar = scope.any(mask & (ctx.type_is(T_STR) | ctx.type_is(T_NUM) | ctx.type_is(T_BOOL)))
        is_arr = scope.any(mask & ctx.type_is(T_ARR))
        hit = scope.any(mask & in_set)
        em = ctx.rows_at(prefix + st.segs + (ARRAY_SEG,))
        e_any_in = scope.any(em & in_set)
        e_any_not = scope.any(em & ~in_set)
        # deprecated In/NotIn list-key strictness (in.go:35-43): any
        # non-string element makes the whole condition false
        e_nonstr = scope.any(em & ~ctx.type_is(T_STR))
        res = {
            "any_in": jnp.where(is_arr, e_any_in, is_scalar & hit),
            "all_in": jnp.where(is_arr, ~e_any_not, is_scalar & hit),
            "any_not_in": jnp.where(is_arr, e_any_not, is_scalar & ~hit),
            "all_not_in": jnp.where(is_arr, ~e_any_in, is_scalar & ~hit),
            "in_strict": jnp.where(is_arr, ~e_any_not & ~e_nonstr,
                                   is_scalar & hit),
            "notin_strict": jnp.where(is_arr, e_any_not & ~e_nonstr,
                                      is_scalar & ~hit),
        }[mode]
        if pc.default is not None:
            falsy = _default_falsy(ctx, pc, mask, scope)
            const = _scalar_membership_const(pc.default, value if isinstance(value, list) else [value], mode)
            res = jnp.where(falsy, const, res)
        return res, err
    if op in ("equals", "notequals"):
        if pc.is_projection:
            # list-vs-literal deep equality: lists never equal scalars;
            # only list literals could match — unsupported at compile
            res = jnp.zeros(shape, dtype=bool)
            return (~res if op == "notequals" else res), err
        res = _eval_scalar_eqop(ctx, pc, op, value, scope, prefix)
        res = _apply_scalar_default(
            ctx, pc, scope, prefix, res,
            lambda dpc: _eval_scalar_eqop(ctx, dpc, op, value, scope, prefix),
            lambda d: _const_cond(d, op, value))
        return res, err
    if op in _NUM_OPS:
        if pc.is_projection:
            return jnp.zeros(shape, dtype=bool), err
        res = _eval_scalar_numeric(ctx, pc, _NUM_OPS[op], value, scope, prefix)
        res = _apply_scalar_default(
            ctx, pc, scope, prefix, res,
            lambda dpc: _eval_scalar_numeric(ctx, dpc, _NUM_OPS[op], value,
                                             scope, prefix),
            lambda d: _const_cond(d, op, value))
        return res, err
    return jnp.zeros(shape, dtype=bool), err


def _const_cond(key: Any, op: str, value: Any) -> bool:
    """Host-folded condition on a constant key (the default arm)."""
    from ..engine.conditions import evaluate_condition_values
    from .ir import _NullDefault

    if isinstance(key, _NullDefault):
        key = None
    try:
        return bool(evaluate_condition_values(key, op, value))
    except Exception:  # noqa: BLE001
        return False


def _eval_scalar_eqop(ctx: Ctx, pc: PathCollect, op: str, value: Any,
                      scope, prefix: Tuple[str, ...]) -> jnp.ndarray:
    """equals/notequals on a scalar chain, with the oracle's null-key
    rule: NotEquals on a nil/missing key is FALSE, not the negation
    (notequal.go:47-49 — unsupported key types evaluate false)."""
    res = _eval_scalar_equals(ctx, pc, value, scope, prefix)
    if op != "notequals":
        return res
    st = pc.states[0]
    mask = ctx.rows_at(prefix + st.segs)
    null_or_missing = (~scope.any(mask)) | scope.any(mask & ctx.type_is(T_NULL))
    return ~res & ~null_or_missing


def _default_falsy(ctx: Ctx, pc: PathCollect, mask: jnp.ndarray,
                   scope) -> jnp.ndarray:
    """When does this key's default arm fire: jmespath `||` on any
    falsy value; not_null() on null/missing only."""
    if pc.default_null_only:
        exists = scope.any(mask)
        null = scope.any(mask & ctx.type_is(T_NULL))
        return (~exists) | null
    return _scalar_falsy(ctx, mask, scope)


def _apply_scalar_default(ctx: Ctx, pc: PathCollect, scope,
                          prefix: Tuple[str, ...], res: jnp.ndarray,
                          eval_chain, eval_const) -> jnp.ndarray:
    """Route a scalar-chain key through its default arm (literal
    constant or not_null's second chain) where the primary chain is
    falsy/null."""
    if pc.default is None and pc.default_collect is None:
        return res
    mask = ctx.rows_at(prefix + pc.states[0].segs)
    falsy = _default_falsy(ctx, pc, mask, scope)
    if pc.default_collect is not None:
        alt = eval_chain(pc.default_collect)
    else:
        alt = jnp.full(res.shape, eval_const(pc.default))
    return jnp.where(falsy, alt, res)


def _jcmp(kind: str, val, const: float, canon_eq) -> jnp.ndarray:
    c = np.float32(const)
    if kind == "ge":
        return (val > c) | canon_eq
    if kind == "le":
        return (val < c) | canon_eq
    if kind == "gt":
        return (val > c) & ~canon_eq
    return (val < c) & ~canon_eq


def _eval_scalar_equals(
    ctx: Ctx, pc: PathCollect, v: Any, scope=None, prefix: Tuple[str, ...] = ()
) -> jnp.ndarray:
    scope = scope if scope is not None else Depth0()
    shape = _cond_shape(ctx, scope)
    st = pc.states[0]
    mask = ctx.rows_at(prefix + st.segs)
    b = ctx.b
    t_str = mask & ctx.type_is(T_STR)
    t_num = mask & ctx.type_is(T_NUM)
    t_bool = mask & ctx.type_is(T_BOOL)
    zero_repr = ctx.heq("repr", hash_str("0", tag="s"))
    key_d_valid = t_str & (b["has_dur"] == 1) & ~zero_repr

    if isinstance(v, bool):
        return scope.any(t_bool & (b["bool_val"] == (1 if v else 0)))
    if v is None:
        return jnp.zeros(shape, dtype=bool)
    if isinstance(v, (int, float)):
        num_eq = scope.any(t_num & ctx.heq("num", canon_number(v)))
        dur_eq = scope.any(key_d_valid & ctx.heq("dur", canon_duration(int(float(v) * 1e9))))
        return num_eq | dur_eq
    if isinstance(v, str):
        vd = parse_duration(v) if v != "0" else None
        vq = parse_quantity(v)
        try:
            vf: Optional[float] = float(v)
        except ValueError:
            vf = None
        # string keys (equal.go:70-99): duration pair, then quantity
        # (no fallthrough), then exact/wildcard string compare
        if vd is not None:
            dur_eq_str = key_d_valid & ctx.heq("dur", canon_duration(vd))
            dur_proc_str = key_d_valid
        else:
            dur_eq_str = jnp.zeros_like(mask)
            dur_proc_str = jnp.zeros_like(mask)
        has_q = t_str & (b["has_qty"] == 1)
        qty_eq = (has_q & ctx.heq("qty", canon_quantity(vq))) if vq is not None \
            else jnp.zeros_like(mask)
        exact = ctx.heq("repr", hash_str(v, tag="s"))
        str_eq = jnp.where(
            dur_proc_str, dur_eq_str, jnp.where(has_q, qty_eq, t_str & exact)
        )
        # numeric keys only try float(value) (equal.go _equals_number)
        if vf is not None:
            num_eq = t_num & ctx.heq("num", canon_number(float(vf)))
        else:
            num_eq = jnp.zeros_like(mask)
        return scope.any((t_str & str_eq) | num_eq)
    return jnp.zeros(shape, dtype=bool)


def _eval_scalar_numeric(
    ctx: Ctx, pc: PathCollect, kind: str, v: Any, scope=None, prefix: Tuple[str, ...] = ()
) -> jnp.ndarray:
    scope = scope if scope is not None else Depth0()
    shape = _cond_shape(ctx, scope)
    st = pc.states[0]
    mask = ctx.rows_at(prefix + st.segs)
    b = ctx.b
    t_str = mask & ctx.type_is(T_STR)
    t_num = mask & ctx.type_is(T_NUM)
    zero_repr = ctx.heq("repr", hash_str("0", tag="s"))
    key_d_valid = t_str & (b["has_dur"] == 1) & ~zero_repr
    z = jnp.zeros_like(mask)

    if isinstance(v, bool) or v is None or isinstance(v, (list, dict)):
        return jnp.zeros(shape, dtype=bool)
    if isinstance(v, (int, float)):
        num_cmp = t_num & _jcmp(kind, b["num_val"], float(v), ctx.heq("num", canon_number(v)))
        dur_cmp = key_d_valid & _jcmp(
            kind, b["dur_val"], float(v), ctx.heq("dur", canon_duration(int(float(v) * 1e9))))
        lane_num = t_str & (b["has_num"] == 1) & _jcmp(
            kind, b["num_val"], float(v), ctx.heq("num", canon_number(v)))
        str_cmp = jnp.where(key_d_valid, dur_cmp, lane_num)
        return scope.any(num_cmp | (t_str & str_cmp))
    # v is str
    vd = parse_duration(v) if v != "0" else None
    vq = parse_quantity(v)
    try:
        vf: Optional[float] = float(v)
    except ValueError:
        vf = None
    num_key = z
    if vd is not None:
        num_key = t_num & _jcmp(kind, b["num_val"] * np.float32(1e9), float(vd),
                                ctx.heq("dur", canon_duration(vd)))
    elif vf is not None:
        num_key = t_num & _jcmp(kind, b["num_val"], vf, ctx.heq("num", canon_number(float(vf))))
    # string key trial order: duration pair, quantity, float lane
    dur_b = z
    if vd is not None:
        dur_b = key_d_valid & _jcmp(kind, b["dur_val"], vd / 1e9, ctx.heq("dur", canon_duration(vd)))
    qty_b = z
    if vq is not None:
        qty_b = t_str & (b["has_qty"] == 1) & _jcmp(
            kind, b["qty_val"], float(vq), ctx.heq("qty", canon_quantity(vq)))
    flt_b = z
    if vf is not None:
        flt_b = t_str & (b["has_num"] == 1) & _jcmp(
            kind, b["num_val"], vf, ctx.heq("num", canon_number(float(vf))))
    dur_proc = key_d_valid if vd is not None else z
    qty_proc = (t_str & (b["has_qty"] == 1)) if vq is not None else z
    str_key = jnp.where(dur_proc, dur_b, jnp.where(qty_proc, qty_b, flt_b))
    return scope.any(num_key | str_key)


# ---------------------------------------------------------------------------
# match / exclude program (MatchesResourceDescription, match.go:168)


def _meta_heq(ctx: Ctx, lane: str, s: str, tag: str) -> jnp.ndarray:
    hi, lo = split32(hash_str(s, tag=tag))
    l = ctx.b["meta_" + lane]
    return (l[..., 0] == np.uint32(hi)) & (l[..., 1] == np.uint32(lo))


def _pairs_any(ctx: Ctx, kh_lane: str, vh_lane: str, n_lane: str,
               k: Optional[str], v: Optional[str], ktag: str, vtag: str) -> jnp.ndarray:
    """Any (key, value) pair matching; None key/value = wildcard slot."""
    kh = ctx.b["meta_" + kh_lane]  # (N, L, 2)
    vh = ctx.b["meta_" + vh_lane]
    n = ctx.b["meta_" + n_lane]
    L = kh.shape[1]
    live = jnp.arange(L, dtype=np.int32)[None, :] < n[:, None]
    acc = live
    if k is not None:
        hi, lo = split32(hash_str(k, tag=ktag))
        acc = acc & (kh[..., 0] == np.uint32(hi)) & (kh[..., 1] == np.uint32(lo))
    if v is not None:
        hi, lo = split32(hash_str(v, tag=vtag))
        acc = acc & (vh[..., 0] == np.uint32(hi)) & (vh[..., 1] == np.uint32(lo))
    return acc.any(axis=-1)


def _glob_or_eq(ctx: Ctx, pattern: str, which: str, hash_lane: str, tag: str) -> jnp.ndarray:
    from ..utils.wildcard import contains_wildcard

    if contains_wildcard(pattern):
        return ctx.glob_meta(pattern, which)
    return _meta_heq(ctx, hash_lane, pattern, tag)


def _eval_selector(ctx: Ctx, sel, kh_lane: str, vh_lane: str, n_lane: str) -> jnp.ndarray:
    if sel.invalid:
        return jnp.zeros((ctx.N,), dtype=bool)
    ok = jnp.ones((ctx.N,), dtype=bool)
    for k, v in sel.match_labels:
        ok = ok & _pairs_any(ctx, kh_lane, vh_lane, n_lane, k, v, "lk", "lv")
    for k_pat, v_pat in getattr(sel, "wild_labels", ()):
        # CheckSelector wildcard expansion: a label pair glob-matching
        # (k_pat, v_pat) satisfies the entry. The '0'-substitution
        # fallback pair is subsumed: the glob itself matches its own
        # '0'-substitution, so no separate exact term is needed
        # (wildcards.go:14 ReplaceInSelector)
        ok = ok & _label_glob_pair_any(ctx, n_lane, k_pat, v_pat)
    for key, op, values in sel.expressions:
        if op == "In":
            hit = jnp.zeros((ctx.N,), dtype=bool)
            for v in values:
                hit = hit | _pairs_any(ctx, kh_lane, vh_lane, n_lane, key, v, "lk", "lv")
            ok = ok & hit
        elif op == "NotIn":
            hit = jnp.zeros((ctx.N,), dtype=bool)
            for v in values:
                hit = hit | _pairs_any(ctx, kh_lane, vh_lane, n_lane, key, v, "lk", "lv")
            ok = ok & ~hit
        elif op == "Exists":
            ok = ok & _pairs_any(ctx, kh_lane, vh_lane, n_lane, key, None, "lk", "lv")
        elif op == "DoesNotExist":
            ok = ok & ~_pairs_any(ctx, kh_lane, vh_lane, n_lane, key, None, "lk", "lv")
        else:
            ok = jnp.zeros((ctx.N,), dtype=bool)
    return ok


def _label_glob_pair_any(ctx: Ctx, n_lane: str, k_pat: str, v_pat: str) -> jnp.ndarray:
    """Any live label slot whose KEY bytes glob-match k_pat AND VALUE
    bytes glob-match v_pat (resource label byte lanes). Literal
    patterns degrade to exact byte equality via the same tables."""
    n = ctx.b["meta_" + n_lane]
    k_acc, k_conf = ctx._accept_confirm_cached("glob", k_pat, "labels_kb")
    v_acc, v_conf = ctx._accept_confirm_cached("glob", v_pat, "labels_vb")
    L = k_acc.shape[1]
    live = jnp.arange(L, dtype=np.int32)[None, :] < n[:, None]
    for conf in (k_conf, v_conf):
        if conf is not None:
            ctx.confirm_acc.append((conf & live).any(-1))
    return (k_acc & v_acc & live).any(-1)


def _hash_in_lanes(ctx: Ctx, lane: str, n_lane: str, values: List[str], tag: str) -> jnp.ndarray:
    """Any of the per-resource hash slots equals any of the values."""
    arr = ctx.b["meta_" + lane]  # (N, L, 2)
    n = ctx.b["meta_" + n_lane]
    L = arr.shape[1]
    live = jnp.arange(L, dtype=np.int32)[None, :] < n[:, None]
    acc = jnp.zeros((ctx.N,), dtype=bool)
    for v in values:
        hi, lo = split32(hash_str(v, tag=tag))
        acc = acc | (live & (arr[..., 0] == np.uint32(hi)) & (arr[..., 1] == np.uint32(lo))).any(-1)
    return acc


def _eval_condition_block(ctx: Ctx, f: FilterIR, with_user: bool) -> jnp.ndarray:
    """doesResourceMatchConditionBlock (match.go:52): AND across
    attributes, OR within list attributes."""
    ok = jnp.ones((ctx.N,), dtype=bool)
    if f.operations:
        codes = [OP_CODES.get(o, -1) for o in f.operations]
        # background scans evaluate as CREATE (the scalar engine default)
        eff = jnp.where(ctx.b["meta_op_code"] == 0, np.int32(OP_CODES["CREATE"]),
                        ctx.b["meta_op_code"])
        hit = jnp.zeros((ctx.N,), dtype=bool)
        for c in codes:
            hit = hit | (eff == np.int32(c))
        ok = ok & hit
    if f.kinds:
        hit = jnp.zeros((ctx.N,), dtype=bool)
        for ks in f.kinds:
            p = jnp.ones((ctx.N,), dtype=bool)
            if ks.group != "*":
                p = p & _meta_heq(ctx, "group_h", ks.group, "g")
            if ks.version != "*":
                p = p & _meta_heq(ctx, "version_h", ks.version, "v")
            if ks.kind != "*":
                p = p & _meta_heq(ctx, "kind_h", ks.kind, "K")
            if ks.sub not in ("", "*"):
                p = p & False  # background scans carry no subresource
            hit = hit | p
        ok = ok & hit
    if f.name:
        ok = ok & _glob_or_eq(ctx, f.name, "name", "name_h", "m")
    if f.names:
        hit = jnp.zeros((ctx.N,), dtype=bool)
        for nm in f.names:
            hit = hit | _glob_or_eq(ctx, nm, "name", "name_h", "m")
        ok = ok & hit
    if f.namespaces:
        # Namespace-kind resources compare their name (match.go:18-31)
        is_ns = ctx.b["meta_is_namespace_kind"] == 1
        hit = jnp.zeros((ctx.N,), dtype=bool)
        for ns in f.namespaces:
            by_ns = _glob_or_eq(ctx, ns, "ns", "ns_h", "N")
            by_name = _glob_or_eq(ctx, ns, "name", "name_h", "m")
            hit = hit | jnp.where(is_ns, by_name, by_ns)
        ok = ok & hit
    if f.annotations:
        for k, v in f.annotations:
            ok = ok & _pairs_any(ctx, "ann_kh", "ann_vh", "ann_n", k, v, "ak", "av")
    if f.selector is not None:
        ok = ok & _eval_selector(ctx, f.selector, "labels_kh", "labels_vh", "labels_n")
    if f.ns_selector is not None:
        is_ns = ctx.b["meta_is_namespace_kind"] == 1
        sel_ok = _eval_selector(ctx, f.ns_selector, "nsl_kh", "nsl_vh", "nsl_n")
        ok = ok & ~is_ns & sel_ok
    if with_user:
        if f.roles:
            ok = ok & _hash_in_lanes(ctx, "roles_h", "roles_n", f.roles, "r")
        if f.cluster_roles:
            ok = ok & _hash_in_lanes(ctx, "croles_h", "croles_n", f.cluster_roles, "r")
        if f.subjects:
            hit = jnp.zeros((ctx.N,), dtype=bool)
            for s in f.subjects:
                kind, name = s.get("kind"), s.get("name", "")
                if kind == "ServiceAccount":
                    uname = f"system:serviceaccount:{s.get('namespace', '')}:{name}"
                    hit = hit | _meta_heq(ctx, "user_h", uname, "u")
                elif kind == "User":
                    hit = hit | _meta_heq(ctx, "user_h", name, "u")
                else:  # Group
                    hit = hit | _hash_in_lanes(ctx, "groups_h", "groups_n", [name], "u")
            ok = ok & hit
    return ok


def _eval_match_filter(ctx: Ctx, f: FilterIR) -> jnp.ndarray:
    """_match_helper (match.go:253): empty-admission requests drop user
    constraints; fully-empty filters never match."""
    adm_empty = ctx.b["meta_admission_empty"] == 1
    with_user = _eval_condition_block(ctx, f, with_user=True)
    without_user = _eval_condition_block(ctx, f, with_user=False)
    empty_bg = f.resources_empty           # user dropped => match cannot be empty
    empty_adm = f.resources_empty and f.user_empty
    bg = jnp.zeros((ctx.N,), dtype=bool) if empty_bg else without_user
    adm = jnp.zeros((ctx.N,), dtype=bool) if empty_adm else with_user
    return jnp.where(adm_empty, bg, adm)


def _eval_exclude_filter(ctx: Ctx, f: FilterIR) -> jnp.ndarray:
    """_exclude_helper (match.go:278): empty excludes nothing; user
    constraints always evaluated (empty admission naturally fails them)."""
    if f.resources_empty and f.user_empty:
        return jnp.zeros((ctx.N,), dtype=bool)
    return _eval_condition_block(ctx, f, with_user=True)


def eval_match(ctx: Ctx, match: MatchIR, exclude: MatchIR, policy_ns: str) -> jnp.ndarray:
    if match.mode == "any":
        m = jnp.zeros((ctx.N,), dtype=bool)
        for f in match.filters:
            m = m | _eval_match_filter(ctx, f)
    else:  # all | legacy
        m = jnp.ones((ctx.N,), dtype=bool)
        for f in match.filters:
            m = m & _eval_match_filter(ctx, f)
    if policy_ns:
        m = m & _meta_heq(ctx, "ns_h", policy_ns, "N")
    if exclude.mode == "any":
        e = jnp.zeros((ctx.N,), dtype=bool)
        for f in exclude.filters:
            e = e | _eval_exclude_filter(ctx, f)
    elif exclude.mode == "all":
        e = jnp.ones((ctx.N,), dtype=bool)
        for f in exclude.filters:
            e = e & _eval_exclude_filter(ctx, f)
    else:
        e = _eval_exclude_filter(ctx, exclude.filters[0])
    return m & ~e


# ---------------------------------------------------------------------------
# rule & policy-set assembly


def _membership_glob_states(prog: RuleProgram) -> List[Tuple[Tuple[str, ...], PathState]]:
    """All (prefix, state) value/key cells that membership operators
    compare against literal sets. Resource strings containing */? at
    those cells wildcard-match in BOTH directions (conditions.py
    _wild_either) — hash equality cannot reproduce that, so such
    resources take the host path."""
    out: List[Tuple[Tuple[str, ...], PathState]] = []

    def add(collect, prefixes) -> None:
        for pfx in prefixes:
            for st in collect.states:
                out.append((pfx, st))
                if not collect.is_projection and st.mode == "value":
                    # scalar-chain keys also compare array elements
                    out.append((pfx, PathState(st.segs + (ARRAY_SEG,), "value")))

    def from_tree(tree: Optional[CondTreeIR], eprefixes) -> None:
        if tree is None:
            return
        for any_list, all_list in tree.blocks:
            for c in any_list + all_list:
                if _op_canon(c.op) not in _IN_MODES:
                    continue
                for side in (c.key, c.value):
                    if isinstance(side, ElementCollect):
                        add(side, eprefixes)
                    elif isinstance(side, PathCollect):
                        add(side, ((),))

    from_tree(prog.preconditions, ((),))
    from_tree(prog.deny, ((),))
    for fe in prog.foreach:
        prefixes = tuple(arr + (ARRAY_SEG,) for arr in fe.arrays)
        from_tree(fe.tree, prefixes)
    return out


def _glob_fallback(ctx: Ctx, prog: RuleProgram) -> jnp.ndarray:
    acc = jnp.zeros((ctx.N,), dtype=bool)
    for pfx, st in _membership_glob_states(prog):
        if st.mode == "keys":
            m = ctx.rows_with_parent(pfx + st.segs) & (ctx.b["key_glob"] == 1)
        else:
            m = ctx.rows_at(pfx + st.segs) & (ctx.b["has_glob"] == 1)
        acc = acc | m.any(axis=-1)
    return acc


def _eval_foreach_deny(
    ctx: Ctx, prog: RuleProgram
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """validate.foreach with deny bodies (validate_resource.go:163-233):
    elements iterate in list order; the first denied element fails the
    rule, a condition error errors it, zero applied (non-null) elements
    skips it. Returns (cls, err, host) each (N,)."""
    inst = InstScope(ctx)
    decided = jnp.zeros((ctx.N,), dtype=bool)
    failed = jnp.zeros((ctx.N,), dtype=bool)
    errored = jnp.zeros((ctx.N,), dtype=bool)
    applied = jnp.zeros((ctx.N,), dtype=bool)
    host = jnp.zeros((ctx.N,), dtype=bool)
    for fe in prog.foreach:
        for arr in fe.arrays:
            arr_rows = ctx.rows_at(arr)
            elem_rows = ctx.rows_at(arr + (ARRAY_SEG,))
            # scalar iterates dict lists as {key,value} pairs and errors
            # on scalar lists; flatten would splice array elements — all
            # shapes the row encoding can't see, so those go host
            bad = arr_rows & ~ctx.type_is(T_ARR) & ~ctx.type_is(T_NULL)
            bad = bad | (elem_rows & ctx.type_is(T_ARR))
            host = host | bad.any(axis=-1)
            nonnull_i = inst.any(elem_rows & ~ctx.type_is(T_NULL))  # (N, I)
            den_i, err_i = eval_cond_tree(ctx, fe.tree, inst, arr + (ARRAY_SEG,))
            den_i = den_i & nonnull_i
            err_i = err_i & nonnull_i
            # first-event ordering along the element axis: an error at
            # element j <= i pre-empts a deny at i (evaluate_conditions
            # raises before returning); a deny at j < i pre-empts an
            # error at i (the loop returned already)
            err_int = err_i.astype(jnp.int32)
            den_int = den_i.astype(jnp.int32)
            err_incl = jnp.cumsum(err_int, axis=-1) > 0
            den_excl = (jnp.cumsum(den_int, axis=-1) - den_int) > 0
            deny_event = (den_i & ~err_incl).any(axis=-1)
            err_event = (err_i & ~den_excl).any(axis=-1)
            failed = failed | (~decided & deny_event)
            errored = errored | (~decided & err_event)
            decided = decided | deny_event | err_event
            applied = applied | nonnull_i.any(axis=-1)
    cls = jnp.where(failed, FAIL, jnp.where(applied, PASS, SKIP))
    return cls, errored, host


# ---------------------------------------------------------------------------
# validate.cel (the matches() subset, ir.compile_cel_validation)


def _eval_cel_node(ctx: Ctx, node: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """CEL three-valued evaluation over (N,) lanes: (val, err) with the
    invariant val is False wherever err — mirroring cel/interp.py
    semantics for the lowered subset, including &&/|| error absorption
    (_logic: a determined operand absorbs the other side's error)."""
    shape = (ctx.N,)
    if isinstance(node, CelConst):
        return jnp.full(shape, node.value, dtype=bool), \
            jnp.zeros(shape, dtype=bool)
    if isinstance(node, CelNot):
        v, e = _eval_cel_node(ctx, node.sub)
        return ~v & ~e, e
    if isinstance(node, CelAnd):
        lv, le = _eval_cel_node(ctx, node.left)
        rv, re_ = _eval_cel_node(ctx, node.right)
        lfalse = ~lv & ~le
        rfalse = ~rv & ~re_
        return lv & rv, (le | re_) & ~lfalse & ~rfalse
    if isinstance(node, CelOr):
        lv, le = _eval_cel_node(ctx, node.left)
        rv, re_ = _eval_cel_node(ctx, node.right)
        val = lv | rv
        return val, (le | re_) & ~val
    if isinstance(node, CelStrCmp):
        mask = ctx.rows_at(node.path)
        exists = mask.any(axis=-1)
        eq = (mask & ctx.type_is(T_STR)
              & ctx.heq("repr", hash_str(node.value, tag="s"))).any(axis=-1)
        # select on a missing path is no_such_field; heterogeneous
        # equality on a present non-string is false, never an error
        err = ~exists
        val = (exists & ~eq) if node.negate else eq
        return val & ~err, err
    if isinstance(node, CelHas):
        prows = ctx.rows_at(node.parent)
        is_map = (prows & ctx.type_is(T_MAP)).any(axis=-1)
        child = ctx.rows_at(node.parent + (node.fld,)).any(axis=-1)
        # has() on a missing/non-map target is a CEL error
        return is_map & child, ~is_map
    if isinstance(node, CelMatches):
        mask = ctx.rows_at(node.path)
        str_rows = mask & ctx.type_is(T_STR)
        is_str = str_rows.any(axis=-1)
        got = ctx._bank_lookup("re2", node.regex, "pool")
        if got is None:
            # compiled without a bank (legacy build_program callers):
            # the whole cell resolves on the host
            ctx.host_acc.append(jnp.ones(shape, dtype=bool))
            return jnp.zeros(shape, dtype=bool), jnp.zeros(shape, dtype=bool)
        acc, conf = ctx._accept_confirm_cached("re2", node.regex, "pool")
        hit = (str_rows & ctx._rows_from_pool(acc)).any(axis=-1)
        if conf is not None:
            ctx.confirm_acc.append(
                (str_rows & ctx._rows_from_pool(conf)).any(axis=-1))
        # matches() on a non-string / missing target is a CEL error
        err = ~is_str
        return hit & ~err, err
    raise Unsupported(f"cel IR node {type(node).__name__}")


def _eval_cel_rule(ctx: Ctx, prog: RuleProgram
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All expressions must hold; any expression error is a rule ERROR
    (engine._validate_cel orders errors before fails). DELETE
    admissions divert per cell to the host — the skip-on-delete guard
    depends on request state the lanes don't carry."""
    from .metadata import OP_CODES as _OPS

    ok = jnp.ones((ctx.N,), dtype=bool)
    err = jnp.zeros((ctx.N,), dtype=bool)
    for node in prog.cel:
        v, e = _eval_cel_node(ctx, node)
        ok = ok & v
        err = err | e
    ctx.host_acc.append(
        ctx.b["meta_op_code"] == np.int32(_OPS["DELETE"]))
    return jnp.where(ok, PASS, FAIL), err


def eval_rule(ctx: Ctx, prog: RuleProgram) -> jnp.ndarray:
    ctx.host_acc = []
    ctx.confirm_acc = []
    matched = eval_match(ctx, prog.match, prog.exclude, prog.policy_namespace)
    pre_ok, pre_err = eval_cond_tree(ctx, prog.preconditions)
    host_extra = jnp.zeros((ctx.N,), dtype=bool)
    if prog.kind == "deny":
        denied, deny_err = eval_cond_tree(ctx, prog.deny)
        cls = jnp.where(denied, FAIL, PASS)
        err = deny_err
    elif prog.kind == "pattern":
        cls = eval_node(ctx, Depth0(), prog.patterns[0])
        err = jnp.zeros((ctx.N,), dtype=bool)
    elif prog.kind == "foreach_deny":
        cls, err, host_extra = _eval_foreach_deny(ctx, prog)
    elif prog.kind == "cel":
        cls, err = _eval_cel_rule(ctx, prog)
    else:  # any_pattern (validate_resource.go:382)
        classes = [eval_node(ctx, Depth0(), p) for p in prog.patterns]
        any_pass = functools.reduce(jnp.logical_or, [c == PASS for c in classes])
        any_skip = functools.reduce(jnp.logical_or, [c == SKIP for c in classes])
        any_fail = functools.reduce(jnp.logical_or, [c == FAIL for c in classes])
        cls = jnp.where(any_pass, PASS, jnp.where(any_skip & ~any_fail, SKIP, FAIL))
        err = jnp.zeros((ctx.N,), dtype=bool)
    verdict = jnp.where(err, ERROR, cls)
    verdict = jnp.where(pre_err, ERROR, jnp.where(pre_ok, verdict, SKIP))
    verdict = jnp.where(matched, verdict, NOT_MATCHED)
    # pattern confirmation (tpu/dfa.py ladder): cells whose pattern
    # verdict is a maybe resolve via the scalar oracle like host cells,
    # but are attributed separately (miss = definitive, hit = confirm)
    if ctx.confirm_acc:
        confirm = functools.reduce(jnp.logical_or, ctx.confirm_acc)
        verdict = jnp.where(confirm, CONFIRM, verdict)
    fallback = (ctx.b["fallback"] == 1) | (ctx.b["meta_fallback"] == 1)
    fallback = fallback | host_extra | _glob_fallback(ctx, prog)
    for h in ctx.host_acc:
        fallback = fallback | h
    return jnp.where(fallback, HOST, verdict)


def build_program(programs: Sequence[RuleProgram], max_instances: int,
                  with_counts: bool = False,
                  dfa: Optional[DfaBank] = None) -> Callable:
    """Returns a jittable fn(batch dict) -> (num_rules, N) int32, or —
    with ``with_counts`` — (table, (num_rules, NUM_VERDICT_CLASSES)
    int32): the per-rule verdict reduction folded into the compiled
    program, so rule analytics ride the dispatch as an O(rules)
    readback instead of an O(rules x resources) host walk (the
    reduction over the batch axis is a handful of fused compares —
    noise next to rule evaluation itself)."""

    def run(batch: Dict[str, jnp.ndarray]):
        ctx = Ctx(densify(batch), max_instances, dfa=dfa)
        outs = [eval_rule(ctx, p) for p in programs]
        if not outs:
            table = jnp.zeros((0, ctx.N), dtype=jnp.int32)
        else:
            table = jnp.stack(outs, axis=0).astype(jnp.int32)
        if not with_counts:
            return table
        counts = jnp.stack(
            [(table == c).sum(axis=1) for c in range(NUM_VERDICT_CLASSES)],
            axis=-1).astype(jnp.int32)
        return table, counts

    return run
