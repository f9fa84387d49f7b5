"""TpuEngine — batch policy evaluation over the device plane.

The scan-path equivalent of the reference's reports-controller hot loop
(pkg/controllers/report/background/controller.go:299 reconcileReport ->
engine.Validate per policy): encode the resource snapshot once, then
evaluate the full policy x resource cross-product as one device
program. Rules the IR compiler cannot lower (RuleEntry.fallback_reason)
and resources exceeding encode caps are completed with the scalar
engine, so results always cover everything.

Verdict codes follow evaluator.py: 0 PASS, 1 SKIP, 2 FAIL,
3 NOT_MATCHED, 4 ERROR (5 HOST and 6 CONFIRM never escape — both are
resolved here; CONFIRM is the pattern-confirmation sub-batch from the
approximate-DFA ladder, counted as device work in coverage terms
because only the rare hits pay it).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..api.policy import ClusterPolicy
from ..engine.engine import Engine as ScalarEngine
from ..engine.match import RequestInfo
from ..engine.policycontext import (PolicyContext, build_scan_context,
                                    scan_json_context as _scan_json_context)
from ..engine.response import EngineResponse
from ..observability.analytics import (NUM_CLASSES, RuleIdent, class_counts,
                                       global_pattern_cells,
                                       global_rule_stats, global_starvation)
from ..observability.profiling import (PATH_DEVICE, PATH_SCALAR_FALLBACK,
                                       PHASE_DISPATCH, PHASE_ENCODE,
                                       PHASE_HOST_COMPLETE, PHASE_READBACK,
                                       global_profiler, maybe_xla_trace,
                                       set_dispatch_path)
from ..observability.tracing import global_tracer
from ..devtools import sanitizer as _sanitizer
from ..resilience.faults import (SITE_MUTATE_TRIAGE, SITE_TPU_DISPATCH,
                                 global_faults)
from .compiler import CompiledPolicySet, compile_policy_set
from .evaluator import (CONFIRM, ERROR, FAIL, HOST, NOT_MATCHED,
                        NUM_VERDICT_CLASSES, PASS, SKIP, batch_to_host,
                        pack_lanes)
from .flatten import EncodeConfig, encode_resources
from .metadata import MetaConfig, encode_metadata

VERDICT_NAMES = {PASS: "pass", SKIP: "skip", FAIL: "fail",
                 NOT_MATCHED: "not_matched", ERROR: "error"}


class DeviceResultError(RuntimeError):
    """The device program returned a wrong-shaped/typed verdict table —
    treated exactly like a dispatch failure (breaker + scalar fallback),
    never silently consumed as truth."""

_STATUS_TO_CODE = {"pass": PASS, "skip": SKIP, "fail": FAIL, "error": ERROR}


@dataclass
class ScanResult:
    """(num_rules_total, N) verdict table + rule metadata."""

    verdicts: np.ndarray
    rules: List[Tuple[str, str]]  # (policy_name, rule_name) per row

    def counts(self) -> Dict[str, int]:
        out = {name: int((self.verdicts == code).sum()) for code, name in VERDICT_NAMES.items()}
        return out

    def violations(self) -> List[Tuple[int, int]]:
        """(rule_row, resource_idx) pairs with FAIL verdicts."""
        rows, cols = np.nonzero(self.verdicts == FAIL)
        return list(zip(rows.tolist(), cols.tolist()))


@dataclass
class MutateTriageResult:
    """(num_mutate_rules, N) needs-mutation verdict table in compiled
    bank order. PASS/FAIL = rule applies; SKIP/NOT_MATCHED = it does
    not; ERROR/HOST/CONFIRM = undecidable on device, the coordinator
    routes the policy to the scalar patcher."""

    verdicts: np.ndarray
    rules: List[Tuple[str, str]]  # (policy_name, rule_name) per row

    def rows_for(self, ci: int) -> List[Tuple[Tuple[str, str], int]]:
        """One resource's bank-ordered ((policy, rule), code) rows —
        the coordinator's input shape."""
        return [(ident, int(self.verdicts[mi, ci]))
                for mi, ident in enumerate(self.rules)]

    def counts(self) -> Dict[str, int]:
        pos = int(((self.verdicts == PASS) | (self.verdicts == FAIL)).sum())
        neg = int(((self.verdicts == SKIP)
                   | (self.verdicts == NOT_MATCHED)).sum())
        return {"positive": pos, "negative": neg,
                "host": int(self.verdicts.size) - pos - neg}


def _scalar_rule_verdicts(
    engine: ScalarEngine, policy: ClusterPolicy, pctx: PolicyContext
) -> Dict[str, int]:
    """Run the scalar engine for one (policy, resource); map each
    validate rule to a verdict code (absent response = not matched)."""
    response: EngineResponse = engine.validate(pctx)
    got = {rr.name: _STATUS_TO_CODE.get(rr.status, ERROR) for rr in response.policy_response.rules}
    out: Dict[str, int] = {}
    for rule in policy.get_rules():
        if rule.has_validate():
            out[rule.name] = got.get(rule.name, NOT_MATCHED)
    return out


def _walk_values(node, segs, i=0):
    """Yield the values at a PathState segment chain over a raw
    resource dict (ARRAY_SEG iterates list elements)."""
    from .hashing import ARRAY_SEG

    if i == len(segs):
        yield node
        return
    seg = segs[i]
    if seg == ARRAY_SEG:
        if isinstance(node, list):
            for el in node:
                yield from _walk_values(el, segs, i + 1)
    elif isinstance(node, dict) and seg in node:
        yield from _walk_values(node[seg], segs, i + 1)


class TpuEngine:
    """Compile once, scan many — the device-backed engineapi.Engine
    slice for background scans and CLI apply."""

    def __init__(
        self,
        policies: Sequence[ClusterPolicy] = (),
        encode_cfg: Optional[EncodeConfig] = None,
        meta_cfg: Optional[MetaConfig] = None,
        cps: Optional[CompiledPolicySet] = None,
        exceptions: Sequence[Any] = (),
        data_sources=None,
        breaker=None,
    ):
        self.cps: CompiledPolicySet = cps if cps is not None \
            else compile_policy_set(policies, encode_cfg, meta_cfg, data_sources)
        self.data_sources = data_sources  # runtime dyn-operand loading
        # device errors are device-wide, so engines share the process
        # breaker by default (engines churn with policy revisions)
        if breaker is None:
            from ..resilience.breaker import tpu_breaker

            breaker = tpu_breaker()
        self.breaker = breaker
        self.scalar = ScalarEngine(exceptions=list(exceptions),
                                   background=True,
                                   data_sources=data_sources)
        # rules named by any PolicyException evaluate on the host: the
        # exception's match/conditions are per-resource dynamic state
        # the compiled program does not model (engine/exceptions.go)
        self._exception_rules: set = set()
        # same host routing for the mutate triage bank: an excepted
        # mutate rule's apply decision is per-resource dynamic state
        self._exception_mutate_rules: set = set()
        if exceptions:
            from ..api.exception import PolicyException

            typed = [e if isinstance(e, PolicyException)
                     else PolicyException.from_dict(e) for e in exceptions]
            for ri, entry in enumerate(self.cps.rules):
                if any(t.contains(entry.policy_name, entry.rule_name)
                       for t in typed):
                    self._exception_rules.add(ri)
            for mi, entry in enumerate(self.cps.mutate_entries):
                if any(t.contains(entry.policy_name, entry.rule_name)
                       for t in typed):
                    self._exception_mutate_rules.add(mi)
        # verdict-cache identity (tpu/cache.py): exceptions change
        # verdicts without changing the compiled set, so they join the
        # policy-set content key
        from .cache import digest as _digest

        self._exceptions_digest = _digest(
            [e if isinstance(e, dict) else getattr(e, "raw", None) or repr(e)
             for e in exceptions]) if exceptions else ""
        self._cache_ident: Optional[str] = None
        self._cache_eligible: Optional[bool] = None
        self._mutate_cache_eligible: Optional[bool] = None
        self._encode_cache_key: Optional[str] = None
        # encoder-pool profile for the rows feed, registered lazily per
        # pool instance (a reconfigured pool gets a fresh profile)
        self._pool_profile: Optional[Tuple[Any, int]] = None
        # policy observatory: per-rule analytics identities + the
        # thread-local slot the device-side verdict-count reduction
        # rides from dispatch to assemble (thread-local because one
        # engine may serve the flusher thread and a scan thread)
        self._rule_idents: Optional[List[RuleIdent]] = None
        self._tls = threading.local()
        try:
            global_rule_stats.register(self.rule_idents())
        except Exception:
            pass  # analytics must never block engine construction
        self.cps.publish_dfa_gauges()

    @classmethod
    def from_compiled(cls, cps: CompiledPolicySet) -> "TpuEngine":
        return cls(cps=cps)

    # -- encoding

    DYN_LIST_L = 32  # padded list-operand lanes per slot

    def encode(
        self,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
    ):
        rows = self._encode_rows(resources)
        meta = encode_metadata(resources, namespace_labels, operations,
                               admission_infos, self.cps.meta_cfg)
        batch = batch_to_host(rows, meta)
        if self.cps.dyn_slots:
            batch.update(self._encode_dyn_lanes(resources, operations,
                                                admission_infos))
        return batch, rows, meta

    def _encode_rows(self, resources: Sequence[Dict[str, Any]]):
        """Row encoding through the content-addressed encode cache: an
        unchanged resource's lane rows restore from the LRU instead of
        re-walking the JSON tree. Keyed by encode config + compiled
        byte-path sets, NOT policy content — a policy-set revision bump
        keeps every entry warm (the verdict cache misses, this one
        doesn't).

        With an encoder pool configured, cache MISSES encode on a
        worker process (the serving batcher's feed rides the same
        supervised ladder as the scan feed), and the pooled results
        populate the shared cache — warm rows never re-enter the pool.
        A pool bypass/infra failure falls back to in-process encode; a
        poison resource (crashes two workers, bisected) comes back
        flagged and is marked for host fallback exactly like an
        encode-cap overflow — the scalar oracle answers its column."""
        from ..cluster.columnar import get_store
        from .cache import (EncodeRowCache, apply_rows_multi, extract_rows,
                            global_encode_cache, resource_content_hash)
        from .flatten import RowBatch

        ec = global_encode_cache
        store = get_store()
        if not ec.enabled and store is None:
            return encode_resources(resources, self.cps.encode_cfg,
                                    self.cps.byte_paths,
                                    self.cps.key_byte_paths)
        if self._encode_cache_key is None:
            self._encode_cache_key = EncodeRowCache.encode_key(
                self.cps.encode_cfg, self.cps.byte_paths,
                self.cps.key_byte_paths)
        batch = RowBatch(len(resources), self.cps.encode_cfg)
        misses: List[Tuple[int, Optional[Tuple[str, str]]]] = []
        hit_entries: List[Any] = []
        hit_idx: List[int] = []
        for i, res in enumerate(resources):
            h = resource_content_hash(res)
            key = (self._encode_cache_key, h) if h is not None else None
            entry = (ec.get_entry(key)
                     if key is not None and ec.enabled else None)
            if entry is None and key is not None and store is not None:
                # columnar tier under the LRU: rows another engine (or
                # the scan loop, or a prior process via mmap) encoded
                entry = store.get_entry(self._encode_cache_key, h)
            if entry is None:
                misses.append((i, key))
            else:
                hit_entries.append(entry)
                hit_idx.append(i)
        # ALL hits land in one vectorized fancy-index scatter per lane
        # (apply_rows_multi) instead of a per-resource Python loop
        apply_rows_multi(hit_entries, batch, hit_idx)
        if misses and self._encode_rows_pooled(resources, batch, misses, ec):
            return batch
        if misses:
            sub = encode_resources([resources[i] for i, _ in misses],
                                   self.cps.encode_cfg, self.cps.byte_paths,
                                   self.cps.key_byte_paths)
            sub_arrays = sub.arrays()
            batch_arrays = batch.arrays()
            for j, (i, key) in enumerate(misses):
                for name, arr in sub_arrays.items():
                    batch_arrays[name][i] = arr[j]
                if key is not None:
                    entry = extract_rows(sub, j)
                    ec.put_entry(key, entry)
                    if store is not None:
                        store.put_entry(self.cps.encode_cfg,
                                        self.cps.byte_paths,
                                        self.cps.key_byte_paths,
                                        key[1], entry)
        return batch

    # pooling a miss set smaller than this costs more in IPC round-trip
    # than the in-process encode it replaces (the admission path is
    # latency-sensitive; a near-warm cache leaves 1-2 misses per flush)
    POOL_ROWS_MIN = 4

    def _encode_rows_pooled(self, resources, batch, misses, ec) -> bool:
        """Encode the cache misses on the encoder pool; True when the
        batch rows were filled (False -> caller encodes in-process)."""
        if len(misses) < self.POOL_ROWS_MIN:
            return False
        from ..encode import (KIND_ROWS, PoolBypassed, PoolInfraError,
                              WorkerEncodeError, get_pool, profile_spec)
        from .cache import apply_rows

        pool = get_pool()
        if pool is None or not pool.running:
            return False
        try:
            if (self._pool_profile is None
                    or self._pool_profile[0] is not pool):
                self._pool_profile = (pool, pool.register_profile(
                    profile_spec(self.cps.encode_cfg,
                                 byte_paths=self.cps.byte_paths,
                                 key_byte_paths=self.cps.key_byte_paths)))
            out = pool.encode_chunk(
                self._pool_profile[1], KIND_ROWS,
                {"resources": [resources[i] for i, _ in misses]})
        except (PoolBypassed, PoolInfraError, WorkerEncodeError):
            # breaker open / infra out -> in-process path; a worker-
            # REPORTED encode error re-raises in-process too, where the
            # existing quarantine ladder owns it
            return False
        from ..cluster.columnar import get_store

        store = get_store()
        poison = set(out.get("poison") or ())
        for j, (i, key) in enumerate(misses):
            if j in poison:
                # quarantined: empty lanes + the fallback flag route
                # this column to the scalar oracle (HOST), and its
                # placeholder rows never enter the cache
                batch.fallback[i] = 1
                continue
            entry = out["rows"][j]
            apply_rows(entry, batch, i)
            if key is not None:
                ec.put_entry(key, entry)
                if store is not None:
                    # pooled results are system-of-record rows too: the
                    # next scan gathers them instead of re-encoding
                    store.put_entry(self.cps.encode_cfg,
                                    self.cps.byte_paths,
                                    self.cps.key_byte_paths,
                                    key[1], entry)
        return True

    def _encode_dyn_lanes(self, resources, operations, admission_infos):
        """Host-resolved context operands (SURVEY §7 context-dependent
        rules): per (slot, resource), load the slot's context entries
        through the REAL loaders (apiCall/configMap I/O included,
        exactly the scalar engine's path) and encode the queried value
        as canonical lanes the device program compares against.
        Load results cache on the substituted entry spec, so
        request-independent entries (static urlPaths, configMaps)
        resolve once per batch."""
        S, N, L = len(self.cps.dyn_slots), len(resources), self.DYN_LIST_L
        lanes = {
            # type: 0=load-error 1=null 2=bool 3=num 4=str 5=list 6=other
            "dyn_type": np.zeros((S, N), np.int8),
            "dyn_bool": np.zeros((S, N), np.int8),
            # 0/1 = the value coerces to that bool ("true"/"false"
            # strings included, equal.go), 2 = no bool coercion
            "dyn_as_bool": np.full((S, N), 2, np.int8),
            "dyn_num": np.zeros((S, N), np.float32),
            "dyn_has_num": np.zeros((S, N), np.int8),
            # canonical number hash (rows carry canon hashes, not floats)
            "dyn_num_h": np.zeros((S, N, 2), np.uint32),
            "dyn_sprint": np.zeros((S, N, 2), np.uint32),
            "dyn_list_h": np.zeros((S, N, L, 2), np.uint32),
            "dyn_list_n": np.zeros((S, N), np.int32),
            # string value that decodes as a JSON string-array
            "dyn_json_list": np.zeros((S, N), np.int8),
            # host-completion flag: list overflow, glob/unit-bearing
            # values, or glob-bearing guarded resource values —
            # anything hash lanes can't compare the way the oracle does
            "dyn_host": np.zeros((S, N), np.int8),
        }
        cache: Dict[Any, Tuple[bool, Any]] = {}
        # scope backend-failure poisoning to this batch: a dead backend
        # costs ONE retry budget here, not one per (slot, resource)
        begin_batch = getattr(self.data_sources, "begin_batch", None)
        if begin_batch is not None:
            begin_batch()
        try:
            return self._encode_dyn_cells(resources, operations,
                                          admission_infos, lanes, cache)
        finally:
            end_batch = getattr(self.data_sources, "end_batch", None)
            if end_batch is not None:
                end_batch()

    def _encode_dyn_cells(self, resources, operations, admission_infos,
                          lanes, cache):
        import json as _json

        from ..engine.contextloaders import load_context_entries
        from ..utils.wildcard import contains_wildcard

        L = self.DYN_LIST_L
        for ci, res in enumerate(resources):
            op = (operations[ci] if operations else "") or ""
            info = admission_infos[ci] if admission_infos else None
            # ONE context build per resource (image extraction is the
            # expensive part); every slot loads into a shallow fork so
            # entries one slot resolves never leak into another slot's
            # substitution or query
            base_ctx = _scan_json_context(res, op, info)
            for si, slot in enumerate(self.cps.dyn_slots):
                ctx = base_ctx.shallow_fork()
                key = None
                try:
                    from ..engine.variables import substitute_all

                    key = (si, _json.dumps(
                        substitute_all(ctx, slot.entries), sort_keys=True,
                        default=str))
                except Exception:  # noqa: BLE001
                    key = None  # request-dependent substitution failed
                if key is not None and key in cache:
                    ok, val = cache[key]
                else:
                    try:
                        load_context_entries(ctx, slot.entries,
                                             self.data_sources)
                        val = ctx.query(slot.query)
                        ok = True
                    except Exception:  # noqa: BLE001
                        ok, val = False, None
                    if key is not None:
                        cache[key] = (ok, val)
                if not ok:
                    lanes["dyn_type"][si, ci] = 0
                    continue
                self._fill_dyn_value(lanes, si, ci, val, L)
                # guarded resource paths: glob-bearing string values
                # defeat hash membership -> host completes the cell
                for segs in slot.guard_paths:
                    for v in _walk_values(res, segs):
                        if isinstance(v, str) and contains_wildcard(v):
                            lanes["dyn_host"][si, ci] = 1
        return lanes

    @staticmethod
    def _fill_dyn_value(lanes, si, ci, val, L):
        from ..engine.pattern import go_parse_float
        from ..utils.duration import parse_duration
        from ..utils.quantity import parse_quantity
        from ..utils.wildcard import contains_wildcard
        from .flatten import go_sprint
        from .hashing import canon_number, hash_str, split32

        if isinstance(val, bool):
            lanes["dyn_type"][si, ci] = 2
            lanes["dyn_bool"][si, ci] = 1 if val else 0
            lanes["dyn_as_bool"][si, ci] = 1 if val else 0
        elif isinstance(val, (int, float)):
            lanes["dyn_type"][si, ci] = 3
            lanes["dyn_num"][si, ci] = float(val)
            lanes["dyn_has_num"][si, ci] = 1
            lanes["dyn_num_h"][si, ci] = split32(canon_number(val))
        elif isinstance(val, str):
            lanes["dyn_type"][si, ci] = 4
            lanes["dyn_sprint"][si, ci] = split32(hash_str(val, tag="s"))
            if val in ("true", "false"):
                lanes["dyn_as_bool"][si, ci] = 1 if val == "true" else 0
            f = go_parse_float(val)
            if f is not None:
                lanes["dyn_num"][si, ci] = f
                lanes["dyn_has_num"][si, ci] = 1
                lanes["dyn_num_h"][si, ci] = split32(canon_number(f))
            # globs act as patterns, unit strings coerce, and range
            # expressions compare structurally in the oracle — hash
            # equality can't see any of those
            from ..engine.operator import (Operator,
                                           get_operator_from_string_pattern)

            if contains_wildcard(val):
                lanes["dyn_host"][si, ci] = 1
            if (val != "0" and parse_duration(val) is not None) or \
                    (f is None and parse_quantity(val) is not None):
                lanes["dyn_host"][si, ci] = 1
            if get_operator_from_string_pattern(val) in (
                    Operator.IN_RANGE, Operator.NOT_IN_RANGE):
                lanes["dyn_host"][si, ci] = 1
            # a valid-JSON string-array value decodes for membership
            # (in.go keyExistsInArray / anyin.go _value_as_string_list)
            from ..engine.conditions import _value_as_string_list

            arr = _value_as_string_list(val)
            if arr is not None:
                lanes["dyn_json_list"][si, ci] = 1
                if len(arr) > L:
                    lanes["dyn_host"][si, ci] = 1
                n = 0
                for v in arr[:L]:
                    if contains_wildcard(v):
                        lanes["dyn_host"][si, ci] = 1
                    lanes["dyn_list_h"][si, ci, n] = split32(
                        hash_str(v, tag="s"))
                    n += 1
                lanes["dyn_list_n"][si, ci] = n
        elif val is None:
            lanes["dyn_type"][si, ci] = 1
        elif isinstance(val, list):
            lanes["dyn_type"][si, ci] = 5
            if len(val) > L:
                lanes["dyn_host"][si, ci] = 1
            n = 0
            for v in val[:L]:
                s = go_sprint(v)
                if s is None:
                    lanes["dyn_host"][si, ci] = 1
                    continue
                if contains_wildcard(s):
                    lanes["dyn_host"][si, ci] = 1
                lanes["dyn_list_h"][si, ci, n] = split32(hash_str(s, tag="s"))
                n += 1
            lanes["dyn_list_n"][si, ci] = n
        else:
            lanes["dyn_type"][si, ci] = 6

    # -- evaluation

    # batch sizes bucket to powers of two so arbitrary N never triggers
    # unbounded XLA recompiles (SURVEY §7 "recompilation churn": the
    # jit cache is keyed by shape; bucketing caps it at ~log2 shapes)
    MIN_BUCKET = 16

    def bucket_size(self, n: int) -> int:
        b = self.MIN_BUCKET
        while b < n:
            b *= 2
        return b

    # -- rule analytics (observability/analytics.py)

    def rule_idents(self) -> List[RuleIdent]:
        """Per-rule analytics identities aligned with cps.rules rows:
        (policy spec hash, names, on-device placement). Exception-named
        rules report as host — that is where their verdicts resolve."""
        if self._rule_idents is None:
            hashes = self.cps.policy_spec_hashes()
            self._rule_idents = [
                RuleIdent(policy_hash=hashes[e.policy_idx],
                          policy_name=e.policy_name,
                          rule_name=e.rule_name,
                          on_device=(e.device_row is not None
                                     and ri not in self._exception_rules))
                for ri, e in enumerate(self.cps.rules)]
        return self._rule_idents

    def set_pending_counts(self, counts: Optional[np.ndarray]) -> None:
        """Stash the device-side per-rule verdict-class reduction for
        the assemble() that follows on this thread. With a corrupt-mode
        fault armed at the dispatch site the post-readback table may be
        altered behind the counts — drop them so analytics fall back to
        counting the (corrupted) truth the verdict path actually
        serves."""
        if counts is not None:
            spec = global_faults.armed().get(SITE_TPU_DISPATCH)
            if spec is not None and spec.mode == "corrupt":
                counts = None
        self._tls.pending_counts = counts

    def confirm_seen(self) -> bool:
        """Did the last scan() on this thread resolve any pattern-
        CONFIRM cell? Flight-recorder outcome classification: a batch
        that exercised the approximate-DFA confirmation ladder is
        always captured (ISSUE: CONFIRM is an always-capture outcome).
        Batch-scoped — scan() clears it at entry, assemble() sets it
        from the device table."""
        return bool(getattr(self._tls, "confirm_seen", False))

    def take_pending_counts(self) -> Optional[np.ndarray]:
        counts = getattr(self._tls, "pending_counts", None)
        self._tls.pending_counts = None
        if counts is not None and (
                not isinstance(counts, np.ndarray)
                or counts.shape != (len(self.cps.device_programs),
                                    NUM_CLASSES)):
            return None
        return counts

    # -- verdict-column caching (tpu/cache.py)

    @property
    def cache_eligible(self) -> bool:
        """A compiled set may serve verdicts from the content-addressed
        cache only when evaluation is a pure function of the cache key:
        no runtime dyn-operand slots (they do real context-backend I/O
        per request), and no statically host-routed rule with context
        entries (the scalar oracle would load them live). Compile-time
        folded configmaps are fine — their content hashes are part of
        the policy-set key, so movement rotates the key."""
        if self._cache_eligible is None:
            eligible = not self.cps.dyn_slots
            if eligible:
                for ri, entry in enumerate(self.cps.rules):
                    if (entry.device_row is not None
                            and ri not in self._exception_rules):
                        continue
                    policy = self.cps.policies[entry.policy_idx]
                    for rule in policy.get_rules():
                        if rule.name == entry.rule_name and rule.context:
                            eligible = False
            self._cache_eligible = eligible
        return self._cache_eligible

    def verdict_cache_keys(
        self,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
        resource_hashes: Optional[Sequence[Optional[str]]] = None,
    ) -> Optional[List[Optional[Tuple[str, str, str]]]]:
        """Per-resource verdict-cache keys, or None when this engine is
        not cache eligible. Individual entries are None for resources
        that cannot be content-hashed (those bypass the cache).
        ``resource_hashes`` lets callers that already hold the content
        hash (the cluster snapshot stores one per resource) skip the
        re-serialization — it MUST be the canonical sha-16 the snapshot
        computes, which is the same function used here."""
        from .cache import request_digest, resource_content_hash

        if not self.cache_eligible:
            return None
        if self._cache_ident is None:
            self._cache_ident = self.cps.cache_key() + self._exceptions_digest
        ns_labels = namespace_labels or {}
        keys: List[Optional[Tuple[str, str, str]]] = []
        for ci, res in enumerate(resources):
            h = (resource_hashes[ci] if resource_hashes is not None
                 else resource_content_hash(res))
            if h is None:
                keys.append(None)
                continue
            try:
                meta = res.get("metadata") or {}
                nsl = ns_labels.get(
                    meta.get("name", "") if res.get("kind") == "Namespace"
                    else meta.get("namespace", ""), {})
            except Exception:  # not dict-shaped
                keys.append(None)
                continue
            op = (operations[ci] if operations else "") or ""
            info = admission_infos[ci] if admission_infos else None
            keys.append((self._cache_ident, h,
                         request_digest(nsl, op, info)))
        return keys

    def scan(
        self,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
        live_n: Optional[int] = None,
    ) -> ScanResult:
        """Cached scan: verdict columns for content-identical
        (resource, request) pairs restore from the LRU; only the misses
        pay encode + dispatch (via the full uncached ladder). Columns
        are per-resource independent in the device program, so a
        miss-only sub-batch is bit-identical to scanning everything.

        ``live_n`` marks the first N resources as real for the rule
        analytics (the serving pipeline pads its batches with empty
        resources — those must not inflate not-matched counts);
        verdicts are computed and returned for every column either
        way."""
        from .cache import global_verdict_cache as vc

        self._tls.confirm_seen = False  # batch-scoped (see confirm_seen)
        keys = (self.verdict_cache_keys(resources, namespace_labels,
                                        operations, admission_infos)
                if vc.enabled else None)
        if keys is None:
            if vc.enabled:
                vc.bypass()
            return self._scan_uncached(resources, namespace_labels,
                                       operations, admission_infos,
                                       live_n=live_n)
        n = len(resources)
        rules = [(e.policy_name, e.rule_name) for e in self.cps.rules]
        total = np.full((len(rules), n), NOT_MATCHED, dtype=np.int32)
        miss: List[int] = []
        hits: List[int] = []
        for i, key in enumerate(keys):
            col = (vc.get(key, expect_rows=len(rules))
                   if key is not None else None)
            if col is None:
                miss.append(i)
            else:
                hits.append(i)
                total[:, i] = col
        if miss:
            # miss indices ascend, and pad resources are a suffix of the
            # batch — so the sub-batch's live prefix is just a count
            sub_live = (sum(1 for i in miss if i < live_n)
                        if live_n is not None else None)
            sub = self._scan_uncached(
                [resources[i] for i in miss], namespace_labels,
                [operations[i] for i in miss] if operations else None,
                [admission_infos[i] for i in miss] if admission_infos
                else None, live_n=sub_live)
            for j, i in enumerate(miss):
                total[:, i] = sub.verdicts[:, j]
                if keys[i] is not None:
                    vc.put(keys[i], sub.verdicts[:, j])
        if hits and global_rule_stats.enabled:
            # cache-served verdicts still count: replay the hit columns
            # into the accumulator so a warm rescan reports the same
            # rule stats as a cold one
            live_hits = ([i for i in hits if i < live_n]
                         if live_n is not None else hits)
            if live_hits:
                global_rule_stats.ingest_table(
                    self.rule_idents(), total[:, live_hits],
                    source="cached")
                self.record_pattern_replay(len(live_hits))
        return ScanResult(verdicts=total, rules=rules)

    def record_pattern_replay(self, n_cols: int) -> None:
        """Pattern-cell accounting for cache-served verdict columns —
        the replay convention every cached path follows for rule stats
        applies to the pattern split too, so warm rescans report the
        same pattern work as the cold scan that populated the cache.
        Cached columns count as path=device (the stored verdict was
        device-derived; any confirmation happened at populate time)."""
        if not n_cols:
            return
        for ri, entry in enumerate(self.cps.rules):
            if entry.device_row is None or ri in self._exception_rules:
                if entry.pattern_host:
                    global_pattern_cells.record(entry.policy_name,
                                                host=n_cols)
                continue
            if getattr(self.cps.device_programs[entry.device_row],
                       "uses_patterns", False):
                global_pattern_cells.record(entry.policy_name,
                                            device=n_cols)

    def _scan_uncached(
        self,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
        live_n: Optional[int] = None,
    ) -> ScanResult:
        n = len(resources)
        padded_n = self.bucket_size(max(n, 1))
        padded = list(resources) + [{} for _ in range(padded_n - n)]
        ops = (list(operations) + [""] * (padded_n - n)) if operations else None
        infos = (list(admission_infos) + [None] * (padded_n - n)) \
            if admission_infos else None
        t_enc0 = time.perf_counter()
        try:
            with global_profiler.phase(PHASE_ENCODE), \
                    global_tracer.span("tpu.encode", resources=n,
                                       padded=padded_n):
                batch, rows, meta = self.encode(padded, namespace_labels,
                                                ops, infos)
        except Exception:
            # a hostile resource broke batch encoding: quarantine it so
            # the rest of the batch still evaluates (device or scalar),
            # and the bad resource degrades to scalar / per-rule ERROR
            return self._scan_quarantining(
                resources, namespace_labels, operations, admission_infos,
                live_n=live_n)
        t_enc = time.perf_counter() - t_enc0
        t_disp0 = time.perf_counter()
        device_table = self._dispatch(batch, padded_n, n)[:, :n]  # (D, N)
        # feed accounting: while the host encoded, the device sat idle
        # (the serial ladder has no overlap); dispatch + readback is
        # device-busy time. Only when the device actually ran: with the
        # breaker open / dispatch failed there is no device to starve,
        # and counting encode time would pin the gauge at 1.0 during an
        # outage — pointing operators at the encoder instead of the
        # device
        from ..observability.profiling import last_dispatch_path

        if last_dispatch_path() == PATH_DEVICE:
            global_starvation.record(busy_s=time.perf_counter() - t_disp0,
                                     starved_s=t_enc)
        with global_tracer.span("tpu.assemble", resources=n):
            return self.assemble(
                device_table, resources, namespace_labels, operations,
                admission_infos, live_n=live_n
            )

    def _breaker_open_fallback(self) -> None:
        from ..observability.metrics import global_registry

        self._tls.pending_counts = None  # no device truth this batch
        set_dispatch_path(PATH_SCALAR_FALLBACK)
        global_registry.breaker_fallback.inc({"reason": "open"})
        global_tracer.add_event("breaker_fallback", reason="open",
                                breaker=self.breaker.name)

    def _record_dispatch_failure(self, e: Exception) -> None:
        from ..observability.metrics import global_registry

        # a stash from a dispatch that then failed validation must not
        # masquerade as truth for the all-HOST fallback table
        self._tls.pending_counts = None
        self.breaker.record_failure()
        set_dispatch_path(PATH_SCALAR_FALLBACK)
        global_registry.breaker_fallback.inc({"reason": "error"})
        error = f"{type(e).__name__}: {e}"
        global_tracer.add_event(
            "breaker_fallback", reason="error", breaker=self.breaker.name,
            breaker_state=self.breaker.state, error=error)
        # a chip compile or runtime error must be readable where
        # operators look, not only a fallback count: the scalar oracle
        # answers this batch bit-identically, so nothing else shows it
        from ..observability.log import global_oplog

        global_oplog.emit("device_dispatch_failed", level="error",
                          breaker_state=self.breaker.state,
                          error=error[:2000])

    def guarded_dispatch(self, dispatch_fn, want_shape) -> Optional[np.ndarray]:
        """The ONE breaker-gated dispatch ladder (shared with
        ShardedScanner so the two paths cannot drift): fault hook,
        dispatch, corrupt filter, shape/dtype validation, breaker
        bookkeeping. Returns the validated verdict table, or None when
        the breaker is open or the dispatch failed — the caller falls
        back to scalar completion (all-HOST). The pipelined scan uses
        the same ladder split in two (guarded_launch/guarded_complete)
        so the device can run chunk k while the host touches k±1."""
        self._tls.pending_counts = None
        if not self.breaker.allow():
            self._breaker_open_fallback()
            return None
        try:
            with global_tracer.span("tpu.dispatch",
                                    breaker=self.breaker.state) as span:
                global_faults.fire(SITE_TPU_DISPATCH)
                if _sanitizer.ENABLED:
                    # lock-order sanitizer: any lock held across the
                    # device call serializes its waiters behind XLA
                    _sanitizer.note_device_dispatch()
                table = dispatch_fn()
                table = self._validate_device_table(table, want_shape)
                span.attributes["engine"] = PATH_DEVICE
                return table
        except Exception as e:
            self._record_dispatch_failure(e)
            return None

    def _validate_device_table(self, table, want_shape) -> np.ndarray:
        table = global_faults.corrupt(SITE_TPU_DISPATCH, table)
        if not (isinstance(table, np.ndarray)
                and table.shape == want_shape
                and np.issubdtype(table.dtype, np.integer)):
            raise DeviceResultError(
                f"device returned shape "
                f"{getattr(table, 'shape', None)}, want {want_shape}")
        self.breaker.record_success()
        set_dispatch_path(PATH_DEVICE)
        return table

    def guarded_launch(self, launch_fn) -> Optional[Tuple[Any]]:
        """Phase 1 of the async dispatch ladder (tpu/pipeline.py):
        breaker gate + fault hook + async launch (device_put + jitted
        call, NO blocking readback). Returns an opaque in-flight handle
        for guarded_complete, or None when the breaker is open or the
        launch itself raised — same fallback semantics as
        guarded_dispatch."""
        self._tls.pending_counts = None
        if not self.breaker.allow():
            self._breaker_open_fallback()
            return None
        try:
            global_faults.fire(SITE_TPU_DISPATCH)
            if _sanitizer.ENABLED:
                _sanitizer.note_device_dispatch()
            return (launch_fn(),)
        except Exception as e:
            self._record_dispatch_failure(e)
            return None

    def guarded_complete(self, handle: Optional[Tuple[Any]], readback_fn,
                         want_shape) -> Optional[np.ndarray]:
        """Phase 2: blocking readback + corrupt filter + shape/dtype
        validation + breaker bookkeeping. A None handle (failed launch)
        passes through as None — the caller scalar-completes, exactly
        like a failed guarded_dispatch."""
        if handle is None:
            return None
        try:
            return self._validate_device_table(readback_fn(handle[0]),
                                               want_shape)
        except Exception as e:
            self._record_dispatch_failure(e)
            return None

    def _run_packed(self, fn, batch: Dict[str, Any]) -> np.ndarray:
        """One device call on a host lane dict through a packed entry
        (CompiledPolicySet.packed_fn, mutate_packed_fn): the lanes cross
        as one slab per dtype (evaluator.pack_lanes) in ONE put, the answer as one int32
        array in ONE readback. Dispatch (pack, put, async launch, any
        XLA compile at this shape) and readback (the blocking D2H) are
        attributed separately. Both transfers are counted on
        ``kyverno_device_transfers_total``/``_bytes_total`` and on the
        active ``tpu.dispatch`` span."""
        import jax

        from ..observability.metrics import global_registry as reg

        with maybe_xla_trace():
            with global_profiler.phase(PHASE_DISPATCH):
                buffers, layout = pack_lanes(batch)
                out = fn(jax.device_put(buffers), layout)
            with global_profiler.phase(PHASE_READBACK):
                out = np.asarray(out)
        bytes_in = sum(b.nbytes for b in buffers)
        reg.device_transfers.inc({"direction": "h2d"}, len(buffers))
        reg.device_transfer_bytes.inc({"direction": "h2d"}, bytes_in)
        reg.device_transfers.inc({"direction": "d2h"})
        reg.device_transfer_bytes.inc({"direction": "d2h"}, out.nbytes)
        span = global_tracer.current_span()
        if span is not None:
            span.attributes.update(buffers_in=len(buffers),
                                   bytes_in=bytes_in, buffers_out=1)
        return out

    def _dispatch(self, batch, padded_n: int,
                  n_live: Optional[int] = None) -> np.ndarray:
        """One device dispatch through the guarded ladder. Any failure
        returns an all-HOST table, which routes the WHOLE batch through
        the scalar oracle in assemble(): verdicts stay bit-identical,
        only latency degrades. The device program also returns the
        per-rule verdict-class reduction; it is stashed (pad columns
        subtracted) for the assemble() that follows this dispatch."""
        if n_live is None:
            n_live = padded_n

        D = len(self.cps.device_programs)

        def run():
            out = self._run_packed(self.cps.packed_fn(), batch)
            # the counts ride after the table's columns: a result of
            # any other width is refused before it is split
            want = (D, padded_n + NUM_VERDICT_CLASSES)
            if out.shape != want:
                raise DeviceResultError(
                    f"device returned shape {out.shape}, want {want}")
            # bucket-pad columns are encoded empties, not workload:
            # their contribution leaves the analytics counts here
            table = out[:, :padded_n]
            counts = out[:, padded_n:].astype(np.int64) - class_counts(
                table[:, n_live:])
            self.set_pending_counts(counts)
            return table

        table = self.guarded_dispatch(run, (D, padded_n))
        if table is None:
            return np.full((D, padded_n), HOST, dtype=np.int32)
        return table

    def _scan_quarantining(
        self,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
        live_n: Optional[int] = None,
    ) -> ScanResult:
        """Batch encode failed: split the batch into resources that
        encode alone (re-scanned as a clean sub-batch) and hostile ones,
        which complete per (policy, resource) on the scalar engine — a
        policy the scalar engine ALSO cannot evaluate yields per-rule
        ERROR verdicts instead of aborting the scan."""
        n = len(resources)
        good: List[int] = []
        bad: List[int] = []
        for ci, res in enumerate(resources):
            op = [(operations[ci] if operations else "") or ""]
            info = [admission_infos[ci]] if admission_infos else None
            try:
                # STRUCTURAL probe only (rows + meta lanes): dyn-lane
                # encoding does real context-backend I/O and catches its
                # own load errors, so probing it here would pay O(batch)
                # duplicate backend calls for nothing. A dyn-lane value
                # that still throws re-enters quarantine from the good
                # sub-batch's scan, which then degrades it to scalar.
                encode_resources([res], self.cps.encode_cfg,
                                 self.cps.byte_paths, self.cps.key_byte_paths)
                encode_metadata([res], namespace_labels, op, info,
                                self.cps.meta_cfg)
                good.append(ci)
            except Exception:
                bad.append(ci)
        if not bad:
            # batch-level failure with no single culprit: degrade the
            # whole batch to the scalar path rather than loop forever
            good, bad = [], list(range(n))
        total = np.full((len(self.cps.rules), n), NOT_MATCHED, dtype=np.int32)
        if good:
            sub = self.scan(
                [resources[i] for i in good], namespace_labels,
                [operations[i] for i in good] if operations else None,
                [admission_infos[i] for i in good] if admission_infos else None,
                live_n=(sum(1 for i in good if i < live_n)
                        if live_n is not None else None))
            total[:, good] = sub.verdicts
        ns_labels = namespace_labels or {}
        for ci in bad:
            res = resources[ci]
            op = (operations[ci] if operations else "") or ""
            info = admission_infos[ci] if admission_infos else None
            try:
                kind = res.get("kind", "")
                meta = res.get("metadata") or {}
                nsl = ns_labels.get(
                    meta.get("name", "") if kind == "Namespace"
                    else meta.get("namespace", ""), {})
            except Exception:  # not even dict-shaped
                nsl = {}
            for pi, policy in enumerate(self.cps.policies):
                try:
                    pctx = build_scan_context(policy, res, nsl, op, info)
                    verdicts = _scalar_rule_verdicts(self.scalar, policy, pctx)
                except Exception:
                    verdicts = None  # ERROR every rule of this policy
                for ri, entry in enumerate(self.cps.rules):
                    if entry.policy_idx != pi:
                        continue
                    total[ri, ci] = ERROR if verdicts is None \
                        else verdicts.get(entry.rule_name, NOT_MATCHED)
        # analytics: the good sub-batch ingested inside self.scan();
        # only the quarantined columns are counted here
        live_bad = [ci for ci in bad if live_n is None or ci < live_n]
        if live_bad and global_rule_stats.enabled:
            global_rule_stats.ingest_table(self.rule_idents(),
                                           total[:, live_bad],
                                           source="quarantine")
        return ScanResult(
            verdicts=total,
            rules=[(e.policy_name, e.rule_name) for e in self.cps.rules],
        )

    def assemble(
        self,
        device_table: np.ndarray,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
        live_n: Optional[int] = None,
    ) -> ScanResult:
        """Merge device verdicts with host completions (host rules +
        HOST-flagged resources), then fold the batch into the rule
        analytics: device-reduced counts for untouched device rows,
        per-cell corrections for everything the host completed."""
        n = len(resources)
        total = np.full((len(self.cps.rules), n), NOT_MATCHED, dtype=np.int32)
        ns_labels = namespace_labels or {}
        # unconditional assignment, not a sticky set: the pipelined
        # scan calls assemble() per chunk WITHOUT going through scan()
        # (the only other place the flag resets), so one CONFIRM cell
        # must not mark every later chunk/tick as a confirm outcome
        self._tls.confirm_seen = bool(
            (np.asarray(device_table) == CONFIRM).any())

        # requests whose identity strings carry globs defeat the
        # device's hash-equality userInfo lanes (_set_in matches
        # wildcards in either direction) -> per-cell host completion
        glob_identity_cis: List[int] = []
        if admission_infos:
            from ..utils.wildcard import contains_wildcard

            for ci in range(n):
                info = admission_infos[ci] if ci < len(admission_infos) else None
                if info is not None and any(
                        contains_wildcard(g) for g in (info.groups or [])):
                    glob_identity_cis.append(ci)

        # which (policy, resource) pairs need the scalar engine? HOST
        # and CONFIRM cells both resolve there — CONFIRM is the
        # pattern-confirmation sub-batch (over-approximate DFA hits,
        # byte-sensitive patterns on non-ASCII subjects), attributed
        # separately in the pattern-cell accounting below
        host_cells: Dict[Tuple[int, int], None] = {}
        live = n if live_n is None else min(live_n, n)
        for ri, entry in enumerate(self.cps.rules):
            if entry.device_row is None or ri in self._exception_rules:
                for ci in range(n):
                    host_cells[(entry.policy_idx, ci)] = None
                if entry.pattern_host and live:
                    # non-lowerable pattern kept this rule on the host
                    global_pattern_cells.record(entry.policy_name,
                                                host=live)
            else:
                row = device_table[entry.device_row].copy()
                if glob_identity_cis and self.cps.device_programs[
                        entry.device_row].uses_userinfo:
                    row[glob_identity_cis] = HOST
                total[ri] = row
                for ci in np.nonzero(row >= HOST)[0]:
                    host_cells[(entry.policy_idx, int(ci))] = None
                if live and getattr(
                        self.cps.device_programs[entry.device_row],
                        "uses_patterns", False):
                    # path attribution for a LOWERED pattern rule:
                    # device = the DFA verdict stood, confirm = the
                    # oracle confirmed a maybe. Its HOST cells are NOT
                    # pattern-caused (encode caps, userinfo globs, CEL
                    # DELETE diversion) and stay out of the split —
                    # path="host" means exactly the non-lowerable
                    # pattern rules counted in the branch above.
                    rowv = row[:live]
                    c = int((rowv == CONFIRM).sum())
                    h = int((rowv == HOST).sum())
                    global_pattern_cells.record(entry.policy_name,
                                                device=live - c - h,
                                                confirm=c)
                    if c:
                        # the ongoing price of over-approximated /
                        # byte-sensitive tables: cells the oracle had
                        # to re-check (kyverno_dfa_confirm_cells_total)
                        try:
                            from ..observability.metrics import (
                                global_registry as _reg)
                            _reg.dfa_confirm_cells.inc(value=c)
                        except Exception:  # noqa: BLE001
                            pass

        from ..engine.match import matches_resource_description

        cache: Dict[Tuple[int, int], Optional[Dict[str, int]]] = {}
        with global_profiler.phase(PHASE_HOST_COMPLETE):
            for (pi, ci) in host_cells:
                policy = self.cps.policies[pi]
                res = resources[ci]
                try:
                    kind = res.get("kind", "")
                    ns = (res.get("metadata") or {}).get("namespace", "")
                    nsl = ns_labels.get((res.get("metadata") or {}).get("name", "") if kind == "Namespace" else ns, {})
                    op = (operations[ci] if operations else "") or ""
                    info = admission_infos[ci] if admission_infos else None
                    # pre-screen with the (cheap) matcher before paying for
                    # context construction + full validation: in a realistic
                    # mix most host (policy, resource) cells are simply not
                    # matched (kind/selector mismatch), making the fallback
                    # cost scale with MATCHED cells, not policies x resources
                    if not any(
                            not matches_resource_description(
                                res, rule, info, nsl,
                                policy_namespace=policy.namespace,
                                operation=op or "CREATE")
                            for rule in policy.get_rules() if rule.has_validate()):
                        cache[(pi, ci)] = {}  # every rule NOT_MATCHED
                        continue
                    pctx = build_scan_context(policy, res, nsl, op, info)
                    cache[(pi, ci)] = _scalar_rule_verdicts(self.scalar, policy, pctx)
                except Exception:
                    # the scalar oracle itself choked on this (policy,
                    # resource) — a quarantined policy whose pattern is
                    # genuinely broken lands here. The cell reports
                    # per-rule ERROR; the rest of the batch is untouched.
                    cache[(pi, ci)] = None
        # merge indexed by policy: each rule row only visits its own
        # policy's completed cells, so the pass is O(rules + host_cells)
        # instead of quadratic on large policy sets
        by_policy: Dict[int, List[Tuple[int, Optional[Dict[str, int]]]]] = {}
        for (pi, ci), verdicts in cache.items():
            by_policy.setdefault(pi, []).append((ci, verdicts))
        # cells whose device verdict the host replaced, per device rule
        # — the analytics correction set (device counts already include
        # the device's original code for these cells)
        replaced: Dict[int, List[int]] = {}
        for ri, entry in enumerate(self.cps.rules):
            cells = by_policy.get(entry.policy_idx)
            if not cells:
                continue
            host_rule = (entry.device_row is None
                         or ri in self._exception_rules)
            for ci, verdicts in cells:
                if host_rule or total[ri, ci] >= HOST:
                    # pre-screened cells carry no verdict rows: the
                    # whole policy was unmatched (HOST must not escape)
                    total[ri, ci] = ERROR if verdicts is None \
                        else verdicts.get(entry.rule_name, NOT_MATCHED)
                    if not host_rule:
                        replaced.setdefault(ri, []).append(ci)

        self._ingest_assembled(total, device_table, replaced, live_n)
        return ScanResult(
            verdicts=total,
            rules=[(e.policy_name, e.rule_name) for e in self.cps.rules],
        )

    def _ingest_assembled(self, total: np.ndarray, device_table: np.ndarray,
                          replaced: Dict[int, List[int]],
                          live_n: Optional[int]) -> None:
        """Exact per-rule verdict counts for one assembled batch.

        With the device-side reduction stashed by the dispatch, a
        device rule's counts are the O(1)-per-rule device totals plus a
        correction per host-completed cell (subtract the device's code,
        add the final one) — the correction set is exactly the cell set
        the host already paid scalar work for. Without a stash (breaker
        fallback, scalar completion, external tables) the counts come
        from one vectorized host reduction over the final table; either
        way the ingested numbers describe the verdicts actually
        served."""
        if not global_rule_stats.enabled or total.shape[0] == 0:
            return
        rules_n, n = total.shape
        dev_counts = self.take_pending_counts()
        if dev_counts is None:
            counts = class_counts(total)
            source = "host"
        else:
            counts = np.zeros((rules_n, NUM_CLASSES), dtype=np.int64)
            host_rows: List[int] = []
            for ri, entry in enumerate(self.cps.rules):
                if entry.device_row is None or ri in self._exception_rules:
                    host_rows.append(ri)
                    continue
                c = dev_counts[entry.device_row].astype(np.int64).copy()
                for ci in replaced.get(ri, ()):
                    c[int(device_table[entry.device_row, ci])] -= 1
                    c[int(total[ri, ci])] += 1
                counts[ri] = c
            if host_rows:
                counts[host_rows] = class_counts(total[host_rows])
            source = "device"
        if live_n is not None and live_n < n:
            counts = counts - class_counts(total[:, live_n:])
        global_rule_stats.ingest_counts(self.rule_idents(), counts,
                                        source=source)

    # -- mutate triage (mutation/): which resources need the patcher?

    @property
    def mutate_cache_eligible(self) -> bool:
        """Mutate-side purity: no host-routed or excepted mutate rule
        carries context entries (the scalar patcher would load them
        live per request, so a replay — cached triage rows feeding a
        shadow-verification re-patch — could observe different state).
        Device-compiled triage rules are pure by construction: dyn-slot
        programs are refused at compile and folded context hashes are
        part of the policy-set key."""
        if self._mutate_cache_eligible is None:
            eligible = True
            for mi, entry in enumerate(self.cps.mutate_entries):
                if (entry.device_row is not None
                        and mi not in self._exception_mutate_rules):
                    continue
                policy = self.cps.policies[entry.policy_idx]
                for rule in policy.get_rules():
                    if rule.name == entry.rule_name and rule.context:
                        eligible = False
            self._mutate_cache_eligible = eligible
        return self._mutate_cache_eligible

    def mutate_triage_cache_keys(
        self,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
    ) -> Optional[List[Optional[Tuple[str, str, str]]]]:
        """Verdict-cache keys for triage rows: the validate keys with a
        namespaced ident, so an (M,) triage column and an (R,) validate
        column for the same (resource, request) can never collide."""
        if not self.mutate_cache_eligible:
            return None
        keys = self.verdict_cache_keys(resources, namespace_labels,
                                       operations, admission_infos)
        if keys is None:
            return None
        return [None if k is None else ("mutate|" + k[0], k[1], k[2])
                for k in keys]

    def triage_mutate(
        self,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
    ) -> MutateTriageResult:
        """Needs-mutation triage over the compiled mutate bank — the
        same cached ladder as scan(): verdict-cache columns for warm
        (resource, request) pairs, guarded dispatch for the misses,
        all-HOST degradation (everything scalar-patches) on any
        failure."""
        from ..observability.metrics import global_registry as reg
        from .cache import global_verdict_cache as vc

        rules = self.cps.mutate_rules
        m, n = len(rules), len(resources)
        if m == 0 or n == 0:
            return MutateTriageResult(
                np.zeros((m, n), dtype=np.int32), rules)
        keys = (self.mutate_triage_cache_keys(
                    resources, namespace_labels, operations,
                    admission_infos)
                if vc.enabled else None)
        if keys is None:
            return self._triage_uncached(resources, namespace_labels,
                                         operations, admission_infos)
        total = np.full((m, n), HOST, dtype=np.int32)
        miss: List[int] = []
        hits = 0
        for i, key in enumerate(keys):
            col = (vc.get(key, expect_rows=m)
                   if key is not None else None)
            if col is None:
                miss.append(i)
            else:
                hits += 1
                total[:, i] = col
        if hits:
            reg.mutate_triage.inc({"outcome": "cached"}, hits)
        if miss:
            sub = self._triage_uncached(
                [resources[i] for i in miss], namespace_labels,
                [operations[i] for i in miss] if operations else None,
                [admission_infos[i] for i in miss] if admission_infos
                else None)
            for j, i in enumerate(miss):
                total[:, i] = sub.verdicts[:, j]
                if keys[i] is not None:
                    vc.put(keys[i], sub.verdicts[:, j])
        return MutateTriageResult(verdicts=total, rules=rules)

    def _triage_uncached(
        self,
        resources: Sequence[Dict[str, Any]],
        namespace_labels: Optional[Dict[str, Dict[str, str]]] = None,
        operations: Optional[Sequence[str]] = None,
        admission_infos: Optional[Sequence[Optional[RequestInfo]]] = None,
    ) -> MutateTriageResult:
        """One device batch over the mutate bank. Host rows (bank host
        entries, excepted rules, userinfo globs) stay HOST; encode or
        dispatch failure degrades the whole batch to HOST — the
        coordinator then scalar-patches everything, bit-identically."""
        from ..observability.metrics import global_registry as reg

        rules = self.cps.mutate_rules
        m, n = len(rules), len(resources)
        total = np.full((m, n), HOST, dtype=np.int32)
        d = len(self.cps.mutate_programs)
        device_table = None
        if d:
            padded_n = self.bucket_size(max(n, 1))
            padded = list(resources) + [{} for _ in range(padded_n - n)]
            ops = (list(operations) + [""] * (padded_n - n)) \
                if operations else None
            infos = (list(admission_infos) + [None] * (padded_n - n)) \
                if admission_infos else None
            try:
                with global_profiler.phase(PHASE_ENCODE), \
                        global_tracer.span("tpu.encode_triage",
                                           resources=n, padded=padded_n):
                    batch, _, _ = self.encode(padded, namespace_labels,
                                              ops, infos)
            except Exception:  # hostile resource: everything scalar
                batch = None
            if batch is not None:
                def run():
                    global_faults.fire(SITE_MUTATE_TRIAGE)
                    return self._run_packed(self.cps.mutate_packed_fn(),
                                            batch)

                device_table = self.guarded_dispatch(run, (d, padded_n))
        if device_table is not None:
            glob_cis: List[int] = []
            if admission_infos:
                from ..utils.wildcard import contains_wildcard

                for ci in range(n):
                    info = (admission_infos[ci]
                            if ci < len(admission_infos) else None)
                    if info is not None and any(
                            contains_wildcard(g)
                            for g in (info.groups or [])):
                        glob_cis.append(ci)
            for mi, entry in enumerate(self.cps.mutate_entries):
                if (entry.device_row is None
                        or mi in self._exception_mutate_rules):
                    continue  # stays HOST
                row = device_table[entry.device_row, :n].copy()
                if glob_cis and self.cps.mutate_programs[
                        entry.device_row].uses_userinfo:
                    row[glob_cis] = HOST
                total[mi] = row
            reg.mutate_triage.inc({"outcome": "device"})
        else:
            reg.mutate_triage.inc({"outcome": "fallback"})
        result = MutateTriageResult(verdicts=total, rules=rules)
        for label, count in result.counts().items():
            if count:
                reg.mutate_triage_rows.inc({"result": label}, count)
        return result

    # -- introspection

    def coverage(self) -> Tuple[int, int]:
        return self.cps.coverage()

    def mutate_coverage(self) -> Tuple[int, int]:
        return self.cps.mutate_coverage()
