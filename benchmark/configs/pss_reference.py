"""Plain reference for the Pod Security Standards policy set.

A straightforward reading of Kyverno's validate semantics for the rules
in ``benchmark/policies/pss``: ``match`` by kind, the DELETE
precondition, ``pattern`` and ``anyPattern`` with the equality (``=()``)
and negation (``X()``) anchors, ``|`` alternatives, ``*`` wildcards,
numeric comparisons and wildcard annotation keys, and the four ``deny``
conditions of the set, each written out by hand. It imports nothing of
the program under test.

``rule_results(policies, pod, operation)`` gives
``{(policy, rule): "pass" | "fail"}`` for every rule that matches.
A construct outside this set raises ``Unsupported``.
"""

from __future__ import annotations

import fnmatch
import glob
import os
from typing import Any, Dict, List, Optional, Tuple

import yaml


class Unsupported(Exception):
    pass


def load_policies(directory: str, failure_action: str) -> List[dict]:
    """The policy documents of ``directory``, with
    ``spec.validationFailureAction`` set to ``failure_action``."""
    out = []
    for path in sorted(glob.glob(os.path.join(directory, "*.yaml"))):
        with open(path) as f:
            for doc in yaml.safe_load_all(f):
                if doc and doc.get("kind") in ("ClusterPolicy", "Policy"):
                    doc["spec"]["validationFailureAction"] = failure_action
                    out.append(doc)
    return out


# ---------------------------------------------------------------------------
# pattern matching


def _is_number(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _scalar_text(v: Any) -> Optional[str]:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, str):
        return v
    if _is_number(v):
        return str(int(v)) if float(v).is_integer() else repr(float(v))
    return None


def _one_condition(value: Any, cond: str) -> bool:
    cond = cond.strip()
    for op in (">=", "<=", ">", "<", "!"):
        if cond.startswith(op):
            operand = cond[len(op):].strip()
            break
    else:
        op, operand = "", cond
    if op in (">=", "<=", ">", "<"):
        try:
            bound = float(operand)
        except ValueError as e:
            raise Unsupported(f"comparison with {operand!r}") from e
        if not _is_number(value):
            return False
        return {">=": value >= bound, "<=": value <= bound,
                ">": value > bound, "<": value < bound}[op]
    text = _scalar_text(value)
    if text is None:
        return False
    hit = fnmatch.fnmatchcase(text, operand)
    return not hit if op == "!" else hit


def _scalar_matches(value: Any, pattern: Any) -> bool:
    if isinstance(pattern, bool):
        return isinstance(value, bool) and value == pattern
    if _is_number(pattern):
        return _is_number(value) and value == pattern
    if pattern is None:
        return value is None
    if isinstance(pattern, str):
        if isinstance(value, (dict, list)):
            return False
        return any(all(_one_condition(value, c) for c in alt.split("&"))
                   for alt in pattern.split("|"))
    raise Unsupported(f"pattern {pattern!r}")


def _split_anchor(key: str) -> Tuple[str, str]:
    for prefix in ("=(", "X("):
        if key.startswith(prefix) and key.endswith(")"):
            return prefix[0], key[2:-1]
    if key.endswith(")") and (key[:1] == "(" or key[:2] in ("^(", "<(", "+(")):
        raise Unsupported(f"anchor {key!r}")
    return "", key


def element_matches(value: Any, pattern: Any) -> bool:
    if isinstance(pattern, dict):
        return isinstance(value, dict) and _map_matches(value, pattern)
    if isinstance(pattern, list):
        if len(pattern) != 1 or not isinstance(pattern[0], dict):
            raise Unsupported(f"array pattern {pattern!r}")
        if not isinstance(value, list):
            return False
        return all(isinstance(v, dict) and _map_matches(v, pattern[0])
                   for v in value)
    return _scalar_matches(value, pattern)


def _map_matches(resource: dict, pattern: dict) -> bool:
    for key, sub in pattern.items():
        anchor, name = _split_anchor(key)
        names = ([k for k in resource if fnmatch.fnmatchcase(k, name)]
                 if "*" in name or "?" in name else [name])
        if anchor == "X":
            if any(n in resource for n in names):
                return False
            continue
        for n in names:
            if n not in resource:
                if anchor == "=":
                    continue
                return False
            if not element_matches(resource[n], sub):
                return False
    return True


# ---------------------------------------------------------------------------
# deny conditions of the set, by their expression


def _all_containers(pod: dict) -> List[dict]:
    spec = pod.get("spec") or {}
    out: List[dict] = []
    for key in ("ephemeralContainers", "initContainers", "containers"):
        out.extend(c for c in spec.get(key) or [] if isinstance(c, dict))
    return out


def _caps(container: dict, which: str) -> List[str]:
    sc = container.get("securityContext") or {}
    return list((sc.get("capabilities") or {}).get(which) or [])


def _any_not_in(key: Any, values: List[Any]) -> bool:
    keys = key if isinstance(key, list) else [key]
    return any(k not in values for k in keys)


def _pod_key(expr: str, pod: dict) -> Any:
    if expr == ("{{ request.object.spec.[ephemeralContainers, initContainers, "
                "containers][].securityContext.capabilities.add[] }}"):
        return [c for cont in _all_containers(pod) for c in _caps(cont, "add")]
    if expr == "{{ request.object.spec.volumes[].keys(@)[] || '' }}":
        vols = (pod.get("spec") or {}).get("volumes") or []
        keys = [k for v in vols if isinstance(v, dict) for k in v]
        return keys or ""
    raise Unsupported(f"deny key {expr!r}")


def _element_key(expr: str, element: dict) -> Any:
    if expr == "{{ element.securityContext.capabilities.drop[] || `[]` }}":
        return _caps(element, "drop")
    if expr == "{{ element.securityContext.capabilities.add[] || `[]` }}":
        return _caps(element, "add")
    raise Unsupported(f"foreach key {expr!r}")


def _resolve(expr: Any, resolve) -> Any:
    return resolve(expr) if isinstance(expr, str) and "{{" in expr else expr


def _deny(conditions: dict, resolve) -> bool:
    if set(conditions) != {"all"}:
        raise Unsupported(f"deny conditions {list(conditions)}")
    for cond in conditions["all"]:
        if cond["operator"] != "AnyNotIn":
            raise Unsupported(f"operator {cond['operator']}")
        key = _resolve(cond["key"], resolve)
        value = _resolve(cond["value"], resolve)
        if not _any_not_in(key, value if isinstance(value, list) else [value]):
            return False
    return True


# ---------------------------------------------------------------------------
# rules


def _matches_kind(rule: dict, pod: dict) -> bool:
    match = rule.get("match") or {}
    if set(match) != {"any"}:
        raise Unsupported(f"match {list(match)}")
    return any(pod.get("kind") in (m.get("resources") or {}).get("kinds", [])
               for m in match["any"])


def _preconditions_hold(rule: dict, operation: str) -> bool:
    pre = rule.get("preconditions")
    if not pre:
        return True
    for cond in pre.get("all", []):
        if (cond["key"] != "{{ request.operation || 'BACKGROUND' }}"
                or cond["operator"] != "NotEquals"):
            raise Unsupported(f"precondition {cond}")
        if (operation or "BACKGROUND") == cond["value"]:
            return False
    return True


def rule_result(rule: dict, pod: dict) -> str:
    v = rule["validate"]
    if "pattern" in v:
        return "pass" if element_matches(pod, v["pattern"]) else "fail"
    if "anyPattern" in v:
        return ("pass" if any(element_matches(pod, p) for p in v["anyPattern"])
                else "fail")
    if "deny" in v:
        denied = _deny(v["deny"]["conditions"], lambda e: _pod_key(e, pod))
        return "fail" if denied else "pass"
    if "foreach" in v:
        for fe in v["foreach"]:
            if fe["list"] != ("request.object.spec.[ephemeralContainers, "
                              "initContainers, containers][]"):
                raise Unsupported(f"foreach list {fe['list']!r}")
            for element in _all_containers(pod):
                if _deny(fe["deny"]["conditions"],
                         lambda e, el=element: _element_key(e, el)):
                    return "fail"
        return "pass"
    raise Unsupported(f"validate {list(v)}")


def rule_results(policies: List[dict], pod: dict,
                 operation: str = "") -> Dict[Tuple[str, str], str]:
    out = {}
    for p in policies:
        for rule in p["spec"]["rules"]:
            if not _matches_kind(rule, pod) or "validate" not in rule:
                continue
            if not _preconditions_hold(rule, operation):
                continue
            out[(p["metadata"]["name"], rule["name"])] = rule_result(rule, pod)
    return out


def enforced(policies: List[dict]) -> set:
    return {p["metadata"]["name"] for p in policies
            if str(p["spec"].get("validationFailureAction", "Audit"))
            .lower().startswith("enforce")}


def admission_verdict(policies: List[dict], pod: dict) -> Tuple[bool, set]:
    """(allowed, {(policy, rule)} that deny) for a CREATE of ``pod``."""
    enforce = enforced(policies)
    denied = {pr for pr, r in rule_results(policies, pod, "CREATE").items()
              if r in ("fail", "error") and pr[0] in enforce}
    return not denied, denied
