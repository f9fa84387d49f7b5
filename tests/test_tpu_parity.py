"""Device-vs-scalar parity: the TPU program must agree with the scalar
oracle on every (rule, resource) verdict."""

import numpy as np
import pytest

from kyverno_tpu.api.policy import ClusterPolicy
from kyverno_tpu.engine.engine import Engine as ScalarEngine
from kyverno_tpu.tpu.engine import (
    NOT_MATCHED,
    TpuEngine,
    VERDICT_NAMES,
    _scalar_rule_verdicts,
    build_scan_context,
)


def make_policy(name, rules):
    return ClusterPolicy.from_dict(
        {
            "apiVersion": "kyverno.io/v1",
            "kind": "ClusterPolicy",
            "metadata": {"name": name},
            "spec": {"rules": rules},
        }
    )


def scalar_table(policies, resources, ns_labels=None, operations=None):
    eng = ScalarEngine()
    rows = []
    for policy in policies:
        rule_names = [r.name for r in policy.get_rules() if r.has_validate()]
        per_rule = {rn: [] for rn in rule_names}
        for ci, res in enumerate(resources):
            kind = res.get("kind", "")
            ns = (res.get("metadata") or {}).get("namespace", "")
            key = (res.get("metadata") or {}).get("name", "") if kind == "Namespace" else ns
            nsl = (ns_labels or {}).get(key, {})
            op = (operations[ci] if operations else "") or ""
            pctx = build_scan_context(policy, res, nsl, op)
            verdicts = _scalar_rule_verdicts(eng, policy, pctx)
            for rn in rule_names:
                per_rule[rn].append(verdicts[rn])
        for rn in rule_names:
            rows.append(((policy.name, rn), per_rule[rn]))
    return rows


def check_parity(policies, resources, ns_labels=None, operations=None):
    eng = TpuEngine(policies)
    result = eng.scan(resources, ns_labels, operations)
    expected = scalar_table(policies, resources, ns_labels, operations)
    assert [r for r in result.rules] == [e[0] for e in expected]
    for row, ((pname, rname), exp) in enumerate(expected):
        got = result.verdicts[row].tolist()
        assert got == exp, (
            f"{pname}/{rname}: device={[VERDICT_NAMES[v] for v in got]} "
            f"scalar={[VERDICT_NAMES[v] for v in exp]}"
        )
    return eng


def pod(name="p", ns="default", spec=None, labels=None, kind="Pod"):
    return {
        "apiVersion": "v1",
        "kind": kind,
        "metadata": {"name": name, "namespace": ns, **({"labels": labels} if labels else {})},
        "spec": spec if spec is not None else {},
    }


HOST_NS_RULE = {
    "name": "host-namespaces",
    "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
    "validate": {
        "message": "host namespaces are disallowed",
        "pattern": {
            "spec": {"=(hostPID)": "false", "=(hostIPC)": "false", "=(hostNetwork)": "false"}
        },
    },
}


def test_equality_anchor_pattern():
    policies = [make_policy("disallow-host-namespaces", [HOST_NS_RULE])]
    resources = [
        pod(spec={}),                                  # keys absent -> pass
        pod(spec={"hostPID": True}),                   # true -> fail
        pod(spec={"hostNetwork": False}),              # false -> pass
        pod(spec={"hostIPC": "false"}),                # string false -> pass
        pod(kind="Service"),                           # not matched
        pod(spec={"hostPID": False, "hostIPC": True}),  # one bad -> fail
    ]
    eng = check_parity(policies, resources)
    assert eng.coverage() == (1, 1)


PRIVILEGED_RULE = {
    "name": "privileged-containers",
    "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
    "validate": {
        "message": "privileged mode is disallowed",
        "pattern": {
            "spec": {
                "=(ephemeralContainers)": [{"=(securityContext)": {"=(privileged)": "false"}}],
                "=(initContainers)": [{"=(securityContext)": {"=(privileged)": "false"}}],
                "containers": [{"=(securityContext)": {"=(privileged)": "false"}}],
            }
        },
    },
}


def test_array_of_maps_anchors():
    policies = [make_policy("disallow-privileged", [PRIVILEGED_RULE])]
    resources = [
        pod(spec={"containers": [{"name": "a"}]}),
        pod(spec={"containers": [{"name": "a", "securityContext": {"privileged": True}}]}),
        pod(spec={"containers": [{"name": "a", "securityContext": {"privileged": False}}]}),
        pod(spec={"containers": [{"name": "a"}],
                  "initContainers": [{"name": "b", "securityContext": {"privileged": True}}]}),
        pod(spec={"containers": []}),
        pod(spec={}),  # containers missing -> fail (plain key)
        pod(spec={"containers": [{"securityContext": {}}]}),
        pod(spec={"containers": [{"securityContext": {"privileged": "true"}}]}),
    ]
    check_parity(policies, resources)


SECCOMP_RULE = {
    "name": "seccomp",
    "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
    "validate": {
        "message": "custom seccomp profiles are disallowed",
        "pattern": {
            "spec": {
                "=(securityContext)": {"=(seccompProfile)": {"=(type)": "RuntimeDefault | Localhost"}},
                "containers": [
                    {"=(securityContext)": {"=(seccompProfile)": {"=(type)": "RuntimeDefault | Localhost"}}}
                ],
            }
        },
    },
}


def test_or_alternatives_leaf():
    policies = [make_policy("restrict-seccomp", [SECCOMP_RULE])]
    resources = [
        pod(spec={"containers": [{"name": "a"}]}),
        pod(spec={"securityContext": {"seccompProfile": {"type": "Unconfined"}},
                  "containers": [{"name": "a"}]}),
        pod(spec={"securityContext": {"seccompProfile": {"type": "RuntimeDefault"}},
                  "containers": [{"name": "a"}]}),
        pod(spec={"containers": [{"securityContext": {"seccompProfile": {"type": "Localhost"}}}]}),
        pod(spec={"containers": [{"securityContext": {"seccompProfile": {"type": "Bad"}}}]}),
    ]
    check_parity(policies, resources)


CAPABILITIES_DENY_RULE = {
    "name": "adding-capabilities",
    "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
    "preconditions": {
        "all": [
            {"key": "{{ request.operation || 'BACKGROUND' }}", "operator": "NotEquals", "value": "DELETE"}
        ]
    },
    "validate": {
        "message": "capabilities beyond the allowed list are disallowed",
        "deny": {
            "conditions": {
                "all": [
                    {
                        "key": "{{ request.object.spec.[ephemeralContainers, initContainers, containers][].securityContext.capabilities.add[] }}",
                        "operator": "AnyNotIn",
                        "value": ["AUDIT_WRITE", "CHOWN", "KILL", "NET_BIND_SERVICE", "SETUID"],
                    }
                ]
            }
        },
    },
}


def test_deny_multiselect_capabilities():
    policies = [make_policy("disallow-capabilities", [CAPABILITIES_DENY_RULE])]
    resources = [
        pod(spec={"containers": [{"name": "a"}]}),
        pod(spec={"containers": [{"securityContext": {"capabilities": {"add": ["CHOWN"]}}}]}),
        pod(spec={"containers": [{"securityContext": {"capabilities": {"add": ["SYS_ADMIN"]}}}]}),
        pod(spec={
            "containers": [{"securityContext": {"capabilities": {"add": ["KILL"]}}}],
            "initContainers": [{"securityContext": {"capabilities": {"add": ["NET_RAW"]}}}],
        }),
        pod(spec={"containers": [{"securityContext": {"capabilities": {}}}]}),
    ]
    check_parity(policies, resources, operations=["", "", "", "", "DELETE"])


VOLUME_TYPES_RULE = {
    "name": "restricted-volumes",
    "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
    "validate": {
        "message": "only allowed volume types",
        "deny": {
            "conditions": {
                "all": [
                    {
                        "key": "{{ request.object.spec.volumes[].keys(@)[] || '' }}",
                        "operator": "AnyNotIn",
                        "value": ["name", "configMap", "secret", "emptyDir",
                                  "projected", "persistentVolumeClaim", "downwardAPI",
                                  "csi", "ephemeral", ""],
                    }
                ]
            }
        },
    },
}


def test_deny_keys_projection():
    policies = [make_policy("restrict-volume-types", [VOLUME_TYPES_RULE])]
    resources = [
        pod(spec={}),
        pod(spec={"volumes": []}),
        pod(spec={"volumes": [{"name": "v", "configMap": {"name": "c"}}]}),
        pod(spec={"volumes": [{"name": "v", "hostPath": {"path": "/"}}]}),
        pod(spec={"volumes": [{"name": "v", "secret": {}}, {"name": "w", "nfs": {}}]}),
    ]
    check_parity(policies, resources)


def test_negation_and_anypattern():
    rules = [
        {
            "name": "no-hostpath",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {
                "pattern": {"spec": {"=(volumes)": [{"X(hostPath)": "null"}]}},
            },
        },
        {
            "name": "run-as-nonroot",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {
                "anyPattern": [
                    {"spec": {"securityContext": {"runAsNonRoot": True},
                              "containers": [{"=(securityContext)": {"=(runAsNonRoot)": True}}]}},
                    {"spec": {"containers": [{"securityContext": {"runAsNonRoot": True}}]}},
                ],
            },
        },
    ]
    policies = [make_policy("p", rules)]
    resources = [
        pod(spec={"volumes": [{"name": "v", "emptyDir": {}}],
                  "containers": [{"name": "a"}]}),
        pod(spec={"volumes": [{"name": "v", "hostPath": {"path": "/"}}],
                  "containers": [{"securityContext": {"runAsNonRoot": True}}]}),
        pod(spec={"securityContext": {"runAsNonRoot": True},
                  "containers": [{"name": "a"}]}),
        pod(spec={"securityContext": {"runAsNonRoot": True},
                  "containers": [{"securityContext": {"runAsNonRoot": False}}]}),
        pod(spec={"containers": [{"securityContext": {"runAsNonRoot": True}},
                                 {"securityContext": {"runAsNonRoot": True}}]}),
    ]
    check_parity(policies, resources)


def test_match_exclude_selectors_namespaces():
    rules = [
        {
            "name": "ns-gate",
            "match": {"any": [{"resources": {"kinds": ["Pod"], "namespaces": ["prod-*"],
                                             "selector": {"matchLabels": {"app": "web"}}}}]},
            "exclude": {"any": [{"resources": {"names": ["skip-me"]}}]},
            "validate": {"pattern": {"spec": {"=(hostNetwork)": "false"}}},
        }
    ]
    policies = [make_policy("gated", rules)]
    resources = [
        pod(ns="prod-eu", labels={"app": "web"}, spec={"hostNetwork": True}),
        pod(ns="prod-eu", labels={"app": "db"}, spec={"hostNetwork": True}),
        pod(ns="dev", labels={"app": "web"}, spec={"hostNetwork": True}),
        pod(name="skip-me", ns="prod-us", labels={"app": "web"}, spec={"hostNetwork": True}),
        pod(ns="prod-us", labels={"app": "web"}, spec={}),
    ]
    check_parity(policies, resources)


def test_glob_leaf_operand():
    rules = [
        {
            "name": "image-registry",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {
                "pattern": {"spec": {"containers": [{"image": "registry.corp.io/* | docker.io/*"}]}},
            },
        }
    ]
    policies = [make_policy("images", rules)]
    resources = [
        pod(spec={"containers": [{"image": "registry.corp.io/app:1"}]}),
        pod(spec={"containers": [{"image": "evil.io/app"}]}),
        pod(spec={"containers": [{"image": "docker.io/nginx"},
                                 {"image": "registry.corp.io/x"}]}),
        pod(spec={"containers": [{"image": "docker.io/nginx"}, {"image": "quay.io/x"}]}),
        pod(spec={"containers": [{"name": "no-image"}]}),
    ]
    check_parity(policies, resources)


def test_operator_leaves():
    rules = [
        {
            "name": "limits",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {
                "pattern": {
                    "spec": {
                        "containers": [
                            {"resources": {"limits": {"memory": "<=1Gi", "cpu": "<2"}}}
                        ]
                    }
                },
            },
        }
    ]
    policies = [make_policy("limits", rules)]
    resources = [
        pod(spec={"containers": [{"resources": {"limits": {"memory": "512Mi", "cpu": "500m"}}}]}),
        pod(spec={"containers": [{"resources": {"limits": {"memory": "2Gi", "cpu": "1"}}}]}),
        pod(spec={"containers": [{"resources": {"limits": {"memory": "1Gi", "cpu": 2}}}]}),
        pod(spec={"containers": [{"resources": {"limits": {"memory": "1024Mi", "cpu": "1.5"}}}]}),
        pod(spec={"containers": [{"name": "a"}]}),
    ]
    check_parity(policies, resources)


def test_host_fallback_rules_complete():
    rules = [
        {
            "name": "foreach-rule",  # unsupported on device
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {
                "foreach": [
                    {"list": "request.object.spec.containers",
                     "pattern": {"image": "docker.io/*"}}
                ],
            },
        },
        HOST_NS_RULE,
    ]
    policies = [make_policy("mixed", rules)]
    resources = [
        pod(spec={"containers": [{"image": "docker.io/a"}], "hostPID": True}),
        pod(spec={"containers": [{"image": "evil.io/a"}]}),
    ]
    eng = check_parity(policies, resources)
    assert eng.coverage() == (1, 2)


def test_engine_buckets_batch_shapes(monkeypatch, no_verdict_cache):
    """Two odd-sized batches must reuse one compiled shape (SURVEY §7
    recompilation churn: bucketing lives in the engine, not in caller
    convention)."""
    from kyverno_tpu.tpu.engine import TpuEngine

    pol = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "b"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"pattern": {"metadata": {"name": "?*"}}},
        }]},
    })
    eng = TpuEngine([pol])
    shapes = []
    real_fn = eng.cps.packed_fn()

    def spying(buffers, layout):
        shapes.append(next(shape[0] for name, _, shape, _ in layout
                           if name == "norm_hi"))
        return real_fn(buffers, layout)

    monkeypatch.setattr(eng.cps, "packed_fn", lambda: spying)

    def mk(i):
        return {"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": f"x{i}", "namespace": "d"}, "spec": {}}

    r1 = eng.scan([mk(i) for i in range(3)])
    r2 = eng.scan([mk(i) for i in range(13)])
    assert shapes == [16, 16]  # both bucket to MIN_BUCKET
    assert r1.verdicts.shape[1] == 3 and r2.verdicts.shape[1] == 13
    r3 = eng.scan([mk(i) for i in range(17)])
    assert shapes[-1] == 32 and r3.verdicts.shape[1] == 17


def test_static_context_folding():
    """Literal `variable` context entries constant-fold at compile so
    the rule lowers to device; jmesPath-only (request-reading) entries
    must NOT fold — an empty compile context would bake their default
    arm in as a wrong constant."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.tpu.compiler import compile_policy_set

    def policy(context, conditions):
        return ClusterPolicy.from_dict({
            "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
            "metadata": {"name": "p"},
            "spec": {"rules": [{
                "name": "r", "context": context,
                "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
                "validate": {"message": "m",
                             "deny": {"conditions": {"any": conditions}}},
            }]}})

    static = policy(
        [{"name": "maxmem", "variable": {"value": "1Gi"}}],
        [{"key": "{{ request.object.spec.mem }}", "operator": "GreaterThan",
          "value": "{{ maxmem }}"}])
    cps = compile_policy_set([static])
    assert cps.coverage() == (1, 1), cps.rules[0].fallback_reason

    # request-reading jmesPath entries now lower by INLINING the
    # expression (with its default) into the references, so per-request
    # values come from the resource rows, never from a baked constant
    dynamic = policy(
        [{"name": "replicas", "variable": {
            "jmesPath": "request.object.spec.replicas", "default": 1}}],
        [{"key": "{{ replicas }}", "operator": "GreaterThan", "value": 10}])
    cps = compile_policy_set([dynamic])
    assert cps.coverage() == (1, 1), cps.rules[0].fallback_reason
    from kyverno_tpu.tpu.engine import TpuEngine as _Eng

    deng = _Eng([dynamic])
    dres = deng.scan([
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "a"}, "spec": {"replicas": 20}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "b"}, "spec": {"replicas": 5}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "c"}, "spec": {}},  # default arm: 1 -> pass
    ])
    assert [int(dres.verdicts[0, i]) for i in range(3)] == [2, 0, 0]
    # a truly dynamic entry (apiCall) lowers via a host-resolved
    # operand slot: the condition compares on device, the value loads
    # through the real loaders per batch
    apicall = policy(
        [{"name": "pods", "apiCall": {"urlPath": "/api/v1/pods"}}],
        [{"key": "{{ pods }}", "operator": "Equals", "value": 1}])
    cps = compile_policy_set([apicall])
    assert cps.coverage() == (1, 1)
    assert len(cps.dyn_slots) == 1
    # ... but an UNREFERENCED dynamic entry drops away (deferred
    # loading never materializes it)
    unused = policy(
        [{"name": "pods", "apiCall": {"urlPath": "/api/v1/pods"}}],
        [{"key": "{{ request.object.spec.x }}", "operator": "Equals",
          "value": 1}])
    assert compile_policy_set([unused]).coverage() == (1, 1)

    # folded constants evaluate correctly end to end
    from kyverno_tpu.tpu.engine import TpuEngine

    eng = TpuEngine([static])
    res = eng.scan([
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "big"}, "spec": {"mem": "2Gi"}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "ok"}, "spec": {"mem": "512Mi"}},
    ])
    assert res.verdicts[0, 0] == 2 and res.verdicts[0, 1] == 0  # FAIL, PASS


def test_literal_key_condition_constant_folds():
    """Non-variable condition keys (e.g. folded constants) lower as
    compile-time constants via the scalar oracle."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.tpu.engine import TpuEngine

    p = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "p"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"message": "m", "deny": {"conditions": {"all": [
                {"key": "prod", "operator": "Equals", "value": "prod"},
                {"key": "{{ request.object.spec.bad }}", "operator": "Equals",
                 "value": True},
            ]}}},
        }]}})
    eng = TpuEngine(p if isinstance(p, list) else [p])
    assert eng.coverage() == (1, 1), eng.cps.rules[0].fallback_reason
    res = eng.scan([
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "a"},
         "spec": {"bad": True}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "b"},
         "spec": {"bad": False}},
    ])
    assert res.verdicts[0, 0] == 2 and res.verdicts[0, 1] == 0


def test_deprecated_in_notin_device_parity():
    """Deprecated In/NotIn lower to device for scalar-chain keys with
    list values; verdicts must match the scalar engine exactly,
    including the strict list-key semantics (in.go:35-43: non-string
    elements force false for both directions)."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.tpu.engine import TpuEngine, build_scan_context

    def policy(op, value):
        return ClusterPolicy.from_dict({
            "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
            "metadata": {"name": "p"},
            "spec": {"rules": [{
                "name": "r",
                "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
                "validate": {"message": "m", "deny": {"conditions": {"any": [
                    {"key": "{{ request.object.spec.val }}",
                     "operator": op, "value": value}]}}},
            }]}})

    pods = [
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "scalar-hit"},
         "spec": {"val": "a"}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "scalar-miss"},
         "spec": {"val": "z"}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "num-key"},
         "spec": {"val": 2}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "missing"},
         "spec": {}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "map-key"},
         "spec": {"val": {"m": 1}}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "list-all-in"},
         "spec": {"val": ["a", "b"]}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "list-partial"},
         "spec": {"val": ["a", "z"]}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "list-nonstr"},
         "spec": {"val": ["a", 2]}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "list-empty"},
         "spec": {"val": []}},
    ]
    scalar = Engine()
    code = {"pass": 0, "skip": 1, "fail": 2, "error": 4}
    for op in ("In", "NotIn"):
        for value in (["a", "b", "2"], ["z"]):
            p = policy(op, value)
            eng = TpuEngine([p])
            assert eng.coverage() == (1, 1), eng.cps.rules[0].fallback_reason
            res = eng.scan(pods)
            for ci, pod in enumerate(pods):
                resp = scalar.validate(build_scan_context(p, pod, {}))
                want = code[resp.policy_response.rules[0].status]
                got = int(res.verdicts[0, ci])
                assert got == want, (op, value, pod["metadata"]["name"], got, want)


def test_deprecated_in_operation_key_and_nonstring_values():
    """Regressions: {{request.operation}} In [...] must not invert on
    device; non-string literal values force host fallback (in.go
    invalidType vs device sprint-coercion)."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.tpu.engine import TpuEngine

    op_pol = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "p"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "preconditions": {"all": [{
                "key": "{{ request.operation }}", "operator": "In",
                "value": ["CREATE", "UPDATE"]}]},
            "validate": {"message": "m",
                         "pattern": {"metadata": {"name": "allowed"}}},
        }]}})
    eng = TpuEngine([op_pol])
    assert eng.coverage() == (1, 1), eng.cps.rules[0].fallback_reason
    pod = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": "other"}, "spec": {}}
    res = eng.scan([pod], operations=["CREATE"])
    assert int(res.verdicts[0, 0]) == 2  # precondition held -> pattern FAIL
    res = eng.scan([pod], operations=["DELETE"])
    assert int(res.verdicts[0, 0]) == 1  # precondition false -> SKIP

    mixed = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "p2"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
            "validate": {"message": "m", "deny": {"conditions": {"any": [{
                "key": "{{ request.object.spec.val }}", "operator": "In",
                "value": ["a", 2]}]}}},
        }]}})
    eng = TpuEngine([mixed])
    assert eng.coverage() == (0, 1)  # non-string values stay host


def test_wildcard_label_selector_device_parity():
    """matchLabels with glob keys/values lower to device via the label
    byte lanes; verdicts match the scalar engine, including the
    '0'-substitution fallback when nothing glob-matches."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.tpu.engine import TpuEngine, build_scan_context

    policy = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "wild"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {
                "kinds": ["Pod"],
                "selector": {"matchLabels": {"app*": "prod-?"}}}}]},
            "validate": {"message": "m",
                         "pattern": {"metadata": {"name": "!bad"}}},
        }]}})
    pods = [
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad", "labels": {"apptier": "prod-1"}},
         "spec": {}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "ok", "labels": {"apptier": "prod-1"}},
         "spec": {}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad", "labels": {"apptier": "staging"}},
         "spec": {}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad", "labels": {"other": "prod-1"}},
         "spec": {}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad", "labels": {"app0": "prod-0"}},
         "spec": {}},  # the '0'-substituted exact pair
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "bad"},
         "spec": {}},
    ]
    eng = TpuEngine([policy])
    assert eng.coverage() == (1, 1), eng.cps.rules[0].fallback_reason
    res = eng.scan(pods)
    code = {"pass": 0, "skip": 1, "fail": 2, "error": 4}
    scalar = Engine()
    for ci, pod in enumerate(pods):
        resp = scalar.validate(build_scan_context(policy, pod, {}))
        want = code[resp.policy_response.rules[0].status] \
            if resp.policy_response.rules else 3
        assert int(res.verdicts[0, ci]) == want, (ci, int(res.verdicts[0, ci]), want)


def test_wildcard_selector_collision_and_invalid_substitution_stay_host():
    """Dict-collision and resource-dependent-validity cases cannot
    lower soundly: they must fall back to host, not silently diverge."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.tpu.engine import TpuEngine

    def pol(match_labels):
        return ClusterPolicy.from_dict({
            "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
            "metadata": {"name": "p"},
            "spec": {"rules": [{
                "name": "r",
                "match": {"any": [{"resources": {
                    "kinds": ["Pod"],
                    "selector": {"matchLabels": match_labels}}}]},
                "validate": {"message": "m",
                             "pattern": {"metadata": {"name": "?*"}}}}]}})

    # wildcard key can expand onto the literal "app" entry -> host
    assert TpuEngine([pol({"app": "x", "app*": "y*"})]).coverage() == (0, 1)
    # two wildcard entries can collide with each other -> host
    assert TpuEngine([pol({"a*": "x", "ap*": "y"})]).coverage() == (0, 1)
    # '0'-substitution of a 64+ char glob key is invalid label syntax,
    # but a real label could substitute validly -> host, not constant
    long_key = "k" * 70 + "*"
    assert TpuEngine([pol({long_key: "v"})]).coverage() == (0, 1)


def test_wildcard_selector_invalid_label_syntax_goes_host():
    """A resource carrying a syntactically invalid label key makes the
    scalar engine ERROR the wildcard selector ('failed to parse
    selector' -> not matched) — on device the resource must take the
    HOST path, not glob-match (parity via fallback)."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.tpu.engine import TpuEngine, build_scan_context

    policy = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "wild"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {
                "kinds": ["Pod"],
                "selector": {"matchLabels": {"app*": "x"}}}}]},
            "validate": {"message": "m",
                         "pattern": {"metadata": {"name": "!bad"}}},
        }]}})
    pods = [
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad", "labels": {"app-": "x"}}, "spec": {}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad", "labels": {"apptier": "x"}}, "spec": {}},
    ]
    eng = TpuEngine([policy])
    assert eng.coverage() == (1, 1)
    res = eng.scan(pods)
    code = {"pass": 0, "skip": 1, "fail": 2, "error": 4}
    scalar = Engine()
    for ci, pod in enumerate(pods):
        resp = scalar.validate(build_scan_context(policy, pod, {}))
        want = code[resp.policy_response.rules[0].status] \
            if resp.policy_response.rules else 3
        assert int(res.verdicts[0, ci]) == want, (ci, int(res.verdicts[0, ci]), want)


def test_value_only_wildcard_multi_entries_lower():
    """Multiple value-only glob entries keep literal keys — no dict
    collision is possible, so they lower and match scalar verdicts."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.tpu.engine import TpuEngine, build_scan_context

    policy = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "wild2"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {
                "kinds": ["Pod"],
                "selector": {"matchLabels": {"app": "prod-*",
                                             "tier": "web-?"}}}}]},
            "validate": {"message": "m",
                         "pattern": {"metadata": {"name": "!bad"}}},
        }]}})
    eng = TpuEngine([policy])
    assert eng.coverage() == (1, 1), eng.cps.rules[0].fallback_reason
    pods = [
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad",
                      "labels": {"app": "prod-1", "tier": "web-a"}}, "spec": {}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad",
                      "labels": {"app": "prod-1", "tier": "webXa"}}, "spec": {}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "bad", "labels": {"app": "prod-1"}}, "spec": {}},
    ]
    res = eng.scan(pods)
    code = {"pass": 0, "skip": 1, "fail": 2, "error": 4}
    scalar = Engine()
    for ci, pod in enumerate(pods):
        resp = scalar.validate(build_scan_context(policy, pod, {}))
        want = code[resp.policy_response.rules[0].status] \
            if resp.policy_response.rules else 3
        assert int(res.verdicts[0, ci]) == want, (ci, int(res.verdicts[0, ci]), want)


def test_userinfo_key_membership_parity():
    """{{ request.userInfo.groups }} membership conditions lower to the
    RBAC identity lanes; device verdicts match the scalar oracle for
    present, absent and empty identities."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.engine.match import RequestInfo
    from kyverno_tpu.tpu.engine import (TpuEngine, _scalar_rule_verdicts,
                                        build_scan_context)

    pol = ClusterPolicy.from_dict({
        "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
        "metadata": {"name": "p"},
        "spec": {"rules": [{
            "name": "r",
            "match": {"any": [{"resources": {"kinds": ["Role"]}}]},
            "preconditions": {"all": [
                {"key": "{{ request.operation }}", "operator": "AnyIn",
                 "value": ["UPDATE", "DELETE"]},
                {"key": "{{ request.userInfo.groups }}",
                 "operator": "AllNotIn", "value": ["system:masters"]}]},
            "validate": {"message": "m", "deny": {}},
        }]}})
    eng = TpuEngine([pol])
    assert eng.cps.coverage() == (1, 1), eng.cps.rules[0].fallback_reason
    scal = Engine()
    role = {"apiVersion": "rbac.authorization.k8s.io/v1", "kind": "Role",
            "metadata": {"name": "r1", "namespace": "d"}}
    cases = [
        ("UPDATE", RequestInfo(username="u", groups=["system:masters", "x"])),
        ("UPDATE", RequestInfo(username="u", groups=["devs"])),
        ("CREATE", RequestInfo(username="u", groups=["devs"])),
        ("DELETE", RequestInfo(username="u", groups=[])),
        ("UPDATE", None),
    ]
    res = eng.scan([role] * len(cases), {},
                   operations=[c[0] for c in cases],
                   admission_infos=[c[1] for c in cases])
    for i, (op, info) in enumerate(cases):
        pctx = build_scan_context(pol, role, {}, op, info)
        sv = _scalar_rule_verdicts(scal, pol, pctx).get("r")
        assert int(res.verdicts[0, i]) == sv, (i, op, info)


def test_not_null_defaults_loader_semantics_parity():
    """Inlined context-variable defaults use not_null() — the loader's
    null-only semantics, NOT jmespath || falsiness: an empty-string
    key keeps the empty string. Literal, chain and numeric defaults."""
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.tpu.engine import (TpuEngine, _scalar_rule_verdicts,
                                        build_scan_context)

    def mk(context, conds):
        return ClusterPolicy.from_dict({
            "apiVersion": "kyverno.io/v1", "kind": "ClusterPolicy",
            "metadata": {"name": "p"},
            "spec": {"rules": [{
                "name": "r", "context": context,
                "match": {"any": [{"resources": {"kinds": ["Pod"]}}]},
                "validate": {"message": "m", "deny": {"conditions": conds}},
            }]}})

    scal = Engine()
    pods = [
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "a"}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "example"}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "b", "generateName": "x"}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "c", "generateName": ""}},
    ]
    policies = [
        mk([{"name": "n", "variable": {
            "jmesPath": "request.object.metadata.generateName",
            "default": "example"}}],
           [{"key": "{{ n }}", "operator": "NotEquals", "value": "example"}]),
        mk([{"name": "n", "variable": {
            "jmesPath": "request.object.metadata.generateName",
            "default": "{{ request.object.metadata.name }}"}}],
           [{"key": "{{ n }}", "operator": "NotEquals", "value": "example"}]),
    ]
    for pol in policies:
        eng = TpuEngine([pol])
        assert eng.cps.coverage() == (1, 1), eng.cps.rules[0].fallback_reason
        res = eng.scan(pods, {})
        for i, r in enumerate(pods):
            pctx = build_scan_context(pol, r, {})
            sv = _scalar_rule_verdicts(scal, pol, pctx).get("r")
            assert int(res.verdicts[0, i]) == sv, (pol.name, i)
