"""The plain reference gives what the program's scalar engine gives on the
cells' Pods: the reference was written from Kyverno's semantics and
imports nothing of the program; this test is where the two meet."""

import os
import random
import sys

import pytest

import pss_reference as ref
from harness import core, gen

ROOT = os.path.dirname(core.BENCH)


@pytest.fixture(scope="module")
def scalar():
    sys.path.insert(0, ROOT)
    from kyverno_tpu.api.policy import ClusterPolicy
    from kyverno_tpu.engine.engine import Engine
    from kyverno_tpu.engine.match import RequestInfo
    from kyverno_tpu.engine.policycontext import build_scan_context

    policies = ref.load_policies(os.path.join(ROOT, "benchmark/policies/pss"), "Enforce")
    compiled = [ClusterPolicy.from_dict(p) for p in policies]
    engine = Engine(background=True)

    def results(pod, operation):
        info = RequestInfo(username="u") if operation else None
        out = {}
        for policy in compiled:
            pctx = build_scan_context(policy, pod, {}, operation, info)
            for rr in engine.validate(pctx).policy_response.rules:
                out[(policy.name, rr.name)] = rr.status
        return out

    return policies, results


@pytest.mark.parametrize("config", ["pss-restricted", "pss-restricted-enforce"])
@pytest.mark.parametrize("seed", [3, 266486846])
def test_reference_agrees_with_the_scalar_engine(scalar, config, seed):
    policies, results = scalar
    cluster = core.load_json(os.path.join(core.BENCH, "configs", config + ".json"))["cluster"]
    rng = random.Random(seed)
    deps = gen.make_deployments(seed, cluster, 4000)
    assert len(deps) > 500
    for d in deps:
        pod = d.make_pod(rng)
        for op in ("", "CREATE"):
            assert ref.rule_results(policies, pod, op) == results(pod, op), pod


def test_every_violation_is_denied_and_compliant_pods_are_allowed():
    policies = ref.load_policies(os.path.join(ROOT, "benchmark/policies/pss"), "Enforce")
    rng = random.Random(5)
    for violation in (None,) + gen.VIOLATIONS:
        tpl = gen.draw_restricted_template(rng, violation)
        pod = gen.Deployment("d", "ns", 1, tpl).make_pod(rng)
        allowed, denied = ref.admission_verdict(policies, pod)
        assert allowed is (violation is None), (violation, denied)


def test_audit_never_denies():
    policies = ref.load_policies(os.path.join(ROOT, "benchmark/policies/pss"), "Audit")
    rng = random.Random(6)
    for violation in gen.VIOLATIONS:
        pod = gen.Deployment("d", "ns", 1, gen.draw_restricted_template(rng, violation)).make_pod(rng)
        assert ref.admission_verdict(policies, pod) == (True, set())
