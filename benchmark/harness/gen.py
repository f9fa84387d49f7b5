"""Seeded generators: the cluster, its rollout waves, and admission
arrivals.

Every draw comes from the run's seed. What sets the amount of work is
the same for every seed: the multiset of Deployment replica counts, the
wave size, the multiset of inter-arrival gaps and the rate's phases. A
seed changes which namespace and template each Deployment gets, the
order in which they roll, and the order of the gaps.

The securityContext distribution of the scan cluster is a copy of
``bench.make_snapshot``'s per-Pod draw (the repo's BASELINE config #2),
kept here so that an edit there does not move this yardstick.
"""

from __future__ import annotations

import copy
import json
import math
import random
import zlib
from typing import Dict, List, Optional, Tuple

GOLDEN = 0.6180339887498949
# the alphabet of Kubernetes' generated names (apimachinery util/rand)
ALNUM = "bcdfghjklmnpqrstvwxz2456789"


def stream(seed: int, name: str) -> random.Random:
    """An independent generator per purpose, keyed by (seed, name)."""
    return random.Random(f"{seed}/{name}")


def replica_sizes(total: int, lo: int, hi: int, alpha: float) -> List[int]:
    """Heavy-tailed (Pareto ``alpha``, clipped to [lo, hi]) replica counts
    summing to ``total``, drawn at golden-ratio quantiles so that the
    multiset does not depend on the seed."""
    sizes: List[int] = []
    acc = 0
    k = 0
    while acc < total:
        k += 1
        u = (k * GOLDEN) % 1.0
        r = max(lo, min(hi, int(u ** (-1.0 / alpha))))
        r = min(r, total - acc)
        sizes.append(r)
        acc += r
    return sizes


def zipf_weights(n: int, s: float) -> List[float]:
    return [1.0 / (i + 1) ** s for i in range(n)]


def exponential_gaps(n: int, rate: float) -> List[float]:
    """``n`` inter-arrival gaps of a Poisson process at ``rate``, taken at
    the midpoints of n equal-probability bins: the same multiset for
    every seed, with mean 1/rate."""
    return [-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)]


def modulate(offsets: List[float], phases: List[List[float]]) -> List[float]:
    """Arrival times of a Poisson process whose rate follows ``phases``,
    a cycle of ``[seconds, rate factor]`` pairs whose factors average 1
    over the cycle, from the ``offsets`` of one at the mean rate: each
    offset is stretched by the inverse of the cycle's cumulative rate,
    so a phase at factor 4 takes four times its share of arrivals and
    one at 0 takes none. The count of arrivals does not change."""
    cycle = sum(d for d, _ in phases)
    mass = sum(d * f for d, f in phases)
    if cycle <= 0 or abs(mass - cycle) > 1e-9 * cycle:
        raise ValueError(f"rate factors of {phases} do not average 1")
    out = []
    for u in offsets:
        k, rem = divmod(u, cycle)
        t = k * cycle
        for d, f in phases:
            if f > 0 and rem <= d * f:
                t += rem / f
                break
            rem -= d * f
            t += d
        out.append(t)
    return out


def weighted_order(rng: random.Random, items: list, weights: List[float]) -> list:
    """A weighted random permutation (Efraimidis-Spirakis keys)."""
    keyed = [(rng.random() ** (1.0 / w), i) for i, w in enumerate(weights)]
    keyed.sort(reverse=True)
    return [items[i] for _, i in keyed]


# ---------------------------------------------------------------------------
# Pod templates


def draw_snapshot_template(rng: random.Random) -> dict:
    """One Pod template with ``bench.make_snapshot``'s securityContext
    distribution (copied draw for draw, without the name and namespace)."""
    containers = []
    for c in range(rng.randint(1, 3)):
        sc: Dict = {}
        if rng.random() < 0.3:
            sc["privileged"] = rng.choice([True, False])
        if rng.random() < 0.4:
            sc["allowPrivilegeEscalation"] = rng.choice([True, False])
        if rng.random() < 0.3:
            sc["runAsNonRoot"] = rng.choice([True, False])
        if rng.random() < 0.3:
            sc["seccompProfile"] = {"type": rng.choice(
                ["RuntimeDefault", "Unconfined", "Localhost"])}
        if rng.random() < 0.2:
            sc["capabilities"] = {"add": rng.sample(
                ["CHOWN", "KILL", "SYS_ADMIN", "NET_RAW"], k=rng.randint(1, 2))}
        if rng.random() < 0.15:
            sc["capabilities"] = {"drop": ["ALL"]}
        containers.append({
            "name": f"c{c}", "image": rng.choice(["nginx", "redis"]),
            **({"securityContext": sc} if sc else {}),
            "resources": {"limits": {"memory": rng.choice(["256Mi", "1Gi", "4Gi"])}},
            **({"ports": [{"containerPort": 80 + c,
                           **({"hostPort": 8080} if rng.random() < 0.1 else {})}]}
               if rng.random() < 0.3 else {}),
        })
    spec: Dict = {"containers": containers}
    if rng.random() < 0.2:
        spec["hostNetwork"] = rng.choice([True, False])
    if rng.random() < 0.3:
        spec["volumes"] = [{"name": "v", rng.choice(
            ["emptyDir", "configMap", "hostPath", "secret"]): {}}]
    if rng.random() < 0.3:
        spec["securityContext"] = {"runAsUser": rng.choice([0, 1000])}
    annotations = None
    if rng.random() < 0.1:
        annotations = {
            "container.apparmor.security.beta.kubernetes.io/c0":
                rng.choice(["runtime/default", "localhost/p1", "unconfined"])}
    return {"spec": spec, "annotations": annotations}


# One restricted control broken per violating template; every entry is a
# field that the scan cluster's distribution also sets.
VIOLATIONS = (
    "privileged", "allow_privilege_escalation", "add_capability",
    "no_drop_all", "run_as_root_container", "run_as_non_root_false",
    "seccomp_unconfined", "host_network", "host_path", "host_port",
    "apparmor_unconfined",
)


def draw_restricted_template(rng: random.Random,
                             violation: Optional[str]) -> dict:
    """A Pod template that satisfies the whole restricted set, or that
    breaks exactly the one control named by ``violation``."""
    containers = []
    for c in range(rng.randint(1, 3)):
        sc = {"allowPrivilegeEscalation": False,
              "capabilities": {"drop": ["ALL"]},
              "runAsNonRoot": True}
        if rng.random() < 0.5:
            sc["readOnlyRootFilesystem"] = True
        cont = {"name": f"c{c}", "image": rng.choice(["nginx", "redis", "envoy"]),
                "securityContext": sc,
                "resources": {"limits": {"memory": rng.choice(["256Mi", "1Gi"])}}}
        if rng.random() < 0.5:
            cont["ports"] = [{"containerPort": 8080 + c}]
        containers.append(cont)
    spec: Dict = {
        "containers": containers,
        "securityContext": {"runAsNonRoot": True,
                            "runAsUser": rng.choice([1000, 65532]),
                            "runAsGroup": rng.choice([1000, 3000]),
                            "seccompProfile": {"type": "RuntimeDefault"}},
    }
    if rng.random() < 0.6:
        spec["volumes"] = [{"name": "v", rng.choice(
            ["emptyDir", "configMap", "secret", "projected"]): {}}]
    annotations = None
    c0 = containers[0]["securityContext"]
    if violation == "privileged":
        c0["privileged"] = True
    elif violation == "allow_privilege_escalation":
        c0["allowPrivilegeEscalation"] = True
    elif violation == "add_capability":
        c0["capabilities"]["add"] = [rng.choice(["SYS_ADMIN", "NET_RAW"])]
    elif violation == "no_drop_all":
        del c0["capabilities"]
    elif violation == "run_as_root_container":
        c0["runAsUser"] = 0
    elif violation == "run_as_non_root_false":
        c0["runAsNonRoot"] = False
    elif violation == "seccomp_unconfined":
        spec["securityContext"]["seccompProfile"]["type"] = "Unconfined"
    elif violation == "host_network":
        spec["hostNetwork"] = True
    elif violation == "host_path":
        spec["volumes"] = [{"name": "v", "hostPath": {"path": "/var/log"}}]
    elif violation == "host_port":
        containers[0]["ports"] = [{"containerPort": 8080, "hostPort": 8080}]
    elif violation == "apparmor_unconfined":
        annotations = {
            "container.apparmor.security.beta.kubernetes.io/c0": "unconfined"}
    elif violation is not None:
        raise ValueError(f"unknown violation {violation!r}")
    return {"spec": spec, "annotations": annotations}


def draw_template(rng: random.Random, kind: str, violate_share: float) -> dict:
    if kind == "make_snapshot":
        return draw_snapshot_template(rng)
    if kind == "restricted":
        violation = (rng.choice(VIOLATIONS)
                     if rng.random() < violate_share else None)
        return draw_restricted_template(rng, violation)
    raise ValueError(f"unknown template distribution {kind!r}")


# ---------------------------------------------------------------------------
# Deployments and their Pods


class Deployment:
    def __init__(self, name: str, namespace: str, replicas: int, template: dict):
        self.name = name
        self.namespace = namespace
        self.replicas = replicas
        self.template = template
        self.generation = 1
        self.pods: List[dict] = []

    def template_hash(self) -> str:
        """pod-template-hash as Kubernetes spells it: the decimal hash,
        each byte mapped into its vowel-free alphabet (SafeEncodeString)."""
        body = json.dumps([self.name, self.generation, self.template],
                          sort_keys=True).encode()
        return "".join(ALNUM[ord(ch) % len(ALNUM)] for ch in str(zlib.crc32(body)))

    def make_pod(self, rng: random.Random) -> dict:
        """A Pod as the ReplicaSet controller creates it from the template."""
        h = self.template_hash()
        suffix = "".join(rng.choice(ALNUM) for _ in range(5))
        meta = {"name": f"{self.name}-{h}-{suffix}",
                "namespace": self.namespace,
                "uid": "%08x-%04x-%04x-%04x-%012x" % tuple(
                    rng.getrandbits(b) for b in (32, 16, 16, 16, 48)),
                "labels": {"app": self.name, "pod-template-hash": h}}
        if self.template["annotations"]:
            meta["annotations"] = dict(self.template["annotations"])
        spec = copy.deepcopy(self.template["spec"])
        for c in spec["containers"]:
            c["image"] = f"registry.example/{c['image']}:v{self.generation}"
        return {"apiVersion": "v1", "kind": "Pod", "metadata": meta,
                "spec": spec}


def make_deployments(seed: int, cluster: dict, total_pods: int) -> List[Deployment]:
    """Deployments whose replicas add up to ``total_pods``, spread over
    ``cluster['namespaces']`` namespaces with Zipf skew."""
    rng = stream(seed, "cluster")
    sizes = replica_sizes(total_pods, cluster["replicas_min"],
                          cluster["replicas_max"], cluster["replicas_alpha"])
    rng.shuffle(sizes)
    namespaces = [f"team-{i:02d}" for i in range(cluster["namespaces"])]
    weights = zipf_weights(len(namespaces), cluster["namespace_zipf_s"])
    deps = []
    for i, r in enumerate(sizes):
        ns = rng.choices(namespaces, weights)[0]
        tpl = draw_template(rng, cluster["templates"],
                            cluster.get("violate_share", 0.0))
        deps.append(Deployment(f"deploy-{i:05d}", ns, r, tpl))
    return deps


class Cluster:
    """The Pods of every Deployment, in their current generation."""

    def __init__(self, seed: int, cluster: dict, total_pods: int):
        self.seed = seed
        self.spec = cluster
        self.deployments = make_deployments(seed, cluster, total_pods)
        rng = stream(seed, "pods")
        for d in self.deployments:
            d.pods = [d.make_pod(rng) for _ in range(d.replicas)]

    def pods(self) -> List[dict]:
        return [p for d in self.deployments for p in d.pods]


class Rollout:
    """Rolling updates over the cluster, one wave of exactly ``wave_pods``
    Pod replacements at a time. Deployments roll in a seeded order
    weighted by their namespace's Zipf weight; a rollout that does not
    fit in a wave goes on in the next one, as a RollingUpdate does."""

    def __init__(self, cluster: Cluster, traffic: dict):
        self.cluster = cluster
        self.wave_pods = traffic["wave_pods"]
        self.new_template_share = traffic["new_template_share"]
        self.rng = stream(cluster.seed, "rollout")
        ns_rank = {f"team-{i:02d}": i for i in range(cluster.spec["namespaces"])}
        w = zipf_weights(cluster.spec["namespaces"], cluster.spec["namespace_zipf_s"])
        self.order = weighted_order(
            self.rng, cluster.deployments,
            [w[ns_rank[d.namespace]] for d in cluster.deployments])
        self.next_index = 0
        self.current: Optional[Deployment] = None
        self.pending: List[dict] = []

    def _start_next(self) -> None:
        d = self.order[self.next_index % len(self.order)]
        self.next_index += 1
        d.generation += 1
        if self.rng.random() < self.new_template_share:
            d.template = draw_template(self.rng, self.cluster.spec["templates"],
                                       self.cluster.spec.get("violate_share", 0.0))
        self.current = d
        self.pending = list(d.pods)

    def next_wave(self) -> List[Tuple[dict, dict]]:
        """(old Pod, successor Pod) pairs; the deployments' Pod lists
        already hold the successors when this returns."""
        out: List[Tuple[dict, dict]] = []
        while len(out) < self.wave_pods:
            if not self.pending:
                self._start_next()
            d = self.current
            old = self.pending.pop()
            new = d.make_pod(self.rng)
            d.pods[d.pods.index(old)] = new
            out.append((old, new))
        return out


# ---------------------------------------------------------------------------
# Admission


def admission_review(pod: dict, uid: str, user: dict) -> dict:
    meta = pod["metadata"]
    return {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
            "request": {
                "uid": uid, "operation": "CREATE",
                "kind": {"group": "", "version": "v1", "kind": "Pod"},
                "resource": {"group": "", "version": "v1", "resource": "pods"},
                "namespace": meta["namespace"], "name": meta["name"],
                "userInfo": dict(user),
                "object": pod}}


class AdmissionStream:
    """Pod CREATEs from the cluster's Deployment templates, as the
    ReplicaSet controller sends them (each Deployment in proportion to
    its replicas), at open-loop Poisson arrivals: steady, or with the
    rate following the traffic file's ``phases``."""

    def __init__(self, seed: int, cluster: dict, traffic: dict, name: str):
        self.deployments = make_deployments(seed, cluster, cluster["pods"])
        self.weights = [d.replicas for d in self.deployments]
        self.user = traffic["user"]
        self.phases = traffic.get("phases")
        self.rng = stream(seed, f"admission/{name}")
        self.name = name

    def requests(self, n: int) -> List[Tuple[dict, bytes]]:
        """``n`` (pod, AdmissionReview body) pairs, in send order."""
        out = []
        for i in range(n):
            d = self.rng.choices(self.deployments, self.weights)[0]
            pod = d.make_pod(self.rng)
            uid = f"{self.name}-{i:06d}-{self.rng.getrandbits(32):08x}"
            body = json.dumps(admission_review(pod, uid, self.user)).encode()
            out.append((pod, body))
        return out

    def schedule(self, rate: float, seconds: float) -> List[float]:
        """Send offsets (s) of ``round(rate * seconds)`` arrivals at the
        mean rate ``rate``."""
        n = max(1, round(rate * seconds))
        gaps = exponential_gaps(n, rate)
        self.rng.shuffle(gaps)
        t, out = 0.0, []
        for g in gaps:
            t += g
            out.append(t)
        return modulate(out, self.phases) if self.phases else out
