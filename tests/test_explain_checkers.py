"""The why-pending explainer builds each pod's inter-pod checkers once and
walks the nodes with them (core/predicates.unschedulable_reason_counts):
its breakdown must equal the per-pair chain (check_node_validity on every
node), key for key, on seeded random clusters that exercise every
predicate of the chain — and the /debug/pods route, which shares it, must
serve that same breakdown."""

import json
import random
import urllib.request

import pytest

from tpu_scheduler.api.objects import PodAntiAffinityTerm, Taint, Toleration, TopologySpreadConstraint
from tpu_scheduler.core.predicates import check_node_validity, unschedulable_reason_counts
from tpu_scheduler.core.snapshot import ClusterSnapshot
from tpu_scheduler.runtime.fake_api import FakeApiServer
from tpu_scheduler.runtime.http_api import HttpApiServer
from tpu_scheduler.testing import make_node, make_pod
from tpu_scheduler.utils.events import FlightRecorder

ZONES = ["a", "b", "c"]
APPS = ["web", "db", "cache", "queue"]
NAMESPACES = ["default", "team-x"]


def per_pair(pod, snap):
    """The reference breakdown: the whole chain, rebuilt for every node."""
    counts, feasible = {}, 0
    for node in snap.nodes:
        reason = check_node_validity(pod, node, snap)
        if reason is None:
            feasible += 1
        else:
            counts[reason.value] = counts.get(reason.value, 0) + 1
    return counts, feasible, len(snap.nodes)


def terms(rng, key):
    return [PodAntiAffinityTerm(match_labels={"app": rng.choice(APPS)}, topology_key=key)]


def pending_pod(rng, i, kind):
    ns = rng.choice(NAMESPACES) if kind == "cross-namespace" else "default"
    spec = {}
    labels = {"app": rng.choice(APPS)}
    if kind in ("anti-affinity", "cross-namespace"):
        spec["anti_affinity"] = terms(rng, rng.choice(["zone", "name"]))
    if kind in ("pod-affinity", "cross-namespace"):
        shape = rng.randrange(3)
        if shape == 0:  # matched by placed pods
            spec["pod_affinity"] = terms(rng, "zone")
        elif shape == 1:  # matches no placed pod, but the pod itself: the bootstrap waives it
            labels["solo"] = f"s{i}"
            spec["pod_affinity"] = [PodAntiAffinityTerm(match_labels={"solo": f"s{i}"}, topology_key="zone")]
        else:  # matches nothing anywhere: unsatisfiable
            spec["pod_affinity"] = [PodAntiAffinityTerm(match_labels={"app": "nobody"}, topology_key="zone")]
    if kind in ("spread", "cross-namespace"):
        spec["topology_spread"] = [
            TopologySpreadConstraint(topology_key="zone", max_skew=rng.choice([1, 2]), match_labels={"app": labels["app"]})
        ]
    if kind == "node-local":
        spec["node_selector"] = {"zone": rng.choice(ZONES)} if rng.random() < 0.5 else None
        spec["anti_affinity"] = terms(rng, "zone") if rng.random() < 0.5 else None
    if rng.random() < 0.5:
        spec["tolerations"] = [Toleration(key="dedicated", operator="Equal", value="infra", effect="NoSchedule")]
    cpu = rng.choice(["500m", "2", "6", "12"])  # the larger ones fail resources first on the small nodes
    return make_pod(f"p{i}", namespace=ns, cpu=cpu, memory="1Gi", labels=labels, priority=rng.randrange(5), **spec)


def cluster(seed, kind, n_nodes=24, n_placed=60, n_pending=16):
    rng = random.Random(f"{seed}/{kind}")
    nodes = []
    for i in range(n_nodes):
        labels = {"name": f"n{i}"}
        if rng.random() < 0.8:  # the rest are keyless under "zone": singleton domains, exempt from spread
            labels["zone"] = rng.choice(ZONES)
        taints = [Taint(key="dedicated", value="infra", effect="NoSchedule")] if rng.random() < 0.2 else None
        nodes.append(make_node(f"n{i}", cpu=rng.choice([4, 8, 16]), memory="64Gi", labels=labels, taints=taints))
    placed = []
    for i in range(n_placed):
        node = rng.choice(nodes)
        anti = terms(rng, rng.choice(["zone", "name"])) if rng.random() < 0.3 else None  # direction-B carriers
        placed.append(
            make_pod(f"q{i}", namespace=rng.choice(NAMESPACES), cpu="250m", memory="512Mi", node_name=node.name,
                     phase="Running", labels={"app": rng.choice(APPS)}, anti_affinity=anti)
        )
    pending = [pending_pod(rng, i, kind) for i in range(n_pending)]
    return nodes, placed + pending


@pytest.mark.parametrize("kind", ["anti-affinity", "pod-affinity", "spread", "cross-namespace", "node-local"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_breakdown_equals_per_pair_chain(seed, kind):
    nodes, pods = cluster(seed, kind)
    snap = ClusterSnapshot.build(nodes, pods)
    seen = set()
    for pod in snap.pending_pods():
        got = unschedulable_reason_counts(pod, snap)
        assert got == per_pair(pod, snap), pod.metadata.name
        seen.update(got[0])
    assert seen, "the cluster rejected no node: it exercises nothing"


def test_debug_pods_route_serves_the_per_pair_breakdown():
    nodes, pods = cluster(7, "cross-namespace")
    api = FakeApiServer()
    api.load(nodes, pods)
    server = HttpApiServer(api, recorder=FlightRecorder()).start()
    try:
        snap = ClusterSnapshot.build(api.list_nodes(), api.list_pods())
        for pod in snap.pending_pods():
            url = f"{server.base_url}/debug/pods/{pod.metadata.namespace}/{pod.metadata.name}"
            with urllib.request.urlopen(url) as r:
                why = json.load(r)["why_pending"]
            counts, feasible, total = per_pair(pod, snap)
            assert (why["reasons"], why["feasible_nodes"], why["nodes_total"]) == (counts, feasible, total)
    finally:
        server.stop()
