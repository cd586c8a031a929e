"""The why-pending explainer charges the chain's node-local predicates as
masks over the snapshot's node table, then walks the surviving nodes with
each pod's inter-pod checkers, built once
(core/predicates.unschedulable_reason_counts): its breakdown must equal the
per-pair chain (check_node_validity on every node), key for key, on seeded
random clusters that exercise every predicate of the chain and the edges of
each mask — and the /debug/pods route, which shares it, must serve that
same breakdown."""

import json
import random
import urllib.request

import pytest

from tpu_scheduler.api.objects import (
    LabelSelectorRequirement,
    NodeSelectorTerm,
    PodAntiAffinityTerm,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.core.predicates import INTER_POD_REASONS, check_node_validity, unschedulable_reason_counts
from tpu_scheduler.core.snapshot import ClusterSnapshot
from tpu_scheduler.runtime.controller import Scheduler
from tpu_scheduler.runtime.fake_api import FakeApiServer
from tpu_scheduler.runtime.http_api import HttpApiServer
from tpu_scheduler.testing import make_node, make_pod
from tpu_scheduler.utils.events import FlightRecorder

ZONES = ["a", "b", "c"]
APPS = ["web", "db", "cache", "queue"]
NAMESPACES = ["default", "team-x"]
GPU = "example.com/gpu"


def req(key, operator, *values):
    return LabelSelectorRequirement(key=key, operator=operator, values=list(values) if values else None)


# Required node-affinity terms over the "gen" label (integer, non-integer or
# missing) and the "disk" label (present on some nodes): every operator,
# Gt/Lt against integer and non-integer labels and wants, an empty term, an
# unknown operator.
AFFINITY_TERMS = [
    NodeSelectorTerm([req("gen", "In", "1", "2")]),
    NodeSelectorTerm([req("absent", "NotIn", "a")]),
    NodeSelectorTerm([req("gen", "NotIn", "3", "x")]),
    NodeSelectorTerm([req("disk", "Exists")]),
    NodeSelectorTerm([req("disk", "DoesNotExist")]),
    NodeSelectorTerm([req("gen", "Gt", "1")]),
    NodeSelectorTerm([req("gen", "Lt", "3")]),
    NodeSelectorTerm([req("gen", "Gt", "x")]),
    NodeSelectorTerm([req("gen", "Lt")]),
    NodeSelectorTerm([req("absent", "Gt", "0")]),
    NodeSelectorTerm([req("disk", "In", "ssd"), req("gen", "Lt", "3")]),
    NodeSelectorTerm([req("gen", "Weird", "1")]),
    NodeSelectorTerm([]),
    NodeSelectorTerm(None),
]

# Taint sets beside which soft (PreferNoSchedule) taints sit, and the
# tolerations the pods draw from.
TAINT_SETS = [
    None,
    [Taint(key="dedicated", value="infra", effect="PreferNoSchedule")],
    [Taint(key="dedicated", value="infra", effect="NoSchedule")],
    [Taint(key="dedicated", value="infra", effect="PreferNoSchedule"), Taint(key="gpu", value="yes", effect="NoExecute")],
    [Taint(key="dedicated", value="infra", effect="NoSchedule"), Taint(key="gpu", value="yes", effect="NoExecute")],
    [Taint(key="spot", value="", effect="PreferNoSchedule"), Taint(key="spot", value="", effect="NoSchedule")],
]
TOLERATION_SETS = [
    None,
    [Toleration(key="dedicated", operator="Equal", value="infra", effect="NoSchedule")],
    [Toleration(key="dedicated", operator="Exists")],
    [Toleration(key="gpu", operator="Exists", effect="NoExecute"), Toleration(key="spot", operator="Exists")],
    [Toleration(operator="Exists")],
    [Toleration(key="dedicated", operator="Equal", value="other")],
]

# The reason each kind of cluster below must charge at least once.
EXERCISES = {
    "node-affinity": "NodeAffinityMismatch",
    "cordon-labelless": "NodeUnschedulable",
    "soft-taints": "TaintNotTolerated",
    "extended": "NotEnoughResources",
}


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
    if kind == "node-affinity":
        spec["node_affinity"] = rng.sample(AFFINITY_TERMS, rng.choice([1, 1, 2]))
        cpu = "500m"
    if kind == "cordon-labelless":
        spec["node_selector"] = {"zone": rng.choice(ZONES)} if rng.random() < 0.5 else None
        cpu = "500m"  # leave the cordon to charge
    if kind == "soft-taints":
        spec["tolerations"] = rng.choice(TOLERATION_SETS)
    if kind == "extended":
        spec["extended"] = {GPU: rng.choice(["0", "0", "1", "3"])} if rng.random() < 0.7 else None
        cpu = rng.choice(["0", "500m", "2"])  # a zero request fails only where net cpu is negative
    return make_pod(f"p{i}", namespace=ns, cpu=cpu, memory="1Gi", labels=labels, priority=rng.randrange(5), **spec)


def cluster(seed, kind, n_nodes=24, n_placed=60, n_pending=16):
    rng = random.Random(f"{seed}/{kind}")
    nodes = []
    for i in range(n_nodes):
        labels = {"name": f"n{i}"}
        if rng.random() < 0.8:  # the rest are keyless under "zone": singleton domains, exempt from spread
            labels["zone"] = rng.choice(ZONES)
        taints = [Taint(key="dedicated", value="infra", effect="NoSchedule")] if rng.random() < 0.2 else None
        cpu, unschedulable, extended = rng.choice([4, 8, 16]), False, None
        if kind == "node-affinity":
            if rng.random() < 0.7:
                labels["gen"] = rng.choice(["1", "2", "3", "x", "+2", "007"])
            if rng.random() < 0.5:
                labels["disk"] = rng.choice(["ssd", "hdd"])
        if kind == "cordon-labelless":
            unschedulable = i % 4 == 1
            labels = None if i % 5 == 2 else labels
        if kind == "soft-taints":
            taints = rng.choice(TAINT_SETS)
        if kind == "extended":
            cpu = rng.choice([1, 4, 8])  # small nodes, oversubscribed below by the placed pods
            extended = {GPU: str(rng.randrange(3))} if rng.random() < 0.6 else None
        nodes.append(
            make_node(f"n{i}", cpu=cpu, memory="64Gi", labels=labels, taints=taints, unschedulable=unschedulable,
                      extended=extended)
        )
    placed = []
    for i in range(n_placed):
        node = rng.choice(nodes)
        anti = terms(rng, rng.choice(["zone", "name"])) if rng.random() < 0.3 else None  # direction-B carriers
        cpu, extended = "250m", None
        if kind == "extended":  # bound pods past the node's cpu and chips: negative net availability
            cpu = rng.choice(["250m", "250m", "3"])
            extended = {GPU: "2"} if rng.random() < 0.3 else None
        placed.append(
            make_pod(f"q{i}", namespace=rng.choice(NAMESPACES), cpu=cpu, memory="512Mi", node_name=node.name,
                     phase="Running", labels={"app": rng.choice(APPS)}, anti_affinity=anti, extended=extended)
        )
    pending = [pending_pod(rng, i, kind) for i in range(n_pending)]
    return nodes, placed + pending


@pytest.mark.parametrize(
    "kind",
    ["anti-affinity", "pod-affinity", "spread", "cross-namespace", "node-local", *EXERCISES],
)
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
    if kind in EXERCISES:
        assert EXERCISES[kind] in seen


def test_negative_availability_edges():
    """A zero extended request fails where the node's net chips are negative
    and passes where the node, or every node, lacks the resource; a positive
    one fails where every node lacks it; any cpu request, zero included,
    fails where net cpu is negative."""
    nodes = [
        make_node("short", cpu=1, extended={GPU: "1"}),
        make_node("plain", cpu=4),
        make_node("oversold", cpu=1),
    ]
    placed = [
        make_pod("chips", cpu="0", node_name="short", phase="Running", extended={GPU: "2"}),
        make_pod("cores", cpu="2", node_name="oversold", phase="Running"),
    ]
    want = {
        GPU: ({"NotEnoughResources": 2}, 1, 3),
        "example.com/fpga": ({"NotEnoughResources": 1}, 2, 3),
    }
    pods = {name: make_pod(name, cpu="0", memory="0", extended={name: "0"}) for name in want}
    needy = make_pod("needy", cpu="0", memory="0", extended={"example.com/fpga": "1"})
    snap = ClusterSnapshot.build(nodes, [*placed, *pods.values(), needy])
    for name, pod in pods.items():
        assert unschedulable_reason_counts(pod, snap) == per_pair(pod, snap) == want[name], name
    assert unschedulable_reason_counts(needy, snap) == per_pair(needy, snap) == ({"NotEnoughResources": 3}, 0, 3)


def test_node_table_is_built_once_a_snapshot():
    nodes, pods = cluster(3, "node-affinity")
    snap = ClusterSnapshot.build(nodes, pods)
    assert snap._node_table is None  # building a snapshot builds no table
    first, second = snap.pending_pods()[:2]
    unschedulable_reason_counts(first, snap)
    table = snap._node_table
    assert table is not None
    unschedulable_reason_counts(second, snap)
    assert snap._node_table is table
    other = ClusterSnapshot.build(nodes, pods)
    unschedulable_reason_counts(first, other)
    assert other._node_table is not table


def test_checked_nodes_counter_is_feasible_plus_inter_pod_counts():
    """A cycle that explains counts the nodes its pods' breakdowns walked
    past the node-local masks: feasible plus the inter-pod reasons."""
    nodes = [
        make_node("a1", cpu=4, labels={"zone": "a"}),
        make_node("a2", cpu=4, labels={"zone": "a"}),
        make_node("b1", cpu=4, labels={"zone": "b"}, taints=[Taint(key="dedicated", value="infra")]),
        make_node("b2", cpu=1, labels={"zone": "b"}),
        make_node("c1", cpu=4, labels={"zone": "c"}, unschedulable=True),
    ]
    placed = make_pod("web", cpu="250m", node_name="a1", phase="Running", labels={"app": "web"})
    pending = [
        make_pod("anti", cpu="2", anti_affinity=[PodAntiAffinityTerm(match_labels={"app": "web"}, topology_key="zone")]),
        make_pod("lonely", cpu="2", pod_affinity=[PodAntiAffinityTerm(match_labels={"app": "nobody"}, topology_key="zone")]),
    ]
    api = FakeApiServer()
    api.load(nodes, [placed, *pending])
    snap = ClusterSnapshot.build(api.list_nodes(), api.list_pods())
    want = 0
    for pod in snap.pending_pods():
        counts, feasible, _ = per_pair(pod, snap)
        want += feasible + sum(counts.get(r, 0) for r in INTER_POD_REASONS)
    sched = Scheduler(api, NativeBackend())
    try:
        sched.run_cycle()
        counters = sched.metrics.snapshot()
    finally:
        sched.close()
    assert counters.get("scheduler_explained_pods_total") == 2
    assert counters.get("scheduler_explain_checked_nodes_total") == want == 4


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
