"""The same-cycle placement overlay (core/snapshot.PlacedOverlay) is a
superset filter: each inter-pod checker built over it must give the verdict
or score the pre-index builders gave over a plain list of the same
placements, node for node; a rejected gang trial must leave the parent
overlay as it was; the walk counters fold once a phase; and the stall
mop-up must bind the same pods to the same nodes as a run of the
brute-force chain.

The reference builders below walk every placement (the snapshot's and the
list's) and test each with ``term_matches``: they share no index with the
program."""

import random
from dataclasses import replace

import numpy as np
import pytest

import tpu_scheduler.core.predicates as P
import tpu_scheduler.runtime.controller as controller
from tpu_scheduler.api.objects import (
    LabelSelectorRequirement,
    PodAntiAffinityTerm,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.cli import MIXED_WORKLOAD
from tpu_scheduler.core.predicates import node_topology_domain, term_matches
from tpu_scheduler.core.snapshot import ClusterSnapshot, PlacedOverlay
from tpu_scheduler.runtime.controller import Scheduler
from tpu_scheduler.runtime.fake_api import FakeApiServer
from tpu_scheduler.testing import make_node, make_pod, synth_cluster

# --- brute-force reference builders (every placement, no index) -------------


def _every(snapshot, placed):
    return [*snapshot.placed_pods(), *placed]


def _matches(term, ns, snapshot, placed):
    return [(q, qn) for q, qn in _every(snapshot, placed) if q.metadata.namespace == ns and term_matches(term, q.metadata.labels)]


def ref_affinity(pod, snapshot, placed):
    ns = pod.metadata.namespace
    blocked, keys = set(), set()
    for t in pod.spec.anti_affinity or []:
        for _q, qn in _matches(t, ns, snapshot, placed):
            blocked.add(node_topology_domain(qn, t.topology_key))
            keys.add(t.topology_key)
    for q, qn in _every(snapshot, placed):
        if q.metadata.namespace == ns and q.spec is not None:
            for t in q.spec.anti_affinity or []:
                if term_matches(t, pod.metadata.labels):
                    blocked.add(node_topology_domain(qn, t.topology_key))
                    keys.add(t.topology_key)
    return lambda node: all(node_topology_domain(node, k) not in blocked for k in keys)


def ref_pod_affinity(pod, snapshot, placed):
    required = []
    for t in pod.spec.pod_affinity or []:
        doms = {node_topology_domain(qn, t.topology_key) for _q, qn in _matches(t, pod.metadata.namespace, snapshot, placed)}
        if doms:
            required.append((t.topology_key, doms))
        elif not term_matches(t, pod.metadata.labels):
            return lambda node: False
    return lambda node: all(node_topology_domain(node, k) in doms for k, doms in required)


def _zone_counts(c, pod, snapshot, placed, seed_named):
    counts = {}
    if seed_named:
        for n in snapshot.nodes:
            v = (n.metadata.labels or {}).get(c.topology_key)
            if v is not None:
                counts.setdefault(v, 0)
    for _q, qn in _matches(c, pod.metadata.namespace, snapshot, placed):
        v = (qn.metadata.labels or {}).get(c.topology_key)
        if v is not None:
            counts[v] = counts.get(v, 0) + 1
    return counts


def ref_spread(pod, snapshot, placed):
    per = []
    for c in pod.spec.topology_spread or []:
        if c.is_hard:
            counts = _zone_counts(c, pod, snapshot, placed, seed_named=True)
            per.append((c.topology_key, c.max_skew, counts, min(counts.values(), default=0)))

    def check(node):
        for key, skew, counts, lo in per:
            here = (node.metadata.labels or {}).get(key)
            if here is not None and counts.get(here, 0) + 1 - lo > skew:
                return False
        return True

    return check


def ref_soft_spread(pod, snapshot, placed):
    per = [
        (c.topology_key, _zone_counts(c, pod, snapshot, placed, seed_named=False))
        for c in pod.spec.topology_spread or []
        if not c.is_hard
    ]
    return lambda node: float(sum(counts.get((node.metadata.labels or {}).get(k), 0) for k, counts in per))


def ref_preferred(pod, snapshot, placed):
    weighted = [(w.weight, w.term) for w in pod.spec.preferred_pod_affinity or []]
    weighted += [(-w.weight, w.term) for w in pod.spec.preferred_pod_anti_affinity or []]
    per = []
    for w, t in weighted:
        counts = {}
        for _q, qn in _matches(t, pod.metadata.namespace, snapshot, placed):
            d = node_topology_domain(qn, t.topology_key)
            counts[d] = counts.get(d, 0) + 1
        per.append((float(w), t.topology_key, counts))

    def score(node):
        total = 0.0
        for w, key, counts in per:
            total += w * counts.get(node_topology_domain(node, key), 0)
        return total

    return score


BUILDERS = {
    "make_affinity_checker": ref_affinity,
    "make_pod_affinity_checker": ref_pod_affinity,
    "make_spread_checker": ref_spread,
    "make_soft_spread_scorer": ref_soft_spread,
    "make_preferred_pod_affinity_scorer": ref_preferred,
}

# --- seeded mixed clusters split into snapshot / overlay / probes -----------

APP_IN = LabelSelectorRequirement(key="app", operator="In", values=[f"app-{i}" for i in range(0, 50, 3)])


def _expression_only_probes():
    """Pods whose every term carries only ``match_expressions``: the overlay
    hands them every placement."""
    term = PodAntiAffinityTerm(topology_key="name", match_expressions=[APP_IN])
    return [
        make_pod("expr-anti", labels={"app": "app-3"}, anti_affinity=[term]),
        make_pod("expr-pa", labels={"app": "app-4"}, pod_affinity=[term]),
        make_pod(
            "expr-spread", labels={"app": "app-6"},
            topology_spread=[
                TopologySpreadConstraint(topology_key="zone", max_skew=1, match_expressions=[APP_IN]),
                TopologySpreadConstraint(
                    topology_key="zone", max_skew=1, match_expressions=[APP_IN], when_unsatisfiable="ScheduleAnyway"
                ),
            ],
        ),
        make_pod(
            "expr-pref", labels={"app": "app-7"},
            preferred_pod_affinity=[WeightedPodAffinityTerm(weight=50, term=term)],
            preferred_pod_anti_affinity=[WeightedPodAffinityTerm(weight=10, term=term)],
        ),
    ]


def _split_cluster(seed):
    """(snapshot, placements for the overlay, probe pods) from one seeded
    mixed cluster: a third of its pending pods are placed in the snapshot, a
    third in the overlay (some moved to a second namespace), the rest probe."""
    rng = random.Random(seed)
    base = synth_cluster(n_nodes=30, n_pending=150, n_bound=40, seed=seed, **MIXED_WORKLOAD)
    nodes = list(base.nodes)
    pending = base.pending_pods()
    third = len(pending) // 3

    def place(pod):
        if rng.random() < 0.15:
            pod = replace(pod, metadata=replace(pod.metadata, namespace="team-x"))
        return pod, rng.choice(nodes)

    in_snapshot = [place(p) for p in pending[:third]]
    overlay = [place(p) for p in pending[third : 2 * third]]
    probes = pending[2 * third :] + _expression_only_probes()
    bound = [p for p in base.pods if p.spec.node_name is not None]
    bound += [replace(q, spec=replace(q.spec, node_name=n.name)) for q, n in in_snapshot]
    return ClusterSnapshot.build(nodes, bound + probes), overlay, probes


@pytest.mark.parametrize("builder", sorted(BUILDERS))
@pytest.mark.parametrize("seed", range(15))
def test_builder_over_overlay_equals_brute_force_chain(seed, builder):
    snap, placements, probes = _split_cluster(seed)
    half = len(placements) // 2
    overlay = PlacedOverlay(placements[:half])
    overlay.carriers()  # index the first half, so the second is indexed on top of it
    overlay.extend(placements[half:])
    make, ref = getattr(P, builder), BUILDERS[builder]
    differing = set()
    for pod in probes:
        got, want = make(pod, snap, overlay), ref(pod, snap, placements)
        verdicts = [(got(n), want(n)) for n in snap.nodes]
        assert all(g == w for g, w in verdicts), (builder, pod.metadata.name)
        differing.update(w for _g, w in verdicts)
    assert len(differing) > 1, f"{builder}: every probe gave one answer on every node; the cluster tests nothing"
    walked, offered = overlay.take_tally()
    assert walked <= offered


# --- forks and counters ------------------------------------------------------


def _overlay_view(overlay):
    return (
        [id(p) for p in overlay],
        [id(p) for p in overlay.carriers()],
        {app: [id(p) for p in overlay.matching("default", {"app": app})] for app in ("web", "db")},
    )


def test_rejected_gang_trial_leaves_the_parent_overlay_unchanged():
    nodes = [make_node(f"n{i}", cpu="4", memory="8Gi", labels={"zone": f"z{i % 2}"}) for i in range(2)]
    carrier = make_pod(
        "carrier", labels={"app": "web"},
        anti_affinity=[PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="zone")],
    )
    parent = PlacedOverlay([(carrier, nodes[0]), (make_pod("db-0", labels={"app": "db"}), nodes[1])])
    # Two members fit; the third asks for more than any node has, so the trial rejects the gang whole.
    gang = [
        make_pod(f"g{i}", cpu=cpu, labels={"app": "web"}, gang="g",
                 anti_affinity=[PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="zone")])
        for i, cpu in enumerate(["1", "1", "16"])
    ]
    snap = ClusterSnapshot.build(nodes, gang)
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, NativeBackend(), requeue_seconds=0.0)
    before = _overlay_view(parent)
    parent.take_tally()
    assert sched._run_constrained_phase(snap, gang, parent) == (0, 3)
    assert sched.metrics.snapshot()["scheduler_gang_host_rejections_total"] == 1
    assert _overlay_view(parent) == before
    assert len(parent) == 2


def test_gang_trial_sees_the_parent_overlay():
    """The trial's fork walks the parent's placements too: an anti-affinity
    member keeps off the zone an overlay placement holds, and the admitted
    gang's placements land in the parent."""
    nodes = [make_node(f"n{i}", cpu="4", memory="8Gi", labels={"zone": f"z{i}"}) for i in range(2)]
    parent = PlacedOverlay([(make_pod("db-0", labels={"app": "db"}), nodes[0])])
    gang = [
        make_pod(f"g{i}", cpu="1", labels={"app": "web"}, gang="g",
                 anti_affinity=[PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="zone")])
        for i in range(2)
    ]
    snap = ClusterSnapshot.build(nodes, gang)
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, NativeBackend(), requeue_seconds=0.0)
    assert sched._run_constrained_phase(snap, gang, parent) == (2, 0)
    assert {p.metadata.name: p.spec.node_name for p in api.list_pods()} == {"g0": "n1", "g1": "n1"}
    assert [q.metadata.name for q, _ in parent] == ["db-0", "g0", "g1"]


@pytest.mark.parametrize("seed", range(3))
def test_overlay_counters_fold_once_a_phase(seed, monkeypatch):
    snap, placements, probes = _split_cluster(seed)
    plain = [p for p in probes if p.spec.gang is None]
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, NativeBackend(), requeue_seconds=0.0)
    calls = []
    inc = sched.metrics.inc
    monkeypatch.setattr(sched.metrics, "inc", lambda name, value=1, labels=None: (calls.append((name, value)), inc(name, value, labels)))
    overlay = PlacedOverlay(placements)
    sched._run_constrained_phase(snap, plain, overlay)
    folded = [(n, v) for n, v in calls if n.startswith("scheduler_checker_overlay_")]
    assert [n for n, _ in folded] == ["scheduler_checker_overlay_walked_total", "scheduler_checker_overlay_offered_total"]
    (_, walked), (_, offered) = folded
    assert 0 < walked < offered
    assert overlay.take_tally() == (0, 0)  # folded, nothing left over
    counters = sched.metrics.snapshot()
    assert counters["scheduler_checker_overlay_walked_total"] == walked
    assert counters["scheduler_checker_overlay_offered_total"] == offered


# --- the mop-up against the brute-force chain -------------------------------


class _DroppingBackend(NativeBackend):
    """Forces a residue: a constrained pack's auction loses every third
    placement, as if the stall stop had deferred those pods."""

    def assign(self, packed, profile):
        assigned, *rest = super().assign(packed, profile)
        if packed.constraints is not None:
            assigned = np.array(assigned, copy=True)
            assigned[np.flatnonzero(assigned >= 0)[::3]] = -1
        return (assigned, *rest)


def _mopup_run(seed, monkeypatch, reference):
    snap = synth_cluster(n_nodes=40, n_pending=240, n_bound=60, seed=seed, **MIXED_WORKLOAD)
    with monkeypatch.context() as m:
        if reference:
            for name, ref in BUILDERS.items():
                m.setattr(controller, name, lambda pod, snapshot, overlay=None, _ref=ref: _ref(pod, snapshot, list(overlay or ())))
        api = FakeApiServer()
        api.load(nodes=snap.nodes, pods=snap.pods)
        sched = Scheduler(api, _DroppingBackend(), requeue_seconds=0.0)
        sched.run_cycle()
    return {p.metadata.name: p.spec.node_name for p in api.list_pods()}, sched.metrics.snapshot()


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_mopup_binds_what_the_brute_force_chain_binds(seed, monkeypatch):
    got, counters = _mopup_run(seed, monkeypatch, reference=False)
    want, ref_counters = _mopup_run(seed, monkeypatch, reference=True)
    assert got == want
    assert "scheduler_constraint_host_fallbacks_total" not in counters
    assert counters["scheduler_stall_mopup_bound_total"] == ref_counters["scheduler_stall_mopup_bound_total"] > 0
    assert 0 < counters["scheduler_checker_overlay_walked_total"] < counters["scheduler_checker_overlay_offered_total"]
