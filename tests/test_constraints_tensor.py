"""Tensorized anti-affinity + topology-spread (ops/constraints.py) — the
device-side form of BASELINE config 5 (VERDICT r1 item #2).

Validity contract: replaying the auction's placements in acceptance order
(round, then priority rank — exported in CycleResult.stats) through the
scalar predicate chain (core/predicates.py) must show zero violations; and
the native and TPU backends must agree binding-for-binding.
"""

import pytest

from dataclasses import replace

import tpu_scheduler.core.predicates as P
from tpu_scheduler.api.objects import PodAntiAffinityTerm, TopologySpreadConstraint, full_name
from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.backends.tpu import TpuBackend
from tpu_scheduler.core.snapshot import ClusterSnapshot, PlacedOverlay
from tpu_scheduler.models.profiles import DEFAULT_PROFILE
from tpu_scheduler.ops.constraints import UntensorizableConstraints, pack_constraints
from tpu_scheduler.ops.pack import pack_snapshot
from tpu_scheduler.runtime.controller import Scheduler
from tpu_scheduler.runtime.fake_api import FakeApiServer
from tpu_scheduler.testing import make_node, make_pod, synth_cluster


def _packed_with_constraints(snap, **kw):
    packed = pack_snapshot(snap)
    cons = pack_constraints(
        snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes, **kw
    )
    return replace(packed, constraints=cons) if cons is not None else packed


def _replay_validity(snap, packed, result) -> int:
    """Sequential-order certificate: the auction commits placements in
    rounds; within a round the kept set is valid under *some* order (rank
    order for anti-affinity, fill-height order for spread waves).  Verify by
    multi-pass greedy replay through the scalar chain: rounds in order,
    within a round keep sweeping for a placement whose scalar check passes.
    Returns the number of placements for which no valid order exists."""
    pending = snap.pending_pods()
    node_by = {n.name: n for n in snap.nodes}
    by_round: dict[int, list] = {}
    for i in range(len(pending)):
        j = int(result.assigned[i])
        if j < 0:
            continue
        r = int(result.stats["acc_round"][i])
        by_round.setdefault(r, []).append((int(result.stats["rank"][i]), pending[i], node_by[packed.node_names[j]]))
    placed = PlacedOverlay()
    stuck = 0
    for r in sorted(by_round):
        group = sorted(by_round[r])  # rank order first — right for AA
        while group:
            progressed = False
            remaining = []
            for rank, pod, node in group:
                if (
                    P.anti_affinity_ok(pod, node, snap, placed)
                    and P.pod_affinity_ok(pod, node, snap, placed)
                    and P.topology_spread_ok(pod, node, snap, placed)
                ):
                    placed.append((pod, node))
                    progressed = True
                else:
                    remaining.append((rank, pod, node))
            if not progressed:
                stuck += len(remaining)
                placed.extend((pod, node) for _, pod, node in remaining)
                break
            group = remaining
    return stuck


def _schedule_both(snap, **kw):
    packed = _packed_with_constraints(snap, **kw)
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings
    assert (rn.stats["acc_round"] == rt.stats["acc_round"]).all()
    return packed, rn


# --- targeted scenarios ------------------------------------------------------


def test_self_anti_affinity_spreads_replicas():
    """Three replicas with hostname self-anti-affinity land on 3 distinct
    nodes even though one node could hold all of them."""
    nodes = [make_node(f"n{i}", cpu="32", memory="64Gi", labels={"name": f"n{i}"}) for i in range(4)]
    term = [PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="name")]
    pods = [
        make_pod(f"db-{i}", cpu="500m", memory="1Gi", labels={"app": "db"}, anti_affinity=term) for i in range(3)
    ]
    snap = ClusterSnapshot.build(nodes, pods)
    packed, r = _schedule_both(snap)
    assert len(r.bindings) == 3
    assert len({n for _, n in r.bindings}) == 3
    assert _replay_validity(snap, packed, r) == 0


def test_anti_affinity_respects_placed_pods():
    """Direction A: a node whose zone already holds a matched placed pod is
    blocked for a carrier."""
    nodes = [
        make_node("a1", labels={"zone": "a"}),
        make_node("a2", labels={"zone": "a"}),
        make_node("b1", labels={"zone": "b"}),
    ]
    placed = [make_pod("old", labels={"app": "db"}, node_name="a1", phase="Running")]
    term = [PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="zone")]
    incoming = [make_pod("new-db", labels={"app": "db"}, anti_affinity=term)]
    snap = ClusterSnapshot.build(nodes, placed + incoming)
    packed, r = _schedule_both(snap)
    assert r.bindings == [("default/new-db", "b1")]


def test_anti_affinity_direction_b_placed_carrier_blocks_matched():
    """Direction B: a *placed* pod's term blocks an incoming pod that
    matches it, even though the incoming pod declares nothing."""
    nodes = [make_node("a1", labels={"zone": "a"}), make_node("b1", labels={"zone": "b"})]
    term = [PodAntiAffinityTerm(match_labels={"app": "web"}, topology_key="zone")]
    placed = [make_pod("carrier", labels={"app": "other"}, anti_affinity=term, node_name="a1", phase="Running")]
    incoming = [make_pod("victim", labels={"app": "web"})]
    snap = ClusterSnapshot.build(nodes, placed + incoming)
    packed, r = _schedule_both(snap)
    assert r.bindings == [("default/victim", "b1")]


def test_anti_affinity_namespace_scoped():
    """A term only sees pods in its own namespace."""
    nodes = [make_node("a1", labels={"zone": "a"})]
    term = [PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="zone")]
    placed = [make_pod("other-ns", namespace="prod", labels={"app": "db"}, node_name="a1", phase="Running")]
    incoming = [make_pod("new-db", namespace="dev", labels={"app": "db"}, anti_affinity=term)]
    snap = ClusterSnapshot.build(nodes, placed + incoming)
    packed, r = _schedule_both(snap)
    assert r.bindings == [("dev/new-db", "a1")]


def test_keyless_node_is_singleton_domain():
    """A node lacking the topology key degrades to per-node granularity:
    the matched placed pod blocks only its own node."""
    nodes = [make_node("k1"), make_node("k2")]  # no zone labels at all
    term = [PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="zone")]
    placed = [make_pod("old", labels={"app": "db"}, node_name="k1", phase="Running")]
    incoming = [make_pod("new-db", labels={"app": "db"}, anti_affinity=term)]
    snap = ClusterSnapshot.build(nodes, placed + incoming)
    packed, r = _schedule_both(snap)
    assert r.bindings == [("default/new-db", "k2")]


def test_spread_hard_skew_enforced():
    """max_skew=1 over two zones: 4 replicas land 2+2."""
    nodes = [
        make_node("a1", cpu="32", memory="64Gi", labels={"zone": "a"}),
        make_node("b1", cpu="32", memory="64Gi", labels={"zone": "b"}),
    ]
    spread = [TopologySpreadConstraint(topology_key="zone", max_skew=1, match_labels={"app": "web"})]
    pods = [
        make_pod(f"web-{i}", cpu="100m", memory="128Mi", labels={"app": "web"}, topology_spread=spread)
        for i in range(4)
    ]
    snap = ClusterSnapshot.build(nodes, pods)
    packed, r = _schedule_both(snap)
    assert len(r.bindings) == 4
    zones = [n[0] for _, n in r.bindings]  # a1 -> 'a', b1 -> 'b'
    assert sorted(zones) == ["a", "a", "b", "b"]
    assert _replay_validity(snap, packed, r) == 0


def test_spread_mass_wave_commits_whole_levels():
    """Water-filling quota: a mass spread workload converges in few rounds,
    not one-pod-per-domain-per-round."""
    nodes = [
        make_node(f"n{i}", cpu="64", memory="256Gi", labels={"zone": f"z{i % 4}"}) for i in range(8)
    ]
    spread = [TopologySpreadConstraint(topology_key="zone", max_skew=1, match_labels={"app": "web"})]
    pods = [
        make_pod(f"web-{i}", cpu="50m", memory="64Mi", labels={"app": "web"}, topology_spread=spread)
        for i in range(64)
    ]
    snap = ClusterSnapshot.build(nodes, pods)
    packed, r = _schedule_both(snap)
    assert len(r.bindings) == 64
    assert r.rounds <= 24  # NOT 16 rounds-per-level × levels
    assert _replay_validity(snap, packed, r) == 0
    # Final counts within the skew band (all placements were new).
    per_zone = {}
    for _, n in r.bindings:
        z = f"z{int(n[1:]) % 4}"
        per_zone[z] = per_zone.get(z, 0) + 1
    assert max(per_zone.values()) - min(per_zone.values()) <= 1


def test_spread_exempts_keyless_nodes():
    nodes = [make_node("a1", labels={"zone": "a"}), make_node("x1")]  # x1 keyless
    spread = [TopologySpreadConstraint(topology_key="zone", max_skew=1, match_labels={"app": "web"})]
    placed = [make_pod("w0", labels={"app": "web"}, node_name="a1", phase="Running")]
    pods = [make_pod("w1", labels={"app": "web"}, topology_spread=spread)]
    snap = ClusterSnapshot.build(nodes, placed + pods)
    packed, r = _schedule_both(snap)
    # zone a is at count 1 = skew + min(1... min over {a}=1 → 1+1-1 <= 1 ok;
    # actually single-domain keys always pass; the point is x1 is legal too.
    assert len(r.bindings) == 1


def test_untensorizable_many_valued_shared_key_raises():
    """A non-unique many-valued topology key must refuse tensorization."""
    nodes = [
        make_node(f"n{i}", labels={"rack": f"r{i // 2}"}) for i in range(40)
    ]  # 20 racks, 2 nodes each
    term = [PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="rack")]
    pods = [make_pod("db-0", labels={"app": "db"}, anti_affinity=term)]
    snap = ClusterSnapshot.build(nodes, pods)
    packed = pack_snapshot(snap)
    with pytest.raises(UntensorizableConstraints):
        pack_constraints(
            snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes,
            max_coarse_domains=8,
        )


# --- synthetic-cluster sweep (the VERDICT acceptance shape) ------------------


# Seeds 4 and 15 are regression anchors: they caught the water-filling lo
# deriving from uncertain (later-dropped) mass, which over-admitted a
# skew-violating placement (fixed in constraint_filter's c0/c0_cert split).
@pytest.mark.parametrize("seed", [0, 3, 4, 11, 15])
def test_synth_constrained_cluster_parity_and_validity(seed):
    snap = synth_cluster(
        n_nodes=60,
        n_pending=400,
        n_bound=100,
        seed=seed,
        anti_affinity_fraction=0.2,
        spread_fraction=0.2,
    )
    packed, r = _schedule_both(snap)
    assert _replay_validity(snap, packed, r) == 0
    assert len(r.bindings) > 300  # the bulk schedules


def test_scheduler_uses_tensor_path_for_constrained_cluster():
    """End-to-end through the controller: a constrained synthetic cluster
    schedules through the batch tensor backend (counter increments), with no
    host-fallback, and every binding is valid."""
    snap = synth_cluster(
        n_nodes=40, n_pending=200, n_bound=50, seed=7, anti_affinity_fraction=0.2, spread_fraction=0.2
    )
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, NativeBackend(), policy="batch", requeue_seconds=0.0)
    sched.run(max_cycles=8, until_settled=True)
    counters = sched.metrics.snapshot()
    assert counters.get("scheduler_constraint_tensor_cycles_total", 0) >= 1
    assert counters.get("scheduler_constraint_host_fallbacks_total", 0) == 0
    assert counters["scheduler_bindings_total"] > 150

    # Every final placement satisfies the scalar chain against the final
    # cluster state minus itself (a necessary condition that is order-free).
    final = ClusterSnapshot.build(api.list_nodes(), api.list_pods())
    node_by = {n.name: n for n in final.nodes}
    for pod, node in final.placed_pods():
        if pod.spec is None or not (pod.spec.anti_affinity or pod.spec.topology_spread):
            continue
        # anti-affinity must hold in the final state (order-free invariant)
        others = ClusterSnapshot.build(
            final.nodes, [q for q in final.pods if q is not pod]
        )
        assert P.anti_affinity_ok(pod, node_by[node.name], others), full_name(pod)


def test_sharded_backend_schedules_constraints_on_mesh():
    """Constrained clusters ride the multi-chip path (replicated domain
    state, parallel/sharded.py) — assignments must equal the native oracle,
    with no host fallback in the controller."""
    from tpu_scheduler.parallel.sharded import ShardedBackend

    nodes = [make_node(f"n{i}", cpu="32", memory="64Gi", labels={"name": f"n{i}"}) for i in range(4)]
    term = [PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="name")]
    pods = [make_pod(f"db-{i}", labels={"app": "db"}, anti_affinity=term) for i in range(3)]
    snap = ClusterSnapshot.build(nodes, pods)
    packed = _packed_with_constraints(snap)
    backend = ShardedBackend(tp=2)
    rs = backend.schedule(packed, DEFAULT_PROFILE)
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    assert rs.bindings == rn.bindings
    assert len({n for _, n in rs.bindings}) == 3  # anti-affinity respected

    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, backend, policy="batch", requeue_seconds=0.0)
    sched.run(until_settled=True)
    counters = sched.metrics.snapshot()
    assert counters.get("scheduler_constraint_host_fallbacks_total", 0) == 0
    assert counters.get("scheduler_constraint_tensor_cycles_total", 0) >= 1
    bound_nodes = {p.spec.node_name for p in api.list_pods() if p.spec.node_name}
    assert len(bound_nodes) == 3


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_sharded_constrained_synth_parity(tp):
    """Mesh-path parity on a synthetic constrained cluster (AA + hard spread
    + ScheduleAnyway + soft taints) across tp factorisations."""
    from tpu_scheduler.parallel.mesh import make_mesh
    from tpu_scheduler.parallel.sharded import ShardedBackend

    snap = synth_cluster(
        n_nodes=24,
        n_pending=120,
        n_bound=48,
        seed=6,
        anti_affinity_fraction=0.15,
        spread_fraction=0.15,
        schedule_anyway_fraction=0.15,
        soft_taint_fraction=0.2,
    )
    packed = _packed_with_constraints(snap)
    assert packed.constraints is not None
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rs = ShardedBackend(make_mesh(tp=tp)).schedule(packed, DEFAULT_PROFILE)
    assert rs.bindings == rn.bindings
    assert rs.rounds == rn.rounds


def test_plain_cycles_unchanged_by_constraint_plumbing():
    """An unconstrained cluster must take the exact pre-existing path
    (constraints=None) — guard against overhead/regression."""
    snap = synth_cluster(n_nodes=30, n_pending=100, seed=1)
    packed = pack_snapshot(snap)
    assert packed.constraints is None
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings
    assert len(rn.unschedulable) == 0


def test_stalled_constraint_auction_stops_early():
    """A spread water line frozen by a capacity-full minimum domain can
    defer the same pods every round; the auction must detect consecutive
    zero-acceptance rounds and stop (measured: 48 wasted rounds to the cap
    before the stall rule), with the stragglers requeued — and the
    controller's NEXT cycle must still make progress on them."""
    from tpu_scheduler.models.profiles import PROFILES
    from tpu_scheduler.ops.constraints import pack_constraints as _pc

    snap = synth_cluster(n_nodes=100, n_pending=1200, n_bound=200, seed=0, spread_fraction=0.15)
    packed = pack_snapshot(snap)
    cons = _pc(
        snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes,
        max_aa_terms=256, max_spread=256,
    )
    packed = replace(packed, constraints=cons)
    prof = PROFILES["throughput"].with_(max_rounds=64)
    rn = NativeBackend().schedule(packed, prof)
    rt = TpuBackend().schedule(packed, prof)
    assert rn.bindings == rt.bindings and rn.rounds == rt.rounds
    assert rn.rounds < 32, f"stall detection failed: {rn.rounds} rounds"
    assert len(rn.bindings) > 1000  # the bulk still binds
    # end-to-end: the controller requeues stragglers and settles
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, NativeBackend(), requeue_seconds=0.0, profile=prof)
    sched.run(until_settled=True, max_cycles=6)
    placed = sum(1 for p in api.list_pods() if p.spec is not None and p.spec.node_name)
    assert placed >= len(rn.bindings) + 200  # pre-bound + at least the one-shot count


def test_deferred_claimant_claims_after_the_pods_behind_it(monkeypatch):
    """A claimant the filter defers holds no capacity, so it must not block
    the lower-ranked claimants of its node: from the round after its
    deferral it claims after them.  Here the filter defers the top-ranked
    pod every round; the node fits it alone or the two pods behind it, and
    those two must bind (before the rule, nothing bound and the stall stop
    requeued all three)."""
    import tpu_scheduler.ops.constraints as C

    nodes = [make_node("n0", cpu=2, memory="8Gi", labels={"zone": "a"})]
    pods = [
        make_pod("head", cpu="2", memory="1Gi", priority=10, labels={"app": "h"},
                 anti_affinity=[PodAntiAffinityTerm(match_labels={"app": "h"}, topology_key="zone")]),
        make_pod("p", cpu="1", memory="1Gi", labels={"app": "p"}),
        make_pod("q", cpu="1", memory="1Gi", labels={"app": "q"}),
    ]
    snap = ClusterSnapshot.build(nodes, pods)
    packed = _packed_with_constraints(snap)
    assert packed.constraints is not None
    real_filter = C.constraint_filter

    def defer_head(xp, accepted, choice, ranks, *a, **k):
        return real_filter(xp, accepted, choice, ranks, *a, **k) & (ranks != 0)

    monkeypatch.setattr(C, "constraint_filter", defer_head)
    r = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    assert sorted(b[0] for b in r.bindings) == ["default/p", "default/q"]
    assert r.unschedulable == ["default/head"]


@pytest.mark.parametrize("backend", [NativeBackend, TpuBackend], ids=["native", "tpu"])
def test_constraint_widths_keep_the_widest_padding_seen(backend):
    """A shared ``widths`` record pads each constraint axis to the widest it
    has been: a cycle with 3 distinct spread constraints after one with 9
    keeps the 16-wide spread axis (8 without the record), and is scheduled
    exactly as without it."""
    nodes = [make_node(f"n{i}", cpu=8, memory="32Gi", labels={"zone": "abc"[i % 3]}) for i in range(6)]

    def pending(n_apps):
        return [
            make_pod(f"p{a}-{k}", cpu="1", memory="1Gi", labels={"app": f"a{a}"},
                     topology_spread=[TopologySpreadConstraint(topology_key="zone", max_skew=1, match_labels={"app": f"a{a}"})])
            for a in range(n_apps) for k in range(2)
        ]

    widths: dict = {}
    wide = _packed_with_constraints(ClusterSnapshot.build(nodes, pending(9)), widths=widths)
    assert wide.constraints.pod_sp_declares.shape[1] == 16 and widths["s"] == 16
    snap = ClusterSnapshot.build(nodes, pending(3))
    plain = _packed_with_constraints(snap)
    sticky = _packed_with_constraints(snap, widths=widths)
    assert plain.constraints.pod_sp_declares.shape[1] == 8
    assert sticky.constraints.pod_sp_declares.shape[1] == 16 and widths["s"] == 16
    a, b = backend().schedule(plain, DEFAULT_PROFILE), backend().schedule(sticky, DEFAULT_PROFILE)
    assert sorted(a.bindings) == sorted(b.bindings) and len(a.bindings) == 6


def test_dense_and_fallback_filter_paths_agree(monkeypatch):
    """The DENSE_CELLS fast path (exclusive-cumsum predecessor checks +
    row scatters) and the sort/scatter fallback in constraint_filter /
    constraint_commit must be bit-identical: same bindings, same rounds,
    same accept rounds.  Run on the native backend, which re-reads the
    budget each call (the jit path's trace cache would mask the patch)."""
    import tpu_scheduler.ops.constraints as C

    snap = synth_cluster(
        n_nodes=60, n_pending=400, n_bound=100, seed=3,
        anti_affinity_fraction=0.2, spread_fraction=0.2, pod_affinity_fraction=0.1,
        preferred_pod_affinity_fraction=0.1, schedule_anyway_fraction=0.1,
    )
    packed = _packed_with_constraints(snap)
    # Force each branch explicitly: at this synth shape terms×D lands ABOVE
    # the default budget, so without the first patch both runs would take
    # the fallback and the comparison would be vacuous.
    monkeypatch.setattr(C, "DENSE_CELLS", 10**9)
    r_dense = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    monkeypatch.setattr(C, "DENSE_CELLS", 0)
    r_fallback = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    assert r_dense.bindings == r_fallback.bindings
    assert r_dense.rounds == r_fallback.rounds
    assert (r_dense.stats["acc_round"] == r_fallback.stats["acc_round"]).all()


def test_pack_constraints_match_memo():
    """A warm match_memo must change nothing: identical tensors vs a fresh
    pack, recompute on object replacement (identity miss), and self-clear
    when the term vocabulary changes."""
    import numpy as onp

    snap = synth_cluster(
        n_nodes=40, n_pending=200, n_bound=80, seed=5,
        anti_affinity_fraction=0.2, spread_fraction=0.2, pod_affinity_fraction=0.1,
        preferred_pod_affinity_fraction=0.1, schedule_anyway_fraction=0.1,
    )
    packed = pack_snapshot(snap)
    args = (snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes)
    memo: dict = {}
    cold = pack_constraints(*args, match_memo=memo)
    assert len(memo) > 1  # sig + per-pod entries
    warm = pack_constraints(*args, match_memo=memo)  # 100% identity hits
    fresh = pack_constraints(*args)  # no memo at all
    for name in vars(cold):
        a, b, c = getattr(cold, name), getattr(warm, name), getattr(fresh, name)
        if isinstance(a, onp.ndarray):
            assert (a == b).all() and (a == c).all(), name
        else:
            assert a == b == c, name

    # A replaced pod object (the API layer's modification contract) misses
    # the memo and is re-matched: flip one pending pod's app label to a
    # value NO term matches (via a NEW object), and check the memoized pack
    # agrees with a fresh one — i.e. the stale cached match is not reused.
    pods2 = list(snap.pods)
    victim_idx = next(
        i for i, p in enumerate(pods2)
        if p.spec is not None and not p.spec.node_name and p.spec.anti_affinity
    )
    donor = pods2[victim_idx]
    import copy

    clone = copy.deepcopy(donor)
    clone.metadata.labels = dict(donor.metadata.labels or {})
    clone.metadata.labels["app"] = "app-definitely-unmatched"
    pods2[victim_idx] = clone
    snap2 = ClusterSnapshot.build(list(snap.nodes), pods2)
    got = pack_constraints(
        snap2, snap2.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes,
        match_memo=memo,
    )
    want = pack_constraints(
        snap2, snap2.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes,
    )
    for name in vars(got):
        a, b = getattr(got, name), getattr(want, name)
        if isinstance(a, onp.ndarray):
            assert (a == b).all(), name

    # Vocab change (the clone's new app label creates a new spread term key
    # only if it declares spread; force a change by dropping every AA term):
    pods3 = [p for p in snap.pods if p.spec is None or not p.spec.anti_affinity]
    snap3 = ClusterSnapshot.build(list(snap.nodes), pods3)
    sig_before = memo["sig"]
    pack_constraints(
        snap3, snap3.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes,
        match_memo=memo,
    )
    assert memo["sig"] != sig_before  # memo was invalidated + re-signed


def test_rich_spread_vocab_rides_tensor_path():
    """A cluster with ~100 distinct spread terms (50 apps x 2 skew levels —
    the CLI's own mixed workload shape) must ride the tensor path, not the
    host sequential fallback: the original 64-term budget silently routed
    it to the scalar phase at 482s per 10k-pod cycle (measured)."""
    snap = synth_cluster(
        n_nodes=60, n_pending=600, n_bound=120, seed=9,
        anti_affinity_fraction=0.1, spread_fraction=0.3, schedule_anyway_fraction=0.2,
        pod_affinity_fraction=0.1, preferred_pod_affinity_fraction=0.1,
    )
    n_terms = len({
        (c.match_labels.get("app"), c.max_skew, c.is_hard)
        for p in snap.pending_pods() if p.spec is not None
        for c in (p.spec.topology_spread or [])
    })
    assert n_terms > 64, f"cluster must exceed the OLD budget to be a regression test (got {n_terms})"
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, NativeBackend(), requeue_seconds=0.0, profile=DEFAULT_PROFILE)
    sched.run_cycle()
    counters = sched.metrics.snapshot()
    assert counters.get("scheduler_constraint_host_fallbacks_total", 0) == 0, counters
    assert counters.get("scheduler_constraint_tensor_cycles_total", 0) == 1, counters


def test_cell_rank_scan_chunked_equals_oneshot(monkeypatch):
    """The spread filter's chunked [P,S,D] passes (byte-budget form, BOTH
    backends) must be bitwise equal to the one-shot form — cross-backend/
    stage parity depends on it (round-5 review finding: the budget must
    bind numpy too, not only the jit path)."""
    import numpy as np

    import tpu_scheduler.ops.constraints as C

    rng = np.random.default_rng(0)
    P, S, D = 533, 7, 5
    mass = (rng.random((P, S)) < 0.3).astype(np.float32)
    nd = np.zeros((P, D), np.float32)
    nd[np.arange(P), rng.integers(0, D, P)] = 1.0
    uses = (rng.random((S, D)) < 0.7).astype(np.float32)
    base = rng.integers(0, 5, (S, D)).astype(np.float32)
    ref_pre = C._cell_rank_prefix(np, mass, nd, uses)
    ref_lvl = C._cell_rank_min_level(np, mass, nd, uses, base)
    monkeypatch.setattr(C, "DENSE_TENSOR_BYTES", 64 * S * D * 4)  # force 64-pod chunks
    assert (C._cell_rank_prefix(np, mass, nd, uses) == ref_pre).all()
    assert (C._cell_rank_min_level(np, mass, nd, uses, base) == ref_lvl).all()
    import jax.numpy as jnp

    jp = np.asarray(C._cell_rank_prefix(jnp, jnp.asarray(mass), jnp.asarray(nd), jnp.asarray(uses)))
    jl = np.asarray(C._cell_rank_min_level(jnp, jnp.asarray(mass), jnp.asarray(nd), jnp.asarray(uses), jnp.asarray(base)))
    assert (jp == ref_pre).all() and (jl == ref_lvl).all()


def test_dense_boundary_parity(monkeypatch):
    """ISSUE 9 satellite: pin dense-vs-fused-segment outcome parity EXACTLY
    at the DENSE_CELLS threshold shape.  The fused segment scatter-min is
    the default AA formulation on the active-set workspace; this proves the
    fork is perf-only right at the boundary (t*d == DENSE_CELLS runs dense,
    one below runs the segment path) for both backends, at the unit level —
    no synth cluster between the inputs and the filter."""
    import numpy as np

    import tpu_scheduler.ops.constraints as C

    t, d, n, p = 16, C.DENSE_CELLS // 16, 96, 512
    assert t * d == C.DENSE_CELLS
    rng = np.random.default_rng(7)
    ndc = np.zeros((n, d), np.float32)
    keyed = rng.random(n) < 0.7  # some nodes lack the coarse key -> fine cells
    ndc[np.flatnonzero(keyed), rng.integers(0, d, int(keyed.sum()))] = 1.0
    meta = {
        "node_dom_c": ndc,
        "term_uses_dom": (rng.random((t, d)) < 0.4).astype(np.float32),
        "sp_uses_dom": np.zeros((8, d), np.float32),
        "sp_skew": np.zeros((8,), np.float32),
        "sp_dom_sel": np.eye(d, dtype=np.float32),
    }
    state = C.augment_round_state(
        np,
        {
            "sp_counts": np.zeros((8, d), np.float32),
            "pa_dom_m": np.zeros((0, d), np.float32),
            "pa_node_m": np.zeros((0, n), np.float32),
        },
        meta,
    )
    args = []
    for seed in range(3):
        r = np.random.default_rng(seed)
        accepted = r.random(p) < 0.4
        choice = r.integers(0, n, p).astype(np.int32)
        ranks = np.arange(p, dtype=np.uint32)
        ps = {
            "pod_aa_carries": (r.random((p, t)) < 0.15).astype(np.float32),
            "pod_aa_matched": (r.random((p, t)) < 0.15).astype(np.float32),
            "pod_sp_declares": np.zeros((p, 8), np.float32),
            "pod_sp_matched": np.zeros((p, 8), np.float32),
        }
        args.append((accepted, choice, ranks, ps))

    def run_all():
        import jax.numpy as jnp

        outs = []
        for accepted, choice, ranks, ps in args:
            o_np = C.constraint_filter(np, accepted, choice, ranks, ps, state, meta, hard_pa=False)
            o_j = C.constraint_filter(
                jnp,
                jnp.asarray(accepted),
                jnp.asarray(choice),
                jnp.asarray(ranks),
                {k: jnp.asarray(v) for k, v in ps.items()},
                {k: jnp.asarray(v) for k, v in state.items()},
                {k: jnp.asarray(v) for k, v in meta.items()},
                hard_pa=False,
            )
            assert (np.asarray(o_j) == o_np).all()  # cross-backend, same branch
            outs.append(o_np)
        return outs

    assert C._dense_ok(p, t * d)  # exactly AT the threshold: dense path
    dense = run_all()
    monkeypatch.setattr(C, "DENSE_CELLS", t * d - 1)
    assert not C._dense_ok(p, t * d)  # one below: fused segment path
    seg = run_all()
    for a, b in zip(dense, seg):
        assert (a == b).all()
    assert any(a.any() for a in dense)  # non-vacuous: some pods survive
    assert any((acc != a).any() for (acc, _c, _r, _ps), a in zip(args, dense))  # ...and some are filtered
