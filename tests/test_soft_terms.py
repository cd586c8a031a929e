"""Soft scoring terms — PreferNoSchedule taints, preferred node affinity,
ScheduleAnyway topology spread — enforced identically on EVERY path:
native (NumPy), tpu (jnp), tpu-sharded (shard_map mesh), the fused Pallas
kernel (tests/test_pallas_choose.py), and the host sequential phase.

This is the parity contract VERDICT r2 item 3 demanded: the soft terms are
exercised from synth_cluster (not hand-built fixtures), and the three
backends must agree binding-for-binding over such clusters.
"""

import numpy as np
import pytest

from tpu_scheduler.api.objects import (
    LabelSelectorRequirement,
    NodeSelectorTerm,
    PreferredSchedulingTerm,
    Taint,
    TopologySpreadConstraint,
)
from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.backends.tpu import TpuBackend
from tpu_scheduler.core.snapshot import ClusterSnapshot, PlacedOverlay
from tpu_scheduler.models.profiles import DEFAULT_PROFILE
from tpu_scheduler.ops.pack import pack_snapshot
from tpu_scheduler.parallel.sharded import ShardedBackend
from tpu_scheduler.runtime.controller import Scheduler
from tpu_scheduler.runtime.fake_api import FakeApiServer
from tpu_scheduler.testing import make_node, make_pod, synth_cluster


def _soft_cluster(seed, n_nodes=32, n_pending=160):
    """Synthetic cluster carrying every soft term the packer understands."""
    return synth_cluster(
        n_nodes=n_nodes,
        n_pending=n_pending,
        n_bound=n_nodes,
        seed=seed,
        soft_taint_fraction=0.4,
        preferred_affinity_fraction=0.4,
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_three_backend_parity_on_soft_cluster(seed):
    """native vs tpu vs tpu-sharded: identical assignments when the cluster
    carries PreferNoSchedule taints and weighted preferred affinity."""
    snap = _soft_cluster(seed)
    packed = pack_snapshot(snap)
    assert packed.soft_taint_vocab and packed.pref_vocab  # soft terms present
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    rs = ShardedBackend(tp=2).schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings
    assert rn.bindings == rs.bindings
    assert rn.rounds == rt.rounds == rs.rounds


def test_soft_taint_steers_away_when_alternative_exists():
    """Two identical nodes, one carrying an untolerated PreferNoSchedule
    taint: every pod prefers the clean node until capacity forces spillover
    — on both backends identically."""
    nodes = [
        make_node("clean", cpu="4", memory="16Gi"),
        make_node(
            "degraded",
            cpu="4",
            memory="16Gi",
            taints=[Taint(key="hw", value="flaky", effect="PreferNoSchedule")],
        ),
    ]
    pods = [make_pod(f"p{i}", cpu="1", memory="1Gi") for i in range(3)]
    snap = ClusterSnapshot.build(nodes, pods)
    packed = pack_snapshot(snap)
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings
    # The soft taint outweighs the balance-score wobble: all three fit on
    # clean (4 cores), so nobody should land on degraded.
    assert all(node == "clean" for _, node in rn.bindings)


def test_soft_taint_never_blocks():
    """PreferNoSchedule is scoring-only: with nowhere else to go, pods still
    bind to the tainted node (unlike NoSchedule)."""
    nodes = [
        make_node(
            "degraded",
            cpu="4",
            memory="16Gi",
            taints=[Taint(key="hw", value="flaky", effect="PreferNoSchedule")],
        )
    ]
    pods = [make_pod("p0", cpu="1", memory="1Gi")]
    snap = ClusterSnapshot.build(nodes, pods)
    packed = pack_snapshot(snap)
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == [("default/p0", "degraded")]


def test_preferred_affinity_steers_to_preferred_zone():
    nodes = [
        make_node("a1", cpu="8", memory="32Gi", labels={"zone": "a"}),
        make_node("b1", cpu="8", memory="32Gi", labels={"zone": "b"}),
    ]
    pref = [
        PreferredSchedulingTerm(
            weight=100,
            term=NodeSelectorTerm(
                match_expressions=[LabelSelectorRequirement(key="zone", operator="In", values=["b"])]
            ),
        )
    ]
    pods = [make_pod("p0", cpu="500m", memory="1Gi", preferred_node_affinity=pref)]
    snap = ClusterSnapshot.build(nodes, pods)
    packed = pack_snapshot(snap)
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings == [("default/p0", "b1")]


def test_schedule_anyway_spreads_but_never_blocks():
    """ScheduleAnyway spread: pods spread across zones while capacity
    allows, but a saturated min-zone never blocks binding (unlike
    DoNotSchedule) — native and tpu agree exactly."""
    nodes = [
        make_node("a1", cpu="32", memory="64Gi", labels={"zone": "a"}),
        make_node("b1", cpu="32", memory="64Gi", labels={"zone": "b"}),
    ]
    soft = [
        TopologySpreadConstraint(
            topology_key="zone", max_skew=1, match_labels={"app": "web"}, when_unsatisfiable="ScheduleAnyway"
        )
    ]
    pods = [
        make_pod(f"w{i}", cpu="100m", memory="128Mi", labels={"app": "web"}, topology_spread=soft)
        for i in range(6)
    ]
    snap = ClusterSnapshot.build(nodes, pods)
    from dataclasses import replace

    from tpu_scheduler.ops.constraints import pack_constraints

    packed = pack_snapshot(snap)
    cons = pack_constraints(snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes)
    assert cons is not None and cons.n_spread_soft == 1 and cons.n_spread == 0
    packed = replace(packed, constraints=cons)
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings
    assert len(rn.bindings) == 6  # soft never blocks
    zones = sorted(n[0] for _, n in rn.bindings)
    assert zones == ["a", "a", "a", "b", "b", "b"]  # penalty balances the zones


@pytest.mark.parametrize("seed", [0, 1])
def test_native_tpu_parity_with_schedule_anyway_synth(seed):
    """Synth clusters mixing ScheduleAnyway with hard constraints ride the
    constraint tensor path with exact native/tpu parity."""
    snap = synth_cluster(
        n_nodes=24,
        n_pending=120,
        n_bound=24,
        seed=seed,
        schedule_anyway_fraction=0.3,
        spread_fraction=0.1,
        soft_taint_fraction=0.3,
        preferred_affinity_fraction=0.3,
    )
    from dataclasses import replace

    from tpu_scheduler.ops.constraints import pack_constraints

    packed = pack_snapshot(snap)
    cons = pack_constraints(snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes)
    assert cons is not None and cons.n_spread_soft >= 1
    packed = replace(packed, constraints=cons)
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings
    assert (rn.stats["acc_round"] == rt.stats["acc_round"]).all()


def test_controller_batches_soft_only_spread_cluster():
    """A cluster whose only constraints are ScheduleAnyway must ride the
    batch tensor path (it is constrained for scoring, not blocking)."""
    snap = synth_cluster(n_nodes=16, n_pending=80, n_bound=16, seed=3, schedule_anyway_fraction=0.4)
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, NativeBackend(), policy="batch", requeue_seconds=0.0)
    sched.run(max_cycles=4, until_settled=True)
    counters = sched.metrics.snapshot()
    assert counters.get("scheduler_constraint_tensor_cycles_total", 0) >= 1
    assert counters.get("scheduler_constraint_host_fallbacks_total", 0) == 0
    assert counters["scheduler_bindings_total"] == 80


def test_host_sequential_phase_applies_soft_terms():
    """The exact host phase (constrained fallback) scores soft terms too:
    an anti-affinity pod with preferred affinity to zone-b lands in zone-b
    when both zones are feasible."""
    from tpu_scheduler.api.objects import PodAntiAffinityTerm

    nodes = [
        make_node("a1", cpu="8", memory="32Gi", labels={"zone": "a"}),
        make_node("b1", cpu="8", memory="32Gi", labels={"zone": "b"}),
    ]
    pref = [
        PreferredSchedulingTerm(
            weight=100,
            term=NodeSelectorTerm(
                match_expressions=[LabelSelectorRequirement(key="zone", operator="In", values=["b"])]
            ),
        )
    ]
    term = [PodAntiAffinityTerm(match_labels={"app": "db"}, topology_key="zone")]
    pod = make_pod("db-0", labels={"app": "db"}, anti_affinity=term, preferred_node_affinity=pref)
    snap = ClusterSnapshot.build(nodes, [pod])
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, NativeBackend(), policy="batch", requeue_seconds=0.0)
    bound, unsched = sched._run_constrained_phase(snap, [pod], PlacedOverlay())
    assert (bound, unsched) == (1, 0)
    placed = [p for p in api.list_pods() if p.spec.node_name]
    assert placed[0].spec.node_name == "b1"


def test_repack_incremental_preserves_soft_tensors():
    """The incremental pack path rebuilds pod-side soft tensors against the
    cached soft vocabularies (regression guard for the r2 checkpoint bug
    class: a new pod field must flow through EVERY pack path)."""
    from tpu_scheduler.ops.pack import repack_incremental

    snap = _soft_cluster(5, n_nodes=8, n_pending=24)
    packed = pack_snapshot(snap)
    repacked = repack_incremental(packed, snap)
    np.testing.assert_array_equal(packed.pod_ntol_soft, repacked.pod_ntol_soft[: packed.padded_pods])
    np.testing.assert_array_equal(packed.pod_pref_w, repacked.pod_pref_w[: packed.padded_pods])
