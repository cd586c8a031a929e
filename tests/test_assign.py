"""Batched assignment properties: validity (never oversubscribes, selector
respected), completeness (−1 only when truly infeasible), priority order,
determinism.  Run on the native backend; parity with TPU is in
test_backends_parity.py.
"""

import numpy as np
import pytest

from tpu_scheduler import ClusterSnapshot
from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.core.predicates import node_selector_matches
from tpu_scheduler.models.profiles import DEFAULT_PROFILE
from tpu_scheduler.ops.pack import pack_snapshot
from tpu_scheduler.testing import make_node, make_pod, synth_cluster


def check_validity(snap, packed, result):
    """Assignments never oversubscribe any node and respect selectors; −1 pods
    are infeasible against the remaining capacity."""
    pending = snap.pending_pods()
    nodes = list(snap.nodes)
    committed = np.zeros((packed.padded_nodes, 2), dtype=np.int64)
    for i, j in enumerate(result.assigned):
        if j >= 0:
            committed[j] += packed.pod_req[i]
            assert node_selector_matches(pending[i], nodes[j]), (pending[i].name, nodes[j].name)
    remaining = packed.node_avail.astype(np.int64) - committed
    assert (remaining[: packed.num_nodes] >= np.minimum(packed.node_avail[: packed.num_nodes], 0)).all(), (
        "oversubscribed a node"
    )
    # Every unscheduled pod is infeasible against what's left.
    for i, j in enumerate(result.assigned):
        if j < 0:
            pod = pending[i]
            for k, node in enumerate(nodes):
                fits = (packed.pod_req[i] <= remaining[k]).all()
                assert not (fits and node_selector_matches(pod, node)), (
                    f"pod {pod.name} left unscheduled but feasible on {node.name}"
                )


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", [(5, 8), (20, 60), (40, 300)])
def test_validity_properties(seed, shape):
    n_nodes, n_pending = shape
    snap = synth_cluster(n_nodes=n_nodes, n_pending=n_pending, n_bound=n_nodes * 2, seed=seed)
    packed = pack_snapshot(snap, pod_block=32, node_block=8)
    result = NativeBackend().schedule(packed, DEFAULT_PROFILE.with_(max_rounds=256))
    assert len(result.bindings) + len(result.unschedulable) == packed.num_pods
    check_validity(snap, packed, result)


def test_all_fit_when_capacity_ample():
    snap = synth_cluster(n_nodes=20, n_pending=30, seed=3, selector_fraction=0.0)
    packed = pack_snapshot(snap)
    result = NativeBackend().schedule(packed)
    assert result.unschedulable == []
    assert len(result.bindings) == 30


def test_contention_single_node():
    # One node, 4 cores; six 1-core pods → exactly 4 bind, highest priority first.
    node = make_node("n0", cpu="4", memory="64Gi")
    pods = [make_pod(f"p{i}", cpu="1", memory="1Gi", priority=i) for i in range(6)]
    snap = ClusterSnapshot.build([node], pods)
    packed = pack_snapshot(snap)
    result = NativeBackend().schedule(packed)
    assert len(result.bindings) == 4
    bound = {name.split("/")[-1] for name, _ in result.bindings}
    assert bound == {"p2", "p3", "p4", "p5"}  # priorities 2..5 win
    assert {n.split("/")[-1] for n in result.unschedulable} == {"p0", "p1"}


def test_fifo_tiebreak_within_priority():
    node = make_node("n0", cpu="2", memory="64Gi")
    pods = [make_pod(f"p{i}", cpu="1", memory="1Gi", priority=0) for i in range(4)]
    snap = ClusterSnapshot.build([node], pods)
    result = NativeBackend().schedule(pack_snapshot(snap))
    bound = {name.split("/")[-1] for name, _ in result.bindings}
    assert bound == {"p0", "p1"}  # FIFO within equal priority


def test_selector_routes_to_matching_node():
    nodes = [
        make_node("gpu-1", cpu="8", memory="32Gi", labels={"pool": "gpu"}),
        make_node("cpu-1", cpu="8", memory="32Gi", labels={"pool": "cpu"}),
    ]
    pods = [make_pod("want-gpu", cpu="1", memory="1Gi", node_selector={"pool": "gpu"})]
    result = NativeBackend().schedule(pack_snapshot(ClusterSnapshot.build(nodes, pods)))
    assert result.bindings == [("default/want-gpu", "gpu-1")]


def test_unschedulable_selector():
    nodes = [make_node("n0", cpu="8", memory="32Gi", labels={"zone": "a"})]
    pods = [make_pod("p", cpu="1", memory="1Gi", node_selector={"zone": "nowhere"})]
    result = NativeBackend().schedule(pack_snapshot(ClusterSnapshot.build(nodes, pods)))
    assert result.bindings == []
    assert result.unschedulable == ["default/p"]


def test_big_pod_does_not_block_small():
    # Big pod (5 cores) can never fit; small pods behind it in priority order
    # must still bind (prefix-greedy recovers across rounds).
    node = make_node("n0", cpu="4", memory="64Gi")
    pods = [
        make_pod("big", cpu="5", memory="1Gi", priority=10),
        make_pod("small1", cpu="2", memory="1Gi", priority=1),
        make_pod("small2", cpu="2", memory="1Gi", priority=0),
    ]
    result = NativeBackend().schedule(pack_snapshot(ClusterSnapshot.build([node], pods)))
    bound = {name.split("/")[-1] for name, _ in result.bindings}
    assert bound == {"small1", "small2"}
    assert [n.split("/")[-1] for n in result.unschedulable] == ["big"]


def test_deterministic():
    snap = synth_cluster(n_nodes=30, n_pending=100, seed=7)
    packed = pack_snapshot(snap)
    r1 = NativeBackend().schedule(packed)
    r2 = NativeBackend().schedule(packed)
    assert (r1.assigned == r2.assigned).all()


def test_empty_cluster():
    snap = ClusterSnapshot.build([], [make_pod("p")])
    result = NativeBackend().schedule(pack_snapshot(snap))
    assert result.bindings == []
    assert result.unschedulable == ["default/p"]


def test_no_pending_pods():
    snap = ClusterSnapshot.build([make_node("n")], [])
    result = NativeBackend().schedule(pack_snapshot(snap))
    assert result.bindings == [] and result.unschedulable == []
    assert result.rounds == 0


# --- the TpuBackend auction (size chain) against the native oracle ----------


@pytest.mark.parametrize(
    "n_nodes,n_pending,seed,kw",
    [
        (16, 200, 0, {}),  # contention: many rounds
        (64, 500, 1, {"selector_fraction": 0.4}),  # 512 rows: the chain drops to a 256-row stage
        (24, 120, 2, {"soft_taint_fraction": 0.3, "preferred_affinity_fraction": 0.3}),
        (24, 160, 5, {"extended_fraction": 0.3}),  # [·,3] resource tensors
    ],
)
def test_size_chain_matches_native(n_nodes, n_pending, seed, kw):
    """assign_cycle through TpuBackend, with a block small enough that the
    pod arrays are block-padded, is bit-identical to the native oracle:
    same bindings, same rounds, same acc_round."""
    from tpu_scheduler.backends.tpu import TpuBackend
    from tpu_scheduler.models.profiles import DEFAULT_PROFILE

    snap = synth_cluster(n_nodes=n_nodes, n_pending=n_pending, n_bound=n_nodes, seed=seed, **kw)
    packed = pack_snapshot(snap, pod_block=16, node_block=16)
    profile = DEFAULT_PROFILE.with_(max_rounds=64, pod_block=32)
    rn = NativeBackend().schedule(packed, profile)
    rt = TpuBackend().schedule(packed, profile)
    assert rn.bindings == rt.bindings
    assert rn.rounds == rt.rounds
    np.testing.assert_array_equal(rn.stats["acc_round"], rt.stats["acc_round"])


def test_constrained_tpu_matches_native():
    """Constraint cycles (AA + spread + ScheduleAnyway) through TpuBackend:
    identical to the native oracle."""
    from dataclasses import replace

    from tpu_scheduler.backends.tpu import TpuBackend
    from tpu_scheduler.models.profiles import DEFAULT_PROFILE
    from tpu_scheduler.ops.constraints import pack_constraints

    snap = synth_cluster(
        n_nodes=24, n_pending=160, n_bound=24, seed=4,
        anti_affinity_fraction=0.2, spread_fraction=0.2, schedule_anyway_fraction=0.2,
    )
    packed = pack_snapshot(snap)
    cons = pack_constraints(snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes)
    assert cons is not None
    packed = replace(packed, constraints=cons)
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings
    assert rn.rounds == rt.rounds
    assert (rn.stats["acc_round"] == rt.stats["acc_round"]).all()


def test_throughput_profile_round_count_stays_low():
    """Round-5 regression guard: bucket-quantized tie-breaking spreads the
    claimant herd across the whole near-tie band, collapsing the flagship
    auction from 9 rounds to 2.  Pin the effect at a moderate shape — a
    tie-break regression (e.g. reverting to additive jitter) re-herds the
    claims and pushes the round count back up."""
    from tpu_scheduler.backends.native import NativeBackend
    from tpu_scheduler.models.profiles import PROFILES
    from tpu_scheduler.ops.pack import pack_snapshot
    from tpu_scheduler.testing import synth_cluster

    snap = synth_cluster(n_nodes=1000, n_pending=10_000, n_bound=2_000, seed=0)
    packed = pack_snapshot(snap, pod_block=4096, node_block=128)
    r = NativeBackend().schedule(packed, PROFILES["throughput"])
    assert len(r.bindings) == 10_000
    assert r.rounds <= 4, f"tie-break regression: {r.rounds} rounds at 10k x 1k"
