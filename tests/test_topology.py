"""Topology-aware gang placement (ISSUE 6): the interconnect distance model,
the fused rank-aware locality term, backend parity, the sim scenarios'
locality verdict, and the observability surface.

The ISSUE acceptance criterion is pinned here: on `slice-fragmented-cluster`
topology-aware scoring places EVERY feasible gang with zero cross-rack edges
where a single-rack fit exists, while the topology-blind baseline does not —
asserted through the scorecard `locality` block.
"""

import json
import random
import urllib.request
from dataclasses import replace

import numpy as np
import pytest

from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.core.snapshot import ClusterSnapshot
from tpu_scheduler.models.profiles import DEFAULT_PROFILE, PROFILES
from tpu_scheduler.ops.pack import pack_snapshot
from tpu_scheduler.testing import make_node, make_pod
from tpu_scheduler.topology.locality import (
    gang_placement_stats,
    gang_state_update,
    gang_topology_term,
    pack_topology,
)
from tpu_scheduler.topology.model import DEFAULT_LEVEL_KEYS, TopologyModel, load_topology_file

SLICE_KEY = DEFAULT_LEVEL_KEYS[0][1]
RACK_KEY = DEFAULT_LEVEL_KEYS[1][1]


def topo_node(i: int, cpu="8", memory="32Gi", slice_size=3, rack_size=6):
    return make_node(
        f"n{i:02d}",
        cpu=cpu,
        memory=memory,
        labels={SLICE_KEY: f"s{i // slice_size}", RACK_KEY: f"r{i // rack_size}", "name": f"n{i:02d}"},
    )


def build_topo_cluster(n_nodes=24, gangs=2, gang_size=4, fillers=6, cpu="8"):
    nodes = [topo_node(i, cpu=cpu) for i in range(n_nodes)]
    pods = []
    for g in range(gangs):
        for m in range(gang_size):
            pods.append(make_pod(f"g{g}-m{m}", cpu="2", memory="4Gi", gang=f"gang-{g}"))
    for f in range(fillers):
        pods.append(make_pod(f"f{f}", cpu="1", memory="2Gi"))
    snap = ClusterSnapshot.build(nodes, pods)
    compiled = TopologyModel.detect(nodes).compile(nodes)
    packed = pack_snapshot(snap)
    topo = pack_topology(compiled, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes)
    return snap, compiled, packed, topo


# --- model ------------------------------------------------------------------


def test_detect_compile_and_distance_matrix():
    nodes = [topo_node(i) for i in range(12)]
    model = TopologyModel.detect(nodes)
    assert [lv.name for lv in model.levels] == ["slice", "rack"]
    compiled = model.compile(nodes)
    dm = compiled.distance_matrix()
    assert dm.shape == (12, 12) and np.allclose(dm, dm.T) and (np.diag(dm) == 0).all()
    assert dm[0, 1] == 0.0  # same slice
    assert dm[0, 3] == 1.0  # same rack, different slice
    assert dm[0, 7] == 2.0  # different rack
    assert compiled.domains_of("n00") == ("s0", "r0")
    assert compiled.domains_of("ghost") is None


def test_detect_none_on_unlabeled_cluster_and_singleton_fallback():
    assert TopologyModel.detect([make_node("plain")]) is None
    # A rack-only cluster compiles to one level; an unlabeled straggler in a
    # labeled cluster gets a singleton domain (maximally far).
    nodes = [
        make_node("a", labels={RACK_KEY: "r0"}),
        make_node("b", labels={RACK_KEY: "r0"}),
        make_node("c", labels={}),
    ]
    model = TopologyModel.detect(nodes)
    assert [lv.name for lv in model.levels] == ["rack"]
    dm = model.compile(nodes).distance_matrix()
    assert dm[0, 1] == 0.0 and dm[0, 2] == 1.0


def test_topology_file_spec_roundtrip(tmp_path):
    spec = {
        "levels": [{"name": "slice", "distance": 1.0}, {"name": "rack", "distance": 2.5}],
        "nodes": {"a": {"slice": "s0", "rack": "r0"}, "b": {"slice": "s1", "rack": "r0"}},
    }
    path = tmp_path / "topo.json"
    path.write_text(json.dumps(spec))
    model = load_topology_file(str(path))
    compiled = model.compile([make_node("a"), make_node("b")])
    dm = compiled.distance_matrix()
    assert dm[0, 1] == 1.0  # slice differs, rack shared
    assert list(compiled.level_distances()) == [1.0, 2.5]
    with pytest.raises(ValueError):
        TopologyModel.from_spec({"levels": []})


# --- locality term ----------------------------------------------------------


def test_pack_topology_gang_ids_and_gangless_none():
    snap, compiled, packed, topo = build_topo_cluster()
    ids = topo.pod_gang_id
    assert topo.gang_names == ("gang-0", "gang-1")
    assert list(ids[:8]) == [1, 1, 1, 1, 2, 2, 2, 2]
    assert (ids[8:] == 0).all()  # fillers + padding ride the zero row
    plain = ClusterSnapshot.build(snap.nodes, [make_pod("solo")])
    p2 = pack_snapshot(plain)
    assert pack_topology(compiled, plain.pending_pods(), p2.padded_pods, p2.node_names, p2.padded_nodes) is None


def test_anchor_term_matches_distance_matrix_factoring():
    """The per-level one-hot factoring in gang_topology_term must equal the
    direct gang_nodes @ distance_matrix product — the algebraic identity
    that lets the device path skip the [N, N] tensor."""
    snap, compiled, packed, topo = build_topo_cluster()
    n_pad = packed.padded_nodes
    g1 = topo.n_gangs + 1
    rng = np.random.RandomState(0)
    gang_nodes = np.zeros((g1, n_pad + 1), dtype=np.float32)
    gang_nodes[1:, : len(compiled.node_names)] = rng.randint(0, 3, size=(g1 - 1, len(compiled.node_names)))
    avail = packed.node_avail
    # Zero-demand pods: the fit bonus applies everywhere equally per level;
    # isolate the anchor by differencing against a zero-placement call.
    no_place = np.zeros_like(gang_nodes)
    req = np.zeros_like(packed.pod_req)
    active = np.zeros((packed.padded_pods,), dtype=bool)
    t_placed = gang_topology_term(np, gang_nodes, topo.meta, avail, topo.pod_gang_id, req, active, np.float32(1.0))
    t_empty = gang_topology_term(np, no_place, topo.meta, avail, topo.pod_gang_id, req, active, np.float32(1.0))
    anchor = t_placed - t_empty  # fit/herd cancel; −ANCHOR_SCALE·Σ counts·dist remains
    from tpu_scheduler.topology.locality import ANCHOR_SCALE

    n_real = len(compiled.node_names)
    dm = compiled.distance_matrix()
    expect = -ANCHOR_SCALE * (gang_nodes[:, :n_real] @ dm)
    assert np.allclose(anchor[:, :n_real], expect, atol=1e-3)
    assert (t_placed[0] == 0).all()  # the no-gang row is pinned to zero


def test_gang_state_update_sentinels():
    gang_nodes = np.zeros((3, 5), dtype=np.float32)  # 2 gangs, 4 nodes + sentinel
    accepted = np.array([True, False, True, True])
    choice = np.array([1, 4, 4, 2], dtype=np.int32)  # 4 = non-claimant sentinel
    gang_id = np.array([1, 1, 2, 0], dtype=np.int32)  # last pod gangless
    out = gang_state_update(np, gang_nodes, accepted, choice, gang_id)
    assert out[1, 1] == 1.0  # accepted member counted
    assert out[1, 4] == 0.0 and out[2, 4] == 1.0  # sentinel column absorbs, never read
    assert out[0, 2] == 1.0  # gangless row absorbs, never read
    assert (gang_nodes == 0).all()  # numpy path copies


def test_gang_placement_stats():
    doms = [("s0", "r0"), ("s0", "r0"), ("s1", "r0"), ("s4", "r2")]
    stats = gang_placement_stats(doms, [1.0, 1.0])
    assert stats["members"] == 4 and stats["pairs"] == 6
    assert stats["max_distance"] == 2.0
    assert stats["cross_edges"] == 3  # every pair involving the r2 member
    one_slice = gang_placement_stats([("s0", "r0")] * 3, [1.0, 1.0])
    assert one_slice["max_distance"] == 0.0 and one_slice["cross_edges"] == 0


# --- placement behaviour + backend parity -----------------------------------


def test_gangs_converge_to_one_slice_and_blind_baseline_scatters():
    snap, compiled, packed, topo = build_topo_cluster()
    packed_t = replace(packed, topology=topo)
    nb = NativeBackend()
    r = nb.schedule(packed_t, DEFAULT_PROFILE)
    dists = compiled.level_distances()
    for g in ("g0", "g1"):
        doms = [compiled.domains_of(n) for pf, n in r.bindings if pf.startswith(f"default/{g}-")]
        assert len(doms) == 4
        assert gang_placement_stats(doms, dists)["max_distance"] == 0.0, g
    r_blind = nb.schedule(packed, DEFAULT_PROFILE)
    blind_worst = 0.0
    for g in ("g0", "g1"):
        doms = [compiled.domains_of(n) for pf, n in r_blind.bindings if pf.startswith(f"default/{g}-")]
        blind_worst = max(blind_worst, gang_placement_stats(doms, dists)["max_distance"])
    assert blind_worst > 0.0  # jitter scatters near-ties without the term


def test_native_tpu_parity_with_topology_both_drivers():
    """ISSUE satellite: identical placements and locality scores for a
    seeded gang workload on both backends (and both auction drivers)."""
    from tpu_scheduler.backends.tpu import TpuBackend

    rng = random.Random(7)
    nodes = [topo_node(i, cpu=str(rng.choice([8, 16, 32])), slice_size=4, rack_size=8) for i in range(32)]
    pods = []
    gi = 0
    for a in range(40):
        if rng.random() < 0.4:
            for m in range(rng.randrange(2, 6)):
                pods.append(
                    make_pod(f"g{gi}-m{m}", cpu=f"{rng.choice([500, 1000, 2000])}m", memory="2Gi",
                             gang=f"gang-{gi}", priority=rng.choice([0, 5]))
                )
            gi += 1
        else:
            pods.append(make_pod(f"p{a}", cpu=f"{rng.choice([250, 500, 1000])}m", memory="1Gi"))
    snap = ClusterSnapshot.build(nodes, pods)
    compiled = TopologyModel.detect(nodes).compile(nodes)
    packed = pack_snapshot(snap)
    topo = pack_topology(compiled, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes)
    packed = replace(packed, topology=topo)
    tb = TpuBackend(use_pallas=False)
    for profile in (DEFAULT_PROFILE, PROFILES["throughput"]):
        rn = NativeBackend().schedule(packed, profile)
        rt = tb.schedule(packed, profile)
        assert rn.bindings == rt.bindings, profile.name
        assert rn.unschedulable == rt.unschedulable
        # identical placements → identical locality scores, asserted explicitly
        dists = compiled.level_distances()
        for g in range(gi):
            dn = [compiled.domains_of(n) for pf, n in rn.bindings if pf.startswith(f"default/g{g}-")]
            dt = [compiled.domains_of(n) for pf, n in rt.bindings if pf.startswith(f"default/g{g}-")]
            if len(dn) >= 2:
                assert gang_placement_stats(dn, dists) == gang_placement_stats(dt, dists)


def test_chaos_trace_replay_parity_on_topology_scenario(tmp_path):
    """Extend the chaos-trace parity pattern: one recorded topology-scenario
    trace replayed on native AND TpuBackend-on-CPU must fingerprint-match."""
    from tpu_scheduler.backends.tpu import TpuBackend
    from tpu_scheduler.sim import run_scenario

    path = str(tmp_path / "topo-trace.jsonl")
    live = run_scenario("slice-fragmented-cluster", seed=3, record=path)
    native = run_scenario(None, replay=path)
    jax_card = run_scenario(None, replay=path, backend=TpuBackend(use_pallas=False))
    fps = {live["fingerprint"], native["fingerprint"], jax_card["fingerprint"]}
    assert len(fps) == 1, fps
    assert native["locality"] == live["locality"] == jax_card["locality"]


# --- controller quality backstop --------------------------------------------


def test_cross_rack_rejects_only_when_single_rack_fit_existed():
    from tpu_scheduler.backends.base import CycleResult
    from tpu_scheduler.runtime.controller import Scheduler

    snap, compiled, packed, topo = build_topo_cluster(n_nodes=12, gangs=1, gang_size=2, fillers=0)
    packed = replace(packed, topology=topo)
    members = {"gang-0": {"default/g0-m0", "default/g0-m1"}}
    local = set(members["gang-0"])

    def result_for(nodes_chosen):
        bindings = list(zip(sorted(local), nodes_chosen))
        return CycleResult(assigned=np.zeros(2, np.int32), bindings=bindings, unschedulable=[], rounds=1)

    # Cross-rack placement while rack fits exist -> rejected for quality.
    rej = Scheduler._cross_rack_rejects(packed, result_for(["n00", "n07"]), members, local, set())
    assert rej == {"gang-0"}
    # Single-rack placement -> clean.
    assert Scheduler._cross_rack_rejects(packed, result_for(["n00", "n01"]), members, local, set()) == set()
    # Cross-rack but NO rack could fit the gang whole -> stands (best available).
    starved = replace(packed, node_avail=np.zeros_like(packed.node_avail), topology=topo)
    assert Scheduler._cross_rack_rejects(starved, result_for(["n00", "n07"]), members, local, set()) == set()


# --- the ISSUE acceptance scenario ------------------------------------------


def test_slice_fragmented_cluster_zero_cross_rack_vs_blind_baseline():
    """ISSUE acceptance: topology-aware scoring admits EVERY gang with zero
    cross-rack edges on slice-fragmented-cluster (scorecard-gated), while
    the topology-BLIND baseline does not."""
    from tpu_scheduler.sim import run_scenario
    from tpu_scheduler.sim.scorecard import SCORECARD_FIELDS

    card = run_scenario("slice-fragmented-cluster", seed=0)
    assert tuple(card) == SCORECARD_FIELDS
    loc = card["locality"]
    assert loc["enabled"] and loc["required"] and loc["levels"] == ["slice", "rack"]
    assert loc["gangs_scored"] > 50  # the workload really is gang-heavy
    assert loc["cross_rack_gangs"] == 0 and loc["cross_rack_edges"] == 0
    assert card["pass"], json.dumps(loc)
    assert card["pods"]["lost"] == 0 and card["pods"]["double_bound"] == 0

    blind = run_scenario("slice-fragmented-cluster", seed=0, topology=None)
    bloc = blind["locality"]
    assert bloc["cross_rack_gangs"] > 0  # the baseline scatters...
    assert not blind["pass"]  # ...and the locality gate fails it


def test_locality_gate_is_virtual_and_deterministic():
    from tpu_scheduler.sim import run_scenario

    c1 = run_scenario("slice-fragmented-cluster", seed=1)
    c2 = run_scenario("slice-fragmented-cluster", seed=1)
    assert json.dumps(c1, sort_keys=True) == json.dumps(c2, sort_keys=True)
    assert c1["pass"] and c1["locality"]["cross_rack_gangs"] == 0


def test_rack_failure_scenario_survives_with_invariants():
    """A whole rack dies mid-admission: no pods lost, invariants hold,
    churn-disturbed gangs are counted-and-skipped by the locality verdict,
    and the surviving admissions stay single-rack."""
    from tpu_scheduler.sim import run_scenario

    for seed in (0, 1):
        card = run_scenario("rack-failure-during-gang-admission", seed=seed)
        assert card["pass"], json.dumps(card["invariants"])
        assert card["pods"]["lost"] == 0 and card["pods"]["double_bound"] == 0
        assert card["pods"]["churn_recreated"] > 0  # the rack really died
        loc = card["locality"]
        assert loc["enabled"] and loc["levels"] == ["rack"]
        assert loc["cross_rack_gangs"] == 0


def test_new_scenarios_record_replay_bit_identical(tmp_path):
    """ISSUE satellite: record→replay bit-identity for both new scenarios
    across seeds {0, 1}."""
    from tpu_scheduler.sim import run_scenario

    for name in ("slice-fragmented-cluster", "rack-failure-during-gang-admission"):
        for seed in (0, 1):
            path = str(tmp_path / f"{name}-{seed}.jsonl")
            live = run_scenario(name, seed=seed, record=path)
            replayed = run_scenario(None, replay=path)
            assert replayed["fingerprint"] == live["fingerprint"], (name, seed)
            assert replayed["locality"] == live["locality"]


# --- observability ----------------------------------------------------------


def test_gang_distance_histogram_and_debug_locality_route():
    from tpu_scheduler.runtime.controller import Scheduler
    from tpu_scheduler.runtime.fake_api import FakeApiServer
    from tpu_scheduler.runtime.http_api import HttpApiServer

    api = FakeApiServer()
    nodes = [topo_node(i) for i in range(12)]
    pods = [make_pod(f"g0-m{m}", cpu="2", memory="4Gi", gang="gang-0") for m in range(3)]
    pods.append(make_pod("solo", cpu="1"))
    api.load(nodes=nodes, pods=pods)
    sched = Scheduler(api, NativeBackend())
    sched.run(until_settled=True)
    snap = sched.metrics.snapshot()
    assert snap.get("scheduler_topology_cycles_total", 0) >= 1
    assert snap.get("scheduler_gangs_admitted_total", 0) == 1
    text = sched.metrics.to_prometheus()
    assert "scheduler_gang_placement_distance_bucket" in text
    assert 'scheduler_gang_placement_distance_bucket{le="0"} 1' in text  # one slice-local gang

    server = HttpApiServer(api, metrics=sched.metrics, recorder=sched.recorder).start()
    try:
        with urllib.request.urlopen(f"{server.base_url}/debug/pods/default/g0-m0") as r:
            d = json.load(r)
    finally:
        server.stop()
    loc = d["locality"]
    assert loc["gang"] == "gang-0" and loc["members"] == 3 and loc["members_bound"] == 3
    assert loc["stats"]["max_distance"] == 0.0 and loc["stats"]["cross_edges"] == 0
    assert loc["stats"]["levels"] == ["slice", "rack"]
    # the admitted-gang timeline carries the locality verdict
    timeline = d["timeline"]
    admitted = [e for e in timeline if e["kind"] == "gang-admitted"]
    assert admitted and "max_dist=0.0" in admitted[-1]["detail"]


def test_no_topology_attach_for_gangless_or_unlabeled_clusters():
    from tpu_scheduler.runtime.controller import Scheduler
    from tpu_scheduler.runtime.fake_api import FakeApiServer

    # Labeled cluster, no gangs: zero topology cycles, zero overhead.
    api = FakeApiServer()
    api.load(nodes=[topo_node(i) for i in range(6)], pods=[make_pod("a"), make_pod("b")])
    sched = Scheduler(api, NativeBackend())
    sched.run(until_settled=True)
    assert sched.metrics.snapshot().get("scheduler_topology_cycles_total", 0) == 0
    # Unlabeled cluster with gangs: auto-detect declines, cycle still binds.
    api2 = FakeApiServer()
    api2.load(
        nodes=[make_node("p1", cpu=8), make_node("p2", cpu=8)],
        pods=[make_pod(f"g-{m}", gang="g") for m in range(2)],
    )
    sched2 = Scheduler(api2, NativeBackend())
    sched2.run(until_settled=True)
    assert sched2.metrics.snapshot().get("scheduler_topology_cycles_total", 0) == 0
    assert sched2.metrics.snapshot().get("scheduler_gangs_admitted_total", 0) == 1


def test_detect_reads_gke_slice_labels():
    """GKE labels each multi-host TPU slice's nodes with their pool and
    topology: a node with the topology label is in the slice its pool names;
    a CPU pool's nodes (no topology label) are in no slice."""
    from tpu_scheduler.topology.model import GKE_NODEPOOL_KEY, GKE_TPU_TOPOLOGY_KEY

    nodes = [make_node(f"t{i}", labels={GKE_NODEPOOL_KEY: f"tpu-{i // 2}", GKE_TPU_TOPOLOGY_KEY: "2x4"}) for i in range(4)]
    nodes += [make_node(f"c{i}", labels={GKE_NODEPOOL_KEY: "cpu-pool"}) for i in range(2)]
    model = TopologyModel.detect(nodes)
    assert [(lv.name, lv.key) for lv in model.levels] == [("slice", GKE_NODEPOOL_KEY)]
    compiled = model.compile(nodes)
    assert compiled.domains_of("t0") == compiled.domains_of("t1") == ("tpu-0",)
    assert compiled.domains_of("t2") == ("tpu-1",)
    assert compiled.domains_of("c0") == ("~c0",) and compiled.domains_of("c1") == ("~c1",)
    # A pool label alone (no TPU topology) is no slice: the cluster stays topology-blind.
    assert TopologyModel.detect([make_node("c", labels={GKE_NODEPOOL_KEY: "cpu-pool"})]) is None


def test_detect_keeps_the_repos_own_labels_over_gke_ones():
    from tpu_scheduler.topology.model import GKE_NODEPOOL_KEY, GKE_TPU_TOPOLOGY_KEY

    nodes = [make_node(f"n{i}", labels={SLICE_KEY: f"s{i % 2}", RACK_KEY: "r0", GKE_NODEPOOL_KEY: "p", GKE_TPU_TOPOLOGY_KEY: "2x4"})
             for i in range(4)]
    model = TopologyModel.detect(nodes)
    assert [(lv.name, lv.key) for lv in model.levels] == [("slice", SLICE_KEY), ("rack", RACK_KEY)]
    assert model.compile(nodes).domains_of("n1") == ("s1", "r0")
    assert TopologyModel.detect([make_node("plain", labels={"zone": "a"})]) is None
