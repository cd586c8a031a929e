"""Soft (preferred) inter-pod affinity / anti-affinity — kube's
``preferredDuringSchedulingIgnoredDuringExecution`` under podAffinity /
podAntiAffinity, the scoring twin of the hard co-location predicates.

Design under test (ops/score.py, ops/constraints.py): one signed-weight
matmul — pod_ppa_w [P,Tp] (±term weight) @ per-round domain match counts —
no global profile knob; the 1-100 term weights are the scale.
"""

import tpu_scheduler.core.predicates as P
from tpu_scheduler.api.objects import PodAffinityTerm, WeightedPodAffinityTerm
from tpu_scheduler.core.snapshot import ClusterSnapshot
from tpu_scheduler.testing import make_node, make_pod, synth_cluster

from test_constraints_tensor import _replay_validity, _schedule_both

ZONE_NODES = [
    make_node(f"n{i}", cpu="8", memory="32Gi", labels={"zone": f"z{i % 3}", "name": f"n{i}"}) for i in range(6)
]


def _pref(weight, app, key="zone"):
    return WeightedPodAffinityTerm(weight=weight, term=PodAffinityTerm(match_labels={"app": app}, topology_key=key))


# --- scalar scorer -----------------------------------------------------------


def test_scalar_scorer_counts_matches_per_domain():
    snap = ClusterSnapshot.build(
        ZONE_NODES,
        [
            make_pod("cache-0", labels={"app": "cache"}, node_name="n1", phase="Running"),  # z1
            make_pod("cache-1", labels={"app": "cache"}, node_name="n4", phase="Running"),  # z1
            make_pod("noisy-0", labels={"app": "noisy"}, node_name="n2", phase="Running"),  # z2
        ],
    )
    web = make_pod(
        "web-0",
        labels={"app": "web"},
        preferred_pod_affinity=[_pref(10, "cache")],
        preferred_pod_anti_affinity=[_pref(50, "noisy")],
    )
    scorer = P.make_preferred_pod_affinity_scorer(web, snap)
    by_zone = {}
    for n in snap.nodes:
        by_zone[n.metadata.labels["zone"]] = scorer(n)
    assert by_zone["z1"] == 20.0  # two cache matches x +10
    assert by_zone["z2"] == -50.0  # one noisy match x -50
    assert by_zone["z0"] == 0.0


def test_scalar_scorer_namespace_scoped():
    snap = ClusterSnapshot.build(
        ZONE_NODES,
        [make_pod("cache-0", namespace="other", labels={"app": "cache"}, node_name="n1", phase="Running")],
    )
    web = make_pod("web-0", namespace="default", preferred_pod_affinity=[_pref(10, "cache")])
    scorer = P.make_preferred_pod_affinity_scorer(web, snap)
    assert all(scorer(n) == 0.0 for n in snap.nodes)


# --- tensor path -------------------------------------------------------------


def test_preference_steers_placement():
    """With capacity everywhere, a strongly-preferring pod lands in the
    match's zone; an anti-preferring pod lands elsewhere."""
    placed = [make_pod("cache-0", labels={"app": "cache"}, node_name="n1", phase="Running")]  # z1
    lover = make_pod("lover", labels={"app": "web"}, preferred_pod_affinity=[_pref(100, "cache")])
    hater = make_pod("hater", labels={"app": "web2"}, preferred_pod_anti_affinity=[_pref(100, "cache")])
    snap = ClusterSnapshot.build(ZONE_NODES, placed + [lover, hater])
    packed, r = _schedule_both(snap)
    node_zone = {n.name: n.metadata.labels["zone"] for n in snap.nodes}
    zones = {p: node_zone[nn] for p, nn in r.bindings}
    assert zones["default/lover"] == "z1"
    assert zones["default/hater"] != "z1"


def test_constraint_commit_updates_preference_counts():
    """The per-round commit path: accepted pods matching a preferred term
    bump their landing domain's count (coarse) or node's count (fine /
    keyless), so later rounds of the SAME cycle see them.  Exercised
    directly — deleting the ppa commit logic must fail this test."""
    import numpy as np

    from tpu_scheduler.ops.constraints import augment_round_state, constraint_commit, pack_constraints, round_blocked_masks
    from tpu_scheduler.ops.pack import pack_snapshot

    keyless = make_node("bare", cpu="8", memory="32Gi")  # no zone label -> fine domain
    nodes = ZONE_NODES + [keyless]
    pods = [
        make_pod("cache-0", labels={"app": "cache"}),  # matches the term, declares nothing
        make_pod("cache-1", labels={"app": "cache"}),
        make_pod("web-0", labels={"app": "web"}, preferred_pod_affinity=[_pref(10, "cache")]),
    ]
    snap = ClusterSnapshot.build(nodes, pods)
    packed = pack_snapshot(snap)
    cons = pack_constraints(snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes)
    assert cons is not None and cons.n_ppa_terms == 1
    p = packed.padded_pods
    accepted = np.zeros((p,), bool)
    accepted[0] = accepted[1] = True  # both cache pods accepted this round
    choice = np.zeros((p,), np.int32)
    choice[0] = 1  # cache-0 -> n1 (zone z1)
    choice[1] = 6  # cache-1 -> bare (keyless -> fine twin)
    state0 = augment_round_state(np, cons.state_arrays(), cons.meta_arrays())
    state = constraint_commit(np, accepted, choice, cons.pod_arrays(), state0, cons.meta_arrays(), soft_pa=True)
    ndc = cons.node_dom_c  # [N, D]
    z1_col = int(np.argmax(ndc[1]))  # n1's one-hot domain column
    assert state["ppa_dom_cnt"][0, z1_col] == 1.0, "coarse domain count not bumped"
    assert state["ppa_node_cnt"][0, 6] == 1.0, "fine (keyless node) count not bumped"
    # and the next round's score operand sees both
    masks = round_blocked_masks(np, state, cons.meta_arrays(), soft_pa=True, hard_pa=False)
    assert masks["ppa_cnt_node"][0, 1] == 1.0  # n1 itself
    assert masks["ppa_cnt_node"][0, 4] == 1.0  # n4 shares zone z1
    assert masks["ppa_cnt_node"][0, 6] == 1.0  # the keyless node
    assert masks["ppa_cnt_node"][0, 2] == 0.0  # z2 untouched


def test_synth_preferred_pod_affinity_parity():
    for seed in (1, 7):
        snap = synth_cluster(
            n_nodes=24,
            n_pending=120,
            n_bound=24,
            seed=seed,
            preferred_pod_affinity_fraction=0.4,
            pod_affinity_fraction=0.1,
            anti_affinity_fraction=0.1,
            schedule_anyway_fraction=0.1,
        )
        packed, r = _schedule_both(snap)  # asserts native == tpu
        assert _replay_validity(snap, packed, r) == 0, f"seed {seed}"


def test_soft_terms_never_block():
    """Anti-preference is scoring only: when the disliked zone is the only
    one with capacity, the pod still binds there."""
    nodes = [
        make_node("n0", cpu="500m", memory="32Gi", labels={"zone": "z0"}),  # too small
        make_node("n1", cpu="8", memory="32Gi", labels={"zone": "z1"}),
    ]
    placed = [make_pod("noisy-0", labels={"app": "noisy"}, node_name="n1", phase="Running")]
    pod = make_pod("web-0", cpu="1", labels={"app": "web"}, preferred_pod_anti_affinity=[_pref(100, "noisy")])
    snap = ClusterSnapshot.build(nodes, placed + [pod])
    packed, r = _schedule_both(snap)
    assert dict(r.bindings)["default/web-0"] == "n1"


def test_round_trip_serialization():
    from tpu_scheduler.api.objects import Pod, pod_to_dict

    pod = make_pod(
        "web-0",
        preferred_pod_affinity=[_pref(10, "cache")],
        preferred_pod_anti_affinity=[_pref(50, "noisy", key="name")],
    )
    back = Pod.from_dict(pod_to_dict(pod))
    assert back.spec.preferred_pod_affinity[0].weight == 10
    assert back.spec.preferred_pod_affinity[0].term.match_labels == {"app": "cache"}
    assert back.spec.preferred_pod_anti_affinity[0].weight == 50
    assert back.spec.preferred_pod_anti_affinity[0].term.topology_key == "name"
