"""A constrained admission wave through the served path, at a small size: the
scheduler ``cli.build_scheduler`` builds for ``--backend tpu --no-fallback``
against a cluster of ``--workload mixed`` pods (selectors, node and pod
affinity hard and soft, anti-affinity, spread hard and soft, gangs, TPU-chip
requests, taints).  The wave must decide within 64 cycles on the tensor
path, explain the pods it leaves pending (``<phase>/explain`` records and
the explained-pods counter), and leave no pod pending that declares no
inter-pod term, belongs to no gang and fits some node."""

import pytest

from tpu_scheduler import cli
from tpu_scheduler.core.predicates import check_node_validity
from tpu_scheduler.core.snapshot import ClusterSnapshot
from tpu_scheduler.testing import synth_cluster


def declares_terms(pod) -> bool:
    s = pod.spec
    return bool(s.anti_affinity or s.pod_affinity or s.topology_spread or s.gang)


@pytest.mark.parametrize("seed", [6, 7])
def test_mixed_wave_decides_on_the_tensor_path_and_explains(seed):
    args = cli.build_parser().parse_args(["--backend", "tpu", "--no-fallback", "--nodes", "0", "--pods", "0"])
    api, sched = cli.build_scheduler(args)
    snap = synth_cluster(n_nodes=240, n_pending=800, n_bound=2400, seed=seed, **cli.MIXED_WORKLOAD)
    api.load(snap.nodes, snap.pods)
    wave = len(snap.pending_pods())
    bound = cycles = 0
    try:
        for cycles in range(1, 65):
            m = sched.run_cycle()
            bound += m.bound
            if bound >= wave or m.bound == 0:
                break
        counters = sched.metrics.snapshot()
        explain = [s for rec in sched.recorder.cycles() for s in rec["spans"] if s["name"].endswith("/explain")]
    finally:
        sched.close()
    assert cycles < 64 and bound > wave // 2
    assert counters.get("scheduler_constraint_tensor_cycles_total", 0) >= 1
    assert counters.get("scheduler_constraint_host_fallbacks_total", 0) == 0
    assert counters.get("scheduler_explained_pods_total", 0) > 0
    assert explain and all(s["end"] >= s["start"] for s in explain)
    final = ClusterSnapshot.build(api.list_nodes(), api.list_pods())
    stranded = [
        p.metadata.name
        for p in final.pending_pods()
        if not declares_terms(p) and any(check_node_validity(p, n, final) is None for n in final.nodes)
    ]
    assert not stranded, f"pending although they fit: {stranded[:5]}"
