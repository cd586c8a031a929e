"""A fleet of multi-host TPU v5e slices, each slice its own node pool, as
GKE builds them (cloud.google.com/kubernetes-engine/docs/concepts/tpus):
``ct5lp-hightpu-4t`` hosts with 4 chips each, labelled with their pool,
topology and accelerator, and tainted ``google.com/tpu=present:NoSchedule``.

- ``gen_nodes(rng, cfg)``: ``cfg["slice_topologies"]`` lists each slice's
  topology ("4x4" is 4 hosts, "4x8" 8);
- ``gen_standing(rng, cfg, nodes)``: ``cfg["running_jobs"]`` JobSet jobs
  already bound, job ``j`` alone on slice ``j``;
- ``job_pods(job, topology, priority)``: one job's pods, for the loop too.
"""

from benchmark import cluster

from tpu_scheduler.api.objects import LabelSelectorRequirement, PodAntiAffinityTerm, Taint, Toleration

POOL = "cloud.google.com/gke-nodepool"
TOPOLOGY = "cloud.google.com/gke-tpu-topology"
ACCELERATOR = "cloud.google.com/gke-tpu-accelerator"
JOB = "jobset.sigs.k8s.io/job-key"
TPU = "google.com/tpu"
HOST = (112, 192)  # ct5lp-hightpu-4t: vCPUs, GiB
CHIPS_PER_HOST = 4


def hosts(topology: str) -> int:
    x, y = (int(v) for v in topology.split("x"))
    return x * y // CHIPS_PER_HOST


def job_pods(job: str, topology: str, priority: int, nodes=None) -> list:
    """Job ``job``'s pods, one a host of a slice of ``topology``, a gang,
    with the two terms JobSet's exclusive placement over the node-pool key
    gives: pod affinity ``job-key In [job]``, anti-affinity ``job-key
    Exists`` and ``job-key NotIn [job]``.  Bound to ``nodes`` where given."""
    affinity = [PodAntiAffinityTerm(topology_key=POOL, match_expressions=[LabelSelectorRequirement(JOB, "In", [job])])]
    anti = [PodAntiAffinityTerm(topology_key=POOL, match_expressions=[LabelSelectorRequirement(JOB, "Exists"),
                                                                      LabelSelectorRequirement(JOB, "NotIn", [job])])]
    return [
        cluster.make_pod(f"{job}-{i}", "8", "32Gi", node_name=nodes[i] if nodes else None, phase="Running" if nodes else "Pending",
                         priority=priority, labels={JOB: job}, extended={TPU: str(CHIPS_PER_HOST)}, node_selector={TOPOLOGY: topology},
                         tolerations=[Toleration(key=TPU, operator="Exists", effect="NoSchedule")],
                         pod_affinity=affinity, anti_affinity=anti, gang=job)
        for i in range(hosts(topology))
    ]


def gen_nodes(rng, cfg):
    nodes = []
    for s, topo in enumerate(cfg["slice_topologies"]):
        for h in range(hosts(topo)):
            labels = {POOL: f"slice-{s}", TOPOLOGY: topo, ACCELERATOR: "tpu-v5-lite-podslice",
                      "topology.tpu-scheduler/slice": f"slice-{s}", "name": f"slice-{s}-{h}"}
            taints = [Taint(key=TPU, value="present", effect="NoSchedule")]
            nodes.append(cluster.make_node(f"slice-{s}-{h}", HOST[0], f"{HOST[1]}Gi", labels, taints, extended={TPU: str(CHIPS_PER_HOST)}))
    return nodes


def gen_standing(rng, cfg, nodes):
    pods = []
    for j in range(cfg["running_jobs"]):
        on = [n for n in nodes if n.metadata.labels[POOL] == f"slice-{j}"]
        pods += job_pods(f"running-{j}", on[0].metadata.labels[TOPOLOGY], rng.randrange(3), [n.metadata.name for n in on])
    return pods
