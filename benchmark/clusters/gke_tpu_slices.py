"""A GKE fleet of multi-host TPU v5e slices running JobSet training jobs,
each job alone on its own slice (cloud.google.com/kubernetes-engine/docs/
concepts/tpus; JobSet's ``alpha.jobset.sigs.k8s.io/exclusive-topology``).

Every slice is its own node pool of ``ct5lp-hightpu-4t`` hosts (4 v5e
chips, 112 vCPUs, 192 GiB).  Its nodes carry GKE's labels alone (pool,
slice topology, accelerator, hostname) and GKE's taint
``google.com/tpu=present:NoSchedule``.  A job asks for one slice shape by a
nodeSelector on the topology label and runs one pod a host, 4 chips each,
with the two terms exclusive placement over the pool key gives.

- ``gen_nodes(rng, cfg)``: ``cfg["slice_pools"]`` maps each topology
  ("4x4" is 4 hosts, "16x16" 64) to its number of pools;
- ``gen_standing(rng, cfg, nodes)``: ``cfg["occupied_share"]`` of each
  topology's pools (drawn from the seed) hold a running job each, bound
  whole, its priority drawn from ``cfg["priority_tiers"]``;
- ``wave_jobs(rng, traffic)`` and ``wave_pods(jobs, prefix)``: one wave's
  pending jobs, topologies drawn with the pool census's weights, for the
  loop.
"""

from __future__ import annotations

import random

from benchmark import cluster

from tpu_scheduler.api.objects import LabelSelectorRequirement, PodAntiAffinityTerm, Taint, Toleration

POOL = "cloud.google.com/gke-nodepool"
TOPOLOGY = "cloud.google.com/gke-tpu-topology"
ACCELERATOR = "cloud.google.com/gke-tpu-accelerator"
HOSTNAME = "kubernetes.io/hostname"
JOB = "jobset.sigs.k8s.io/job-key"
TPU = "google.com/tpu"
V5E = "tpu-v5-lite-podslice"
HOST = (112, "192Gi")  # ct5lp-hightpu-4t: vCPUs, memory
CHIPS_PER_HOST = 4
POD = ("8", "32Gi")  # one training worker a host: cpu, memory requests beside its 4 chips


def hosts(topology: str) -> int:
    x, y = (int(v) for v in topology.split("x"))
    return x * y // CHIPS_PER_HOST


def job_pods(job: str, topology: str, priority: int, nodes: list[str] | None = None) -> list:
    """Job ``job``'s pods, one a host of a slice of ``topology``, a gang, with
    JobSet's exclusive placement over the pool key: pod affinity ``job-key
    In [job]``, anti-affinity ``job-key Exists`` and ``job-key NotIn [job]``.
    Bound to ``nodes`` where given."""
    affinity = [PodAntiAffinityTerm(topology_key=POOL, match_expressions=[LabelSelectorRequirement(JOB, "In", [job])])]
    anti = [PodAntiAffinityTerm(topology_key=POOL, match_expressions=[LabelSelectorRequirement(JOB, "Exists"),
                                                                      LabelSelectorRequirement(JOB, "NotIn", [job])])]
    return [
        cluster.make_pod(f"{job}-{i}", POD[0], POD[1], node_name=nodes[i] if nodes else None,
                         phase="Running" if nodes else "Pending", priority=priority, labels={JOB: job},
                         extended={TPU: str(CHIPS_PER_HOST)}, node_selector={TOPOLOGY: topology},
                         tolerations=[Toleration(key=TPU, operator="Exists", effect="NoSchedule")],
                         pod_affinity=affinity, anti_affinity=anti, gang=job)
        for i in range(hosts(topology))
    ]


def _priority(rng: random.Random, tiers: dict) -> int:
    return rng.choices([t["priority"] for t in tiers.values()], [t["weight"] for t in tiers.values()])[0]


def gen_nodes(rng: random.Random, cfg: dict) -> list:
    nodes = []
    for topo, pools in cfg["slice_pools"].items():
        for s in range(pools):
            pool = f"{V5E}-{topo}-{s}"
            for h in range(hosts(topo)):
                name = f"{pool}-{h}"
                labels = {POOL: pool, TOPOLOGY: topo, ACCELERATOR: V5E, HOSTNAME: name}
                nodes.append(cluster.make_node(name, HOST[0], HOST[1], labels, [Taint(key=TPU, value="present", effect="NoSchedule")],
                                               extended={TPU: str(CHIPS_PER_HOST)}))
    return nodes


def gen_standing(rng: random.Random, cfg: dict, nodes: list) -> list:
    members: dict[str, list[str]] = {}
    topology_of: dict[str, str] = {}
    for n in nodes:
        members.setdefault(n.metadata.labels[POOL], []).append(n.metadata.name)
        topology_of[n.metadata.labels[POOL]] = n.metadata.labels[TOPOLOGY]
    pods = []
    j = 0
    for topo in sorted({t for t in topology_of.values()}, key=hosts):
        pools = sorted(p for p, t in topology_of.items() if t == topo)
        for pool in sorted(rng.sample(pools, int(len(pools) * cfg["occupied_share"]))):
            pods += job_pods(f"running-{j}", topo, _priority(rng, cfg["priority_tiers"]), members[pool])
            j += 1
    return pods


def wave_jobs(rng: random.Random, traffic: dict) -> list[tuple[str, int]]:
    """``traffic["wave_jobs"]`` pending jobs as (topology, priority), each
    topology drawn with ``traffic["job_topologies"]``'s weights and each
    priority from a tier."""
    topos = traffic["job_topologies"]
    out = []
    for _ in range(traffic["wave_jobs"]):
        topo = rng.choices(list(topos), list(topos.values()))[0]
        out.append((topo, _priority(rng, traffic["priority_tiers"])))
    return out


def wave_pods(jobs: list[tuple[str, int]], prefix: str) -> list:
    """The pending pods of ``jobs`` (wave_jobs), job ``j`` named
    ``<prefix>job-<j>``."""
    return [p for j, (topo, priority) in enumerate(jobs) for p in job_pods(f"{prefix}job-{j}", topo, priority)]
