"""Scheduling-backend interface — the trait boundary of the north star.

The reference gates everything behind ``check_node_validity``
(``src/predicates.rs:63``); here the boundary is one cycle-level call: packed
tensors in, per-pod node assignments out.  Two implementations with identical
semantics: ``native`` (NumPy, the recovery/parity path) and ``tpu``
(JAX/XLA).  Selected by the ``--backend={native,tpu}`` flag (runtime/cli).
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field

import numpy as np

from ..models.profiles import DEFAULT_PROFILE, SchedulingProfile
from ..ops.pack import PackedCluster

__all__ = ["CycleResult", "SchedulingBackend"]


@dataclass
class CycleResult:
    """Outcome of one scheduling cycle."""

    assigned: np.ndarray  # [num_pods] int32 — node index into packed.node_names, or −1
    bindings: list[tuple[str, str]]  # (pod full name, node name) for assigned pods
    unschedulable: list[str]  # pod full names with no feasible node this cycle
    rounds: int
    stats: dict = field(default_factory=dict)


class SchedulingBackend(abc.ABC):
    name: str = "abstract"

    @abc.abstractmethod
    def assign(self, packed: PackedCluster, profile: SchedulingProfile) -> tuple:
        """Run the cycle over padded tensors; return (assigned [padded_pods],
        rounds) or (assigned, rounds, extras) where ``extras`` carries
        per-pod diagnostics (acceptance round, priority rank) into
        ``CycleResult.stats``."""

    # Whether a routed cycle may solve shards from concurrent threads.
    # Mesh backends whose assign issues cross-host collectives must say
    # False: a multi-controller runtime requires identical collective launch
    # order on every process, which a thread pool cannot guarantee.
    supports_concurrent_shards: bool = True

    # Whether assign() consumes PackedCluster.topology (the rank-aware gang
    # locality term, topology/locality.py).  The controller only attaches
    # the tensors — and only then arms the cross-rack quality backstop —
    # for backends that say True: a topology-BLIND backend judged by the
    # locality gate would have its gangs deferred every cycle (starvation).
    supports_topology: bool = False

    # Whether assign() runs the exclusive-family ops (ops/constraints.py
    # ExclusiveFamilies).  The controller folds JobSet's exclusive-placement
    # terms only for backends that say True; for the rest they stay
    # per-term rows under the usual budgets.
    supports_exclusive_families: bool = False

    def shard_for(self, index: int) -> "SchedulingBackend":
        """Backend instance for the ``index``-th parallel shard of a routed
        cycle (parallel/routing.py).  Default: this backend (serialized on
        one device); device-owning backends override to spread shards over
        the device set — the expert-parallel dispatch."""
        return self

    def schedule(self, packed: PackedCluster, profile: SchedulingProfile = DEFAULT_PROFILE) -> CycleResult:
        result = self.assign(packed, profile)
        assigned_padded, rounds = result[0], result[1]
        extras = result[2] if len(result) > 2 else {}
        assigned = np.asarray(assigned_padded)[: packed.num_pods]
        # Vectorized binding construction: at 100k pods a Python loop with
        # per-element int() casts costs ~0.2 s — a third of the whole cycle.
        pod_arr = np.asarray(packed.pod_names, dtype=object)
        node_arr = np.asarray(packed.node_names, dtype=object)
        placed = np.flatnonzero(assigned >= 0)
        bindings = list(zip(pod_arr[placed].tolist(), node_arr[assigned[placed]].tolist()))
        unschedulable = pod_arr[np.flatnonzero(assigned < 0)].tolist()
        stats = {"backend": self.name}
        for k, v in extras.items():
            stats[k] = np.asarray(v)[: packed.num_pods]
        return CycleResult(
            assigned=assigned,
            bindings=bindings,
            unschedulable=unschedulable,
            rounds=int(rounds),
            stats=stats,
        )
