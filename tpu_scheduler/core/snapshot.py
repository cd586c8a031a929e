"""ClusterSnapshot — an immutable, I/O-free view of cluster state.

The reference's resource predicate reaches straight to the API server from
inside the filter (``src/predicates.rs:21-34`` lists pods live per candidate
node — its single most expensive operation, and the source of its TOCTOU
race).  This framework instead evaluates every predicate against an explicit
snapshot taken once per scheduling cycle: predicates become pure functions,
fully unit-testable (fixing the untestability called out in SURVEY.md §4), and
the snapshot is exactly what gets packed into device tensors (ops/pack.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from itertools import chain
from typing import Iterable

import numpy as np

from ..api.objects import Node, Pod, PodResources, is_extended_resource, is_pod_bound, total_pod_resources
from ..api.quantity import cpu_to_millis, memory_to_bytes

__all__ = [
    "ClusterSnapshot",
    "NodeTable",
    "PlacedOverlay",
    "node_allocatable",
    "node_net_available",
    "node_topology_domain",
    "node_used_resources",
]


def node_allocatable(node: Node, snapshot: "ClusterSnapshot | None" = None) -> PodResources:
    """Allocatable (cpu millicores, memory bytes) of a node.

    Matches reference semantics (``src/predicates.rs:28-32``): a node without
    ``status.allocatable`` has zero allocatable of both resources.

    With ``snapshot``, the quantity parsing memoizes on it (snapshots are
    immutable): the host scalar paths call this per (pod, node) candidate —
    11M+ re-parses of the same quantity strings per 10k-pod constrained
    cycle before the cache (measured ~40 s of a 480 s host phase).  Returns
    a fresh copy either way; callers mutate the result with -=.
    """
    if snapshot is not None:
        cached = snapshot._alloc_cache.get(node.name)
        if cached is None:
            snapshot._alloc_cache[node.name] = cached = node_allocatable(node)
        return cached.copy()
    out = PodResources()
    if node.status is not None and node.status.allocatable is not None:
        alloc = node.status.allocatable
        for name, q in alloc.items():
            if name == "cpu":
                out.cpu = cpu_to_millis(q)
            elif name == "memory":
                out.memory = memory_to_bytes(q)
            elif is_extended_resource(name):
                # Extended resources (device plugins: google.com/tpu,
                # nvidia.com/gpu, hugepages-*): exact integers.  Kube-native
                # names the framework doesn't model (pods,
                # ephemeral-storage) are ignored on both sides.
                if out.extended is None:
                    out.extended = {}
                out.extended[name] = memory_to_bytes(q)
    return out


@dataclass(frozen=True)
class ClusterSnapshot:
    """Point-in-time cluster state: all nodes + all pods.

    ``pods`` includes both bound pods (they consume node capacity) and
    pending pods (the scheduling workload).
    """

    nodes: tuple[Node, ...]
    pods: tuple[Pod, ...]
    _pods_by_node: dict[str, list[Pod]] = field(default_factory=dict, compare=False, repr=False)
    # Lazy per-node memos (snapshots are immutable once built): parsed
    # allocatable quantities and summed bound-pod usage — see
    # node_allocatable / node_used_resources.
    _alloc_cache: dict[str, PodResources] = field(default_factory=dict, compare=False, repr=False)
    _used_cache: dict[str, PodResources] = field(default_factory=dict, compare=False, repr=False)
    _net_cache: dict[str, PodResources] = field(default_factory=dict, compare=False, repr=False)
    # Caches for the affinity predicates (built once; snapshots are immutable):
    # all (pod, node) placements, and the subset whose pod carries
    # anti-affinity terms (the direction-B forbidders).
    _placed: list = field(default_factory=list, compare=False, repr=False)
    _placed_with_terms: list = field(default_factory=list, compare=False, repr=False)
    # Lazy index of ``_placed`` by (namespace, label key, label value), for
    # the inter-pod checkers' selector walks (placed_matching).
    _placed_by_label: dict | None = field(default=None, compare=False, repr=False)
    # Lazy per-key memo of the named topology domains (named_domains).
    _domains_by_key: dict[str, tuple[str, ...]] = field(default_factory=dict, compare=False, repr=False)
    # Lazy indexes for JobSet's exclusive-placement terms (api/objects
    # .exclusive_placement), whose selectors carry no matchLabels to index:
    # label_domains / exclusive_carrier_domains memos, and the carriers'
    # other terms (placed_other_terms).
    _exclusive: dict = field(default_factory=dict, compare=False, repr=False)
    # Lazy column view of the nodes (node_table), built by the first
    # why-pending breakdown a snapshot serves.
    _node_table: "NodeTable | None" = field(default=None, compare=False, repr=False)
    # Lazy pending-pod memo (immutable snapshot, so one scan suffices): the
    # controller consults the pending list several times per cycle — at
    # flagship scale each uncached scan walks 200k+ pods.
    _pending: list | None = field(default=None, compare=False, repr=False)
    # Compiled interconnect topology for THIS node set (topology/model
    # .CompiledTopology — carries the node-distance tensor): attached by the
    # controller once per cycle (attach_topology) so every consumer of the
    # snapshot — pack, scoring, debug — reads the same resolved hierarchy.
    topology: object | None = field(default=None, compare=False, repr=False)

    @staticmethod
    def build(
        nodes: Iterable[Node], pods: Iterable[Pod], topology: object | None = None
    ) -> "ClusterSnapshot":
        snap = ClusterSnapshot(nodes=tuple(nodes), pods=tuple(pods), topology=topology)
        by_name = {n.name: n for n in snap.nodes}
        for p in snap.pods:
            if p.spec is not None and p.spec.node_name is not None:
                snap._pods_by_node.setdefault(p.spec.node_name, []).append(p)
                node = by_name.get(p.spec.node_name)
                if node is not None:
                    snap._placed.append((p, node))
                    if p.spec.anti_affinity:
                        snap._placed_with_terms.append((p, node))
        return snap

    def attach_topology(self, compiled) -> None:
        """Attach a compiled topology post-build (the dataclass is frozen;
        the field is cache-like non-compare state, same stance as the lazy
        ``_pending`` memo)."""
        object.__setattr__(self, "topology", compiled)

    def placed_pods(self) -> list:
        """All (pod, node) placements onto nodes present in the snapshot."""
        return self._placed

    def placed_pods_with_terms(self) -> list:
        """Placements whose pod declares anti-affinity terms."""
        return self._placed_with_terms

    def placed_matching(self, namespace: str | None, match_labels: dict[str, str] | None) -> list:
        """The placements in ``namespace`` whose pod carries the rarest pair
        of ``match_labels`` (all placements when it has none): a superset of
        the placements a selector with those labels matches, from an index
        built once a snapshot, so a per-pod checker walks the pods its
        selector can match and not every placed pod."""
        if not match_labels:
            return self._placed
        index = self._placed_by_label
        if index is None:
            index = {}
            for q, qnode in self._placed:
                for k, v in (q.metadata.labels or {}).items():
                    index.setdefault((q.metadata.namespace, k, v), []).append((q, qnode))
            object.__setattr__(self, "_placed_by_label", index)
        return min((index.get((namespace, k, v), ()) for k, v in match_labels.items()), key=len)

    def label_domains(self, namespace: str | None, key: str, topology_key: str) -> dict[str, set]:
        """value -> the topology domains (node_topology_domain under
        ``topology_key``) of the placed pods in ``namespace`` labelled
        ``key = value``; built once a snapshot per (namespace, key, topology
        key)."""
        memo_key = ("labels", namespace, key, topology_key)
        out = self._exclusive.get(memo_key)
        if out is None:
            out = {}
            for q, qnode in self._placed:
                v = (q.metadata.labels or {}).get(key)
                if v is not None and q.metadata.namespace == namespace:
                    out.setdefault(v, set()).add(node_topology_domain(qnode, topology_key))
            self._exclusive[memo_key] = out
        return out

    def exclusive_carrier_domains(self, namespace: str | None) -> dict[tuple[str, str], dict[str, set]]:
        """(label key K, topology key) -> v -> the domains of the placed pods
        in ``namespace`` carrying the exclusive anti-affinity term ``{K
        Exists, K NotIn [v]}`` over that topology key."""
        self._split_carriers()
        return self._exclusive[("carriers", namespace)] if ("carriers", namespace) in self._exclusive else {}

    def placed_other_terms(self) -> list:
        """(pod, node, terms) of every placed carrier, with its anti-affinity
        terms other than the exclusive ones exclusive_carrier_domains holds
        (carriers left with none are left out)."""
        self._split_carriers()
        return self._exclusive["other"]

    def _split_carriers(self) -> None:
        if "other" in self._exclusive:
            return
        from ..api.objects import exclusive_placement

        other = []
        for q, qnode in self._placed_with_terms:
            rest = []
            for t in q.spec.anti_affinity:
                shape = exclusive_placement(t)
                if shape is not None and shape[0] == "aa":
                    by_v = self._exclusive.setdefault(("carriers", q.metadata.namespace), {}).setdefault((shape[1], t.topology_key), {})
                    by_v.setdefault(shape[2], set()).add(node_topology_domain(qnode, t.topology_key))
                else:
                    rest.append(t)
            if rest:
                other.append((q, qnode, rest))
        self._exclusive["other"] = other

    def named_domains(self, topology_key: str) -> tuple[str, ...]:
        """The distinct values of ``topology_key`` over the nodes, in node
        order — the key's named domains, memoized per key (a hard-spread
        checker seeds its zero counts from them once a constraint)."""
        domains = self._domains_by_key.get(topology_key)
        if domains is None:
            labels = ((n.metadata.labels or {}).get(topology_key) for n in self.nodes)
            domains = self._domains_by_key[topology_key] = tuple(dict.fromkeys(v for v in labels if v is not None))
        return domains

    def node_table(self) -> "NodeTable":
        """The nodes as numpy columns, built once a snapshot, on first use."""
        if self._node_table is None:
            object.__setattr__(self, "_node_table", NodeTable(self))
        return self._node_table

    def pods_on_node(self, node_name: str) -> list[Pod]:
        """Snapshot equivalent of the reference's live field-selector list
        ``spec.nodeName=<node>`` (``src/predicates.rs:22-26``)."""
        return self._pods_by_node.get(node_name, [])

    def pending_pods(self) -> list[Pod]:
        """Pods the controller schedules: phase Pending and not yet bound
        (reference filters the watch to ``status.phase=Pending`` at
        ``src/main.rs:141-142`` and skips bound pods at ``src/main.rs:74-76``).
        Memoized (snapshots are immutable); callers must not mutate the
        returned list.
        """
        if self._pending is None:
            object.__setattr__(
                self, "_pending", [p for p in self.pods if p.status.phase == "Pending" and not is_pod_bound(p)]
            )
        return self._pending


# shape: (node: obj, topology_key: str) -> obj
def node_topology_domain(node: Node, topology_key: str) -> tuple[str, str]:
    """The topology domain of a node under ``topology_key``.

    Named domain ``(key, value)`` when the node carries the label; otherwise
    the node is its own singleton domain ``("~node", name)`` — a keyless node
    degrades to per-node (hostname-like) granularity.
    """
    labels = node.metadata.labels or {}
    v = labels.get(topology_key)
    return (topology_key, v) if v is not None else ("~node", node.name)


class NodeTable:
    """A snapshot's nodes as numpy columns, in node order, for predicates
    charged over the whole node axis at once (the why-pending breakdown,
    core/predicates.unschedulable_reason_counts).

    ``cpu``, ``memory`` and ``extended[name]``: net-available resources
    (node_net_available), 0 where a node lacks an extended resource; exact
    Python-int semantics (numpy widens a column past int64 to objects).
    ``label_ids(key)``: each node's value id under ``key`` (-1 where the key
    is missing) and the vocabulary the ids index.  ``cordoned``:
    ``spec.unschedulable``.
    ``taint_ids``: each node's id among the distinct (key, value, effect)
    tuples of its taints, and ``taint_nodes`` the first node of each, for a
    per-pod verdict a tuple that the ids broadcast.
    """

    __slots__ = ("cpu", "memory", "extended", "cordoned", "taint_ids", "taint_nodes", "_labels")

    def __init__(self, snapshot: ClusterSnapshot):
        nodes = snapshot.nodes
        net = [node_net_available(snapshot, node) for node in nodes]
        self.cpu = np.array([r.cpu for r in net])
        self.memory = np.array([r.memory for r in net])
        names = dict.fromkeys(k for r in net if r.extended for k in r.extended)
        self.extended = {k: np.array([r.extended.get(k, 0) if r.extended else 0 for r in net]) for k in names}
        self.cordoned = np.array([node.spec is not None and bool(node.spec.unschedulable) for node in nodes], dtype=bool)
        labels: dict[str, tuple[np.ndarray, dict]] = {}
        taint_of: dict[tuple, int] = {}
        self.taint_nodes: list[Node] = []
        self.taint_ids = np.empty(len(nodes), dtype=np.int32)
        for i, node in enumerate(nodes):
            for k, v in (node.metadata.labels or {}).items():
                col = labels.get(k)
                if col is None:
                    col = labels[k] = (np.full(len(nodes), -1, dtype=np.int32), {})
                col[0][i] = col[1].setdefault(v, len(col[1]))
            taints = tuple((t.key, t.value, t.effect) for t in ((node.spec.taints or []) if node.spec is not None else []))
            tid = taint_of.get(taints)
            if tid is None:
                tid = taint_of[taints] = len(self.taint_nodes)
                self.taint_nodes.append(node)
            self.taint_ids[i] = tid
        self._labels = {k: (ids, list(vocab)) for k, (ids, vocab) in labels.items()}

    def label_ids(self, key: str) -> tuple[np.ndarray, list]:
        """(value id per node, -1 where ``key`` is missing; the values)."""
        col = self._labels.get(key)
        if col is None:
            col = self._labels[key] = (np.full(len(self.cpu), -1, dtype=np.int32), [])
        return col


class PlacedOverlay:
    """Same-cycle placements not yet in the snapshot, indexed for the
    inter-pod checker builds that walk them beside the snapshot's own.

    ``matching`` is the overlay's twin of ``ClusterSnapshot.placed_matching``
    (the same (namespace, label pair) index and rarest-pair superset), and
    ``carriers`` lists the placements whose pod declares anti-affinity (the
    direction-B forbidders).  ``append`` only records: a walk first indexes
    what was appended since the last one, so a cycle that never builds a
    checker (a plain wave's 100k binds) pays for no index.  ``fork`` layers
    a scratch overlay over this one: a gang trial appends to the fork and
    drops it on reject, and this overlay's placements never change.  Each
    walk tallies the placements it hands out (walked) and the overlay's
    whole length (offered, what an unindexed walk visits); forks share
    their root's tally, which the caller folds once a phase (``take_tally``).
    """

    __slots__ = ("_base", "layer", "_indexed", "_by_label", "_carriers", "_tally")

    def __init__(self, placements: Iterable[tuple[Pod, Node]] = (), base: "PlacedOverlay | None" = None):
        self._base = base
        self.layer: list[tuple[Pod, Node]] = list(placements)  # this layer's placements, in append order
        self._indexed = 0
        self._by_label: dict[tuple, list] = {}
        self._carriers: list[tuple[Pod, Node]] = []
        self._tally = base._tally if base is not None else [0, 0]

    def append(self, placement: tuple[Pod, Node]) -> None:
        self.layer.append(placement)

    def extend(self, placements: Iterable[tuple[Pod, Node]]) -> None:
        self.layer.extend(placements)

    def fork(self) -> "PlacedOverlay":
        return PlacedOverlay(base=self)

    def __len__(self) -> int:
        return len(self.layer) + (len(self._base) if self._base is not None else 0)

    def __iter__(self):
        return chain(self._base or (), self.layer)

    def _indexed_layers(self) -> list["PlacedOverlay"]:
        """The layers, root first, each indexed up to its last placement."""
        layers = [self] if self._base is None else [*self._base._indexed_layers(), self]
        for placement in self.layer[self._indexed:]:
            q = placement[0]
            ns = q.metadata.namespace
            for k, v in (q.metadata.labels or {}).items():
                self._by_label.setdefault((ns, k, v), []).append(placement)
            if q.spec is not None and q.spec.anti_affinity:
                self._carriers.append(placement)
        self._indexed = len(self.layer)
        return layers

    def _walk(self, parts: list) -> Iterable[tuple[Pod, Node]]:
        self._tally[0] += sum(map(len, parts))
        self._tally[1] += len(self)
        return parts[0] if len(parts) == 1 else chain.from_iterable(parts)

    def matching(self, namespace: str | None, match_labels: dict[str, str] | None) -> Iterable[tuple[Pod, Node]]:
        """The placements in ``namespace`` whose pod carries the rarest pair
        of ``match_labels`` (every placement when it has none): a superset of
        those a selector with these labels matches."""
        layers = self._indexed_layers()
        if not match_labels:
            return self._walk([o.layer for o in layers])
        pairs = [(namespace, k, v) for k, v in match_labels.items()]
        return self._walk([min((o._by_label.get(pair, ()) for pair in pairs), key=len) for o in layers])

    def carriers(self) -> Iterable[tuple[Pod, Node]]:
        """The placements whose pod declares anti-affinity terms."""
        return self._walk([o._carriers for o in self._indexed_layers()])

    def take_tally(self) -> tuple[int, int]:
        """(walked, offered) since the last call; resets both."""
        walked, offered = self._tally
        self._tally[:] = [0, 0]
        return walked, offered


def node_net_available(snapshot: ClusterSnapshot, node: Node) -> PodResources:
    """allocatable − Σ bound-pod requests, memoized per snapshot (both
    inputs are snapshot-constant); returns a fresh copy — in-cycle callers
    subtract their assumed-resources ledger from it."""
    cached = snapshot._net_cache.get(node.name)
    if cached is None:
        net = node_allocatable(node, snapshot)
        net -= node_used_resources(snapshot, node.name)
        snapshot._net_cache[node.name] = cached = net
    return cached.copy()


def node_used_resources(snapshot: ClusterSnapshot, node_name: str) -> PodResources:
    """Sum of resource requests of pods bound to ``node_name``.

    Memoized on the (immutable) snapshot — the host scalar paths call this
    per (pod, node) candidate, re-summing the same bound pods' requests
    (34M ``total_pod_resources`` calls per 10k-pod constrained cycle before
    the cache).  Returns a fresh copy; callers mutate with += / -=."""
    cached = snapshot._used_cache.get(node_name)
    if cached is None:
        used = PodResources()
        for p in snapshot.pods_on_node(node_name):
            used += total_pod_resources(p)
        snapshot._used_cache[node_name] = cached = used
    return cached.copy()
