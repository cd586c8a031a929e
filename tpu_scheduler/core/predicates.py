"""Scalar (per pod, per node) predicates — pure reference semantics.

Mirrors ``src/predicates.rs:20-77`` exactly, minus the I/O: where the
reference lists pods live from the API server inside ``can_pod_fit``
(``predicates.rs:21-34``), these functions take a ``ClusterSnapshot``.  The
scalar path is the semantic oracle: the batched native and TPU backends must
agree with it pod-by-pod (tests/test_backends_parity.py).

Predicate registry: predicates are named, ordered, and composable, so the
chain can grow (the reference hard-codes two, ``predicates.rs:63-77``).
"""

from __future__ import annotations

import enum
from itertools import chain
from typing import Callable, Iterable, Sequence

import numpy as np

from ..api.objects import LabelSelectorRequirement, Node, Pod, exclusive_placement, full_name, total_pod_resources
from .snapshot import ClusterSnapshot, NodeTable, PlacedOverlay, node_net_available, node_topology_domain

__all__ = [
    "InvalidNodeReason",
    "pod_fits_resources",
    "node_selector_matches",
    "node_schedulable",
    "taints_tolerated",
    "node_affinity_matches",
    "node_selector_term_matches",
    "HARD_TAINT_EFFECTS",
    "anti_affinity_ok",
    "pod_affinity_ok",
    "topology_spread_ok",
    "make_pod_affinity_checker",
    "labels_match_selector",
    "selector_matches",
    "term_matches",
    "node_topology_domain",
    "make_affinity_checker",
    "make_spread_checker",
    "preferred_affinity_score",
    "soft_taint_penalty",
    "make_soft_spread_scorer",
    "make_preferred_pod_affinity_scorer",
    "check_node_validity",
    "unschedulable_reason_counts",
    "dominant_reason",
    "PREDICATE_CHAIN",
    "NODE_LOCAL_PREDICATES",
    "INTER_POD_REASONS",
]


class InvalidNodeReason(enum.Enum):
    """Typed failure reason — reference ``predicates.rs:14-18``; variants
    beyond the first two extend the reference (BASELINE.json config 5 +
    standard kube-scheduler predicates)."""

    NOT_ENOUGH_RESOURCES = "NotEnoughResources"
    NODE_SELECTOR_MISMATCH = "NodeSelectorMismatch"
    NODE_AFFINITY_MISMATCH = "NodeAffinityMismatch"
    NODE_UNSCHEDULABLE = "NodeUnschedulable"
    TAINT_NOT_TOLERATED = "TaintNotTolerated"
    ANTI_AFFINITY_VIOLATION = "AntiAffinityViolation"
    POD_AFFINITY_UNSATISFIED = "PodAffinityUnsatisfied"
    TOPOLOGY_SPREAD_VIOLATION = "TopologySpreadViolation"


# shape: (pod: obj, node: obj, snapshot: obj) -> bool
def pod_fits_resources(pod: Pod, node: Node, snapshot: ClusterSnapshot) -> bool:
    """Resource-fit predicate — reference ``can_pod_fit``
    (``predicates.rs:20-43``).

    available = node.status.allocatable − Σ requests of pods on the node;
    fits iff request.cpu ≤ available.cpu AND request.memory ≤ available.memory.
    A node with no allocatable has zero available (only zero-request pods fit).
    """
    available = node_net_available(snapshot, node)
    req = total_pod_resources(pod)
    return req.fits_in(available)


# shape: (pod: obj, node: obj, snapshot: obj) -> bool
def node_selector_matches(pod: Pod, node: Node, snapshot: ClusterSnapshot | None = None) -> bool:
    """nodeSelector predicate — reference ``does_node_selector_match``
    (``predicates.rs:45-61``).

    Every selector key must equal the node label exactly; a pod with no
    selector matches vacuously; a node with no labels fails any selector.
    """
    if pod.spec is None or not pod.spec.node_selector:
        return True
    labels = node.metadata.labels
    if not labels:
        return False
    return all(labels.get(k) == v for k, v in pod.spec.node_selector.items())


HARD_TAINT_EFFECTS = ("NoSchedule", "NoExecute")


def _node_expression_matches(r: LabelSelectorRequirement, labels: dict[str, str]) -> bool:
    """Node-affinity expression match — label-selector operators plus the
    numeric ``Gt``/``Lt`` (single integer value; a missing or non-integer
    label never matches)."""
    if r.operator in ("Gt", "Lt"):
        if r.key not in labels or not r.values:
            return False
        try:
            label_num = int(labels[r.key])
            want = int(r.values[0])
        except (TypeError, ValueError):
            return False
        return label_num > want if r.operator == "Gt" else label_num < want
    return _expression_matches(r, labels)


# shape: (term: obj, labels: dict) -> bool
def node_selector_term_matches(term, labels: dict[str, str] | None) -> bool:
    """A nodeSelectorTerm matches iff every expression holds; a term with no
    expressions matches nothing (the empty-selector deviation)."""
    exprs = term.match_expressions
    if not exprs:
        return False
    labels = labels or {}
    return all(_node_expression_matches(r, labels) for r in exprs)


# shape: (pod: obj, node: obj, snapshot: obj) -> bool
def node_affinity_matches(pod: Pod, node: Node, snapshot: ClusterSnapshot | None = None) -> bool:
    """Required node-affinity predicate (standard kube-scheduler; absent in
    the reference).  Terms are ORed; a pod without affinity matches
    vacuously."""
    terms = (pod.spec.node_affinity or []) if pod.spec is not None else []
    if not terms:
        return True
    labels = node.metadata.labels
    return any(node_selector_term_matches(t, labels) for t in terms)


# shape: (pod: obj, node: obj, snapshot: obj) -> bool
def node_schedulable(pod: Pod, node: Node, snapshot: ClusterSnapshot | None = None) -> bool:
    """False iff the node is cordoned (``spec.unschedulable`` — kubectl
    cordon).  Beyond the reference, which has no Node.spec handling."""
    return not (node.spec is not None and node.spec.unschedulable)


# shape: (pod: obj, node: obj, snapshot: obj) -> bool
def taints_tolerated(pod: Pod, node: Node, snapshot: ClusterSnapshot | None = None) -> bool:
    """Taints/tolerations predicate (standard kube-scheduler; absent in the
    reference).  Every NoSchedule/NoExecute taint on the node must be
    matched by some toleration of the pod; PreferNoSchedule is soft and
    ignored by the hard filter."""
    taints = (node.spec.taints or []) if node.spec is not None else []
    if not taints:
        return True
    tolerations = (pod.spec.tolerations or []) if pod.spec is not None else []
    for taint in taints:
        if taint.effect not in HARD_TAINT_EFFECTS:
            continue
        if not any(t.tolerates(taint) for t in tolerations):
            return False
    return True


# shape: (selector: dict, labels: dict) -> bool
def labels_match_selector(selector: dict[str, str] | None, labels: dict[str, str] | None) -> bool:
    """True iff ``labels`` carries every pair of ``selector``.

    An empty/None selector matches *nothing* (documented deviation from the
    Kubernetes empty-selector-matches-all rule — see PodAntiAffinityTerm).
    """
    if not selector or not labels:
        return False
    return all(labels.get(k) == v for k, v in selector.items())


def _expression_matches(r: LabelSelectorRequirement, labels: dict[str, str]) -> bool:
    if r.operator == "In":
        return r.key in labels and labels[r.key] in (r.values or [])
    if r.operator == "NotIn":
        return r.key not in labels or labels[r.key] not in (r.values or [])
    if r.operator == "Exists":
        return r.key in labels
    if r.operator == "DoesNotExist":
        return r.key not in labels
    return False  # unknown operator matches nothing (fail closed)


def selector_matches(
    match_labels: dict[str, str] | None,
    match_expressions: Sequence[LabelSelectorRequirement] | None,
    labels: dict[str, str] | None,
) -> bool:
    """Full label-selector match: every ``match_labels`` pair AND every
    ``match_expressions`` requirement must hold.

    An entirely empty selector (no pairs, no expressions) matches *nothing*
    (the documented deviation — see PodAntiAffinityTerm).
    """
    if not match_labels and not match_expressions:
        return False
    if match_labels and not labels_match_selector(match_labels, labels):
        return False
    labels = labels or {}
    return all(_expression_matches(r, labels) for r in match_expressions or [])


# shape: (term: obj, labels: dict) -> bool
def term_matches(term, labels: dict[str, str] | None) -> bool:
    """Selector match of an anti-affinity term or spread constraint against
    a pod's labels (both carry ``match_labels`` + ``match_expressions``)."""
    return selector_matches(term.match_labels, getattr(term, "match_expressions", None), labels)


def _placed_matching(
    snapshot: ClusterSnapshot, overlay: PlacedOverlay | None, namespace: str | None, match_labels
) -> Iterable[tuple[Pod, Node]]:
    """The snapshot's and the overlay's placements a selector with
    ``match_labels`` in ``namespace`` can match — a superset, from their
    indexes; every caller still tests each placement with term_matches."""
    placed = snapshot.placed_matching(namespace, match_labels)
    if overlay is None:
        return placed
    return chain(placed, overlay.matching(namespace, match_labels))


def make_affinity_checker(
    pod: Pod,
    snapshot: ClusterSnapshot,
    overlay: PlacedOverlay | None = None,
) -> Callable[[Node], bool]:
    """Precompute ``pod``'s anti-affinity state into a set of blocked
    topology domains, returning an O(#keys) per-node checker.

    Enforced in both directions, as kube-scheduler does:
      A. none of ``pod``'s terms may match a placed pod in ``node``'s domain;
      B. no placed pod in ``node``'s domain may carry a term matching ``pod``.
    Terms are namespace-scoped: a term only sees pods sharing the namespace
    of the pod that declares it.  ``overlay`` lets a sequential caller
    add same-cycle commitments not yet visible in the snapshot.

    A node is blocked iff its domain under some relevant topology key is in
    the blocked set.  Merging keys into one set is exact: a keyless-node
    domain ``("~node", name)`` can only collide across keys when the
    candidate *is* that placed pod's node, in which case every generating
    term blocks it anyway (same node ⇒ same domain under any key).
    """
    my_terms = (pod.spec.anti_affinity or []) if pod.spec is not None else []
    my_ns = pod.metadata.namespace
    my_labels = pod.metadata.labels or {}
    blocked: set[tuple[str, str]] = set()
    keys: set[str] = set()

    # Direction A: domains holding a pod matched by one of my terms.  An
    # exclusive-placement term {K Exists, K NotIn [v]} matches the pods
    # labelled K with another value: the snapshot's per-value index.
    for t in my_terms:
        shape = exclusive_placement(t)
        if shape is not None and shape[0] == "aa":
            for x, doms in snapshot.label_domains(my_ns, shape[1], t.topology_key).items():
                if x != shape[2]:
                    blocked |= doms
                    keys.add(t.topology_key)
            placed = overlay.matching(my_ns, None) if overlay is not None else ()
        else:
            placed = _placed_matching(snapshot, overlay, my_ns, t.match_labels)
        for q, qnode in placed:
            if q.metadata.namespace == my_ns and term_matches(t, q.metadata.labels):
                blocked.add(node_topology_domain(qnode, t.topology_key))
                keys.add(t.topology_key)
    # Direction B: domains of placed term-carriers whose term matches me —
    # the exclusive-placement carriers by value, the rest term by term.
    for (k, tk), by_value in snapshot.exclusive_carrier_domains(my_ns).items():
        x = my_labels.get(k)
        if x is not None:
            for v, doms in by_value.items():
                if v != x:
                    blocked |= doms
                    keys.add(tk)
    carriers = snapshot.placed_other_terms()
    if overlay is not None:
        carriers = chain(carriers, ((q, qnode, q.spec.anti_affinity) for q, qnode in overlay.carriers()))
    for q, qnode, terms in carriers:
        if q.metadata.namespace != my_ns:
            continue
        for t in terms:
            if term_matches(t, my_labels):
                blocked.add(node_topology_domain(qnode, t.topology_key))
                keys.add(t.topology_key)

    if not blocked:
        return lambda node: True
    return lambda node: all(node_topology_domain(node, k) not in blocked for k in keys)


def anti_affinity_ok(
    pod: Pod,
    node: Node,
    snapshot: ClusterSnapshot,
    overlay: PlacedOverlay | None = None,
) -> bool:
    """Inter-pod anti-affinity predicate (config 5; absent in the reference).

    One-shot form of :func:`make_affinity_checker` — see it for semantics.
    """
    return make_affinity_checker(pod, snapshot, overlay)(node)


def make_pod_affinity_checker(
    pod: Pod,
    snapshot: ClusterSnapshot,
    overlay: PlacedOverlay | None = None,
    exclude: frozenset[str] = frozenset(),
) -> Callable[[Node], bool]:
    """Positive inter-pod affinity (requiredDuringScheduling co-location):
    for EVERY declared term, the candidate node's topology domain must hold
    a placed pod (same namespace) matched by the term's selector.

    Bootstrap rule, matching kube-scheduler's InterPodAffinity filter: a term
    that matches *no* placed pod anywhere is waived iff the incoming pod
    matches its own term — the first pod of a self-affine group can place;
    a non-self-matching pod with an unmatchable term fails everywhere.

    Unlike anti-affinity there is no symmetric direction: a placed pod's
    affinity terms do not constrain newcomers.  ``overlay`` adds
    same-cycle commitments (the sequential host path), which also activate
    waived terms for later pods in the same cycle.  ``exclude`` removes
    placed pods (by full name) from consideration — the preemption pass
    re-checks candidates as if its victims were already evicted (kube's
    selectVictimsOnNode re-filter).
    """
    my_terms = (pod.spec.pod_affinity or []) if pod.spec is not None else []
    if not my_terms:
        return lambda node: True
    my_ns = pod.metadata.namespace
    # Per term: the set of domains holding a match, or None = waived.
    term_domains: list[set[tuple[str, str]] | None] = []
    for t in my_terms:
        doms: set[tuple[str, str]] = set()
        shape = exclusive_placement(t)
        if shape is not None and shape[0] == "pa" and not exclude:
            # {K In [v]}: the pods labelled K = v, from the snapshot's index.
            doms |= snapshot.label_domains(my_ns, shape[1], t.topology_key).get(shape[2], set())
            placed = overlay.matching(my_ns, None) if overlay is not None else ()
        else:
            placed = _placed_matching(snapshot, overlay, my_ns, t.match_labels)
        for q, qnode in placed:
            if exclude and full_name(q) in exclude:
                continue
            if q.metadata.namespace == my_ns and term_matches(t, q.metadata.labels):
                doms.add(node_topology_domain(qnode, t.topology_key))
        if doms:
            term_domains.append(doms)
        elif term_matches(t, pod.metadata.labels):
            term_domains.append(None)  # waived: self-match bootstrap
        else:
            return lambda node: False  # unmatchable, no self-match

    def check(node: Node) -> bool:
        for t, doms in zip(my_terms, term_domains):
            if doms is not None and node_topology_domain(node, t.topology_key) not in doms:
                return False
        return True

    return check


def pod_affinity_ok(
    pod: Pod,
    node: Node,
    snapshot: ClusterSnapshot,
    overlay: PlacedOverlay | None = None,
) -> bool:
    """Positive inter-pod affinity predicate — one-shot form of
    :func:`make_pod_affinity_checker` (see it for semantics)."""
    return make_pod_affinity_checker(pod, snapshot, overlay)(node)


def make_spread_checker(
    pod: Pod,
    snapshot: ClusterSnapshot,
    overlay: PlacedOverlay | None = None,
    exclude: frozenset[str] = frozenset(),
) -> Callable[[Node], bool]:
    """Precompute per-constraint domain counts once, returning an
    O(#constraints) per-node checker for the hard topology-spread predicate.

    For each constraint: count placed pods matching the selector (in the
    pod's namespace) per *named* domain of the key; placing here must keep
    ``count(domain(node)) + 1 − min(counts) ≤ max_skew``.  A node lacking the
    key is exempt; keyless nodes' pods don't enter the counts or the min.
    ``overlay`` adds same-cycle commitments not yet in the snapshot.
    """
    constraints = [c for c in ((pod.spec.topology_spread or []) if pod.spec is not None else []) if c.is_hard]
    if not constraints:
        return lambda node: True
    my_ns = pod.metadata.namespace
    per_constraint: list[tuple[str, int, dict[str, int], int]] = []
    for c in constraints:
        counts = dict.fromkeys(snapshot.named_domains(c.topology_key), 0)
        for q, qnode in _placed_matching(snapshot, overlay, my_ns, c.match_labels):
            if exclude and full_name(q) in exclude:
                continue
            v = (qnode.metadata.labels or {}).get(c.topology_key)
            if v is None or q.metadata.namespace != my_ns:
                continue
            if term_matches(c, q.metadata.labels):
                counts[v] = counts.get(v, 0) + 1
        per_constraint.append((c.topology_key, c.max_skew, counts, min(counts.values(), default=0)))

    def check(node: Node) -> bool:
        labels = node.metadata.labels or {}
        for key, max_skew, counts, lo in per_constraint:
            here = labels.get(key)
            if here is None:
                continue  # node exempt from this constraint
            if counts.get(here, 0) + 1 - lo > max_skew:
                return False
        return True

    return check


def topology_spread_ok(
    pod: Pod,
    node: Node,
    snapshot: ClusterSnapshot,
    overlay: PlacedOverlay | None = None,
) -> bool:
    """Hard topology-spread predicate (config 5; absent in the reference).

    One-shot form of :func:`make_spread_checker` — see it for semantics.
    """
    return make_spread_checker(pod, snapshot, overlay)(node)


# --- soft (scoring) terms ---------------------------------------------------


# shape: (pod: obj, node: obj) -> float
def preferred_affinity_score(pod: Pod, node: Node) -> float:
    """Sum of weights of the pod's matching preferredDuringScheduling node-
    affinity terms (kube NodeAffinity scoring, pre-normalization)."""
    terms = (pod.spec.preferred_node_affinity or []) if pod.spec is not None else []
    if not terms:
        return 0.0
    labels = node.metadata.labels
    return float(sum(t.weight for t in terms if node_selector_term_matches(t.term, labels)))


# shape: (pod: obj, node: obj) -> int
def soft_taint_penalty(pod: Pod, node: Node) -> int:
    """Count of the node's PreferNoSchedule taints the pod does not
    tolerate (kube TaintToleration scoring, pre-normalization)."""
    taints = (node.spec.taints or []) if node.spec is not None else []
    if not taints:
        return 0
    tolerations = (pod.spec.tolerations or []) if pod.spec is not None else []
    n = 0
    for taint in taints:
        if taint.effect != "PreferNoSchedule":
            continue
        if not any(t.tolerates(taint) for t in tolerations):
            n += 1
    return n


def make_preferred_pod_affinity_scorer(
    pod: Pod,
    snapshot: ClusterSnapshot,
    overlay: PlacedOverlay | None = None,
) -> Callable[[Node], float]:
    """Soft inter-pod (anti-)affinity — kube InterPodAffinity scoring: every
    placed pod (same namespace) in the candidate node's topology domain that
    matches one of this pod's preferred terms contributes +weight (affinity)
    or −weight (anti-affinity).  Term weights (1-100) are the only scale —
    no global profile knob, matching the tensor path (ops/score.py).
    Symmetric scoring from placed pods' own preferred terms is deliberately
    out of scope (see WeightedPodAffinityTerm)."""
    spec = pod.spec
    weighted = [
        *((w.weight, w.term) for w in ((spec.preferred_pod_affinity or []) if spec is not None else [])),
        *((-w.weight, w.term) for w in ((spec.preferred_pod_anti_affinity or []) if spec is not None else [])),
    ]
    if not weighted:
        return lambda node: 0.0
    my_ns = pod.metadata.namespace
    # Per (signed weight, term): match counts per domain of the term's key.
    per_term: list[tuple[float, str, dict[tuple[str, str], int]]] = []
    for w, t in weighted:
        counts: dict[tuple[str, str], int] = {}
        for q, qnode in _placed_matching(snapshot, overlay, my_ns, t.match_labels):
            if q.metadata.namespace == my_ns and term_matches(t, q.metadata.labels):
                d = node_topology_domain(qnode, t.topology_key)
                counts[d] = counts.get(d, 0) + 1
        per_term.append((float(w), t.topology_key, counts))

    def score(node: Node) -> float:
        total = 0.0
        for w, key, counts in per_term:
            total += w * counts.get(node_topology_domain(node, key), 0)
        return total

    return score


def make_soft_spread_scorer(
    pod: Pod,
    snapshot: ClusterSnapshot,
    overlay: PlacedOverlay | None = None,
) -> Callable[[Node], float]:
    """Penalty for the pod's ScheduleAnyway spread constraints: the count of
    matching placed pods already in the node's domain (emptier domains score
    higher).  Scaled by the profile's ``topology_weight`` at the call site."""
    constraints = [c for c in ((pod.spec.topology_spread or []) if pod.spec is not None else []) if not c.is_hard]
    if not constraints:
        return lambda node: 0.0
    my_ns = pod.metadata.namespace
    per_constraint: list[tuple[str, dict[str, int]]] = []
    for c in constraints:
        counts: dict[str, int] = {}
        for q, qnode in _placed_matching(snapshot, overlay, my_ns, c.match_labels):
            v = (qnode.metadata.labels or {}).get(c.topology_key)
            if v is None or q.metadata.namespace != my_ns:
                continue
            if term_matches(c, q.metadata.labels):
                counts[v] = counts.get(v, 0) + 1
        per_constraint.append((c.topology_key, counts))

    def penalty(node: Node) -> float:
        labels = node.metadata.labels or {}
        total = 0.0
        for key, counts in per_constraint:
            v = labels.get(key)
            if v is not None:
                total += counts.get(v, 0)
        return total

    return penalty


# Ordered chain: fixed resource-then-selector order, as in the reference
# (``predicates.rs:68,72``), extended with the config-5 predicates.  Each
# entry: (reason-on-failure, predicate fn).
# Pure (pod, node) predicates that need no snapshot-wide state — the middle
# of the chain, shared verbatim by the controller's ledger-adjusted path so a
# predicate added here is enforced everywhere at once.
NODE_LOCAL_PREDICATES: list[tuple[InvalidNodeReason, Callable[[Pod, Node, ClusterSnapshot], bool]]] = [
    (InvalidNodeReason.NODE_SELECTOR_MISMATCH, node_selector_matches),
    (InvalidNodeReason.NODE_AFFINITY_MISMATCH, node_affinity_matches),
    (InvalidNodeReason.NODE_UNSCHEDULABLE, node_schedulable),
    (InvalidNodeReason.TAINT_NOT_TOLERATED, taints_tolerated),
]

PREDICATE_CHAIN: list[tuple[InvalidNodeReason, Callable[[Pod, Node, ClusterSnapshot], bool]]] = [
    (InvalidNodeReason.NOT_ENOUGH_RESOURCES, pod_fits_resources),
    *NODE_LOCAL_PREDICATES,
    (InvalidNodeReason.ANTI_AFFINITY_VIOLATION, anti_affinity_ok),
    (InvalidNodeReason.POD_AFFINITY_UNSATISFIED, pod_affinity_ok),
    (InvalidNodeReason.TOPOLOGY_SPREAD_VIOLATION, topology_spread_ok),
]

# The one-shot inter-pod predicates of the chain, each with the factory of
# the per-pod checker it builds on every call.
_CHECKER_OF: dict[Callable, Callable[[Pod, ClusterSnapshot], Callable[[Node], bool]]] = {
    anti_affinity_ok: make_affinity_checker,
    pod_affinity_ok: make_pod_affinity_checker,
    topology_spread_ok: make_spread_checker,
}

# The reasons the inter-pod checkers charge: a breakdown's nodes that pass
# every node-local predicate are the feasible ones plus these.
INTER_POD_REASONS: tuple[str, ...] = tuple(r.value for r, pred in PREDICATE_CHAIN if pred in _CHECKER_OF)


# --- the chain's node-local predicates over the node axis -------------------
# Each takes (pod, table) and returns the bool verdict of every node of the
# table, or None where the pod passes every node (the vacuous case).  They
# are the scalar predicates above, node for node: the label and taint masks
# evaluate the scalar code once per distinct value or taint tuple and
# broadcast it by id.


def _lookup(lut: list[bool], ids: np.ndarray) -> np.ndarray:
    """Per-node verdicts from per-id ones; ``lut[-1]`` is the verdict of id -1."""
    return np.array(lut, dtype=bool)[ids]


def _resources_mask(pod: Pod, table: NodeTable) -> np.ndarray:
    req = total_pod_resources(pod)  # PodResources.fits_in, node for node
    ok = (req.cpu <= table.cpu) & (req.memory <= table.memory)
    for k, v in (req.extended or {}).items():
        avail = table.extended.get(k)
        ok &= v <= avail if avail is not None else v <= 0
    return ok


def _node_selector_mask(pod: Pod, table: NodeTable) -> np.ndarray | None:
    if pod.spec is None or not pod.spec.node_selector:
        return None
    ok = np.ones(len(table.cpu), dtype=bool)
    for k, v in pod.spec.node_selector.items():
        ids, vocab = table.label_ids(k)
        ok &= _lookup([u == v for u in vocab] + [False], ids)  # a node lacking the key, or any label, fails
    return ok


def _node_affinity_mask(pod: Pod, table: NodeTable) -> np.ndarray | None:
    terms = (pod.spec.node_affinity or []) if pod.spec is not None else []
    if not terms:
        return None
    ok = np.zeros(len(table.cpu), dtype=bool)
    for term in terms:
        if not term.match_expressions:
            continue  # matches nothing
        held = np.ones(len(table.cpu), dtype=bool)
        for r in term.match_expressions:
            ids, vocab = table.label_ids(r.key)
            lut = [_node_expression_matches(r, {r.key: u}) for u in vocab]
            held &= _lookup(lut + [_node_expression_matches(r, {})], ids)
        ok |= held
    return ok


def _schedulable_mask(pod: Pod, table: NodeTable) -> np.ndarray:
    return ~table.cordoned


def _taints_mask(pod: Pod, table: NodeTable) -> np.ndarray:
    return _lookup([taints_tolerated(pod, node) for node in table.taint_nodes], table.taint_ids)


_MASK_OF: dict[Callable, Callable[[Pod, NodeTable], np.ndarray | None]] = {
    pod_fits_resources: _resources_mask,
    node_selector_matches: _node_selector_mask,
    node_affinity_matches: _node_affinity_mask,
    node_schedulable: _schedulable_mask,
    taints_tolerated: _taints_mask,
}
# The chain's leading predicates that have a mask: the breakdown masks them
# and walks the rest node by node.
_N_MASKED = next((i for i, (_, pred) in enumerate(PREDICATE_CHAIN) if pred not in _MASK_OF), len(PREDICATE_CHAIN))


# shape: (pod: obj, node: obj, snapshot: obj) -> obj
def check_node_validity(pod: Pod, node: Node, snapshot: ClusterSnapshot) -> InvalidNodeReason | None:
    """Run the predicate chain; return the first failure reason or None if
    the node is valid — reference ``check_node_validity``
    (``predicates.rs:63-77``, which returns ``Result<(), InvalidNodeReason>``).
    """
    for reason, pred in PREDICATE_CHAIN:
        if not pred(pod, node, snapshot):
            return reason
    return None


# shape: (pod: obj, snapshot: obj) -> (dict, int, int)
def unschedulable_reason_counts(pod: Pod, snapshot: ClusterSnapshot) -> tuple[dict[str, int], int, int]:
    """Per-reason candidate-node rejection counts for one pod — kube's
    "0/N nodes are available: 3 Insufficient cpu, ..." breakdown: each node
    is charged to the FIRST failing predicate in chain order.  Returns
    ``(counts-by-reason-value, feasible_nodes, nodes_total)`` — the payload
    of the flight recorder's "unschedulable" event and the /debug why-pending
    route (utils/events.py, runtime/http_api.py).  O(nodes) work per pod:
    callers on the cycle path budget it (Scheduler.EXPLAIN_WORK).

    The same chain as :func:`check_node_validity`, node for node.  Its
    node-local prefix (resources through taints) is charged as one mask per
    predicate over the snapshot's node table; the nodes that pass it walk,
    in snapshot order, the inter-pod checkers (anti-affinity, pod affinity,
    hard spread), each built once for the pod when the first node reaches
    it: a build walks every placed pod."""
    table = snapshot.node_table()
    alive = np.ones(len(snapshot.nodes), dtype=bool)
    counts: dict[str, int] = {}
    for reason, pred in PREDICATE_CHAIN[:_N_MASKED]:
        passed = _MASK_OF[pred](pod, table)
        if passed is not None:
            failed = int(np.count_nonzero(alive & ~passed))
            if failed:
                counts[reason.value] = failed
            alive &= passed
    chain_ = [(reason.value, pred, _CHECKER_OF.get(pred)) for reason, pred in PREDICATE_CHAIN[_N_MASKED:]]
    checkers: dict[Callable, Callable[[Node], bool]] = {}
    feasible = 0
    for i in np.flatnonzero(alive).tolist():
        node = snapshot.nodes[i]
        for reason, pred, make in chain_:
            if make is None:
                ok = pred(pod, node, snapshot)
            else:
                check = checkers.get(pred)
                if check is None:
                    check = checkers[pred] = make(pod, snapshot)
                ok = check(node)
            if not ok:
                counts[reason] = counts.get(reason, 0) + 1
                break
        else:
            feasible += 1
    return counts, feasible, len(snapshot.nodes)


# shape: (counts: dict, feasible: int) -> str
def dominant_reason(counts: dict[str, int], feasible: int) -> str:
    """The one typed reason a timeline entry carries: the predicate that
    rejected the most nodes — or NotEnoughResources when some node WAS
    feasible against the pre-cycle snapshot (the capacity went to other pods
    in the same cycle: scheduling contention is a resource shortfall)."""
    if feasible > 0 or not counts:
        return InvalidNodeReason.NOT_ENOUGH_RESOURCES.value
    return max(sorted(counts), key=lambda k: counts[k])
