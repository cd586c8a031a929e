"""The plain reference: an independent replay of what the API server accepted.

It imports nothing of the program's logic.  It reads the nodes and pods the
benchmark generated (the program's API types are only the input format) and
the ordered log of creates, deletes and bindings that the API server accepted
in the run, and replays it with its own quantity parsing and its own
predicates, in Kubernetes semantics:

- ``oversubscribed``: bindings after which a node's requests (cpu, memory,
  extended resources) exceed its allocatable;
- ``node_predicates``: bindings to a cordoned node, to a node whose labels
  fail the pod's nodeSelector or required node affinity, or whose
  NoSchedule/NoExecute taints the pod does not tolerate;
- ``inter_pod``: bindings that break required pod anti-affinity (both
  directions) or required pod affinity (with the self-match bootstrap), and
  DoNotSchedule topology spreads that no order could have kept.  A term's
  selector is its ``matchLabels`` pairs ANDed with its ``matchExpressions``
  (``In``, ``NotIn``, ``Exists``, ``DoesNotExist``); an entirely empty one
  matches nothing (the program's stated semantics).  Its topology key may be
  any node label: the key's domains are indexed when a created pod's term
  first names it, and a node without the key is a domain of its own.  The program
  commits a cycle's placements at once, so these are judged as a pod-at-a-time
  scheduler would see them under *some* order of that cycle's bindings.
  Anti-affinity does not depend on the order, and pod affinity only on which
  member of a group comes first, so repeated sweeps that place whatever
  passes decide both exactly.  A spread (the API's stated rule: placing a pod
  keeps ``count(domain) + 1 - min(count over the key's domains) <= max_skew``,
  counting placed pods that match the selector) is judged by a condition
  every valid order meets: the ``m`` pods of one selector that a cycle puts
  into domain ``h`` see at least ``count_before(h) + m - 1`` there at the last
  of them, and a minimum of at most ``min(count_after)``;
- ``gangs_partial``: gangs with some but not all members bound at a
  checkpoint (a wave's end, the end of the run);
- ``fit_but_pending``: at a checkpoint, pods still pending that carry no
  inter-pod constraint and no gang, yet fit some node (resources, node
  predicates, other pods' anti-affinity).  Within a wave such a pod's room
  only shrank, so it also fitted when the scheduler passed it over.  Where
  the configuration guarantees it (``judge_gangs``), also gangs with no
  member bound that fit: member by member by first fit, each in the room
  of those before it and under its own anti-affinity and pod affinity too
  (a first member put where the rest find no room misses a fit; it never
  makes one up).  Such a gang is judged only where its room, too, only
  shrank: no member carries a hard spread (a rising minimum widens it) or a
  pod affinity that a placed pod matches (its bootstrap, which needs none
  anywhere, then held all wave long).  Each pod found is kept in
  ``fitting``.

Every count must be 0.
"""

from __future__ import annotations

from collections import Counter, defaultdict

import numpy as np

CHECKS = ("oversubscribed", "node_predicates", "inter_pod", "gangs_partial", "fit_but_pending")
HARD_EFFECTS = ("NoSchedule", "NoExecute")
_BIN = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40, "Pi": 2**50, "Ei": 2**60}
_DEC = {"k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12, "P": 10**15, "E": 10**18}


def cpu_millis(q) -> int:
    s = str(q).strip()
    if s.endswith("m"):
        return int(s[:-1])
    return int(round(float(s) * 1000))


def amount(q) -> int:
    """Bytes of a memory quantity, or the count of an extended resource."""
    s = str(q).strip()
    for table in (_BIN, _DEC):
        for suf, mult in table.items():
            if s.endswith(suf):
                return int(float(s[: -len(suf)]) * mult)
    return int(float(s))


class NodeFacts:
    __slots__ = ("name", "index", "alloc", "labels", "taints", "cordoned")

    def __init__(self, node, index):
        self.name = node.metadata.name
        self.index = index
        alloc = (node.status.allocatable if node.status else None) or {}
        self.alloc = {k: (cpu_millis(v) if k == "cpu" else amount(v)) for k, v in alloc.items()}
        self.labels = dict(node.metadata.labels or {})
        spec = node.spec
        self.taints = [(t.key, t.value, t.effect) for t in ((spec.taints or []) if spec else []) if t.effect in HARD_EFFECTS]
        self.cordoned = bool(spec and spec.unschedulable)


def _hard_spreads(spec) -> list:
    """The DoNotSchedule topology spread constraints of a pod's ``spec``."""
    return [c for c in ((spec.topology_spread if spec else None) or []) if c.when_unsatisfiable != "ScheduleAnyway"]


def carries_inter_pod(pod) -> bool:
    """Whether ``pod`` carries required anti-affinity, pod affinity or a hard
    spread."""
    s = pod.spec
    return s is not None and bool(s.anti_affinity or s.pod_affinity or _hard_spreads(s))


class PodFacts:
    __slots__ = ("key", "req", "labels", "selector", "affinity", "tolerations", "anti", "pod_aff", "spread", "inter_pod", "gang", "vec")

    def __init__(self, pod):
        self.key = (pod.metadata.namespace or "default", pod.metadata.name)
        req: dict[str, int] = {}
        spec = pod.spec
        for c in (spec.containers if spec else []):
            for k, v in ((c.resources.requests if c.resources else None) or {}).items():
                req[k] = req.get(k, 0) + (cpu_millis(v) if k == "cpu" else amount(v))
        self.req = req
        self.labels = dict(pod.metadata.labels or {})
        self.selector = dict((spec.node_selector if spec else None) or {})
        self.affinity = [
            [(r.key, r.operator, tuple(r.values or ())) for r in (t.match_expressions or [])] for t in ((spec.node_affinity if spec else None) or [])
        ]
        self.tolerations = [(t.key, t.operator, t.value, t.effect) for t in ((spec.tolerations if spec else None) or [])]
        self.anti = [(_selector(t), t.topology_key) for t in ((spec.anti_affinity if spec else None) or [])]
        self.pod_aff = [(_selector(t), t.topology_key) for t in ((spec.pod_affinity if spec else None) or [])]
        self.spread = [(c.topology_key, c.max_skew, _selector(c)) for c in _hard_spreads(spec)]
        self.inter_pod = carries_inter_pod(pod)
        self.gang = spec.gang if spec else None

    @property
    def terms(self) -> list:
        """(selector, topology key) of each inter-pod term the pod carries."""
        return self.anti + self.pod_aff + [(sel, key) for key, _skew, sel in self.spread]


def _selector(term) -> tuple:
    """A label selector as ``(pairs, expressions)``: its ``matchLabels``
    pairs and its ``matchExpressions`` as ``(key, operator, values)``."""
    exprs = tuple((r.key, r.operator, tuple(r.values or ())) for r in (term.match_expressions or ()))
    return tuple(sorted((term.match_labels or {}).items())), exprs


def _matches(sel: tuple, labels: dict) -> bool:
    # An empty selector matches nothing (the program's stated semantics).
    pairs, exprs = sel
    return bool(pairs or exprs) and all(labels.get(k) == v for k, v in pairs) and all(_expr_ok(k, op, vals, labels) for k, op, vals in exprs)


def _show(sel: tuple) -> str:
    pairs, exprs = sel
    return str(dict(pairs)) + "".join(f" {k} {op} {list(vals)}" for k, op, vals in exprs)


def _expr_ok(key, op, values, labels) -> bool:
    if op == "In":
        return key in labels and labels[key] in values
    if op == "NotIn":
        return key not in labels or labels[key] not in values
    if op == "Exists":
        return key in labels
    if op == "DoesNotExist":
        return key not in labels
    if op in ("Gt", "Lt"):
        try:
            have, want = int(labels[key]), int(values[0])
        except (KeyError, IndexError, ValueError):
            return False
        return have > want if op == "Gt" else have < want
    return False


def _tolerated(taint, tolerations) -> bool:
    tkey, tval, teff = taint
    for key, op, val, eff in tolerations:
        if eff and eff != teff:
            continue
        if not key:
            if op == "Exists":
                return True
            continue
        if key == tkey and (op == "Exists" or (op == "Equal" and val == tval)):
            return True
    return False


def affinity_ok(p: PodFacts, n: NodeFacts) -> bool:
    """nodeSelector and required node affinity (terms ORed, expressions ANDed;
    a term with no expressions matches nothing)."""
    if any(n.labels.get(k) != v for k, v in p.selector.items()):
        return False
    return not p.affinity or any(t and all(_expr_ok(k, op, vals, n.labels) for k, op, vals in t) for t in p.affinity)


def node_local_ok(p: PodFacts, n: NodeFacts) -> bool:
    return not n.cordoned and affinity_ok(p, n) and all(_tolerated(t, p.tolerations) for t in n.taints)


class _Domains:
    """Placed pods per topology domain, indexed by label pair, plus the
    anti-affinity terms their carriers hold there."""

    def __init__(self):
        self.pairs: dict = defaultdict(Counter)  # domain -> Counter[(label, value)]
        self.sets: dict = defaultdict(Counter)  # domain -> Counter[frozenset(labels)]
        self.carriers: dict = defaultdict(list)  # domain -> [selector of each anti term held there]
        self.everywhere: Counter = Counter()  # frozenset(labels) over the cluster
        self._anywhere: dict = {}  # selector -> any_anywhere's answer, until ``everywhere`` changes

    def count(self, dom, sel) -> int:
        pairs, exprs = sel
        if len(pairs) == 1 and not exprs:
            return self.pairs[dom][pairs[0]]
        return sum(c for ls, c in self.sets[dom].items() if _matches(sel, dict(ls)))

    def add_everywhere(self, labels: dict, sign: int) -> None:
        self.everywhere[frozenset(labels.items())] += sign
        self._anywhere.clear()

    def any_anywhere(self, sel) -> bool:
        hit = self._anywhere.get(sel)
        if hit is None:
            hit = self._anywhere[sel] = any(c > 0 and _matches(sel, dict(ls)) for ls, c in self.everywhere.items())
        return hit


class Audit:
    def __init__(self, nodes, inter_pod: bool, judge_gangs: bool = False):
        self.nodes = {n.metadata.name: NodeFacts(n, i) for i, n in enumerate(nodes)}
        self.node_list = list(self.nodes.values())
        self.resources = sorted({k for n in self.node_list for k in n.alloc})
        self.alloc = np.array([[n.alloc.get(r, 0) for r in self.resources] for n in self.node_list], dtype=np.int64)
        self.used = np.zeros_like(self.alloc)
        self.topology_keys: list = []  # the keys the created pods' terms name, indexed in that order
        self.inter_pod = inter_pod  # keep per-domain state for inter-pod terms
        self.judge_gangs = judge_gangs  # a gang with no member bound that fits counts in fit_but_pending
        self.namespace = None  # the one namespace inter-pod terms are judged in
        self.domains = _Domains()
        self.facts: dict = {}  # key -> PodFacts
        self.where: dict = {}  # key -> node name, for pods bound now
        self.gone: set = set()  # keys of deleted pods
        self.counts = {c: 0 for c in CHECKS}
        self.examples: dict = {c: [] for c in CHECKS}
        self.fitting: set = set()  # keys of the pods fit_but_pending found
        self._key_domains: dict = {}
        self._local: dict = {}  # a pod's node-local terms -> mask of the nodes that pass them

    # -- bookkeeping ---------------------------------------------------------

    def _fail(self, check: str, what) -> None:
        self.counts[check] += 1
        if len(self.examples[check]) < 3:
            self.examples[check].append(str(what))

    def _dom(self, n: NodeFacts, key: str):
        v = n.labels.get(key)
        return (key, v) if v is not None else ("~node", key, n.name)

    def _count_in(self, p: PodFacts, n: NodeFacts, keys, sign: int) -> None:
        """Add (``sign`` 1) or remove (-1) a bound pod's labels in the domains
        of ``keys`` its node belongs to."""
        ls = frozenset(p.labels.items())
        for key in keys:
            dom = self._dom(n, key)
            for pair in p.labels.items():
                self.domains.pairs[dom][pair] += sign
            self.domains.sets[dom][ls] += sign

    def _place(self, p: PodFacts, n: NodeFacts, sign: int) -> None:
        """Add (``sign`` 1) or remove (-1) a bound pod's labels and anti terms
        in the domains of its node."""
        self._count_in(p, n, self.topology_keys, sign)
        self.domains.add_everywhere(p.labels, sign)
        for sel, key in p.anti:
            lst = self.domains.carriers[self._dom(n, key)]
            lst.append(sel) if sign > 0 else lst.remove(sel)

    def _index_keys(self, p: PodFacts) -> None:
        """Index the domains of each topology key ``p``'s terms name for the
        first time, counting the pods bound now (``p`` is not yet)."""
        new = list(dict.fromkeys(key for _, key in p.terms if key not in self.topology_keys))
        if not new:
            return
        self.topology_keys += new
        for key, node in self.where.items():
            self._count_in(self.facts[key], self.nodes[node], new, +1)

    # -- events ----------------------------------------------------------------

    def create(self, pod) -> None:
        p = PodFacts(pod)
        if any(k not in self.resources for k, v in p.req.items() if v > 0):
            p.vec = None  # asks for a resource no node has: fits nowhere
        else:
            p.vec = np.array([p.req.get(r, 0) for r in self.resources], dtype=np.int64)
        if p.inter_pod and not self.inter_pod:
            raise ValueError(f"{p.key[1]} has inter-pod terms but the replay keeps no domain state")
        if self.inter_pod:
            if self.namespace is None:
                self.namespace = p.key[0]
            elif p.key[0] != self.namespace:
                raise ValueError(f"{p.key[1]} is in namespace {p.key[0]}: the replay judges inter-pod terms within one namespace, {self.namespace}")
            self._index_keys(p)
        self.facts[p.key] = p
        if pod.spec is not None and pod.spec.node_name:
            n = self.nodes[pod.spec.node_name]
            self.where[p.key] = n.name
            self.used[n.index] += p.vec
            if self.inter_pod:
                self._place(p, n, +1)

    def delete(self, key) -> None:
        self.gone.add(key)
        node = self.where.pop(key, None)
        if node is not None:
            p, n = self.facts[key], self.nodes[node]
            self.used[n.index] -= p.vec
            if self.inter_pod:
                self._place(p, n, -1)

    def bind_group(self, binds) -> None:
        """One cycle's bindings ``[(key, node_name), ...]`` in accepted order."""
        placed = []
        for key, node_name in binds:
            p, n = self.facts.get(key), self.nodes.get(node_name)
            if p is None or n is None or key in self.where:
                self._fail("node_predicates", f"bind of {key} to {node_name}: unknown or already bound")
                continue
            if not node_local_ok(p, n):
                self._fail("node_predicates", f"{key[1]} on {node_name}")
            if p.vec is None:
                self._fail("oversubscribed", f"{node_name} after {key[1]}: resource the node lacks")
                p.vec = np.zeros(len(self.resources), dtype=np.int64)
            self.used[n.index] += p.vec
            if (self.used[n.index] > self.alloc[n.index]).any():
                self._fail("oversubscribed", f"{node_name} after {key[1]}")
            self.where[key] = node_name
            placed.append((p, n))
        if self.inter_pod:
            self._inter_pod_sweeps(placed)

    def _inter_pod_sweeps(self, placed) -> None:
        spreads = {(key, sel) for p, _ in placed for key, _skew, sel in p.spread}
        before = {sp: self._spread_counts(*sp) for sp in spreads}
        todo = placed
        while todo:
            left = []
            for p, n in todo:
                if self._inter_pod_ok(p, n):
                    self._place(p, n, +1)
                else:
                    left.append((p, n))
            if len(left) == len(todo):
                for p, n in left:
                    self._fail("inter_pod", f"{p.key[1]} on {n.name}: {self._inter_pod_fault(p, n)}")
                    self._place(p, n, +1)
                break
            todo = left
        into: dict = defaultdict(lambda: [0, 0])  # (key, sel, domain) -> [pods, largest max skew]
        for p, n in placed:
            for key, skew, sel in p.spread:
                here = self._dom(n, key)
                if here[0] != "~node":  # a node without the key is exempt
                    g = into[(key, sel, here)]
                    g[0] += 1
                    g[1] = max(g[1], skew)
        after = {sp: self._spread_counts(*sp) for sp in spreads}
        for (key, sel, here), (m, skew) in into.items():
            low = min(after[(key, sel)].values())
            if before[(key, sel)][here] + m - low > skew:
                self._fail("inter_pod", f"spread {_show(sel)} over {key}: {m} pods into {here[1]} with max skew {skew}, "
                           f"counts before {sorted((k[1], v) for k, v in before[(key, sel)].items())}, after {sorted((k[1], v) for k, v in after[(key, sel)].items())}")

    def _spread_counts(self, key: str, sel: tuple) -> dict:
        doms = self._key_domains.get(key)
        if doms is None:
            doms = self._key_domains[key] = sorted({(key, n.labels[key]) for n in self.node_list if key in n.labels})
        return {dom: self.domains.count(dom, sel) for dom in doms}

    def _inter_pod_ok(self, p: PodFacts, n: NodeFacts) -> bool:
        return self._inter_pod_fault(p, n) is None

    def _inter_pod_fault(self, p: PodFacts, n: NodeFacts) -> str | None:
        """None when ``p`` may join ``n`` now, else which affinity term it
        breaks (spreads are judged per cycle, in ``_inter_pod_sweeps``)."""
        d = self.domains
        for sel, key in p.anti:
            if d.count(self._dom(n, key), sel):
                return f"anti-affinity {_show(sel)} over {key}"
        for key in self.topology_keys:
            for sel in d.carriers.get(self._dom(n, key), ()):
                if _matches(sel, p.labels):
                    return f"a placed pod's anti-affinity {_show(sel)} over {key}"
        for sel, key in p.pod_aff:
            if d.count(self._dom(n, key), sel) == 0 and (d.any_anywhere(sel) or not _matches(sel, p.labels)):
                return f"pod affinity {_show(sel)} over {key}"
        return None

    def checkpoint(self, keys) -> None:
        """Judge the pods ``keys`` (one wave, or the run's last pods) now."""
        gangs: dict = defaultdict(list)
        pending = []  # pending pods of no gang, and the gangs judged
        for key in keys:
            p = self.facts.get(key)
            if p is None or key in self.gone:
                continue
            if p.gang:
                gangs[p.gang].append(p)
            elif key not in self.where and not p.inter_pod:
                pending.append([p])
        for name, members in gangs.items():
            bound = sum(m.key in self.where for m in members)
            if 0 < bound < len(members):
                self._fail("gangs_partial", f"{name}: {bound}/{len(members)} bound")
            elif not bound and self.judge_gangs and self._room_only_shrank(members):
                pending.append(members)
        for members in pending:
            node = self._group_fit(members)
            if node is not None:
                self.fitting.update(m.key for m in members)
                what = f"gang {members[0].gang} ({len(members)} pods)" if members[0].gang else members[0].key[1]
                self._fail("fit_but_pending", f"{what} fits {node}")

    def _room_only_shrank(self, members) -> bool:
        """Whether the room of a pending gang only shrank while its wave was
        placed: no hard spread, and no pod affinity that a placed pod matches
        (pods are only added within a wave)."""
        return not any(m.spread or any(self.domains.any_anywhere(sel) for sel, _ in m.pod_aff) for m in members)

    def _group_fit(self, members) -> str | None:
        """The node of the first of ``members`` if all of them fit now, each
        by first fit in the room the ones before it leave; the state is as it
        was afterwards."""
        held = []
        try:
            for p in members:
                node = self.first_fit(p)
                if node is None:
                    return None
                n = self.nodes[node]
                self.used[n.index] += p.vec
                if self.inter_pod:
                    self._place(p, n, +1)
                held.append((p, n))
            return held[0][1].name
        finally:
            for p, n in reversed(held):
                self.used[n.index] -= p.vec
                if self.inter_pod:
                    self._place(p, n, -1)

    def first_fit(self, p: PodFacts) -> str | None:
        if p.vec is None:
            return None
        room = ((self.alloc - self.used) >= p.vec).all(axis=1) & self._local_mask(p)
        for i in np.flatnonzero(room):
            n = self.node_list[i]
            if self._inter_pod_ok(p, n):
                return n.name
        return None

    def _local_mask(self, p: PodFacts) -> np.ndarray:
        terms = (tuple(sorted(p.selector.items())), tuple(tuple(t) for t in p.affinity), tuple(p.tolerations))
        mask = self._local.get(terms)
        if mask is None:
            mask = self._local[terms] = np.array([node_local_ok(p, n) for n in self.node_list])
        return mask

    def report(self) -> dict:
        return {c: {"value": self.counts[c], "limit": 0} for c in CHECKS}

    @property
    def correct(self) -> bool:
        return all(v == 0 for v in self.counts.values())


def replay(nodes, log, group_of, judge_gangs: bool = False) -> Audit:
    """Replay ``log`` against ``nodes``.  ``log`` entries are
    ``(t, "create", pod)``, ``(t, "delete", key)``, ``(t, "bind", (key, node))``
    and ``(t, "checkpoint", keys)`` in the order the API server accepted them;
    ``group_of(t)`` names the scheduling cycle a binding at ``t`` belongs to.
    The per-domain state that inter-pod terms need is kept when some created
    pod, standing or not, carries one.  ``judge_gangs``: the configuration
    guarantees that a gang that fits is not left pending."""
    audit = Audit(nodes, any(kind == "create" and carries_inter_pod(obj) for _, kind, obj in log), judge_gangs)
    group, group_id = [], None
    for t, kind, obj in log:
        if kind == "bind":
            gid = group_of(t)
            if group and gid != group_id:
                audit.bind_group(group)
                group = []
            group_id = gid
            group.append(obj)
            continue
        if group:
            audit.bind_group(group)
            group = []
        if kind == "create":
            audit.create(obj)
        elif kind == "delete":
            audit.delete(obj)
        elif kind == "checkpoint":
            audit.checkpoint(obj)
    if group:
        audit.bind_group(group)
    return audit
