"""Tensorized inter-pod anti-affinity, POSITIVE pod affinity + topology
spread (BASELINE config 5).

The scalar predicates (core/predicates.py: anti_affinity_ok /
topology_spread_ok) are pods×pods×nodes relations — the memory wall SURVEY.md
§2b SP/CP warns about.  This module never materializes that 3-tensor.  The
key observation: both predicates only consult *topology domains* (the set of
nodes sharing a value of the term's topology key), so the device state is
domain-granular:

  AA term vocab T:  distinct (namespace, topology_key, selector) terms among
                    pending + placed pods.
  PA term vocab Ta: positive (requiredDuringScheduling podAffinity) terms
                    among PENDING pods only — affinity constrains just the
                    declarer, so placed pods' terms need no columns.  The
                    blocked mask is the inverted matched-domain mask, gated
                    by the bootstrap waiver (a term matching nothing
                    anywhere is waived for self-matching declarers); the
                    within-round filter keeps only the first accepted match
                    per waived term (see constraint_filter).
  Spread vocab S:   distinct (namespace, key, max_skew, selector) constraints
                    among pending pods.
  Coarse domains D: (key, value) pairs over the referenced topology keys —
                    node_dom_c[N, D] is each node's one-hot domain membership
                    (one column per key it carries).
  Fine domains:     keys whose values are unique per node (hostname-like) and
                    nodes lacking a coarse key degrade to per-node singleton
                    domains — state at node granularity [T, N], exactly as
                    the scalar ``("~node", name)`` rule.

Per auction round (ops/assign.py), the blocked pods×nodes mask is three
matmuls — pod_carries[B,T] @ aa_matched_node[T,N] etc. — so constrained pods
ride the same MXU path as everything else; per-round state updates are
[T,P]@[P,D] matmuls plus O(P·T) scatters.

Within-round conflicts (two mutually-anti-affine pods accepted into one
domain in the same round; a domain over-filling past max_skew) are resolved
by rank (the auction's priority order):
  • AA: in each (term, domain) cell, a matched pod survives only if it
    out-ranks every accepted carrier in the cell and vice versa (exact
    min-rank rule; at worst it defers a pod the greedy oracle would accept
    by one round — never admits a violation).
  • Spread: rank-prefix admission.  A declarer on a keyed node is kept iff
    ``count(cell) + prefix(p) + 1 ≤ max_skew + lo_p`` where ``prefix(p)``
    is the matched CANDIDATE mass of lower rank in its cell and ``lo_p``
    the per-pod water line — the round-start minimum lifted by the
    COMMITTED lower-rank fills of SPREAD_CASCADE in-round sweeps.  All
    in-round matched mass rides the rank prefix (not a static denominator),
    so two same-selector constraints can no longer mutually freeze each
    other's quotas, and whole multi-level waves admit per round.
Deferred pods stay active and retry next round against the committed state,
behind the other claimants of their node (ops/assign.py), so that a pod the
filter keeps deferring holds no one back;
the round-start choose mask blocks domains beyond the cascade's reach, so
claimants target cells the filter can actually admit.

Filter cost model (round 7): only ACCEPTED claimants can conflict, so
``constraint_filter`` gathers them into a compact [A] workspace before any
cell machinery runs (exact rows in NumPy; a stable accepted-first partition
whose scans stop after ``ceil(A / ACTIVE_CHUNK)`` tiles under jit) and
scatters survivors back — per-round filter cost tracks the accepted count,
not the padded pod axis.  Per-pod cell lookups ride one banded gather
matmul, the AA carrier/matched predecessor checks one fused segment
scatter-min over a unified (term, coarse-domain ∪ node) cell space, and the
spread water line / PA bootstrap flags are ROUND-CARRIED state
(``augment_round_state``) updated incrementally by ``constraint_commit``
instead of re-derived from the domain history every round.  All of it is
bitwise-neutral: masses are exact small-integer f32, so dropping zero rows,
banding independent matmul columns, and re-chunking prefix sums cannot
change a single admission.

Validity is *order-witnessed*: each round's kept set admits a sequential
order in which every placement passes the scalar chain — ASCENDING RANK for
both predicates: no conflicting AA pair survives at all, and a kept spread
declarer at its turn sees cell count ≤ count0 + prefix(p) (kept ⊆
candidates) and min ≥ lo_p (its cascade fills are lower-rank commits,
placed before it), so ``count+1−min ≤ max_skew`` holds at its turn
(tests/test_constraints_tensor.py replays this certificate through
core/predicates.py).  This holds uniformly for multi-constraint declarers —
admission requires every declared constraint's bound at the same rank turn.

Everything is written against an ``xp`` namespace (numpy | jax.numpy) so the
native and TPU backends share one expression tree — the same bit-parity
contract as ops/masks.py.

Scale guards: clusters whose constraint structure exceeds the static budgets
(too many distinct terms, or a many-valued non-unique topology key) raise
:class:`UntensorizableConstraints`; the controller then falls back to the
exact host-side sequential phase (runtime/controller.py), so the tensor path
is an accelerator, never a semantics change.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, replace

import numpy as np

from ..api.objects import Pod, exclusive_placement
from ..core.predicates import node_topology_domain, term_matches
from .pack import round_up

__all__ = [
    "ConstraintSet",
    "UntensorizableConstraints",
    "pack_constraints",
    "prune_match_memo",
    "augment_round_state",
    "round_blocked_masks",
    "blocked_block",
    "constraint_filter",
    "constraint_commit",
    "RANK_INF",
]

RANK_INF = np.float32(3.0e38)

# Default budgets (padded): sized so the per-term state ([T,N]/[S,D], ~10 MB
# at 256×10k) and the pod-side bitmaps ([P,T] etc., ~110 MB each at 100k×256)
# stay well under HBM at north-star scale while admitting realistic
# vocabularies — per-app selectors (one term per deployment) are the common
# shape, and a 50-deployment cluster with two skew levels already needs ~100
# spread terms.  History: the original 128/64 budgets silently routed the
# CLI's own mixed workload to the exact-but-glacial host sequential phase
# (UntensorizableConstraints fallback — measured 482 s for ONE 10k×1k cycle
# vs ~1 s on the tensor path), so the defaults now match what the hardware
# comfortably holds, and the controller exposes them as knobs.
MAX_AA_TERMS = 256
MAX_SPREAD = 256
MAX_COARSE_DOMAINS = 256

# Fast-path budget for the ANTI-AFFINITY within-round filter: below this
# terms×D product, "who came earlier into my cell" is computed DENSELY — a
# [A,T,D] exclusive cumsum along the (rank-ordered) pod axis of the ACTIVE
# workspace (see constraint_filter) — instead of the fused scatter-min
# formulation, because XLA lowers arbitrary-index scalar scatters near-serially
# while cumsums ride the parallel prefix path; since the round-7 active-set
# compaction the scatter index count tracks the accepted workspace, so the
# fused segment path is the default at every real vocabulary and the dense
# path survives for sub-budget term structures.  Bit-identical results
# either way — counts are small exact f32 integers and array order IS rank
# order (tests/test_constraints_tensor.py pins parity exactly at this
# threshold).  (The SPREAD filter has no such split: its rank-prefix
# admission always uses the cell formulation, chunked along the pod axis —
# see _cell_rank_prefix.)
DENSE_CELLS = 1024
# The cells product alone does not bound the 3-tensor: its bytes scale with
# the POD axis too (round-4 advisor finding — at 128k padded pods a
# threshold-sized [P,T,D] is ~0.5 GB, and several temporaries live inside
# the jit round body at once).  The dense path therefore also requires
# p·t·d·4 ≤ this per-tensor byte budget; the flagship constrained shape
# (106k × 832 spread cells ≈ 354 MB, measured fast and well inside v5e-1's
# 16 GB HBM) stays dense, while larger pod axes degrade to the sort/scatter
# formulation — same results, bounded memory.  The in-jit size chain
# (ops/assign.py) re-evaluates the predicate per stage, so shrunk tail
# stages can re-enter the dense path even when the full-size stage could not.
DENSE_TENSOR_BYTES = 400 * 1024 * 1024


# shape: (p: int, cells: int) -> bool
def _dense_ok(p: int, cells: int) -> bool:
    return cells <= DENSE_CELLS and p * cells * 4 <= DENSE_TENSOR_BYTES


# Within-round water-line sweeps of the spread admission filter
# (constraint_filter) — each sweep can lift a constraint's certain minimum
# one level, so a round admits up to this many fill levels at once; the
# choose-time mask (round_blocked_masks) offers declarers domains within the
# same reach.  4 sweeps measured best on the flagship constrained row: each
# sweep only lifts levels whose fills come from LOWER-RANK commits, and
# cross-cell rank interleaving caps the useful depth — 8 sweeps bound no
# more pods and cost ~0.3 s/cycle more ([P,S,D] cumsum per sweep).  MUST be
# a global constant: a size-dependent sweep count would make admission
# depend on the stage shape and break native↔TPU bit-parity.
SPREAD_CASCADE = 4


class UntensorizableConstraints(Exception):
    """Constraint structure exceeds the tensor budgets — use the host path."""


# Sentinel key under which a match_memo stores the term-vocabulary signature
# it is valid for.  Key spaces (owned HERE, with prune_match_memo and
# _sig_independent — callers must not hand-filter by key type):
#   _MEMO_SIG            — the signature sentinel
#   id(pod) ints         — matched-term ids (vocab-DEPENDENT)
#   ("dk", id(pod))      — declared canonical keys (vocab-independent)
_MEMO_SIG = "sig"
_MEMO_DK = "dk"


def _sig_independent(k) -> bool:
    """Memo keys that survive a vocabulary-signature change."""
    return isinstance(k, tuple) and len(k) == 2 and k[0] == _MEMO_DK


# shape: (memo: dict, live_ids: obj) -> dict
def prune_match_memo(memo: dict, live_ids: set) -> dict:
    """Drop memo entries for dead pod objects, preserving the signature
    sentinel (see the key-space table above)."""
    return {
        k: v
        for k, v in memo.items()
        if k == _MEMO_SIG or k in live_ids or (isinstance(k, tuple) and k[1] in live_ids)
    }




def _term_probe_index(term_list):
    """(indexed, residual) over ``[(key, (ns, term)), ...]`` — the matched-
    bitmap hot loops are O(pods × terms) naively (13M term_matches calls at
    50k pods × ~260 terms, ~15 s host-side); a term with match_labels can
    only match a pod carrying its first sorted (k, v) pair, so pods probe
    the index with their own labels and run the full matcher on the few
    candidates (the same near-linear trick as the controller's
    _split_affinity_pending).  Terms without match_labels land in the
    per-namespace residual."""
    indexed: dict[tuple, list[int]] = {}
    residual: dict[str | None, list[int]] = {}
    for ti, (_key, (t_ns, term)) in enumerate(term_list):
        ml = term.match_labels
        if ml:
            k, v = sorted(ml.items())[0]
            indexed.setdefault((t_ns, k, v), []).append(ti)
        else:
            residual.setdefault(t_ns, []).append(ti)
    return indexed, residual


def _matched_term_ids(term_list, indexed, residual, ns, labels):
    """Term indices of ``term_list`` whose selector matches ``labels`` in
    namespace ``ns`` — candidates from the probe index, verified exactly."""
    cand: set[int] = set(residual.get(ns, ()))
    if labels:
        for kv in labels.items():
            cand.update(indexed.get((ns, kv[0], kv[1]), ()))
    return [ti for ti in cand if term_matches(term_list[ti][1][1], labels)]


def _canon_selector(match_labels, match_expressions) -> tuple:
    ml = tuple(sorted((match_labels or {}).items()))
    mx = tuple(
        sorted(
            (r.key, r.operator, tuple(sorted(r.values or ())) if r.operator in ("In", "NotIn") else tuple(r.values or ()))
            for r in (match_expressions or [])
        )
    )
    return (ml, mx)


def _aa_key(ns, term) -> tuple:
    return (ns, term.topology_key, _canon_selector(term.match_labels, term.match_expressions))


# ---------------------------------------------------------------------------
# Exclusive-placement families (JobSet's ``exclusive-topology`` term pair)
# ---------------------------------------------------------------------------
#
# JobSet places each job alone in a domain of key D (a TPU slice's node pool)
# by giving every pod of job ``v`` two required terms over D: pod affinity
# ``K In [v]`` and anti-affinity ``K Exists, K NotIn [v]`` (K the job-key
# label).  Per-term rows would spend one anti-affinity row per job and one
# coarse column per pool, so a fleet of a few hundred jobs or pools passes
# the budgets above.  A FAMILY (namespace, D, K) folds every such term into
# two summaries per domain d of D, each "none", one value id, or "many":
#
#   M[d]  the K values the pods in d carry as labels;
#   C[d]  the v of the pods in d that carry the anti-affinity term.
#
# A carrier of ``v`` may enter d only if M[d] is none or v; a pod labelled
# ``K = x`` only if C[d] is none or x — exact for any mix of labelled pods
# and carriers.  A pod-affinity declarer of ``a`` may enter only a domain
# whose M[d] is a, or anywhere while no pod anywhere carries a and it carries
# a itself (the bootstrap the per-term rows implement; ``seen[a]`` tracks
# "some pod carries a").  A domain reading "many" cannot say whether it holds
# a, so a family with such a domain at pack time keeps per-term rows for its
# pod-affinity terms (a domain that turns "many" inside a cycle only blocks
# more).  State is [F, D_k] per family and [F, V] per value, read through a
# [F, N] node -> domain index: no budget above applies.
EX_NONE = -1
EX_MANY = -2


@dataclass(frozen=True)
class ExclusiveFamilies:
    """The folded families of one cycle: pod-side value ids, the node ->
    domain index, and the per-domain and per-value state (see above)."""

    pod: dict  # pod_ex_label / pod_ex_carry / pod_ex_aff: [P, F] int32, -1 = none
    meta: dict  # ex_node_dom [F, N] int32; ex_aff_declared [F, V] f32
    state: dict  # ex_m / ex_c [F, D_k] int32 (EX_NONE / EX_MANY / id); ex_seen [F, V] f32
    n_families: int
    folded_aa: frozenset  # canonical anti-affinity keys that left the per-term rows
    folded_pa: frozenset  # canonical pod-affinity keys that left the per-term rows


# shape: (snapshot: obj, pending: obj, placed_carrier_keys: obj, aa_vocab: dict,
#   pa_vocab: dict, declared: fn, p_pad: int, n_pad: int, pad: fn) -> obj
def _pack_exclusive(snapshot, pending, placed_carrier_keys, aa_vocab, pa_vocab, declared, p_pad, n_pad, pad):
    """The cycle's :class:`ExclusiveFamilies`, or None when no term has the
    exclusive shape.  A family folds its anti-affinity terms when no pod
    carries two of them, and its pod-affinity terms when no pending pod
    declares two and no domain reads "many"; what does not fold keeps its
    per-term rows.  The placed pods' summaries come from the snapshot's
    exclusive-term indexes (ClusterSnapshot.label_domains,
    exclusive_carrier_domains)."""
    aa_shape = {}  # key -> (family, v)
    for key, (ns, t) in aa_vocab.items():
        s = exclusive_placement(t)
        if s is not None and s[0] == "aa":
            aa_shape[key] = ((ns, t.topology_key, s[1]), s[2])
    pa_shape = {}
    for key, (ns, t) in pa_vocab.items():
        s = exclusive_placement(t)
        if s is not None and s[0] == "pa":
            pa_shape[key] = ((ns, t.topology_key, s[1]), s[2])
    if not aa_shape and not pa_shape:
        return None

    # Families a pod declares twice stay per-term (one value id a pod).
    no_aa: set = set()
    no_pa: set = set()
    for aa_d in [declared(p)[0] for p in pending] + [d for _q, _qn, d in placed_carrier_keys]:
        fams = [aa_shape[k][0] for k, _t in aa_d if k in aa_shape]
        no_aa.update(f for f in fams if fams.count(f) > 1)
    for p in pending:
        fams = [pa_shape[k][0] for k, _t in declared(p)[1] if k in pa_shape]
        no_pa.update(f for f in fams if fams.count(f) > 1)

    values: dict[str, int] = {}

    def vid(v):
        return values.setdefault(v, len(values))

    # Node -> domain id under each family's topology key; a keyless node is
    # its own domain, as core/snapshot.node_topology_domain makes it.
    dom_index: dict[str, tuple[np.ndarray, dict]] = {}

    def domains(d_key):
        if d_key not in dom_index:
            doms: dict = {}
            ids = np.zeros((n_pad,), dtype=np.int32)
            for i, n in enumerate(snapshot.nodes):
                ids[i] = doms.setdefault(node_topology_domain(n, d_key), len(doms))
            dom_index[d_key] = (ids, doms)
        return dom_index[d_key][1]

    def summary(by_value: dict, d_key: str) -> dict:
        """domain id -> the one value id placed there, or EX_MANY (a
        domain left out holds none)."""
        out: dict = {}
        doms = domains(d_key)
        for v, placed_in in by_value.items():
            i = vid(v)
            for d in placed_in:
                s = out.get(doms[d], i)
                out[doms[d]] = i if s == i else EX_MANY
        return out

    labelled = {
        f: snapshot.label_domains(f[0], f[2], f[1])
        for f in sorted({f for f, _v in aa_shape.values()} | {f for f, _v in pa_shape.values()})
    }
    m_sum = {f: summary(by_value, f[1]) for f, by_value in labelled.items()}
    fold_aa = {k for k, (f, _v) in aa_shape.items() if f not in no_aa}
    fold_pa = {k for k, (f, _v) in pa_shape.items() if f not in no_pa and EX_MANY not in m_sum[f].values()}
    used = sorted({aa_shape[k][0] for k in fold_aa} | {pa_shape[k][0] for k in fold_pa})
    if not used:
        return None
    c_sum = {f: summary(snapshot.exclusive_carrier_domains(f[0]).get((f[2], f[1]), {}), f[1]) for f in used}

    n_fam = len(used)
    col = {f: i for i, f in enumerate(used)}
    rows = []
    for p in pending:
        ns, labels = p.metadata.namespace, p.metadata.labels or {}
        aa_d, pa_d = declared(p)[0], declared(p)[1]
        lab = [vid(labels[f[2]]) if f[0] == ns and f[2] in labels else EX_NONE for f in used]
        car = [EX_NONE] * n_fam
        aff = [EX_NONE] * n_fam
        for key, _t in aa_d:
            if key in fold_aa:
                f, v = aa_shape[key]
                car[col[f]] = vid(v)
        for key, _t in pa_d:
            if key in fold_pa:
                f, v = pa_shape[key]
                aff[col[f]] = vid(v)
        rows.append((lab, car, aff))

    f_pad = pad("f", n_fam, 1)
    dk_pad = pad("exd", max(len(dom_index[f[1]][1]) for f in used), 8)
    v_pad = pad("exv", len(values), 8)
    pod_label = np.full((p_pad, f_pad), EX_NONE, dtype=np.int32)
    pod_carry = np.full((p_pad, f_pad), EX_NONE, dtype=np.int32)
    pod_aff = np.full((p_pad, f_pad), EX_NONE, dtype=np.int32)
    for pi, (lab, car, aff) in enumerate(rows):
        pod_label[pi, :n_fam] = lab
        pod_carry[pi, :n_fam] = car
        pod_aff[pi, :n_fam] = aff
    node_dom = np.zeros((f_pad, n_pad), dtype=np.int32)
    ex_m = np.full((f_pad, dk_pad), EX_NONE, dtype=np.int32)
    ex_c = np.full((f_pad, dk_pad), EX_NONE, dtype=np.int32)
    ex_seen = np.zeros((f_pad, v_pad), dtype=np.float32)
    declared_aff = np.zeros((f_pad, v_pad), dtype=np.float32)
    for i, f in enumerate(used):
        node_dom[i] = dom_index[f[1]][0]
        for d, s in m_sum[f].items():
            ex_m[i, d] = s
        for d, s in c_sum[f].items():
            ex_c[i, d] = s
        ex_seen[i, [values[x] for x in labelled[f]]] = 1.0
    pi, fi = np.nonzero(pod_aff >= 0)
    declared_aff[fi, pod_aff[pi, fi]] = 1.0
    return ExclusiveFamilies(
        pod={"pod_ex_label": pod_label, "pod_ex_carry": pod_carry, "pod_ex_aff": pod_aff},
        meta={"ex_node_dom": node_dom, "ex_aff_declared": declared_aff},
        state={"ex_m": ex_m, "ex_c": ex_c, "ex_seen": ex_seen},
        n_families=n_fam,
        folded_aa=frozenset(fold_aa),
        folded_pa=frozenset(fold_pa),
    )


def _sp_key(ns, c) -> tuple:
    return (ns, c.topology_key, int(c.max_skew), _canon_selector(c.match_labels, c.match_expressions))


@dataclass(frozen=True)
class ConstraintSet:
    """Device tensors for AA + spread over one packed cycle.

    Pod rows align with PackedCluster's pending-pod order (padded to P).
    State arrays are the *round-start* state (from placed pods); the auction
    threads them through its while-loop carry.
    """

    # Pod side [P, T] / [P, Ta] / [P, S] / [P, Ss] float32
    pod_aa_carries: np.ndarray
    pod_aa_matched: np.ndarray
    pod_pa_declares: np.ndarray  # positive affinity: the pod declares term
    pod_pa_matched: np.ndarray  # the pod's labels satisfy the term's selector
    pod_sp_declares: np.ndarray
    pod_sp_matched: np.ndarray
    pod_sps_declares: np.ndarray  # soft (ScheduleAnyway) spread declarations
    pod_sps_matched: np.ndarray
    pod_ppa_w: np.ndarray  # [P, Tp] SIGNED preferred-(anti-)affinity weights
    pod_ppa_matched: np.ndarray  # [P, Tp] pod matches the preferred term
    # Node side
    node_dom_c: np.ndarray  # [N, D] float32 one-hot (one col per carried key)
    # Term metadata
    term_uses_dom: np.ndarray  # [T, D] float32 — domains of the term's key
    pa_uses_dom: np.ndarray  # [Ta, D] float32 — positive-affinity term keys
    ppa_uses_dom: np.ndarray  # [Tp, D] float32 — preferred-term keys
    sp_uses_dom: np.ndarray  # [S, D] float32
    sp_skew: np.ndarray  # [S] float32
    sps_uses_dom: np.ndarray  # [Ss, D] float32 — soft-spread constraint keys
    # Spread-domain selection [D, Ds] one-hot: the Ds ≤ D coarse domains any
    # HARD spread constraint references.  The filter's [·,S,D] cell passes
    # project through it so their domain axis carries only spread-relevant
    # columns (a zone-keyed cluster runs them at Ds=8 instead of the full
    # padded vocabulary) — dropped columns have sp_uses_dom ≡ 0, so every
    # product/min they fed was identically zero/INF and admissions are
    # bitwise unchanged.
    sp_dom_sel: np.ndarray
    # Initial state (from placed pods)
    aa_dom_m: np.ndarray  # [T, D] 0/1 — domain holds a pod matched by term
    aa_dom_c: np.ndarray  # [T, D] 0/1 — domain holds a carrier of term
    aa_node_m: np.ndarray  # [T, N] 0/1 — fine-granularity (singleton) twin
    aa_node_c: np.ndarray  # [T, N] 0/1
    pa_dom_m: np.ndarray  # [Ta, D] 0/1 — domain holds a pod matched by PA term
    pa_node_m: np.ndarray  # [Ta, N] 0/1 — fine-granularity twin
    ppa_dom_cnt: np.ndarray  # [Tp, D] float32 — preferred-term match counts
    ppa_node_cnt: np.ndarray  # [Tp, N] float32 — fine-granularity twin
    sp_counts: np.ndarray  # [S, D] float32 — matching placed pods per domain
    sps_counts: np.ndarray  # [Ss, D] float32 — soft-spread matching counts

    n_terms: int
    n_pa_terms: int
    n_ppa_terms: int
    n_spread: int
    n_spread_soft: int
    exclusive: ExclusiveFamilies | None = None

    @property
    def n_families(self) -> int:
        return 0 if self.exclusive is None else self.exclusive.n_families

    @property
    def padded_pods(self) -> int:
        return self.pod_aa_carries.shape[0]

    def pod_arrays(self) -> dict:
        ex = {} if self.exclusive is None else self.exclusive.pod
        return {
            **ex,
            "pod_aa_carries": self.pod_aa_carries,
            "pod_aa_matched": self.pod_aa_matched,
            "pod_pa_declares": self.pod_pa_declares,
            "pod_pa_matched": self.pod_pa_matched,
            "pod_sp_declares": self.pod_sp_declares,
            "pod_sp_matched": self.pod_sp_matched,
            "pod_sps_declares": self.pod_sps_declares,
            "pod_sps_matched": self.pod_sps_matched,
            "pod_ppa_w": self.pod_ppa_w,
            "pod_ppa_matched": self.pod_ppa_matched,
        }

    def meta_arrays(self) -> dict:
        ex = {} if self.exclusive is None else self.exclusive.meta
        return {
            **ex,
            "node_dom_c": self.node_dom_c,
            "term_uses_dom": self.term_uses_dom,
            "pa_uses_dom": self.pa_uses_dom,
            "ppa_uses_dom": self.ppa_uses_dom,
            "sp_uses_dom": self.sp_uses_dom,
            "sp_skew": self.sp_skew,
            "sps_uses_dom": self.sps_uses_dom,
            "sp_dom_sel": self.sp_dom_sel,
        }

    def state_arrays(self) -> dict:
        ex = {} if self.exclusive is None else self.exclusive.state
        return {
            **ex,
            "aa_dom_m": self.aa_dom_m,
            "aa_dom_c": self.aa_dom_c,
            "aa_node_m": self.aa_node_m,
            "aa_node_c": self.aa_node_c,
            "pa_dom_m": self.pa_dom_m,
            "pa_node_m": self.pa_node_m,
            "ppa_dom_cnt": self.ppa_dom_cnt,
            "ppa_node_cnt": self.ppa_node_cnt,
            "sp_counts": self.sp_counts,
            "sps_counts": self.sps_counts,
        }


def pack_constraints(
    snapshot,
    pending: list[Pod],
    padded_pods: int,
    node_names: tuple[str, ...],
    padded_nodes: int,
    max_aa_terms: int = MAX_AA_TERMS,
    max_spread: int = MAX_SPREAD,
    max_coarse_domains: int = MAX_COARSE_DOMAINS,
    label_block: int = 8,
    match_memo: dict | None = None,
    widths: dict | None = None,
    exclusive: bool = True,
) -> ConstraintSet | None:
    """Build constraint tensors for one cycle; None if nothing constrained.

    Raises :class:`UntensorizableConstraints` when the structure exceeds the
    budgets (the controller's cue to run the host sequential phase instead).

    ``match_memo`` (same contract as ops/pack.py ``res_memo``: object-
    identity keyed, ``id(pod) -> (pod, matched-id tuples)``, caller-owned
    and caller-pruned) memoizes the five selector-match queries per pod —
    the dominant host cost of a constrained cycle (the matched-bitmap and
    placed-state loops are O(pods × terms) term_matches calls without it;
    PERF.md "known remaining headroom").  The memo is only valid for one
    term-vocabulary signature: it self-clears whenever the vocab changes
    (a new app's term appearing is a full-rematch event, steady-state
    cycles hit ~100%).  The API layer replaces pod objects on every
    modification, so identity hits are exactly the unchanged pods.

    ``widths`` (caller-owned, axis name -> padded width) makes each term and
    domain axis pad to the widest it has been: a cycle whose distinct-term
    count dips below a ``label_block`` multiple then reuses the compiled
    programs instead of compiling new ones (a 29 s solve on a v5e at the
    5,000-node envelope).  The extra columns are zero, as padding is.

    ``exclusive`` folds JobSet's exclusive-placement terms into families
    (:class:`ExclusiveFamilies`, under a ``exclusive`` span) for backends
    whose round engine runs them; without it those terms are per-term rows."""
    nodes = list(snapshot.nodes)
    assert tuple(n.name for n in nodes) == tuple(node_names)

    def _declared(pod):
        """The pod's declared canonical keys, memoized by object identity:
        (aa [(key, term)], pa [(key, term)], ppa [(key, term, signed_w)],
        sp [(key, c)], sps [(key, c)]).  Valid independent of the term
        vocabulary (derived from the pod object alone), so cached under a
        ("dk", id) key that survives vocab changes only incidentally — a
        sig-triggered clear recomputes it for the price of one pass."""
        mk = (_MEMO_DK, id(pod))
        if match_memo is not None:
            hit = match_memo.get(mk)
            if hit is not None and hit[0] is pod:
                return hit[1]
        ns, spec = pod.metadata.namespace, pod.spec
        aa = [(_aa_key(ns, t), t) for t in (spec.anti_affinity or ())] if spec is not None else []
        pa = [(_aa_key(ns, t), t) for t in (spec.pod_affinity or ())] if spec is not None else []
        ppa = []
        sp: list = []
        sps: list = []
        if spec is not None:
            for w in spec.preferred_pod_affinity or ():
                ppa.append((_aa_key(ns, w.term), w.term, float(w.weight)))
            for w in spec.preferred_pod_anti_affinity or ():
                ppa.append((_aa_key(ns, w.term), w.term, -float(w.weight)))
            for c in spec.topology_spread or ():
                (sp if c.is_hard else sps).append((_sp_key(ns, c), c))
        data = (aa, pa, ppa, sp, sps)
        # Unconstrained pods: recomputing the five empty lists is cheaper
        # than a memo entry per pod (the memo would double in size).
        if match_memo is not None and (aa or pa or ppa or sp or sps):
            match_memo[mk] = (pod, data)
        return data

    # --- vocabularies -----------------------------------------------------
    aa_vocab: dict[tuple, tuple] = {}  # key -> (ns, term)
    pa_vocab: dict[tuple, tuple] = {}
    ppa_vocab: dict[tuple, tuple] = {}  # preferred (soft, signed) — scoring only
    sp_vocab: dict[tuple, tuple] = {}  # hard (DoNotSchedule) — blocking
    sps_vocab: dict[tuple, tuple] = {}  # soft (ScheduleAnyway) — scoring only
    for p in pending:
        ns = p.metadata.namespace
        aa, pa, ppa, sp, sps = _declared(p)
        for key, t in aa:
            aa_vocab.setdefault(key, (ns, t))
        # Positive affinity: only PENDING pods' terms constrain anyone (no
        # symmetric direction — a placed pod's affinity is already satisfied).
        for key, t in pa:
            pa_vocab.setdefault(key, (ns, t))
        for key, t, _w in ppa:
            ppa_vocab.setdefault(key, (ns, t))
        for key, c in sp:
            sp_vocab.setdefault(key, (ns, c))
        for key, c in sps:
            sps_vocab.setdefault(key, (ns, c))
    # One _declared pass per placed carrier: the (key, term) pairs feed both
    # the vocab walk here and the carrier-mark loop at the bottom.
    placed_carrier_keys = [(q, qn, _declared(q)[0]) for q, qn in snapshot.placed_pods_with_terms()]
    for q, _qn, aa_d in placed_carrier_keys:
        ns = q.metadata.namespace
        for key, t in aa_d:
            aa_vocab.setdefault(key, (ns, t))

    def pad(axis: str, n: int, block: int = label_block) -> int:
        w = round_up(max(n, 1), block)
        if widths is not None:
            w = widths[axis] = max(w, widths.get(axis, 0))
        return w

    families = None
    if exclusive:
        from ..utils.tracing import span

        with span("exclusive"):
            families = _pack_exclusive(
                snapshot, pending, placed_carrier_keys, aa_vocab, pa_vocab, _declared, padded_pods, padded_nodes, pad
            )
        if families is not None:
            aa_vocab = {k: v for k, v in aa_vocab.items() if k not in families.folded_aa}
            pa_vocab = {k: v for k, v in pa_vocab.items() if k not in families.folded_pa}
            # A JobSet wave's pending count moves with the slice shapes its
            # jobs draw: the pod axis rounds up to a power of two and keeps
            # the widest it has had, so waves of other sizes reuse the
            # compiled programs.  The caller pads its own pod arrays to
            # match (ops/pack.pad_pods).
            rows = pad("p", 1 << (padded_pods - 1).bit_length(), 1)
            if rows > padded_pods:
                grown = {k: np.pad(v, ((0, rows - padded_pods), (0, 0)), constant_values=EX_NONE) for k, v in families.pod.items()}
                families = replace(families, pod=grown)
                padded_pods = rows

    if not aa_vocab and not pa_vocab and not ppa_vocab and not sp_vocab and not sps_vocab and families is None:
        return None
    if len(aa_vocab) > max_aa_terms:
        raise UntensorizableConstraints(f"{len(aa_vocab)} anti-affinity terms > budget {max_aa_terms}")
    if len(pa_vocab) > max_aa_terms:
        raise UntensorizableConstraints(f"{len(pa_vocab)} pod-affinity terms > budget {max_aa_terms}")
    if len(ppa_vocab) > max_aa_terms:
        raise UntensorizableConstraints(f"{len(ppa_vocab)} preferred pod-affinity terms > budget {max_aa_terms}")
    if len(sp_vocab) > max_spread:
        raise UntensorizableConstraints(f"{len(sp_vocab)} spread constraints > budget {max_spread}")
    if len(sps_vocab) > max_spread:
        raise UntensorizableConstraints(f"{len(sps_vocab)} soft spread constraints > budget {max_spread}")

    # --- topology keys → coarse domains or fine (per-node) ----------------
    keys = (
        {k for (_ns, k, _sel) in aa_vocab}
        | {k for (_ns, k, _sel) in pa_vocab}
        | {k for (_ns, k, _sel) in ppa_vocab}
        | {k for (_ns, k, _sk, _sel) in sp_vocab}
        | {k for (_ns, k, _sk, _sel) in sps_vocab}
    )
    spread_keys = {k for (_ns, k, _sk, _sel) in sp_vocab} | {k for (_ns, k, _sk, _sel) in sps_vocab}
    key_values: dict[str, dict[str, list[int]]] = {k: {} for k in keys}
    for i, n in enumerate(nodes):
        labels = n.metadata.labels or {}
        for k in keys:
            v = labels.get(k)
            if v is not None:
                key_values[k].setdefault(v, []).append(i)

    dom_vocab: dict[tuple[str, str], int] = {}  # (key, value) -> column
    fine_keys: set[str] = set()
    budget = max_coarse_domains
    for k in sorted(keys):
        vals = key_values[k]
        if len(vals) <= budget - len(dom_vocab):
            for v in sorted(vals):
                dom_vocab[(k, v)] = len(dom_vocab)
        elif all(len(nids) == 1 for nids in vals.values()):
            # Hostname-like: unique value per node ⇒ domain ≡ node, exact at
            # fine granularity with zero coarse columns.
            fine_keys.add(k)
            if k in spread_keys:
                raise UntensorizableConstraints(f"spread key {k!r} is per-node-granular ({len(vals)} values)")
        else:
            raise UntensorizableConstraints(f"topology key {k!r} has {len(vals)} shared-value domains > budget")

    d_pad = pad("d", len(dom_vocab))
    t_pad = pad("t", len(aa_vocab))
    ta_pad = pad("ta", len(pa_vocab))
    tp_pad = pad("tp", len(ppa_vocab))
    s_pad = pad("s", len(sp_vocab))
    ss_pad = pad("ss", len(sps_vocab))
    n_pad = padded_nodes

    node_dom_c = np.zeros((n_pad, d_pad), dtype=np.float32)
    for (k, v), j in dom_vocab.items():
        for i in key_values[k][v]:
            node_dom_c[i, j] = 1.0

    aa_terms = list(aa_vocab.items())  # [(key, (ns, term))]
    pa_terms = list(pa_vocab.items())
    ppa_terms = list(ppa_vocab.items())
    sp_terms = list(sp_vocab.items())
    sps_terms = list(sps_vocab.items())

    term_uses_dom = np.zeros((t_pad, d_pad), dtype=np.float32)
    for ti, (key, (_ns, term)) in enumerate(aa_terms):
        if term.topology_key not in fine_keys:
            for v in key_values.get(term.topology_key, ()):  # noqa: B007
                term_uses_dom[ti, dom_vocab[(term.topology_key, v)]] = 1.0
    pa_uses_dom = np.zeros((ta_pad, d_pad), dtype=np.float32)
    for ti, (key, (_ns, term)) in enumerate(pa_terms):
        if term.topology_key not in fine_keys:
            for v in key_values.get(term.topology_key, ()):  # noqa: B007
                pa_uses_dom[ti, dom_vocab[(term.topology_key, v)]] = 1.0
    ppa_uses_dom = np.zeros((tp_pad, d_pad), dtype=np.float32)
    for ti, (key, (_ns, term)) in enumerate(ppa_terms):
        if term.topology_key not in fine_keys:
            for v in key_values.get(term.topology_key, ()):  # noqa: B007
                ppa_uses_dom[ti, dom_vocab[(term.topology_key, v)]] = 1.0
    sp_uses_dom = np.zeros((s_pad, d_pad), dtype=np.float32)
    sp_skew = np.zeros((s_pad,), dtype=np.float32)
    for si, (key, (_ns, c)) in enumerate(sp_terms):
        sp_skew[si] = float(c.max_skew)
        for v in key_values.get(c.topology_key, ()):
            sp_uses_dom[si, dom_vocab[(c.topology_key, v)]] = 1.0
    sps_uses_dom = np.zeros((ss_pad, d_pad), dtype=np.float32)
    for si, (key, (_ns, c)) in enumerate(sps_terms):
        for v in key_values.get(c.topology_key, ()):
            sps_uses_dom[si, dom_vocab[(c.topology_key, v)]] = 1.0
    # Spread-domain selection (see the ConstraintSet field comment): one-hot
    # columns for the domains any hard spread constraint references, padded
    # to the label block so the filter's cell passes stay tile-aligned.
    sp_cols = np.flatnonzero((sp_uses_dom > 0).any(axis=0))
    ds_pad = pad("ds", len(sp_cols))
    sp_dom_sel = np.zeros((d_pad, ds_pad), dtype=np.float32)
    sp_dom_sel[sp_cols, np.arange(len(sp_cols))] = 1.0

    # --- pod-side bitmaps -------------------------------------------------
    pod_aa_carries = np.zeros((padded_pods, t_pad), dtype=np.float32)
    pod_aa_matched = np.zeros((padded_pods, t_pad), dtype=np.float32)
    pod_pa_declares = np.zeros((padded_pods, ta_pad), dtype=np.float32)
    pod_pa_matched = np.zeros((padded_pods, ta_pad), dtype=np.float32)
    pod_sp_declares = np.zeros((padded_pods, s_pad), dtype=np.float32)
    pod_sp_matched = np.zeros((padded_pods, s_pad), dtype=np.float32)
    pod_sps_declares = np.zeros((padded_pods, ss_pad), dtype=np.float32)
    pod_sps_matched = np.zeros((padded_pods, ss_pad), dtype=np.float32)
    pod_ppa_w = np.zeros((padded_pods, tp_pad), dtype=np.float32)
    pod_ppa_matched = np.zeros((padded_pods, tp_pad), dtype=np.float32)
    aa_index = {key: i for i, (key, _) in enumerate(aa_terms)}
    pa_index = {key: i for i, (key, _) in enumerate(pa_terms)}
    ppa_index = {key: i for i, (key, _) in enumerate(ppa_terms)}
    sp_index = {key: i for i, (key, _) in enumerate(sp_terms)}
    sps_index = {key: i for i, (key, _) in enumerate(sps_terms)}
    aa_probe, aa_res = _term_probe_index(aa_terms)
    pa_probe, pa_res = _term_probe_index(pa_terms)
    ppa_probe, ppa_res = _term_probe_index(ppa_terms)
    sp_probe, sp_res = _term_probe_index(sp_terms)
    sps_probe, sps_res = _term_probe_index(sps_terms)

    if match_memo is not None:
        sig = (
            tuple(k for k, _ in aa_terms),
            tuple(k for k, _ in pa_terms),
            tuple(k for k, _ in ppa_terms),
            tuple(k for k, _ in sp_terms),
            tuple(k for k, _ in sps_terms),
        )
        if match_memo.get(_MEMO_SIG) != sig:
            # Matched-id entries are vocab-dependent — drop them; declared-
            # keys entries derive from the pod object alone and survive
            # (_sig_independent owns that distinction).
            keep = {k: v for k, v in match_memo.items() if _sig_independent(k)}
            match_memo.clear()
            match_memo.update(keep)
            match_memo[_MEMO_SIG] = sig

    def _matched_all(pod):
        """(aa, pa, ppa, sp, sps) matched-id lists for one pod, memoized."""
        if match_memo is not None:
            hit = match_memo.get(id(pod))
            if hit is not None and hit[0] is pod:
                return hit[1]
        ns, labels = pod.metadata.namespace, pod.metadata.labels
        ids = (
            _matched_term_ids(aa_terms, aa_probe, aa_res, ns, labels),
            _matched_term_ids(pa_terms, pa_probe, pa_res, ns, labels),
            _matched_term_ids(ppa_terms, ppa_probe, ppa_res, ns, labels),
            _matched_term_ids(sp_terms, sp_probe, sp_res, ns, labels),
            _matched_term_ids(sps_terms, sps_probe, sps_res, ns, labels),
        )
        if match_memo is not None:
            match_memo[id(pod)] = (pod, ids)
        return ids

    for pi, p in enumerate(pending):
        aa_d, pa_d, ppa_d, sp_d, sps_d = _declared(p)
        for key, _t in aa_d:
            if key in aa_index:  # folded keys live in the families
                pod_aa_carries[pi, aa_index[key]] = 1.0
        for key, _t in pa_d:
            if key in pa_index:
                pod_pa_declares[pi, pa_index[key]] = 1.0
        for key, _t, w in ppa_d:
            pod_ppa_w[pi, ppa_index[key]] += w
        for key, _c in sp_d:
            pod_sp_declares[pi, sp_index[key]] = 1.0
        for key, _c in sps_d:
            pod_sps_declares[pi, sps_index[key]] = 1.0
        aa_m, pa_m, ppa_m, sp_m, sps_m = _matched_all(p)
        for ti in aa_m:
            pod_aa_matched[pi, ti] = 1.0
        for ti in pa_m:
            pod_pa_matched[pi, ti] = 1.0
        for ti in ppa_m:
            pod_ppa_matched[pi, ti] = 1.0
        for si in sp_m:
            pod_sp_matched[pi, si] = 1.0
        for si in sps_m:
            pod_sps_matched[pi, si] = 1.0

    # --- initial state from placed pods -----------------------------------
    aa_dom_m = np.zeros((t_pad, d_pad), dtype=np.float32)
    aa_dom_c = np.zeros((t_pad, d_pad), dtype=np.float32)
    aa_node_m = np.zeros((t_pad, n_pad), dtype=np.float32)
    aa_node_c = np.zeros((t_pad, n_pad), dtype=np.float32)
    pa_dom_m = np.zeros((ta_pad, d_pad), dtype=np.float32)
    pa_node_m = np.zeros((ta_pad, n_pad), dtype=np.float32)
    ppa_dom_cnt = np.zeros((tp_pad, d_pad), dtype=np.float32)
    ppa_node_cnt = np.zeros((tp_pad, n_pad), dtype=np.float32)
    sp_counts = np.zeros((s_pad, d_pad), dtype=np.float32)
    sps_counts = np.zeros((ss_pad, d_pad), dtype=np.float32)
    node_index = {n.name: i for i, n in enumerate(nodes)}

    def _mark(arr_dom, arr_node, ti, term, qnode_name):
        ni = node_index[qnode_name]
        k = term.topology_key
        v = (nodes[ni].metadata.labels or {}).get(k)
        if k not in fine_keys and v is not None:
            arr_dom[ti, dom_vocab[(k, v)]] = 1.0
        else:
            arr_node[ti, ni] = 1.0

    def _count(arr_dom, arr_node, ti, term, qnode_name):
        """+= twin of _mark for the count-valued preferred-term state."""
        ni = node_index[qnode_name]
        k = term.topology_key
        v = (nodes[ni].metadata.labels or {}).get(k)
        if k not in fine_keys and v is not None:
            arr_dom[ti, dom_vocab[(k, v)]] += 1.0
        else:
            arr_node[ti, ni] += 1.0

    if aa_terms or pa_terms or ppa_terms or sp_terms or sps_terms:
        want_sp = bool(sp_terms or sps_terms)
        for q, qnode in snapshot.placed_pods():
            aa_m, pa_m, ppa_m, sp_m, sps_m = _matched_all(q)
            for ti in aa_m:
                _mark(aa_dom_m, aa_node_m, ti, aa_terms[ti][1][1], qnode.name)
            for ti in pa_m:
                _mark(pa_dom_m, pa_node_m, ti, pa_terms[ti][1][1], qnode.name)
            for ti in ppa_m:
                _count(ppa_dom_cnt, ppa_node_cnt, ti, ppa_terms[ti][1][1], qnode.name)
            if want_sp and (sp_m or sps_m):
                nlabels = (nodes[node_index[qnode.name]].metadata.labels) or {}
                for si in sp_m:
                    c = sp_terms[si][1][1]
                    v = nlabels.get(c.topology_key)
                    if v is not None:
                        sp_counts[si, dom_vocab[(c.topology_key, v)]] += 1.0
                for si in sps_m:
                    c = sps_terms[si][1][1]
                    v = nlabels.get(c.topology_key)
                    if v is not None:
                        sps_counts[si, dom_vocab[(c.topology_key, v)]] += 1.0
        for _q, qnode, aa_d in placed_carrier_keys:
            for key, t in aa_d:
                if key in aa_index:
                    _mark(aa_dom_c, aa_node_c, aa_index[key], t, qnode.name)

    return ConstraintSet(
        pod_aa_carries=pod_aa_carries,
        pod_aa_matched=pod_aa_matched,
        pod_pa_declares=pod_pa_declares,
        pod_pa_matched=pod_pa_matched,
        pod_sp_declares=pod_sp_declares,
        pod_sp_matched=pod_sp_matched,
        pod_sps_declares=pod_sps_declares,
        pod_sps_matched=pod_sps_matched,
        pod_ppa_w=pod_ppa_w,
        pod_ppa_matched=pod_ppa_matched,
        node_dom_c=node_dom_c,
        term_uses_dom=term_uses_dom,
        pa_uses_dom=pa_uses_dom,
        ppa_uses_dom=ppa_uses_dom,
        sp_uses_dom=sp_uses_dom,
        sp_skew=sp_skew,
        sps_uses_dom=sps_uses_dom,
        sp_dom_sel=sp_dom_sel,
        aa_dom_m=aa_dom_m,
        aa_dom_c=aa_dom_c,
        aa_node_m=aa_node_m,
        aa_node_c=aa_node_c,
        pa_dom_m=pa_dom_m,
        pa_node_m=pa_node_m,
        ppa_dom_cnt=ppa_dom_cnt,
        ppa_node_cnt=ppa_node_cnt,
        sp_counts=sp_counts,
        sps_counts=sps_counts,
        n_terms=len(aa_terms),
        n_pa_terms=len(pa_terms),
        n_ppa_terms=len(ppa_terms),
        n_spread=len(sp_terms),
        n_spread_soft=len(sps_terms),
        exclusive=families,
    )


# ---------------------------------------------------------------------------
# xp-generic round engine (shared by ops/assign.py and backends/native.py)
# ---------------------------------------------------------------------------


# shape: (a: any) -> any
def _clip01(xp, a):
    return xp.minimum(a, 1.0)


# shape: (state: dict, meta: dict, soft_spread: bool, soft_pa: bool, hard_pa: bool) -> dict
def round_blocked_masks(
    xp, state: dict, meta: dict, soft_spread: bool = False, soft_pa: bool = False, hard_pa: bool = True
) -> dict:
    """Per-round [·, N] blocked-node masks from the current domain state.

    aa_m_node[T,N]: node's domain (under term t's key) holds a matched pod —
    blocks *carriers* of t.  aa_c_node[T,N]: holds a carrier — blocks
    *matched* pods.  sp_node[S,N]: placing a matching pod there would exceed
    ``max_skew + min(counts)`` — blocks *declarers* of s.

    sp_penalty_node[Ss,N] (soft/ScheduleAnyway — scoring, never blocking;
    built only with ``soft_spread=True``, a trace-time constant, so clusters
    without ScheduleAnyway constraints skip the matmuls entirely): the count
    of matching placed pods in the node's domain under soft constraint s,
    the tensor twin of core/predicates.make_soft_spread_scorer; score_block
    subtracts ``topology_weight · (pod_sps_declares @ sp_penalty_node)``.
    """
    ndc_t = meta["node_dom_c"].T
    aa_m_node = _clip01(xp, state["aa_dom_m"] @ ndc_t + state["aa_node_m"])
    aa_c_node = _clip01(xp, state["aa_dom_c"] @ ndc_t + state["aa_node_c"])
    # Positive affinity: a declarer is blocked wherever its term has NO match
    # in the node's domain — the inverted twin of aa_m_node — except while
    # the term is globally inactive (no match anywhere) AND the pod matches
    # its own term (the bootstrap waiver; blocked_block applies the pod-side
    # gate from pa_inactive).
    if hard_pa:
        pa_m_node = _clip01(xp, state["pa_dom_m"] @ ndc_t + state["pa_node_m"])
        pa_unmatched_node = 1.0 - pa_m_node
        # Round-carried bootstrap flags (augment_round_state / constraint_commit).
        pa_inactive = state["pa_inactive"]
    uses = meta["sp_uses_dom"]
    counts = state["sp_counts"]
    lo = state["sp_lo"]  # round-carried water line (augment_round_state)
    # Choose-time slack of CASCADE levels: the within-round admission filter
    # (constraint_filter) can raise the water line by up to CASCADE levels,
    # so domains within that reach are offered to declarers — otherwise the
    # whole herd targets only the min-count domains (few nodes), starving
    # the capacity prefix.  The filter remains the exact gate; the mask is
    # only a targeting hint.
    blockcell = uses * (counts >= (meta["sp_skew"] + lo + SPREAD_CASCADE)[:, None])
    sp_node = _clip01(xp, blockcell @ ndc_t)
    # Per-level steering for hard-spread DECLARERS (score side): each node's
    # domain height above the constraint's water line.  score_block charges
    # 2x the tie-break amplitude per level, so a declarer prefers min-count
    # domains outright (a lone straggler goes where admission will accept
    # it) while same-level domains stay jitter-spread — the slack mask above
    # offers the reachable levels, the steering orders them.
    sp_level_node = ((counts - lo[:, None]) * uses) @ ndc_t
    masks = {
        "aa_m_node": aa_m_node,
        "aa_c_node": aa_c_node,
        "sp_node": sp_node,
        "sp_level_node": sp_level_node,
    }
    if hard_pa:
        masks["pa_unmatched_node"] = pa_unmatched_node
        masks["pa_inactive"] = pa_inactive
    if soft_spread:
        masks["sp_penalty_node"] = state["sps_counts"] @ ndc_t
    if soft_pa:
        # Preferred inter-pod terms: per-term match COUNT at each node's
        # domain; score_block adds pod_ppa_w (signed weights) @ this.
        masks["ppa_cnt_node"] = state["ppa_dom_cnt"] @ ndc_t + state["ppa_node_cnt"]
    if "ex_m" in state:
        # Exclusive families: each node reads its domain's summaries.
        node_dom = meta["ex_node_dom"]
        masks["ex_m_node"] = xp.take_along_axis(state["ex_m"], node_dom, axis=1)  # [F, N]
        masks["ex_c_node"] = xp.take_along_axis(state["ex_c"], node_dom, axis=1)
        masks["ex_seen"] = state["ex_seen"]
    return masks


# shape: (table: [F, V] any, ids: [B, F] i32) -> [B, F] any
def _per_family(xp, table, ids):
    """``table[f, ids[b, f]]`` (ids below 0 read column 0; callers mask them)."""
    f, v = table.shape
    return table.reshape(-1)[xp.arange(f, dtype=xp.int32)[None, :] * v + xp.maximum(ids, 0)]


# shape: (blk: dict, masks: dict) -> [B, N] bool
def exclusive_blocked(xp, blk: dict, masks: dict):
    """[B, N] nodes the exclusive families block for one pod block: a
    carrier of v where the domain holds a label other than v, a pod
    labelled x where the domain holds a carrier of another value, and a
    pod-affinity declarer of a where the domain's labels are not a alone —
    unless a is carried nowhere and the pod carries it (the bootstrap)."""
    car = blk["pod_ex_carry"][:, :, None]  # [B, F, 1]
    lab = blk["pod_ex_label"][:, :, None]
    aff = blk["pod_ex_aff"]
    m = masks["ex_m_node"][None, :, :]  # [1, F, N]
    c = masks["ex_c_node"][None, :, :]
    waived = (blk["pod_ex_label"] == aff) & (_per_family(xp, masks["ex_seen"], aff) == 0)  # [B, F]
    hold = (aff >= 0) & ~waived
    b = (car >= 0) & (m != EX_NONE) & (m != car)
    b = b | ((lab >= 0) & (c != EX_NONE) & (c != lab))
    b = b | (hold[:, :, None] & (m != aff[:, :, None]))
    return b.any(axis=1)


# shape: (blk: dict, masks: dict) -> any
def blocked_block(xp, blk: dict, masks: dict):
    """[B, N] constraint-blocked mask for one pod block (four matmuls)."""
    b = blk["pod_aa_carries"] @ masks["aa_m_node"]
    b = b + blk["pod_aa_matched"] @ masks["aa_c_node"]
    b = b + blk["pod_sp_declares"] @ masks["sp_node"]
    # Positive affinity with the bootstrap waiver: a declared term that is
    # globally inactive AND self-matched drops out of the pod's requirement
    # set for this round; every remaining declared term blocks its unmatched
    # nodes (terms AND — any unmet term blocks).  A non-self-matching pod
    # with an inactive term keeps it → unmatched everywhere → unschedulable
    # this round, exactly the scalar checker's "unmatchable" rule.
    if "pa_unmatched_node" in masks:
        gated = blk["pod_pa_declares"] * (1.0 - blk["pod_pa_matched"] * masks["pa_inactive"][None, :])
        b = b + gated @ masks["pa_unmatched_node"]
    if "ex_m_node" in masks:
        return (b > 0) | exclusive_blocked(xp, blk, masks)
    return b > 0


# shape: (size: int, idx: [P] i32, vals: [P] f32) -> [size] f32
def _scatter_min(xp, size: int, idx, vals):
    if xp is np:
        out = np.full((size,), RANK_INF, dtype=np.float32)
        np.minimum.at(out, idx, vals)
        return out
    return xp.full((size,), RANK_INF, dtype=xp.float32).at[idx].min(vals)


# shape: (n_rows: int, idx: [P] i32, vals: [P, C] f32) -> [n_rows, C] f32
def _row_scatter_min(xp, n_rows: int, idx, vals):
    """out[r, c] = min over {p : idx[p] == r} of vals[p, c]  (RANK_INF fill).

    Row-granular scatters (one [C]-wide update per pod) lower to fast
    windowed scatters on TPU, unlike the near-serial scalar form."""
    if xp is np:
        out = np.full((n_rows, vals.shape[1]), RANK_INF, dtype=np.float32)
        np.minimum.at(out, idx, vals)
        return out
    return xp.full((n_rows, vals.shape[1]), RANK_INF, dtype=xp.float32).at[idx].min(vals)


# shape: (state_tn: [T, N] f32, idx: [P] i32, vals: [P, T] f32) -> [T, N] f32
def _row_scatter_max_t(xp, state_tn, idx, vals):
    """[T,N] state with state[c, idx[p]] = max(state, vals[p, c]) folded in —
    the row-scatter twin of the flattened t·n scalar scatter (transposed
    round-trip is two [T,N] relayouts, a rounding error next to the
    near-serial scalar form it replaces)."""
    if xp is np:
        out = state_tn.T.copy()  # always copy — callers may hold the old state
        np.maximum.at(out, idx, vals)
        return out.T
    return state_tn.T.at[idx].max(vals).T


# shape: (state_tn: [T, N] f32, idx: [P] i32, vals: [P, T] f32) -> [T, N] f32
def _row_scatter_add_t(xp, state_tn, idx, vals):
    """+= twin of :func:`_row_scatter_max_t` for count-valued state."""
    if xp is np:
        out = state_tn.T.copy()  # always copy — callers may hold the old state
        np.add.at(out, idx, vals)
        return out.T
    return state_tn.T.at[idx].add(vals).T


# shape: (p: int, cells: int) -> int
def _cell_chunk(p: int, cells: int) -> int:
    """Pod-axis chunk length keeping one [chunk, S, D] tile inside the byte
    budget (0 = no chunking needed — the full tensor fits)."""
    if p * cells * 4 <= DENSE_TENSOR_BYTES:
        return 0
    return max(256, DENSE_TENSOR_BYTES // (cells * 4))


# Static pod-axis tile for the ACTIVE-SET cell passes under jit: the fused
# filter compacts the round's accepted claimants into a workspace prefix and
# the jnp cell scans run ``ceil(A / ACTIVE_CHUNK)`` tiles under a
# dynamic-bound while_loop, so per-round filter cost tracks the accepted
# count the way the size chain tracks actives (the NumPy oracle gathers the
# exact [A] rows instead and needs no tiling).  Chunked and one-shot results
# are bitwise equal (exact small-integer sums — pinned by
# test_cell_rank_scan_chunked_equals_oneshot), so the constant is perf-only:
# any value yields identical placements.
ACTIVE_CHUNK = 256


# shape: (mass: [P, S] f32, nd: [P, D] f32, uses: [S, D] f32, out_fn: fn,
#   n_live: any) -> [P, S] f32
def _cell_rank_scan(xp, mass, nd, uses, out_fn, n_live=None):
    """Shared chunked driver for the spread filter's exclusive-by-rank cell
    passes: feeds ``out_fn(ec3, m3)`` — ``ec3`` the [·,S,D] exclusive
    cumulative cell mass including all lower-rank pods, ``m3`` the same
    rows' own-cell one-hots — per pod-axis chunk and concatenates the [·,S]
    outputs.  Exact small-integer sums, so chunked and one-shot results are
    bitwise equal — cross-backend/stage parity depends on that.

    Without ``n_live``: one-shot when [P,S,D] fits the byte budget, else
    chunks with an [S,D] carry (``lax.scan`` under jit, a plain loop in
    numpy — the budget applies to BOTH backends, round-5 review finding).

    With ``n_live`` (jit active-set path — rows beyond it must carry zero
    mass): a while_loop over ``ceil(n_live / ACTIVE_CHUNK)`` tiles, leaving
    later tiles' outputs at zero — their rows are exactly the non-accepted
    workspace tail the filter masks out anyway, so cost tracks the live
    count without a shape-dependent semantic."""
    p, s = mass.shape
    d = nd.shape[1]

    def step(carry, mch, ndch):
        m3 = ndch[:, None, :] * uses[None, :, :]  # [·,S,D]
        c3 = mch[:, :, None] * m3
        ec3 = carry[None, :, :] + xp.cumsum(c3, axis=0) - c3
        return carry + c3.sum(axis=0), out_fn(ec3, m3)

    if xp is np or n_live is None:
        chunk = _cell_chunk(p, s * d)
        if chunk == 0:
            return step(xp.zeros((s, d), xp.float32), mass, nd)[1]
        pad = (-p) % chunk
        mass_c = xp.pad(mass, ((0, pad), (0, 0))).reshape(-1, chunk, s)
        nd_c = xp.pad(nd, ((0, pad), (0, 0))).reshape(-1, chunk, d)
        if xp is np:
            carry = np.zeros((s, d), np.float32)
            outs = []
            for k in range(mass_c.shape[0]):
                carry, out = step(carry, mass_c[k], nd_c[k])
                outs.append(out)
            return np.concatenate(outs, axis=0)[:p]
        from jax import lax

        _, outs = lax.scan(lambda c, inp: step(c, *inp), xp.zeros((s, d), xp.float32), (mass_c, nd_c))
        return outs.reshape(-1, s)[:p]

    chunk = min(p, ACTIVE_CHUNK)
    if chunk >= p:
        return step(xp.zeros((s, d), xp.float32), mass, nd)[1]
    from jax import lax

    pad = (-p) % chunk
    mass_c = xp.pad(mass, ((0, pad), (0, 0))).reshape(-1, chunk, s)
    nd_c = xp.pad(nd, ((0, pad), (0, 0))).reshape(-1, chunk, d)
    k_live = (n_live.astype(xp.int32) + chunk - 1) // chunk

    def cond(st):
        return st[0] < k_live

    def body(st):
        k, carry, outs = st
        carry, out = step(carry, mass_c[k], nd_c[k])
        return k + 1, carry, outs.at[k].set(out)

    _, _, outs = lax.while_loop(
        cond, body, (xp.int32(0), xp.zeros((s, d), xp.float32), xp.zeros(mass_c.shape, xp.float32))
    )
    return outs.reshape(-1, s)[:p]


# shape: (mass: [P, S] f32, nd: [P, D] f32, uses: [S, D] f32, n_live: any) -> [P, S] f32
def _cell_rank_prefix(xp, mass, nd, uses, n_live=None):
    """[P,S] exclusive-by-rank (array order) mass before each pod in its own
    (s, domain) cell — the quota prefix."""
    return _cell_rank_scan(xp, mass, nd, uses, lambda ec3, m3: (ec3 * m3).sum(axis=2), n_live=n_live)


# shape: (mass: [P, S] f32, nd: [P, D] f32, uses: [S, D] f32, base: [S, D] f32,
#   n_live: any) -> [P, S] f32
def _cell_rank_min_level(xp, mass, nd, uses, base, n_live=None):
    """[P,S] per-pod water line: min over the constraint's used domains of
    ``base`` plus the exclusive-by-rank fill of ``mass`` — the cascade's
    lower bound on the minimum count at each pod's witness-order turn."""

    def out_fn(ec3, m3):
        lvl = xp.where(uses[None, :, :] > 0, base[None, :, :] + ec3, RANK_INF)
        lo = xp.min(lvl, axis=2)
        return xp.where(lo >= RANK_INF, 0.0, lo)

    return _cell_rank_scan(xp, mass, nd, uses, out_fn, n_live=n_live)


# shape: (nd: [A, D] f32, uses_sp: [S, D] f32, sp0: [S, D] f32, sel: [D, C] f32)
#   -> ([A, C] f32, [S, C] f32, [S, C] f32)
def _project_spread_domains(xp, nd, uses_sp, sp0, sel):
    """Project the spread cell operands onto the pack-time spread-domain
    selection (``ConstraintSet.sp_dom_sel``): the [·,S,D] cell passes then
    carry only the C ≤ D domains a hard spread constraint references.
    One-hot selection of exact small-integer columns — bitwise-neutral."""
    return nd @ sel, uses_sp @ sel, sp0 @ sel


# Stateless reusable no-op span context for the fused filter's family
# sub-phases: the jit path (and any caller without a tracer) pays nothing,
# while backends/native.py passes utils.tracing.span so the NumPy oracle's
# attribution profile splits ``choose/filter`` into filter/aa|pa|spread.
_NULL_SPAN_CTX = contextlib.nullcontext()


# shape: (name: str) -> obj
def _null_span(name):
    return _NULL_SPAN_CTX


# shape: (cell: [A, F] i32, size: int, rank: [A, 1] f32, live: [A, F] bool,
#   val: [A, F] i32) -> ([A, F] f32, [A, F] f32, [A, F] f32)
def _first_two(xp, cell, size: int, rank, live, val):
    """Per pod, in its own cell: the least rank of a live pod, that pod's
    value, and the least rank of a live pod whose value differs from it."""
    flat = cell.reshape(-1)
    vf = val.astype(xp.float32)
    min1 = _scatter_min(xp, size, flat, xp.where(live, rank, RANK_INF).reshape(-1))[cell]
    v1 = -_scatter_min(xp, size, flat, xp.where(live & (rank == min1), -vf, RANK_INF).reshape(-1))[cell]
    min2 = _scatter_min(xp, size, flat, xp.where(live & (vf != v1), rank, RANK_INF).reshape(-1))[cell]
    return min1, v1, min2


# shape: (pw: dict, choice: [A] i32, rank: [A] f32, acc: [A] bool, meta: dict,
#   dk: int) -> [A] bool
def _exclusive_conflicts(xp, pw: dict, choice, rank, acc, meta: dict, dk: int):
    """Within-round anti-affinity of the exclusive families: a pod loses to
    an earlier-rank accepted pod in its domain whose term it matches (a
    carrier of another value than its label) or which matches its term (a
    label other than its value) — the per-term rule, pair for pair."""
    node_dom = meta["ex_node_dom"]
    f = node_dom.shape[0]
    cell = xp.arange(f, dtype=xp.int32)[None, :] * dk + node_dom.T[choice]  # [A, F]
    r = rank[:, None]
    lab, car = pw["pod_ex_label"], pw["pod_ex_carry"]
    live = acc[:, None]
    c_min1, c_v1, c_min2 = _first_two(xp, cell, f * dk, r, live & (car >= 0), car)
    l_min1, l_v1, l_min2 = _first_two(xp, cell, f * dk, r, live & (lab >= 0), lab)
    labf, carf = lab.astype(xp.float32), car.astype(xp.float32)
    bad = (lab >= 0) & (((c_min1 < r) & (c_v1 != labf)) | (c_min2 < r))
    bad = bad | ((car >= 0) & (((l_min1 < r) & (l_v1 != carf)) | (l_min2 < r)))
    return bad.any(axis=1)


# shape: (pw: dict, rank: [A] f32, keep: [A] bool, state: dict) -> [A] bool
def _exclusive_bootstrap_conflicts(xp, pw: dict, rank, keep, state: dict):
    """Within-round pod affinity of the exclusive families, the per-term
    bootstrap rule: of the kept pods carrying a value no pod carried at round
    start, only the least-ranked may rely on the waiver."""
    seen = state["ex_seen"]
    f, v = seen.shape
    lab, aff = pw["pod_ex_label"], pw["pod_ex_aff"]
    r = rank[:, None]
    base = xp.arange(f, dtype=xp.int32)[None, :] * v
    first = _scatter_min(xp, f * v, (base + xp.maximum(lab, 0)).reshape(-1),
                         xp.where(keep[:, None] & (lab >= 0), r, RANK_INF).reshape(-1))
    waived = (aff >= 0) & (lab == aff) & (_per_family(xp, seen, aff) == 0)
    return (waived & keep[:, None] & (r > first[base + xp.maximum(aff, 0)])).any(axis=1)


# shape: (state: dict, meta: dict) -> dict
def augment_round_state(xp, state: dict, meta: dict) -> dict:
    """Derive the ROUND-CARRIED conflict-state entries from a cycle-start
    constraint state: ``sp_cell`` ([S,D] per-cell counts masked to used
    domains), ``sp_lo`` ([S] spread water line) and ``pa_inactive`` ([Ta]
    positive-affinity bootstrap flags).  The auction threads them through
    its while-loop carry and :func:`constraint_commit` updates them
    INCREMENTALLY from each round's commits, so neither the choose-mask
    build nor the conflict filter re-derives them from the accumulated
    domain history every round.  The consumers (round_blocked_masks,
    constraint_filter, constraint_commit) read these keys unconditionally,
    so every caller augments its cycle-start ``state_arrays()`` here."""
    uses = meta["sp_uses_dom"]
    sp_cell = state["sp_counts"] * uses
    lo = xp.min(xp.where(uses > 0, sp_cell, RANK_INF), axis=1)
    lo = xp.where(lo >= RANK_INF, 0.0, lo)
    out = {**state, "sp_cell": sp_cell, "sp_lo": lo}
    out["pa_inactive"] = ((state["pa_dom_m"].sum(axis=1) + state["pa_node_m"].sum(axis=1)) == 0).astype(xp.float32)
    return out


# shape: (accepted: [P] bool, choice: [P] i32, ranks: [P] u32, ps: dict,
#   state: dict, meta: dict, hard_pa: bool, spans: fn) -> [P] bool
def constraint_filter(
    xp, accepted, choice, ranks, ps: dict, state: dict, meta: dict, hard_pa: bool = True, spans=None
) -> object:
    """Within-round conflict resolution — returns the surviving subset of
    ``accepted`` (see module docstring for the rank rules).

    ACTIVE-SET COMPACTION (round 7): only the round's accepted claimants can
    conflict — every mass the filter consumes is ``accepted``-gated and a
    non-accepted row's verdict is discarded — so the filter gathers accepted
    rows into a compact workspace before any cell machinery runs, and
    scatters survivors back at the end.  NumPy gathers the exact [A] rows;
    under jit the workspace is a stable accepted-first permutation of the
    (static-size) pod arrays whose cell scans stop after
    ``ceil(A / ACTIVE_CHUNK)`` tiles, so both backends' per-round filter
    cost tracks the accepted count instead of the padded pod axis.  Sums
    and mins over the dropped all-zero rows are exact no-ops, so compacted
    and full-width filtering are bitwise identical.

    ``spans`` (optional ``name -> context-manager``, e.g.
    utils.tracing.span) opens the ``aa`` / ``pa`` / ``spread`` sub-spans
    around the three constraint families so an attribution profile names
    WHICH family dominates; the default is a shared no-op context.
    """
    sp_span = spans if spans is not None else _null_span
    p = accepted.shape[0]
    ndc = meta["node_dom_c"]
    d = ndc.shape[1]
    n = ndc.shape[0]

    # ---- active-set workspace --------------------------------------------
    ws_keys = ["pod_aa_carries", "pod_aa_matched", "pod_sp_declares", "pod_sp_matched"]
    if hard_pa:
        ws_keys += ["pod_pa_declares", "pod_pa_matched"]
    exclusive = "ex_m" in state
    if exclusive:
        ws_keys += ["pod_ex_label", "pod_ex_carry", "pod_ex_aff"]
    if xp is np:
        gperm = np.flatnonzero(accepted)
        if gperm.size == 0:
            return accepted.copy()
        n_live = None  # exact [A] rows — the scans need no tile bound
        acc_ws = np.ones((gperm.size,), dtype=bool)
    else:
        # Stable accepted-first partition (the _compact cumsum trick): the
        # gather permutation keeps relative order, so workspace array order
        # is still rank order and every prefix/min below is unchanged.
        acc_i = accepted.astype(xp.int32)
        n_acc = acc_i.sum()
        pos_acc = xp.cumsum(acc_i) - acc_i
        pos_rej = xp.cumsum(1 - acc_i) - (1 - acc_i)
        dest = xp.where(accepted, pos_acc, n_acc + pos_rej)
        gperm = xp.zeros((p,), xp.int32).at[dest].set(xp.arange(p, dtype=xp.int32))
        n_live = n_acc
        acc_ws = accepted[gperm]
    choice_ws = choice[gperm]
    rank_f = ranks[gperm].astype(xp.float32)
    pw = {k: ps[k][gperm] for k in ws_keys}
    nd = ndc[choice_ws]  # [A, D] one-hot domains of each accepted pod's node
    accf = acc_ws.astype(xp.float32)

    uses = meta["term_uses_dom"]  # [T, D]
    uses_sp = meta["sp_uses_dom"]  # [S, D]
    t = uses.shape[0]
    sp0 = state["sp_cell"]
    # ONE fused gather matmul for every per-pod cell lookup: AA coarse-key
    # flags + coarse cell ids, spread key flags + own-cell round-start
    # counts ride a single banded [A,D] @ [D, 2T+2S] dispatch instead of
    # four.  Each output column is an independent exact small-integer dot,
    # so banding is bitwise-neutral.
    dom_ids = xp.arange(d, dtype=xp.float32)
    band = xp.concatenate([uses, uses * dom_ids[None, :], uses_sp, sp0], axis=0)  # [2T+2S, D]
    g_all = nd @ band.T  # [A, 2T+2S]
    has_c = g_all[:, :t]  # [A, T] 1 if the chosen node has the term's coarse key
    cc = g_all[:, t : 2 * t]  # [A, T] coarse cell id (sum of ≤1 one-hot)
    s_sp = uses_sp.shape[0]
    in_cell = g_all[:, 2 * t : 2 * t + s_sp]  # [A, S] 1 iff node carries the key
    c_at = g_all[:, 2 * t + s_sp :]  # [A, S] own-cell round-start count

    # ---- anti-affinity ----------------------------------------------------
    # Rule: in each (term, cell) — cell = coarse domain when the chosen node
    # carries the term's key, else the node itself — a matched pod survives
    # only if no earlier-rank accepted carrier shares the cell, and vice
    # versa.  "Earlier rank" ≡ earlier array index (pods are compacted in
    # priority-rank order), so existence-of-a-predecessor is ONE fused
    # min-rank segment scatter over the unified (term, cell) id space —
    # coarse domains and fine (per-node) cells share the space, and the
    # carrier/matched tables ride a single offset dispatch — with a dense
    # [A,T,D] exclusive-cumsum path below the DENSE_CELLS budget; identical
    # outcomes by construction (pinned at the threshold by
    # test_dense_boundary_parity).
    with sp_span("aa"):
        carr = pw["pod_aa_carries"] * accf[:, None]
        matc = pw["pod_aa_matched"] * accf[:, None]
        if _dense_ok(nd.shape[0], t * d):
            m3 = nd[:, None, :] * uses[None, :, :]  # [A,T,D] one-hot coarse cell under t

            def _earlier_in_cell(v):  # [A,T] 0/1 → [A,T] "an earlier v-pod shares my coarse cell"
                v3 = v[:, :, None] * m3
                ec = xp.cumsum(v3, axis=0) - v3  # exclusive
                return (ec * m3).sum(axis=2) > 0

            fine = has_c == 0
            carr_c, matc_c = carr * has_c, matc * has_c
            # Fine cells: min accepted rank per (node, term) via one row scatter.
            min_c_fine = _row_scatter_min(xp, n, choice_ws, xp.where((carr * fine) > 0, rank_f[:, None], RANK_INF))
            min_m_fine = _row_scatter_min(xp, n, choice_ws, xp.where((matc * fine) > 0, rank_f[:, None], RANK_INF))
            earlier_c = _earlier_in_cell(carr_c) | (fine & (rank_f[:, None] > min_c_fine[choice_ws]))
            earlier_m = _earlier_in_cell(matc_c) | (fine & (rank_f[:, None] > min_m_fine[choice_ws]))
            bad_aa = ((matc > 0) & earlier_c) | ((carr > 0) & earlier_m)
        else:
            cells = d + n
            cell = xp.where(has_c > 0, cc, d + choice_ws[:, None].astype(xp.float32))
            g = (xp.arange(t, dtype=xp.float32)[None, :] * cells + cell).astype(xp.int32)  # [A, T]
            # Fused dispatch: carrier mins in [0, t·cells), matched mins
            # offset by t·cells — ONE segment scatter-min, two gathers.
            gf2 = xp.concatenate([g.reshape(-1), (g + t * cells).reshape(-1)])
            vals2 = xp.concatenate(
                [
                    xp.where(carr > 0, rank_f[:, None], RANK_INF).reshape(-1),
                    xp.where(matc > 0, rank_f[:, None], RANK_INF).reshape(-1),
                ]
            )
            mins = _scatter_min(xp, 2 * t * cells, gf2, vals2)
            min_c_at = mins[g]  # [A, T]
            min_m_at = mins[g + t * cells]
            bad_aa = ((matc > 0) & (rank_f[:, None] > min_c_at)) | ((carr > 0) & (rank_f[:, None] > min_m_at))
        keep = acc_ws & ~bad_aa.any(axis=1)
        if exclusive:
            keep = keep & ~_exclusive_conflicts(xp, pw, choice_ws, rank_f, acc_ws, meta, state["ex_m"].shape[1])

    # ---- positive affinity bootstrap (within-round) -----------------------
    # A term inactive at round start was waived for self-matching declarers
    # (blocked_block let them choose freely).  Sequentially, only the FIRST
    # accepted pod matching the term may rely on the waiver: any earlier-rank
    # accepted match re-activates the term before a later pod's turn in the
    # witness order, and the later pod's free placement would then violate
    # it.  Keep the min-rank accepted match; defer other waived declarers
    # one round (the term is then active and the round-start mask routes
    # them to its domain).  Over-inclusive min (it counts matches a later
    # filter may drop) only defers more — never admits a violation.  This
    # family cannot ride the AA segment scatter: its min is over the
    # POST-AA keep set, a sequential dependency.
    if hard_pa or exclusive:
        with sp_span("pa"):
            bad_pa = xp.zeros_like(keep)
            if hard_pa:
                pa_inactive_f = state["pa_inactive"]
                keep_pa_f = keep.astype(xp.float32)
                pa_m_acc = pw["pod_pa_matched"] * keep_pa_f[:, None]  # [A, Ta]
                min_match_rank = xp.min(xp.where(pa_m_acc > 0, rank_f[:, None], RANK_INF), axis=0)  # [Ta]
                waived = pw["pod_pa_declares"] * pw["pod_pa_matched"] * pa_inactive_f[None, :]  # [A, Ta]
                bad_pa = ((waived > 0) & keep[:, None] & (rank_f[:, None] > min_match_rank[None, :])).any(axis=1)
            if exclusive:
                # Family twin, judged on the same post-anti-affinity keep set.
                bad_pa = bad_pa | _exclusive_bootstrap_conflicts(xp, pw, rank_f, keep, state)
            keep = keep & ~bad_pa

    # ---- topology spread (rank-prefix admission + in-round cascade) -------
    # The scalar rule (core/predicates.make_spread_checker): placing a
    # DECLARER on a keyed node requires count(domain) + 1 − min(counts) ≤
    # max_skew at its turn.  The witness order for this round's kept set is
    # simply ASCENDING RANK, so for pod p the domain count at its turn is
    # bounded by the round-start count plus the matched CANDIDATE mass of
    # lower rank in its cell (kept ⊆ candidates), and the min is bounded
    # below by the round-start min plus lower-rank COMMITTED fills.
    # Admission is therefore
    #     c_at(p) + pre_all(p) + 1 ≤ skew + lo_p
    # with pre_all the exclusive-by-rank candidate-mass prefix in p's cell
    # and lo_p the per-pod water line.  History: round 4 charged all
    # capacity-accepted matching-only mass to a STATIC denominator instead
    # of the rank prefix; two same-selector constraints (e.g. the mixed
    # workload's two skew levels per app) then mutually inflated each
    # other's minimum cells every round, pinning every quota at zero and
    # serializing the tail to ~1 accept/constraint/round (the measured
    # 64-round cap: a diagnostic run printed "quota sum=0, open cells=0"
    # for all eight fixpoint iterations).  The rank prefix breaks
    # the deadlock structurally: the lowest-rank candidate of an open cell
    # always admits.
    with sp_span("spread"):
        skew = meta["sp_skew"]  # [S]
        declares, matched = pw["pod_sp_declares"], pw["pod_sp_matched"]
        keep_f = keep.astype(xp.float32)
        # Candidate matched mass: every post-AA/PA survivor whose chosen node
        # carries the key and whose labels match the selector — non-declarers
        # (they commit unconditionally; nothing after this filter drops them)
        # and declarers (they commit iff admitted below) ride ONE prefix.
        cand_m = keep_f[:, None] * matched * in_cell  # [A, S]
        decl_cell = keep_f[:, None] * declares * in_cell  # declarers on keyed nodes

        lo0 = state["sp_lo"]  # [S] round-start water line

        # Domain-axis projection: the cell passes only ever touch domains a
        # spread constraint references, so they run on the [D, Ds] pack-time
        # selection (sp_dom_sel) — dropped columns were identically zero in
        # every product and RANK_INF in every min, so admissions are bitwise
        # unchanged while a zone-keyed cluster's passes shrink ~D/Ds-fold.
        nd_sp, uses_spc, sp0c = _project_spread_domains(xp, nd, uses_sp, sp0, meta["sp_dom_sel"])
        # ONE spread formulation for every size: the [A,S,Ds] cell passes
        # run one-shot when they fit the byte budget and pod-axis CHUNKED
        # otherwise (exact small-integer sums — bitwise identical either
        # way).  No pod-count- or backend-dependent SEMANTIC: the jit size
        # chain runs this filter at several static pod sizes and the native
        # backend at one, so admission must never depend on the stage shape.
        pre_all = _cell_rank_prefix(xp, cand_m, nd_sp, uses_spc, n_live=n_live)  # [A,S] mass before p in own cell

        bound = c_at + pre_all + 1.0  # [A, S] count-after-placement upper bound
        lo_p = xp.zeros_like(c_at) + lo0[None, :]
        admit = bound <= (skew[None, :] + lo_p)
        # In-round water-line cascade.  Each sweep recomputes, per pod, the
        # min over the constraint's domains of round-start counts plus the
        # COMMITTED fills of lower rank — commits from the previous sweep's
        # admissions, which only grow (admit is OR-accumulated), so every
        # sweep is sound: a kept pod's witness-order turn really does see
        # those lower-rank commits placed.  One sweep lifts the line one
        # level; SPREAD_CASCADE sweeps admit a whole multi-level wave per
        # round instead of one level per ROUND.
        for _ in range(SPREAD_CASCADE):
            rejected = ((decl_cell > 0) & ~admit).any(axis=1)
            committed_pod = keep_f * (1.0 - rejected.astype(xp.float32))  # [A]
            lo_p = _cell_rank_min_level(xp, cand_m * committed_pod[:, None], nd_sp, uses_spc, sp0c, n_live=n_live)
            admit = admit | (bound <= (skew[None, :] + lo_p))
        bad_sp = (decl_cell > 0) & ~admit
        keep = keep & ~bad_sp.any(axis=1)

    # ---- scatter survivors back ------------------------------------------
    if xp is np:
        out = np.zeros_like(accepted)
        out[gperm] = keep
        return out
    full = xp.zeros_like(accepted).at[gperm].set(keep)
    return accepted & full


# shape: (summary: [F, D] i32, lo: [F, D] f32, hi: [F, D] f32) -> [F, D] i32
def _ex_merge(xp, summary, lo, hi):
    """Fold a round's landing values (least ``lo``, greatest ``hi`` per
    domain; ``lo`` RANK_INF where none landed) into a family summary."""
    landed = lo < RANK_INF
    lo_i = xp.where(landed, lo, 0.0).astype(xp.int32)
    hi_i = xp.where(landed, hi, 0.0).astype(xp.int32)
    one = (lo_i == hi_i) & ((summary == EX_NONE) | (summary == lo_i))
    return xp.where(landed, xp.where(one, lo_i, EX_MANY), summary)


# shape: (accepted: [P] bool, choice: [P] i32, ps: dict, state: dict, meta: dict) -> dict
def _exclusive_commit(xp, accepted, choice, ps: dict, state: dict, meta: dict) -> dict:
    """Fold the round's accepted pods into the families' summaries."""
    f, dk = state["ex_m"].shape
    v = state["ex_seen"].shape[1]
    cell = (xp.arange(f, dtype=xp.int32)[None, :] * dk + meta["ex_node_dom"].T[choice]).reshape(-1)  # [P·F]
    out = {}
    for name, key in (("ex_m", "pod_ex_label"), ("ex_c", "pod_ex_carry")):
        val = ps[key]
        live = (accepted[:, None] & (val >= 0)).reshape(-1)
        vf = val.astype(xp.float32).reshape(-1)
        lo = _scatter_min(xp, f * dk, cell, xp.where(live, vf, RANK_INF)).reshape(f, dk)
        hi = -_scatter_min(xp, f * dk, cell, xp.where(live, -vf, RANK_INF)).reshape(f, dk)
        out[name] = _ex_merge(xp, state[name], lo, hi)
    lab = ps["pod_ex_label"]
    vcell = (xp.arange(f, dtype=xp.int32)[None, :] * v + xp.maximum(lab, 0)).reshape(-1)
    landed = _scatter_min(xp, f * v, vcell, xp.where((accepted[:, None] & (lab >= 0)).reshape(-1), 0.0, RANK_INF))
    out["ex_seen"] = xp.maximum(state["ex_seen"], (landed.reshape(f, v) == 0.0).astype(xp.float32))
    return out


# shape: (ps: dict, accepted: [P] bool, meta: dict, hard_pa: bool) -> [P] bool
def pa_progress(xp, ps: dict, accepted, meta: dict, hard_pa: bool):
    """[P] pod-affinity declarers worth keeping active though blocked
    everywhere this round: some pending pod-affinity term gained a match
    (per-term rows or family values), which can open nodes for them."""
    declares = xp.zeros(accepted.shape, bool)
    progressed = xp.bool_(False)
    if hard_pa:
        new_match = (ps["pod_pa_matched"] * accepted[:, None].astype(xp.float32)).sum(axis=0) > 0  # [Ta]
        declares = declares | (ps["pod_pa_declares"].sum(axis=1) > 0)
        progressed = progressed | new_match.any()
    if "pod_ex_aff" in ps:
        lab = ps["pod_ex_label"]
        wanted = _per_family(xp, meta["ex_aff_declared"], lab) > 0
        declares = declares | (ps["pod_ex_aff"] >= 0).any(axis=1)
        progressed = progressed | (accepted[:, None] & (lab >= 0) & wanted).any()
    return declares & progressed


# shape: (accepted: [P] bool, choice: [P] i32, ps: dict, state: dict,
#   meta: dict, soft_spread: bool, soft_pa: bool, hard_pa: bool) -> dict
def constraint_commit(
    xp,
    accepted,
    choice,
    ps: dict,
    state: dict,
    meta: dict,
    soft_spread: bool = False,
    soft_pa: bool = False,
    hard_pa: bool = True,
) -> dict:
    """Fold the round's final accepted placements into the domain state."""
    ndc = meta["node_dom_c"]
    nd = ndc[choice]
    accf = accepted.astype(xp.float32)
    matc = ps["pod_aa_matched"] * accf[:, None]  # [P, T]
    carr = ps["pod_aa_carries"] * accf[:, None]
    uses = meta["term_uses_dom"]
    aa_dom_m = _clip01(xp, state["aa_dom_m"] + (matc.T @ nd) * uses)
    aa_dom_c = _clip01(xp, state["aa_dom_c"] + (carr.T @ nd) * uses)
    # Fine-granularity: chosen node lacks the term's coarse key (or the key
    # itself is fine) → the node is its own domain.  Row scatters (one
    # [T]-wide update per pod, see _row_scatter_max_t) replace the flattened
    # t·n scalar form — bit-identical, ~free vs ~14 ms each on TPU.
    has_c = nd @ uses.T  # [P, T]
    aa_node_m = _row_scatter_max_t(xp, state["aa_node_m"], choice, matc * (has_c == 0))
    aa_node_c = _row_scatter_max_t(xp, state["aa_node_c"], choice, carr * (has_c == 0))
    if hard_pa:
        # Positive affinity: every accepted pod matching a PA term activates
        # its landing domain (declaring or not — matches are matches).
        uses_pa = meta["pa_uses_dom"]
        matc_pa = ps["pod_pa_matched"] * accf[:, None]  # [P, Ta]
        pa_dom_m = _clip01(xp, state["pa_dom_m"] + (matc_pa.T @ nd) * uses_pa)
        has_c_pa = nd @ uses_pa.T  # [P, Ta]
        pa_node_m = _row_scatter_max_t(xp, state["pa_node_m"], choice, matc_pa * (has_c_pa == 0))
    else:
        pa_dom_m = state["pa_dom_m"]
        pa_node_m = state["pa_node_m"]
    if soft_pa:
        # Preferred terms: accepted matched pods bump their landing domain's
        # count (coarse) or node's count (fine/keyless) — same split as PA.
        uses_ppa = meta["ppa_uses_dom"]
        matc_ppa = ps["pod_ppa_matched"] * accf[:, None]  # [P, Tp]
        ppa_dom_cnt = state["ppa_dom_cnt"] + (matc_ppa.T @ nd) * uses_ppa
        has_c_ppa = nd @ uses_ppa.T  # [P, Tp]
        ppa_node_cnt = _row_scatter_add_t(xp, state["ppa_node_cnt"], choice, matc_ppa * (has_c_ppa == 0))
    else:
        ppa_dom_cnt = state["ppa_dom_cnt"]
        ppa_node_cnt = state["ppa_node_cnt"]
    sp_m = ps["pod_sp_matched"] * accf[:, None]  # [P, S]
    sp_counts = state["sp_counts"] + (sp_m.T @ nd) * meta["sp_uses_dom"]
    if soft_spread:
        sps_m = ps["pod_sps_matched"] * accf[:, None]  # [P, Ss]
        sps_counts = state["sps_counts"] + (sps_m.T @ nd) * meta["sps_uses_dom"]
    else:
        sps_counts = state["sps_counts"]
    out = {
        "aa_dom_m": aa_dom_m,
        "aa_dom_c": aa_dom_c,
        "aa_node_m": aa_node_m,
        "aa_node_c": aa_node_c,
        "pa_dom_m": pa_dom_m,
        "pa_node_m": pa_node_m,
        "ppa_dom_cnt": ppa_dom_cnt,
        "ppa_node_cnt": ppa_node_cnt,
        "sp_counts": sp_counts,
        "sps_counts": sps_counts,
    }
    # Round-carried conflict state (augment_round_state): updated HERE from
    # the round's commits instead of re-derived from the accumulated domain
    # history next round.  ``sp_cell``/``sp_lo`` re-reduce the just-updated
    # [S,D] counts (domain-granular — a rounding error next to the pod
    # tensors); ``pa_inactive`` flips per-term the moment any accepted match
    # commits (exactly when the pa_dom_m/pa_node_m sums leave zero: a
    # matched accepted pod lands in its domain when the node carries the
    # key, in its node row otherwise — either way the sum grows).
    if "ex_m" in state:
        out.update(_exclusive_commit(xp, accepted, choice, ps, state, meta))
    uses_sp = meta["sp_uses_dom"]
    sp_cell = sp_counts * uses_sp
    lo = xp.min(xp.where(uses_sp > 0, sp_cell, RANK_INF), axis=1)
    out["sp_cell"] = sp_cell
    out["sp_lo"] = xp.where(lo >= RANK_INF, 0.0, lo)
    if hard_pa:
        newly_matched = (matc_pa.sum(axis=0) > 0).astype(xp.float32)  # [Ta]
        out["pa_inactive"] = state["pa_inactive"] * (1.0 - newly_matched)
    else:
        out["pa_inactive"] = state["pa_inactive"]
    return out
