"""Rank-aware gang co-placement scoring — the fused locality term.

A gang (an MPI-style training job's workers) is only as fast as its slowest
link, so placement quality IS communication performance: every pair of
members split across racks pays the spine.  This module turns the compiled
topology (model.py) plus the cycle's gang membership into ONE per-round
additive score tensor ``T[G+1, N]`` shared by every member of a gang, so the
whole term costs a per-block row gather inside the existing pods×nodes score
path (ops/score.py) — batched over ALL ranks at once, no per-rank Python
loop on either backend.

Three components, all per (gang, node), recomputed each auction round from
the loop-carried placement state:

  anchor   −w·Σ_l d_l·(placed_total_g − same_l[g, n]) — the distance from
           node n to every already-placed member of g, factored through the
           per-level membership one-hots (identical to multiplying by the
           [N, N] distance matrix, without materializing it on device);
  fit      +w·Σ_l d_l·fits_l[g, dom_l(n)] — the gang's remaining demand
           fits the node's level-l domain whole.  Because a finer domain's
           free capacity is a subset of its parent's, a node whose SLICE
           fits the gang collects the slice AND rack bonuses — automatic
           preference for the finest domain that can take the whole gang;
  herd     +w·Σ_l d_l·tb_l[g, dom_l(n)] — a deterministic per-(gang,
           domain) tie-break in [0, 1) (crc32, no PYTHONHASHSEED exposure)
           shared by every member, so on the FIRST round — before any
           anchor exists — all members rank fitting domains identically and
           converge on one, instead of scattering across near-ties by the
           per-pod jitter hash.

``w`` is the profile's ``gang_locality_weight`` (weights[6]); at its
default the term dominates the packing score for gang members — intended:
for tightly-coupled workloads, locality outranks bin-packing aesthetics.
Pods outside any gang ride row 0 of T, which is pinned to zero: the term is
score-neutral for everything else.

Demand/capacity fit uses cpu+memory in float32 — a scoring heuristic, never
a validity decision (the feasibility mask and the accept prefix-sum stay
exact int32), so float rounding can only nudge a bonus, not oversubscribe.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass

import numpy as np

__all__ = [
    "SCORING_KNOBS",
    "TopologySet",
    "gang_placement_stats",
    "gang_state_update",
    "gang_topology_term",
    "pack_topology",
]

# The profile knobs this subsystem reads (drift-gated into the README
# "Topology & gang placement" catalogue by the TOPO analyze rule).
SCORING_KNOBS = ("gang_locality_weight",)

# Component scales inside the term (all further multiplied by the profile's
# gang_locality_weight).  The ordering invariant that makes convergence
# robust: ANCHOR > max herd spread > fit > per-pod jitter — once any member
# is placed, no herd tie-break can pull the rest of the gang to a different
# domain (a demand that shrank mid-admission may open a "better-hashed"
# rack; the anchor must still win), while before any placement the herd
# spread dominates base-score differences between near-tied fitting
# domains.  With 2 levels and w=64: anchor ≥ 64·16·1 = 1024 per placed
# member per level crossed vs max herd+fit = 64·(1+4)·2 = 640.
ANCHOR_SCALE = 16.0
HERD_SCALE = 4.0


@dataclass(frozen=True)
class TopologySet:
    """Per-cycle topology tensors for one packed cluster (the topology twin
    of ops/constraints.ConstraintSet).  Pod rows align with PackedCluster's
    pending order (padded to P); node columns with its node order (padded to
    N, padding nodes in per-level sentinel domains that never fit)."""

    pod_gang_id: np.ndarray  # [P] int32 — 0 = no gang, 1..G
    # meta (static per cycle): per level l in 0..Lv-1:
    #   dom_id_l   [N]        int32   node's domain id (D_l = padding sentinel)
    #   dom_onehot_l [D_l+1, N] f32   domain membership rows
    #   gang_tb_l  [G+1, D_l+1] f32  per-(gang, domain) herd tie-break [0,1)
    # plus level_dist [Lv] f32.
    meta: dict
    n_gangs: int
    gang_names: tuple[str, ...]  # 1-based: gang_names[g-1] is gang id g
    compiled: object  # the CompiledTopology (host-side consumers)

    # shape: (self: obj) -> dict
    def meta_arrays(self) -> dict:
        return self.meta

    # shape: (self: obj) -> dict
    def pod_arrays(self) -> dict:
        return {"pod_gang_id": self.pod_gang_id}

    # shape: (self: obj) -> dict
    def state_arrays(self) -> dict:
        """Round-start loop-carry state: per-(gang, node) placed-member
        counts.  Column N is the non-claimant sentinel (ops/assign.py uses
        node index n for pods with no accepted choice), row 0 the no-gang
        dump — both never read back."""
        n = self.meta["dom_id_0"].shape[0]
        rows = self.meta["gang_tb_0"].shape[0]  # n_gangs + 1, or the wider padded width
        return {"gang_nodes": np.zeros((rows, n + 1), dtype=np.float32)}


# shape: (gang: str, level: int, dom: int) -> float
def _herd_tb(gang: str, level: int, dom: int) -> float:
    """Deterministic per-(gang, level, domain) tie-break in [0, 1) — crc32,
    so it is stable across processes, backends, and replays."""
    return zlib.crc32(f"{gang}|{level}|{dom}".encode()) / 4294967296.0


# shape: (compiled: obj, pending: obj, p_pad: int, node_names: obj, n_pad: int,
#   widths: dict) -> obj
def pack_topology(
    compiled, pending, p_pad: int, node_names: tuple[str, ...], n_pad: int, widths: dict | None = None
) -> TopologySet | None:
    """Build the cycle's TopologySet, or None when no pending pod declares a
    gang (the term would be all-zero; skipping keeps gangless cycles free).

    ``compiled`` node order must cover ``node_names`` (same snapshot);
    padding rows/columns get gang 0 / per-level sentinel domains.
    ``widths`` (caller-owned, as ops/constraints.pack_constraints' is) keeps
    the gang axis at the widest it has had, so a cycle with fewer gangs
    reuses the compiled programs; the extra gang rows are never named."""
    gang_ids = np.zeros((p_pad,), dtype=np.int32)
    gang_names: list[str] = []
    by_name: dict[str, int] = {}
    for i, pod in enumerate(pending):
        g = pod.spec.gang if pod.spec is not None else None
        if not g:
            continue
        gid = by_name.get(g)
        if gid is None:
            gang_names.append(g)
            by_name[g] = gid = len(gang_names)  # 1-based
        gang_ids[i] = gid
    if not gang_names:
        return None

    row = {n: i for i, n in enumerate(compiled.node_names)}
    gather = np.asarray([row[n] for n in node_names], dtype=np.intp)
    n_real = len(node_names)
    g1 = len(gang_names) + 1
    if widths is not None:
        g1 = widths["gangs"] = max(g1, widths.get("gangs", 0))
    meta: dict[str, np.ndarray] = {"level_dist": compiled.level_distances()}
    for l_idx in range(compiled.n_levels):
        d = int(compiled.dom_counts[l_idx])
        dom_id = np.full((n_pad,), d, dtype=np.int32)  # padding → sentinel
        dom_id[:n_real] = compiled.dom_ids[l_idx][gather]
        onehot = np.zeros((d + 1, n_pad), dtype=np.float32)
        onehot[dom_id, np.arange(n_pad)] = 1.0
        tb = np.zeros((g1, d + 1), dtype=np.float32)
        for g, name in enumerate(gang_names, start=1):
            for dom in range(d):  # sentinel column stays 0 (never fits anyway)
                tb[g, dom] = _herd_tb(name, l_idx, dom)
        meta[f"dom_id_{l_idx}"] = dom_id
        meta[f"dom_onehot_{l_idx}"] = onehot
        meta[f"gang_tb_{l_idx}"] = tb
    return TopologySet(
        pod_gang_id=gang_ids,
        meta=meta,
        n_gangs=len(gang_names),
        gang_names=tuple(gang_names),
        compiled=compiled,
    )


# shape: (gang_nodes: [G, M] f32, meta: dict, avail: [N, R] i32,
#   pod_gang_id: [P] i32, pod_req: [P, R] i32, active: [P] bool,
#   weight: scalar f32) -> [G, N] f32
def gang_topology_term(xp, gang_nodes, meta, avail, pod_gang_id, pod_req, active, weight):
    """The per-round [G+1, N] additive score tensor (module docstring).

    ``gang_nodes`` is the loop-carried [G+1, N+1] placed-member count (its
    sentinel column is sliced off here); ``avail``/``pod_req``/``active``
    are the round's live capacity and pod state — remaining gang demand is
    derived from them, so nothing else needs to ride the loop carry.
    xp-generic (numpy / jax.numpy): one expression tree for both backends,
    and jit-pure (no host syncs) for the device path.
    """
    f32 = xp.float32
    n = avail.shape[0]
    placed = gang_nodes[:, :n]  # [G+1, N] — drop the sentinel column
    g1 = placed.shape[0]
    level_dist = meta["level_dist"]
    n_levels = level_dist.shape[0]
    # Remaining demand of each gang's still-active members (cpu, mem) —
    # float32 on purpose: a scoring heuristic, never a validity decision.
    live_req = xp.where(active[:, None], pod_req[:, :2], 0).astype(f32)  # [P, 2]
    rem = xp.zeros((g1, 2), f32)
    if xp is np:
        np.add.at(rem, pod_gang_id, live_req)
    else:
        rem = rem.at[pod_gang_id].add(live_req)
    free = xp.maximum(avail[:, :2], 0).astype(f32)  # [N, 2]
    total = placed.sum(axis=1, keepdims=True)  # [G+1, 1]

    t = xp.zeros((g1, n), f32)
    for l_idx in range(n_levels):
        d_l = level_dist[l_idx]
        dom_id = meta[f"dom_id_{l_idx}"]  # [N] i32
        onehot = meta[f"dom_onehot_{l_idx}"]  # [D+1, N] f32
        # anchor: same-level placed count per (gang, node) via the one-hot
        # factoring of the [N, N] distance matrix.
        same = (placed @ onehot.T)[:, dom_id]  # [G+1, N]
        t = t - (f32(ANCHOR_SCALE) * d_l) * (total - same)
        # fit: remaining demand vs the node's level-l domain free capacity.
        dom_free = onehot @ free  # [D+1, 2]
        fits = (rem[:, None, :] <= dom_free[None, :, :]).all(-1).astype(f32)  # [G+1, D+1]
        # herd: the per-(gang, domain) shared tie-break rides only on
        # FITTING domains — a domain that cannot take the gang whole must
        # not attract it.
        t = t + d_l * ((fits * (f32(1.0) + f32(HERD_SCALE) * meta[f"gang_tb_{l_idx}"]))[:, dom_id])
    # Row 0 (no gang) pinned to zero: score-neutral for gangless pods.
    t = xp.where((xp.arange(g1) > 0)[:, None], weight * t, f32(0.0))
    return t.astype(f32)


# shape: (gang_nodes: [G, M] f32, accepted: [P] bool, choice: [P] i32,
#   pod_gang_id: [P] i32) -> [G, M] f32
def gang_state_update(xp, gang_nodes, accepted, choice, pod_gang_id):
    """Commit a round's accepted placements into the [G+1, N+1] per-(gang,
    node) count state.  ``choice`` may carry the non-claimant sentinel N
    (lands in the sentinel column, never read back); gangless pods land in
    row 0 (same).  xp-generic and jit-pure."""
    acc = accepted.astype(xp.float32)
    if xp is np:
        out = gang_nodes.copy()
        np.add.at(out, (pod_gang_id, choice), acc)
        return out
    return gang_nodes.at[pod_gang_id, choice].add(acc)


# shape: (member_domains: obj, level_dists: obj) -> dict
def gang_placement_stats(member_domains, level_dists) -> dict:
    """Pairwise placement-distance statistics for ONE gang's placed members.

    ``member_domains``: per member, the (finest → coarsest) domain-name
    tuple of its node (CompiledTopology.domains_of); ``level_dists`` the
    matching per-level distance contributions.  Returns max/mean pairwise
    distance plus ``cross_edges`` — the pair count differing at the
    COARSEST level (the "cross-rack edge" count the scorecard gates on).
    Host-side only (scorecard, debug API, bench, controller metrics)."""
    k = len(member_domains)
    pairs = 0
    dist_sum = 0.0
    dist_max = 0.0
    cross = 0
    for i in range(k):
        for j in range(i + 1, k):
            pairs += 1
            d = 0.0
            for lvl, w in enumerate(level_dists):
                if member_domains[i][lvl] != member_domains[j][lvl]:
                    d += float(w)
            dist_sum += d
            dist_max = max(dist_max, d)
            if member_domains[i][-1] != member_domains[j][-1]:
                cross += 1
    return {
        "members": k,
        "pairs": pairs,
        "max_distance": round(dist_max, 6),
        "mean_distance": round(dist_sum / pairs, 6) if pairs else 0.0,
        "cross_edges": cross,
    }
