"""Native (NumPy) batched backend — the ``--backend=native`` path.

Same auction-round algorithm as ops/assign.py, expressed in NumPy.  It shares
the mask/score expression trees (ops/masks.py, ops/score.py, xp-generic) so
float behaviour is identical; the segmented prefix-sum is exact int64 clamped
to INT32_MAX, which equals the TPU path's saturating scan (see
ops/assign.py overflow note).  Serves three roles from SURVEY.md:
  • parity oracle for the TPU backend (binding-for-binding equality),
  • recovery path when the TPU backend is unavailable (§5 failure handling),
  • the "native" side of the north star's --backend flag.
"""

from __future__ import annotations

import contextlib

import numpy as np

from ..models.profiles import SchedulingProfile
from ..ops.masks import feasibility_block
from ..ops.pack import INT32_MAX, STALL_ROUNDS, PackedCluster
from ..ops.score import score_block
from ..topology.locality import gang_state_update, gang_topology_term
from ..utils.tracing import span
from .base import SchedulingBackend

__all__ = ["NativeBackend"]

# Stateless reusable no-op context: the mask/score/choose sub-spans only
# open on constrained/topology rounds (where the split carries signal);
# plain rounds pay one span, not four — the <2% profiler-overhead budget.
_NULL = contextlib.nullcontext()


class NativeBackend(SchedulingBackend):
    name = "native"
    supports_topology = True
    supports_exclusive_families = True

    # shape: (packed: obj, profile: obj) -> ([P] i32, scalar i32, dict)
    def assign(self, packed: PackedCluster, profile: SchedulingProfile) -> tuple[np.ndarray, int]:
        node_alloc, node_avail = packed.node_alloc, packed.node_avail
        node_labels, node_valid = packed.node_labels, packed.node_valid
        node_taints = packed.node_taints
        node_aff = packed.node_aff
        weights = profile.weights()
        p = packed.padded_pods
        n = packed.padded_nodes
        block = profile.pod_block

        perm = np.argsort(-packed.pod_prio, kind="stable")
        req = packed.pod_req[perm]
        sel = packed.pod_sel[perm]
        selc = packed.pod_sel_count[perm]
        ntol = packed.pod_ntol[perm]
        aff = packed.pod_aff[perm]
        has_aff = packed.pod_has_aff[perm]
        valid = packed.pod_valid[perm]
        pref_w = packed.pod_pref_w[perm]
        ntol_soft = packed.pod_ntol_soft[perm]
        node_pref, node_taints_soft = packed.node_pref, packed.node_taints_soft

        cons = packed.constraints
        cmeta = cstate = cpods = None
        soft_spread = cons is not None and cons.n_spread_soft > 0
        soft_pa = cons is not None and cons.n_ppa_terms > 0
        hard_pa = cons is not None and cons.n_pa_terms > 0
        if cons is not None:
            from ..ops.constraints import (
                augment_round_state,
                blocked_block,
                constraint_commit,
                constraint_filter,
                pa_progress,
                round_blocked_masks,
            )

            cmeta = cons.meta_arrays()
            # Round-carried conflict state (spread water line, per-cell
            # counts, PA bootstrap flags) — derived once, then updated
            # incrementally by constraint_commit (ops/assign.py twin).
            cstate = augment_round_state(np, {k: v.copy() for k, v in cons.state_arrays().items()}, cmeta)
            cpods = {k: v[perm] for k, v in cons.pod_arrays().items()}
        topo = packed.topology
        tmeta = gang_nodes = pod_gang = None
        if topo is not None:
            # Rank-aware gang co-placement (topology/locality.py) — the
            # exact NumPy twin of the jnp round-body path in ops/assign.py.
            tmeta = topo.meta_arrays()
            gang_nodes = topo.state_arrays()["gang_nodes"].copy()
            pod_gang = topo.pod_gang_id[perm]

        avail = node_avail.copy()
        assigned = np.full((p,), -1, dtype=np.int32)
        acc_round = np.full((p,), -1, dtype=np.int32)
        active = valid.copy()
        ranks = np.arange(p, dtype=np.uint32)  # already in priority-rank order
        rounds = 0
        stall = 0  # consecutive zero-acceptance rounds (ops/assign.py STALL_ROUNDS)
        deferred = np.zeros((p,), dtype=bool)  # deferred by the filter in an earlier round: claims last

        while rounds < profile.max_rounds and active.any() and stall < STALL_ROUNDS:
            # Per-round attribution (utils/profiler.py): each round nests a
            # mask/score/choose split under ``round[NN]`` so a constrained
            # cycle's cost names the round that ate it.  Spans are inert
            # (two clock reads) without an active trace — bench and parity
            # tests calling assign() directly pay nothing.
            detail = cons is not None or topo is not None
            with span(f"round[{rounds:02d}]"):
                with span("mask") if detail else _NULL:
                    round_masks = (
                        round_blocked_masks(np, cstate, cmeta, soft_spread=soft_spread, soft_pa=soft_pa, hard_pa=hard_pa)
                        if cons is not None
                        else None
                    )
                    topo_t = None
                    if topo is not None:
                        topo_t = gang_topology_term(np, gang_nodes, tmeta, avail, pod_gang, req, active, weights[6])
                choice = np.zeros((p,), dtype=np.int32)
                has = np.zeros((p,), dtype=bool)
                node_idx = np.arange(n, dtype=np.uint32)
                with span("score") if detail else _NULL:
                    for lo in range(0, p, block):
                        hi = min(lo + block, p)
                        m = feasibility_block(
                            np, req[lo:hi], sel[lo:hi], selc[lo:hi], active[lo:hi], avail, node_labels, node_valid,
                            ntol[lo:hi], node_taints, aff[lo:hi], has_aff[lo:hi], node_aff,
                        )
                        if round_masks is not None:
                            blk = {k: v[lo:hi] for k, v in cpods.items()}
                            m = m & ~blocked_block(np, blk, round_masks)
                        pod_idx = np.arange(lo, hi, dtype=np.uint32)
                        sc = score_block(
                            np, req[lo:hi], node_alloc, avail, weights, pod_idx, node_idx,
                            pod_pref_w=pref_w[lo:hi], node_pref=node_pref,
                            pod_ntol_soft=ntol_soft[lo:hi], node_taints_soft=node_taints_soft,
                            pod_sps_declares=cpods["pod_sps_declares"][lo:hi] if soft_spread else None,
                            sp_penalty_node=round_masks["sp_penalty_node"] if soft_spread else None,
                            pod_sp_declares=cpods["pod_sp_declares"][lo:hi] if round_masks is not None else None,
                            sp_level_node=round_masks["sp_level_node"] if round_masks is not None else None,
                            pod_ppa_w=cpods["pod_ppa_w"][lo:hi] if soft_pa else None,
                            ppa_cnt_node=round_masks["ppa_cnt_node"] if soft_pa else None,
                            salt=rounds,
                            pod_gang_id=pod_gang[lo:hi] if topo is not None else None,
                            topo_gang_node=topo_t,
                        )
                        sc = np.where(m, sc, -np.inf)
                        choice[lo:hi] = sc.argmax(axis=1).astype(np.int32)
                        has[lo:hi] = m.any(axis=1)

                with span("choose") if detail else _NULL:
                    cand = active & has
                    ch = np.where(cand, choice, n).astype(np.int32)
                    claim = np.where(cand[:, None], req, 0)

                    order = np.argsort(ch * 2 + deferred if cons is not None else ch, kind="stable")
                    ch_s = ch[order]
                    claim_s = claim[order].astype(np.int64)
                    cum = claim_s.cumsum(axis=0)
                    is_start = np.concatenate([[True], ch_s[1:] != ch_s[:-1]])
                    start_idx = np.maximum.accumulate(np.where(is_start, np.arange(p), 0))
                    base = (cum - claim_s)[start_idx]
                    within = np.minimum(cum - base, INT32_MAX)

                    avail_ext = np.concatenate([avail, np.zeros((1, avail.shape[1]), avail.dtype)], axis=0)
                    fits_prefix = (within <= avail_ext[ch_s]).all(-1)
                    acc_s = fits_prefix & (ch_s < n)
                    accepted = np.zeros((p,), dtype=bool)
                    accepted[order] = acc_s

                    if cons is not None:
                        # Named separately under choose: measured (PERF.md
                        # "Reading an attribution profile") the within-round
                        # conflict filter dominated constrained rounds at
                        # ~99% of round wall before the round-7 active-set
                        # fusion; ``spans=span`` opens the filter/aa|pa|
                        # spread sub-spans so the attribution names WHICH
                        # constraint family dominates, not just the filter.
                        prefix = accepted
                        with span("filter"):
                            accepted = constraint_filter(
                                np, accepted, choice, ranks, cpods, cstate, cmeta, hard_pa=hard_pa, spans=span
                            )
                        deferred |= prefix & ~accepted
                        stall = 0 if accepted.any() else stall + 1
                        with span("commit"):
                            cstate = constraint_commit(
                                np, accepted, choice, cpods, cstate, cmeta, soft_spread=soft_spread, soft_pa=soft_pa, hard_pa=hard_pa
                            )

                    if topo is not None:
                        gang_nodes = gang_state_update(np, gang_nodes, accepted, ch, pod_gang)
                    assigned = np.where(accepted, choice, assigned)
                    acc_round = np.where(accepted, rounds, acc_round)
                    dec = np.zeros((n + 1, avail.shape[1]), dtype=np.int64)
                    np.add.at(dec, ch, np.where(accepted[:, None], req, 0).astype(np.int64))
                    avail = (avail.astype(np.int64) - dec[:n]).astype(np.int32)
                    was_active = active
                    active = cand & ~accepted
                    if cons is not None and (hard_pa or cons.n_families):
                        # Positive-affinity declarers blocked everywhere stay
                        # active while ANY pending PA term gained a match this
                        # round (mirrors ops/assign.py exactly — see its
                        # rationale).
                        active = active | (was_active & ~has & pa_progress(np, cpods, accepted, cmeta, hard_pa))
            rounds += 1

        out = np.full((p,), -1, dtype=np.int32)
        out[perm] = assigned
        out_acc = np.full((p,), -1, dtype=np.int32)
        out_acc[perm] = acc_round
        rank_of = np.zeros((p,), dtype=np.int32)
        rank_of[perm] = np.arange(p, dtype=np.int32)
        return out, rounds, {"acc_round": out_acc, "rank": rank_of}
