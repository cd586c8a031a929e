"""Multi-chip scheduling cycle: the auction of ops/assign.py distributed over
a (dp, tp) mesh with jax.shard_map — pods sharded over ``dp``, nodes over
``tp``, XLA collectives over ICI (SURVEY.md §2b).

Identical results to the single-device path, by construction:

  choose   — each device scores its pod shard against its node shard; the
             per-pod best node is reduced across ``tp`` with all_gather +
             (score desc, node-index asc) tie-break, which equals the global
             first-max argmax.
  accept   — pod claims (choice, request) are all_gathered over ``dp`` in
             global priority order (pods are pre-permuted before sharding,
             so the tiled gather *is* rank order); each tp column runs the
             segmented saturating prefix acceptance for the nodes it owns;
             per-pod accepted flags come back via a tp psum (node shards are
             disjoint).
  commit   — each column scatter-subtracts its own nodes; every dp row in a
             column computes identically, keeping replicated state in sync
             without extra traffic.

Constrained cycles (anti-affinity / topology spread, ops/constraints.py)
ride the same mesh: the constraint tensors are [T,D]/[S,D]/[T,N]-shaped — a
rounding error next to the [P/dp × N/tp] choose tiles — so the domain state
and pod bitmaps are REPLICATED on every device, the round-start blocked
masks are computed redundantly (each device slices its node columns), and
the within-round filter + state commit run identically on every device over
the already-gathered global claims.  No collectives beyond the two the
unconstrained path already pays; determinism keeps the replicas in lockstep
(same inputs → same state), exactly like the replicated ``avail`` columns.
Exclusive families (ops/constraints.ExclusiveFamilies) are not run here:
the backend does not claim ``supports_exclusive_families``, so the
controller keeps those terms as per-term rows, and a constraint set that
carries families raises UntensorizableConstraints.

Per-round traffic: O(P) int32s over dp + O(P) over tp — a few MB at 100k
pods, ICI-trivial next to the [P/dp × N/tp] compute tiles.

The same code scales to multi-host (DCN) by building the mesh over
``jax.distributed`` processes; nothing below is aware of the difference.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..models.profiles import SchedulingProfile
from ..ops.assign import _seg_scan_op
from ..ops.pack import STALL_ROUNDS
from ..ops.masks import feasibility_block
from ..ops.pack import PackedCluster, round_up
from ..ops.score import score_block
from ..backends.base import SchedulingBackend
from ..utils.compile_cache import aot_call
from .mesh import make_mesh

__all__ = ["sharded_assign_cycle", "ShardedBackend", "IN_SPECS", "CONSTRAINT_KEYS", "constraint_operands"]


# shape: (avail: [N, R] i32, active: [B] bool, req: [B, R] i32,
#   sel: [B, L] f32, selc: [B] f32, ntol: [B, T] f32, aff: [B, A] f32,
#   has_aff: [B] f32, pref_w: [B, A2] f32, ntol_soft: [B, Ts] f32,
#   node_alloc: [N, R] i32, node_labels: [N, L] f32, node_taints: [N, T] f32,
#   node_aff: [N, A] f32, node_valid: [N] bool, node_pref: [N, A2] f32,
#   node_taints_soft: [N, Ts] f32, weights: [W] f32, pod_idx: [B] u32,
#   node_idx: [N] u32, blocked: [B, N] bool, sps_declares: [B, Ss] f32,
#   sp_penalty: [Ss, N] f32, spd_declares: [B, S] f32, sp_level: [S, N] f32,
#   ppa_w: [B, Tp] f32, ppa_cnt: [Tp, N] f32, salt: scalar any)
#   -> ([B] f32, [B] i32, [B] bool)
def _local_choose(
    avail, active, req, sel, selc, ntol, aff, has_aff, pref_w, ntol_soft, node_alloc, node_labels, node_taints,
    node_aff, node_valid, node_pref, node_taints_soft, weights, pod_idx, node_idx,
    blocked=None, sps_declares=None, sp_penalty=None, spd_declares=None, sp_level=None,
    ppa_w=None, ppa_cnt=None, salt=None,
):
    """Best local node per pod of this shard: (best_score, local idx, has).

    ``pod_idx``/``node_idx`` are *global* (rank-space) indices so the score
    jitter hash matches the single-device path exactly.  ``blocked`` is the
    constraint-blocked [p_local, n_local] mask (constrained cycles only);
    ``sps_declares``/``sp_penalty`` the ScheduleAnyway scoring operands;
    ``spd_declares``/``sp_level`` the hard-spread level-steering pair."""
    m = feasibility_block(
        jnp, req, sel, selc, active, avail, node_labels, node_valid, ntol, node_taints, aff, has_aff, node_aff
    )
    if blocked is not None:
        m = m & ~blocked
    sc = score_block(
        jnp, req, node_alloc, avail, weights, pod_idx, node_idx,
        pod_pref_w=pref_w, node_pref=node_pref, pod_ntol_soft=ntol_soft, node_taints_soft=node_taints_soft,
        pod_sps_declares=sps_declares, sp_penalty_node=sp_penalty,
        pod_sp_declares=spd_declares, sp_level_node=sp_level,
        pod_ppa_w=ppa_w, ppa_cnt_node=ppa_cnt, salt=salt,
    )
    sc = jnp.where(m, sc, -jnp.inf)
    return jnp.max(sc, axis=1), jnp.argmax(sc, axis=1).astype(jnp.int32), m.any(axis=1)


# Plain pod operand order — must match IN_SPECS positionally; shared by the
# single-process run wrapper and multihost.py so the three stay in lockstep.
POD_KEYS = (
    "pod_req",
    "pod_sel",
    "pod_sel_count",
    "pod_ntol",
    "pod_aff",
    "pod_has_aff",
    "pod_pref_w",
    "pod_ntol_soft",
    "pod_valid",
)

# Flat operand order for the constrained extension (all REPLICATED — specs
# P()): pod bitmaps in global rank order, then meta, then initial state.
CONSTRAINT_KEYS = (
    # pod side (ConstraintSet.pod_arrays, priority-permuted + dp-padded)
    "pod_aa_carries",
    "pod_aa_matched",
    "pod_pa_declares",
    "pod_pa_matched",
    "pod_sp_declares",
    "pod_sp_matched",
    "pod_sps_declares",
    "pod_sps_matched",
    "pod_ppa_w",
    "pod_ppa_matched",
    # meta (node_dom_c is [N,D] with N padded to the tp multiple)
    "node_dom_c",
    "term_uses_dom",
    "pa_uses_dom",
    "ppa_uses_dom",
    "sp_uses_dom",
    "sp_skew",
    "sps_uses_dom",
    "sp_dom_sel",
    # initial state (aa_node_* / pa_node_m are [·,N] padded to the tp multiple)
    "aa_dom_m",
    "aa_dom_c",
    "aa_node_m",
    "aa_node_c",
    "pa_dom_m",
    "pa_node_m",
    "ppa_dom_cnt",
    "ppa_node_cnt",
    "sp_counts",
    "sps_counts",
)
_N_PODKEYS = 10
_N_METAKEYS = 8


@lru_cache(maxsize=64)
def _build_shard_map(
    mesh,
    max_rounds: int,
    constrained: bool = False,
    soft_spread: bool = False,
    soft_pa: bool = False,
    hard_pa: bool = True,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
):
    """The shard_map'd per-device cycle fn (not yet jitted/wrapped) — shared
    by the single-process run wrapper below and the multi-host path
    (parallel/multihost.py), so both execute the identical program.

    ``use_pallas`` routes each shard's choose through the fused kernel
    (ops/pallas_choose.py) — the per-shard best SCORE rides out as the
    kernel's third output for the cross-tp merge, and the jitter hash gets
    this shard's global node base via ``node_offset``, so results stay
    bit-identical to the jnp shard program.  ``pallas_interpret`` runs the
    kernel in interpreter mode (CPU meshes: tests, dryrun_multichip)."""
    dp = mesh.shape["dp"]
    tp = mesh.shape["tp"]

    def local_fn(
        node_alloc, node_avail, node_labels, node_taints, node_aff, node_valid, node_pref, node_taints_soft,
        req, sel, selc, ntol, aff, has_aff, pref_w, ntol_soft, valid, w, *cargs,
    ):
        p_local = req.shape[0]
        n_local = node_avail.shape[0]
        p_tot = p_local * dp
        n_tot = n_local * tp
        dp_idx = lax.axis_index("dp")
        tp_idx = lax.axis_index("tp")
        node_base = tp_idx * n_local
        g_pod_idx = (dp_idx * p_local + jnp.arange(p_local)).astype(jnp.uint32)
        g_node_idx = (node_base + jnp.arange(n_local)).astype(jnp.uint32)
        if use_pallas:
            # Loop-invariant transposed node operands (kernel layout).
            labels_t, taints_t, aff_t = node_labels.T, node_taints.T, node_aff.T
            pref_t, tsoft_t = node_pref.T, node_taints_soft.T

        if constrained:
            from ..ops.constraints import (
                augment_round_state,
                blocked_block,
                constraint_commit,
                constraint_filter,
                round_blocked_masks,
            )

            named = dict(zip(CONSTRAINT_KEYS, cargs))
            cpods = {k: named[k] for k in CONSTRAINT_KEYS[:_N_PODKEYS]}
            cmeta = {k: named[k] for k in CONSTRAINT_KEYS[_N_PODKEYS : _N_PODKEYS + _N_METAKEYS]}
            cst0 = {k: named[k] for k in CONSTRAINT_KEYS[_N_PODKEYS + _N_METAKEYS :]}
            # Round-carried conflict state, replicated like the rest of the
            # constraint carry (ops/assign.py twin).
            cst0 = augment_round_state(jnp, cst0, cmeta)
            cst0["stall"] = jnp.int32(0)
            cst0["deferred"] = jnp.zeros((p_tot,), bool)  # global rows the filter deferred: they claim last
            # This device's dp rows of the (replicated) pod bitmaps.
            blk_l = {k: lax.dynamic_slice_in_dim(v, dp_idx * p_local, p_local) for k, v in cpods.items()}
            g_ranks = jnp.arange(p_tot, dtype=jnp.uint32)
        else:
            cst0 = {}

        def cond(state):
            _, _, _, go, rounds, cst = state
            keep = (rounds < max_rounds) & go
            if constrained:
                keep = keep & (cst["stall"] < STALL_ROUNDS)
            return keep

        def body(state):
            avail, assigned, active, _, rounds, cst = state

            # 1. choose: local tile (with the constraint-blocked columns of
            # this shard when constrained), then argmax across the tp axis.
            blocked_l = sps_dec_l = sp_pen_l = ppa_w_l = ppa_cnt_l = None
            spd_dec_l = sp_lvl_l = None
            cons_pod_l = cons_node_l = None
            if constrained:
                masks = round_blocked_masks(jnp, cst, cmeta, soft_spread=soft_spread, soft_pa=soft_pa, hard_pa=hard_pa)  # [·, n_tot]
                # Node-axis masks slice to this shard's columns; pa_inactive
                # is per-TERM ([Ta], no node axis) and stays whole.
                lm = {
                    k: (v if k == "pa_inactive" else lax.dynamic_slice_in_dim(v, node_base, n_local, axis=1))
                    for k, v in masks.items()
                }
                if use_pallas:
                    # Constrained kernel operands over this shard's sliced
                    # masks — the SAME helpers as ops/assign._choose, so the
                    # zero-fill and PA-gating conventions have one home.
                    from ..ops.pallas_choose import (
                        constrained_kernel_node_operands,
                        constrained_kernel_pod_operands,
                    )

                    cons_node_l, pa_inactive = constrained_kernel_node_operands(blk_l, lm, n_local)
                    cons_pod_l = constrained_kernel_pod_operands(blk_l, pa_inactive)
                else:
                    blocked_l = blocked_block(jnp, blk_l, lm)  # [p_local, n_local]
                    if soft_spread:
                        sps_dec_l = blk_l["pod_sps_declares"]
                        sp_pen_l = lm["sp_penalty_node"]
                    spd_dec_l = blk_l["pod_sp_declares"]
                    sp_lvl_l = lm["sp_level_node"]
                    if soft_pa:
                        ppa_w_l = blk_l["pod_ppa_w"]
                        ppa_cnt_l = lm["ppa_cnt_node"]
            if use_pallas:
                from ..ops.pallas_choose import build_node_info, choose_block_pallas

                idx_l, _has_l, best_l = choose_block_pallas(
                    req, sel, selc, ntol, aff, has_aff, pref_w, ntol_soft, active, g_pod_idx,
                    build_node_info(avail, node_alloc, node_valid),
                    labels_t, taints_t, aff_t, pref_t, tsoft_t, w,
                    salt=rounds, cons_pod=cons_pod_l, cons_node=cons_node_l,
                    node_offset=node_base, interpret=pallas_interpret, return_best=True,
                )
            else:
                best_l, idx_l, _ = _local_choose(
                    avail, active, req, sel, selc, ntol, aff, has_aff, pref_w, ntol_soft, node_alloc, node_labels,
                    node_taints, node_aff, node_valid, node_pref, node_taints_soft, w, g_pod_idx, g_node_idx,
                    blocked=blocked_l, sps_declares=sps_dec_l, sp_penalty=sp_pen_l,
                    spd_declares=spd_dec_l, sp_level=sp_lvl_l,
                    ppa_w=ppa_w_l, ppa_cnt=ppa_cnt_l, salt=rounds,
                )
            bests = lax.all_gather(best_l, "tp")  # [tp, p_local]
            idxs = lax.all_gather(idx_l + node_base, "tp")
            best, choice = bests[0], idxs[0]
            for k in range(1, tp):
                take = (bests[k] > best) | ((bests[k] == best) & (idxs[k] < choice))
                best = jnp.where(take, bests[k], best)
                choice = jnp.where(take, idxs[k], choice)
            has = jnp.isfinite(best)
            cand = active & has

            # 2. accept: gather all claims (already in global priority order).
            g_choice = lax.all_gather(jnp.where(cand, choice, n_tot), "dp", tiled=True)  # [P]
            g_req = lax.all_gather(jnp.where(cand[:, None], req, 0), "dp", tiled=True)  # [P,2]
            in_range = (g_choice >= node_base) & (g_choice < node_base + n_local)
            ch_local = jnp.where(in_range, g_choice - node_base, n_local).astype(jnp.int32)
            claim = jnp.where(in_range[:, None], g_req, 0)

            # Deferred claimants after the others of their node (ops/assign.py).
            key = ch_local * 2 + cst["deferred"].astype(jnp.int32) if constrained else ch_local
            order = jnp.argsort(key, stable=True)
            ch_s = ch_local[order]
            claim_s = claim[order]
            is_start = jnp.concatenate([jnp.ones((1,), bool), ch_s[1:] != ch_s[:-1]])[:, None]
            _, within = lax.associative_scan(_seg_scan_op, (is_start, claim_s))
            avail_ext = jnp.concatenate([avail, jnp.zeros((1, avail.shape[1]), avail.dtype)], axis=0)
            acc_s = (within <= avail_ext[ch_s]).all(-1) & (ch_s < n_local)
            accepted_rng = jnp.zeros((p_tot,), bool).at[order].set(acc_s)

            # Flags across node shards are disjoint → psum replicates the
            # global accepted set on every device.
            accepted = lax.psum(accepted_rng.astype(jnp.int32), "tp") > 0

            # 3. constraints: filter + state commit run REPLICATED — every
            # device holds the same global claims, bitmaps, and state, so
            # every device computes the identical result (no collective).
            if constrained:
                gi = jnp.minimum(g_choice, n_tot - 1).astype(jnp.int32)  # clamp the non-claimant sentinel
                prefix = accepted
                accepted = constraint_filter(jnp, accepted, gi, g_ranks, cpods, cst, cmeta, hard_pa=hard_pa)
                stall = jnp.where(accepted.any(), jnp.int32(0), cst["stall"] + 1)
                deferred = cst["deferred"] | (prefix & ~accepted)
                cst = constraint_commit(jnp, accepted, gi, cpods, cst, cmeta, soft_spread=soft_spread, soft_pa=soft_pa, hard_pa=hard_pa)
                cst["stall"] = stall
                cst["deferred"] = deferred

            # 4. capacity commit from the FILTERED accepted set; each column
            # scatter-subtracts its own nodes.
            acc_here = accepted & in_range
            dec = jnp.zeros((n_local + 1, avail.shape[1]), jnp.int32).at[ch_local].add(jnp.where(acc_here[:, None], claim, 0))
            avail = avail - dec[:n_local]
            acc_local = lax.dynamic_slice(accepted, (dp_idx * p_local,), (p_local,))

            assigned = jnp.where(acc_local, choice, assigned)
            was_active = active  # round-start actives (not yet rebound)
            new_active = cand & ~acc_local
            if constrained and hard_pa:
                # PA declarers blocked everywhere stay active while ANY
                # pending PA term gained a match this round (see
                # ops/assign.py).  `accepted` and the pod bitmaps (cpods)
                # are global and replicated, so every device computes the
                # same flag; the per-pod gate uses this dp shard's rows.
                new_match = (cpods["pod_pa_matched"] * accepted[:, None].astype(jnp.float32)).sum(axis=0) > 0
                pa_hope = (blk_l["pod_pa_declares"].sum(axis=1) > 0) & new_match.any()
                new_active = new_active | (was_active & ~has & pa_hope)
            active = new_active
            n_active = lax.psum(active.sum(), "dp")
            return avail, assigned, active, n_active > 0, rounds + 1, cst

        state0 = (
            node_avail,
            jnp.full((p_local,), -1, jnp.int32),
            valid,
            lax.psum(valid.sum(), "dp") > 0,
            jnp.int32(0),
            cst0,
        )
        avail, assigned, _, _, rounds, _ = lax.while_loop(cond, body, state0)
        return assigned, rounds, avail

    extra_specs = (P(),) * len(CONSTRAINT_KEYS) if constrained else ()
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=IN_SPECS + extra_specs,
        out_specs=(P("dp"), P(), P("tp", None)),
        # The while-carry mixes tp-varying (avail) and dp-varying (assigned)
        # state that converges by construction; VMA inference can't see that.
        check_vma=False,
    )


# shard_map input layout, shared with parallel/multihost.py: node tensors
# over tp, pod tensors (pre-permuted to priority order) over dp, weights
# replicated; constrained cycles append CONSTRAINT_KEYS operands, all P().
IN_SPECS = (
    P("tp", None),  # node_alloc
    P("tp", None),  # node_avail
    P("tp", None),  # node_labels
    P("tp", None),  # node_taints
    P("tp", None),  # node_aff
    P("tp"),  # node_valid
    P("tp", None),  # node_pref
    P("tp", None),  # node_taints_soft
    P("dp", None),  # pod_req
    P("dp", None),  # pod_sel
    P("dp"),  # pod_sel_count
    P("dp", None),  # pod_ntol
    P("dp", None),  # pod_aff
    P("dp"),  # pod_has_aff
    P("dp", None),  # pod_pref_w
    P("dp", None),  # pod_ntol_soft
    P("dp"),  # pod_valid (already priority-permuted)
    P(),  # weights
)


# shape: (cons: obj, n_pad_from: int, n_pad_to: int) -> dict
def constraint_operands(cons, n_pad_from: int, n_pad_to: int) -> dict:
    """Numpy constraint operands in CONSTRAINT_KEYS order (as a dict), with
    the node axis padded from the pack's padding to the mesh's tp multiple.
    Pod bitmaps are returned in PACK order — the caller permutes + pads them
    alongside the pod tensors."""
    extra = n_pad_to - n_pad_from
    ops = {}
    ops.update(cons.pod_arrays())
    meta = cons.meta_arrays()
    state = cons.state_arrays()
    ops["node_dom_c"] = np.pad(meta["node_dom_c"], ((0, extra), (0, 0)))
    for k in ("term_uses_dom", "pa_uses_dom", "ppa_uses_dom", "sp_uses_dom", "sp_skew", "sps_uses_dom", "sp_dom_sel"):
        ops[k] = meta[k]
    for k in ("aa_dom_m", "aa_dom_c", "pa_dom_m", "ppa_dom_cnt", "sp_counts", "sps_counts"):
        ops[k] = state[k]
    ops["aa_node_m"] = np.pad(state["aa_node_m"], ((0, 0), (0, extra)))
    ops["aa_node_c"] = np.pad(state["aa_node_c"], ((0, 0), (0, extra)))
    ops["pa_node_m"] = np.pad(state["pa_node_m"], ((0, 0), (0, extra)))
    ops["ppa_node_cnt"] = np.pad(state["ppa_node_cnt"], ((0, 0), (0, extra)))
    return ops


@lru_cache(maxsize=64)
def _build_sharded_fn(
    mesh,
    max_rounds: int,
    constrained: bool = False,
    soft_spread: bool = False,
    soft_pa: bool = False,
    hard_pa: bool = True,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
):
    """Jitted (mesh, max_rounds)-specialised cycle fn — cached so repeated
    cycles reuse the compiled executable (jit re-specialises per shape)."""
    dp = mesh.shape["dp"]
    sharded = _build_shard_map(mesh, max_rounds, constrained, soft_spread, soft_pa, hard_pa, use_pallas, pallas_interpret)

    @jax.jit
    def run(a, c):
        p_tot = a["pod_req"].shape[0]
        # Permute BEFORE dp padding: ranks feed the score-jitter hash and
        # must equal the unpadded native backend's (see ops/assign.py).
        perm = jnp.argsort(-a["pod_prio"], stable=True)
        pods = {k: a[k][perm] for k in POD_KEYS}
        cpods = {k: c[k][perm] for k in CONSTRAINT_KEYS[:_N_PODKEYS]} if constrained else {}
        extra = (-p_tot) % dp
        if extra:
            pad = lambda v: jnp.pad(v, ((0, extra),) + ((0, 0),) * (v.ndim - 1))  # noqa: E731
            pods = {k: pad(v) for k, v in pods.items()}
            cpods = {k: pad(v) for k, v in cpods.items()}
        cargs = tuple(cpods[k] if i < _N_PODKEYS else c[k] for i, k in enumerate(CONSTRAINT_KEYS)) if constrained else ()
        node_args = tuple(
            a[k]
            for k in (
                "node_alloc", "node_avail", "node_labels", "node_taints", "node_aff", "node_valid",
                "node_pref", "node_taints_soft",
            )
        )
        assigned_p, rounds, avail = sharded(
            *node_args,
            *(pods[k] for k in POD_KEYS),
            a["weights"],
            *cargs,
        )
        assigned = jnp.full((p_tot,), -1, jnp.int32).at[perm].set(assigned_p[:p_tot])
        return assigned, rounds, avail

    return run


# shape: (mesh: obj, arrays: dict, weights: [W] f32, max_rounds: int,
#   constraints: dict, soft_spread: bool, soft_pa: bool, hard_pa: bool,
#   use_pallas: bool, pallas_interpret: bool) -> ([P] i32, scalar i32, [N, R] i32)
def sharded_assign_cycle(
    mesh, arrays: dict, weights, max_rounds: int = 32, constraints: dict | None = None,
    soft_spread: bool = False, soft_pa: bool = False, hard_pa: bool = True,
    use_pallas: bool = False, pallas_interpret: bool = False,
):
    """Run one cycle over the mesh. ``arrays`` are the PackedCluster device
    arrays with N pre-padded to a tp multiple (pods pad internally, post-
    permute); ``constraints`` the :func:`constraint_operands` dict for
    constrained cycles.  Returns (assigned [P], rounds, avail [N_padded,2])."""
    assert arrays["node_avail"].shape[0] % mesh.shape["tp"] == 0
    a = dict(arrays)
    a["weights"] = np.asarray(weights, dtype=np.float32)
    run = _build_sharded_fn(
        mesh, max_rounds, constraints is not None, soft_spread, soft_pa, hard_pa, use_pallas, pallas_interpret
    )
    return aot_call(run, {}, a, constraints if constraints is not None else {})


class ShardedBackend(SchedulingBackend):
    """SchedulingBackend over a device mesh — DP×TP distribution of the
    cycle, including constrained (anti-affinity / topology-spread) cycles
    via replicated domain state.  Drop-in for TpuBackend; used by
    dryrun_multichip, the CLI ``--backend=tpu-sharded``, and the multi-chip
    benches."""

    name = "tpu-sharded"
    # One mesh program at a time: concurrent shard solves would interleave
    # collective launches, which deadlocks multi-controller runtimes (and
    # buys nothing on a single mesh — the devices are shared anyway).
    supports_concurrent_shards = False

    def __init__(self, mesh=None, tp: int | None = None, use_pallas: bool | None = None, pallas_interpret: bool = False):
        self.mesh = mesh if mesh is not None else make_mesh(tp=tp)
        # The fused kernel runs compiled on TPU meshes; elsewhere it runs
        # only when the caller asks for interpret mode (tests, dryrun).
        platform = next(iter(self.mesh.devices.flat)).platform
        if use_pallas is None:
            use_pallas = platform == "tpu"
        self.use_pallas = use_pallas
        self.pallas_interpret = pallas_interpret

    def _dispatch(self, a, c, profile, soft_spread, soft_pa, hard_pa, use_pallas):
        if jax.process_count() > 1:
            # Multi-controller runtime: host-local numpy can't feed a jit
            # over non-addressable devices — route through the global-
            # array path (parallel/multihost.py; same shard_map program).
            from .multihost import sharded_assign_multihost

            assigned, rounds = sharded_assign_multihost(
                self.mesh, a, profile.weights(), profile.max_rounds, constraints=c,
                soft_spread=soft_spread, soft_pa=soft_pa, hard_pa=hard_pa,
                use_pallas=use_pallas, pallas_interpret=self.pallas_interpret,
            )
            return np.asarray(assigned), int(rounds)
        assigned, rounds, _avail = sharded_assign_cycle(
            self.mesh, a, profile.weights(), profile.max_rounds, constraints=c,
            soft_spread=soft_spread, soft_pa=soft_pa, hard_pa=hard_pa,
            use_pallas=use_pallas, pallas_interpret=self.pallas_interpret,
        )
        return np.asarray(jax.device_get(assigned)), int(rounds)

    # shape: (packed: obj, profile: obj) -> ([P] i32, scalar i32)
    # bucket: n_pad
    def assign(self, packed: PackedCluster, profile: SchedulingProfile) -> tuple[np.ndarray, int]:
        from ..errors import BackendUnavailable

        tp = self.mesh.shape["tp"]
        a = dict(packed.device_arrays())
        # Node padding to the tp multiple happens here; pod padding to the dp
        # multiple happens inside the jitted run, after the priority permute.
        n_pad = round_up(packed.padded_nodes, tp)
        for k in ("node_alloc", "node_avail", "node_labels", "node_taints", "node_aff", "node_pref", "node_taints_soft"):
            a[k] = np.pad(a[k], ((0, n_pad - packed.padded_nodes), (0, 0)))
        a["node_valid"] = np.pad(a["node_valid"], ((0, n_pad - packed.padded_nodes),))
        cons = packed.constraints
        if cons is not None and cons.n_families:
            # The mesh program has no exclusive-family operands; the
            # controller never folds families for this backend
            # (supports_exclusive_families), so only a direct caller lands here.
            from ..ops.constraints import UntensorizableConstraints

            raise UntensorizableConstraints(f"{cons.n_families} exclusive families: the sharded mesh does not run them")
        c = constraint_operands(cons, packed.padded_nodes, n_pad) if cons is not None else None
        soft_spread = cons is not None and cons.n_spread_soft > 0
        soft_pa = cons is not None and cons.n_ppa_terms > 0
        hard_pa = cons is not None and cons.n_pa_terms > 0
        from ..ops.pallas_choose import pallas_kernel_supported

        use_pallas = self.use_pallas and pallas_kernel_supported(a, a)
        try:
            return self._dispatch(a, c, profile, soft_spread, soft_pa, hard_pa, use_pallas)
        except jax.errors.JaxRuntimeError as e:
            # Same contract as TpuBackend: device-runtime failures become the
            # explicit unavailability signal the controller's fallback keys
            # on; programming errors and compile failures (CompileFailed,
            # raised by aot_call) propagate.
            raise BackendUnavailable(f"sharded backend runtime failure: {e}") from e
