"""Conflict-free batched assignment — the jitted scheduling cycle.

This is the TPU replacement for the reference's sequential reconcile loop
(``src/main.rs:51-71`` + the controller dispatch at ``main.rs:141-149``):
instead of one pod at a time × ≤5 random candidates × one RPC each, all
pending pods are assigned in a small number of *auction rounds*, entirely
on-device, with capacity commits that make oversubscription impossible —
closing the reference's by-design TOCTOU race (SURVEY.md §5: two concurrent
reconciles can both fit the same gap).

Round structure (all under ``lax.while_loop``; shapes static):
  1. choose:  blockwise over pods — feasibility mask + scores vs the
     *current* remaining capacity; per-pod masked argmax → choice[P].
  2. accept:  pods are pre-permuted into (priority desc, FIFO) order; a
     stable sort by chosen node groups each node's claimants in priority
     order; a segmented saturating prefix-sum of their requests accepts the
     longest prefix that fits remaining capacity.
  3. commit:  accepted requests scatter-subtract from remaining capacity;
     accepted pods leave the pool; pods with no feasible node drop out
     (capacity only shrinks within a cycle, so they can never become
     feasible again this cycle → they requeue, reference ``main.rs:122-125``).
  4. compact: a stable sort on ``~active`` packs the still-active pods to
     the front, so the next round's choose only touches
     ``ceil(n_active / block)`` blocks instead of all of them.  Measured on
     the north-star shape (100k×10k), active counts decay 100k → 76k → 53k
     → … → 8 over 32 rounds, so compaction cuts choose work ~4-5×.  The
     stable sort preserves relative order among active pods (= priority
     order), and each pod's original rank rides along for the score-jitter
     hash, so results are bit-identical to the uncompacted algorithm and to
     the native backend.

Pod- and node-side tensors travel as dicts (the PackedCluster
``device_arrays`` names, split by prefix), so adding a predicate tensor is a
one-key change: the permutation, padding, compaction, and block slicing are
generic over the pod dict.

Every round with any claimant accepts at least the highest-priority claimant
of each contended node, so the loop strictly progresses; ``max_rounds`` is a
safety cap only.

Overflow note: within-segment demand prefix-sums can exceed int32 (100k pods
× multi-GiB requests in KiB), so the scan uses *saturating* int32 addition —
associative for non-negatives, yielding exactly ``min(true_sum, INT32_MAX)``,
which the native NumPy backend mirrors with exact int64 + clamp.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .masks import feasibility_block
from .pack import INT32_MAX, STALL_ROUNDS
from .score import score_block
from ..topology.locality import gang_state_update, gang_topology_term

__all__ = ["assign_cycle", "split_device_arrays", "INT32_MAX"]

# Pod-side keys the choose step consumes (sliced per block); the rest of the
# pod state (assigned, active bookkeeping) never enters the score math.
_CHOOSE_KEYS = (
    "pod_req",
    "pod_sel",
    "pod_sel_count",
    "pod_ntol",
    "pod_aff",
    "pod_has_aff",
    "pod_ntol_soft",
    "pod_pref_w",
    "active",
    "ranks",
)
# Constraint pod-side keys (present only when the cycle carries anti-affinity
# or topology-spread tensors, ops/constraints.py).
_CONSTRAINT_KEYS = (
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
)
# Exclusive-family pod-side keys (present only when the cycle folds JobSet's
# exclusive-placement terms, ops/constraints.ExclusiveFamilies).
_EXCLUSIVE_KEYS = ("pod_ex_label", "pod_ex_carry", "pod_ex_aff")


# shape: (arrays: dict) -> (dict, dict)
def split_device_arrays(arrays: dict) -> tuple[dict, dict]:
    """Split a PackedCluster.device_arrays() dict into (node_side, pod_side)."""
    nodes = {k: v for k, v in arrays.items() if k.startswith("node_")}
    pods = {k: v for k, v in arrays.items() if k.startswith("pod_")}
    return nodes, pods


# shape: (a: [S, C] i32, b: [S, C] i32) -> [S, C] i32
def _sat_add(a, b):
    """Saturating int32 add for non-negative operands: min(a+b, INT32_MAX)."""
    s = a + b
    return jnp.where(s < 0, INT32_MAX, s)


# shape: (x: any, y: any) -> any
def _seg_scan_op(x, y):
    """Segmented saturating-sum operator for lax.associative_scan.

    Elements are (segment_start_flag [.,1] bool, value [.,2] int32).
    """
    fx, vx = x
    fy, vy = y
    return fx | fy, jnp.where(fy, vy, _sat_add(vx, vy))


# shape: (avail: [N, R] i32, nodes: dict, weights: [W] f32, blk: dict,
#   pallas_pack: obj, round_masks: dict, salt: scalar any,
#   topo_t: [G, N] f32) -> ([B] i32, [B] bool)
def _choose_block(avail, nodes, weights, blk, pallas_pack=None, round_masks=None, salt=None, topo_t=None):
    """[B] best feasible node (+feasibility flag) for one block of pods.

    ``blk`` is the pod-side dict sliced to one block.  With ``pallas_pack``
    (node_info, labels_t, taints_t, interpret) the fused Pallas kernel runs
    (ops/pallas_choose.py — bit-identical results, one VMEM pass); otherwise
    the xp-generic jnp expression tree.  ``round_masks`` (constraint cycles
    only) adds the anti-affinity/spread blocked-node matmuls.
    """
    if pallas_pack is not None:
        from .pallas_choose import choose_block_pallas, constrained_kernel_pod_operands

        node_info, labels_t, taints_t, aff_t, pref_t, taints_soft_t, interpret, cons_node = pallas_pack
        cons_pod = cons_node_args = None
        if cons_node is not None:
            cons_node_args, pa_inactive = cons_node
            cons_pod = constrained_kernel_pod_operands(blk, pa_inactive)
        return choose_block_pallas(
            blk["pod_req"],
            blk["pod_sel"],
            blk["pod_sel_count"],
            blk["pod_ntol"],
            blk["pod_aff"],
            blk["pod_has_aff"],
            blk["pod_pref_w"],
            blk["pod_ntol_soft"],
            blk["active"],
            blk["ranks"],
            node_info,
            labels_t,
            taints_t,
            aff_t,
            pref_t,
            taints_soft_t,
            weights,
            salt=salt,
            cons_pod=cons_pod,
            cons_node=cons_node_args,
            interpret=interpret,
        )
    node_idx = jnp.arange(avail.shape[0], dtype=jnp.uint32)
    m = feasibility_block(
        jnp,
        blk["pod_req"],
        blk["pod_sel"],
        blk["pod_sel_count"],
        blk["active"],
        avail,
        nodes["node_labels"],
        nodes["node_valid"],
        blk["pod_ntol"],
        nodes["node_taints"],
        blk["pod_aff"],
        blk["pod_has_aff"],
        nodes["node_aff"],
    )
    if round_masks is not None:
        from .constraints import blocked_block

        m = m & ~blocked_block(jnp, blk, round_masks)
    soft_sp = round_masks is not None and "sp_penalty_node" in round_masks
    soft_pa = round_masks is not None and "ppa_cnt_node" in round_masks
    steer_sp = round_masks is not None and "sp_level_node" in round_masks
    sc = score_block(
        jnp,
        blk["pod_req"],
        nodes["node_alloc"],
        avail,
        weights,
        blk["ranks"],
        node_idx,
        pod_pref_w=blk["pod_pref_w"],
        node_pref=nodes["node_pref"],
        pod_ntol_soft=blk["pod_ntol_soft"],
        node_taints_soft=nodes["node_taints_soft"],
        pod_sps_declares=blk["pod_sps_declares"] if soft_sp else None,
        sp_penalty_node=round_masks["sp_penalty_node"] if soft_sp else None,
        pod_sp_declares=blk["pod_sp_declares"] if steer_sp else None,
        sp_level_node=round_masks["sp_level_node"] if steer_sp else None,
        pod_ppa_w=blk["pod_ppa_w"] if soft_pa else None,
        ppa_cnt_node=round_masks["ppa_cnt_node"] if soft_pa else None,
        salt=salt,
        pod_gang_id=blk["pod_gang_id"] if topo_t is not None else None,
        topo_gang_node=topo_t,
    )
    sc = jnp.where(m, sc, -jnp.inf)
    return jnp.argmax(sc, axis=1).astype(jnp.int32), m.any(axis=1)


# shape: (use_pallas: bool, pods: dict, nodes: dict, topology: bool) -> bool
def uses_kernel(use_pallas: bool, pods: dict, nodes: dict, topology: bool) -> bool:
    """Whether a cycle's choose step runs the fused Pallas kernel — the one
    place the decision is made.  The kernel has no gang-locality operand
    and no exclusive-family operand yet, so topology cycles and cycles that
    fold exclusive families run the jnp expression tree (bit-identical to
    native by construction — the terms are the same xp trees on both
    backends); otherwise the widths must fit it."""
    if not use_pallas or topology or "pod_ex_carry" in pods:
        return False
    from .pallas_choose import pallas_kernel_supported

    return bool(pallas_kernel_supported(pods, nodes))


# shape: (avail: [N, R] i32, ps: dict, n_active: scalar i32, nodes: dict,
#   weights: [W] f32, block: int, use_pallas: bool, pallas_interpret: bool,
#   round_masks: dict, salt: scalar any, topo_t: [G, N] f32) -> ([P] i32, [P] bool)
def _choose(
    avail, ps, n_active, nodes, weights, block, use_pallas=False, pallas_interpret=False, round_masks=None, salt=None,
    topo_t=None,
):
    """Per-pod best feasible node vs current capacity, blockwise over pods.

    Never materialises the full [P,N] score matrix: peak live memory is one
    [block, N] tile (HBM-bandwidth friendly; the pipeline analogue of
    SURVEY.md §2b PP).  Pods are compacted (active-first), so only the
    first ``ceil(n_active / block)`` blocks are evaluated — a dynamic bound
    on a ``lax.while_loop`` over blocks.  ``ps["ranks"]`` carries each pod's
    original priority rank into the score-jitter hash.
    """
    p = ps["pod_req"].shape[0]
    use_pallas = uses_kernel(use_pallas, ps, nodes, topo_t is not None)
    pallas_pack = None
    if use_pallas:
        from .pallas_choose import build_node_info

        cons_node = None
        if round_masks is not None:
            # Constrained kernel operands: the per-round [·, N] masks ride
            # into the kernel directly (zero-fill convention documented on
            # the helper — one source of truth with parallel/sharded.py).
            from .pallas_choose import constrained_kernel_node_operands

            cons_node = constrained_kernel_node_operands(ps, round_masks, avail.shape[0])
        # Rebuilt each round (avail changes); O(N) next to the O(B·N) choose.
        pallas_pack = (
            build_node_info(avail, nodes["node_alloc"], nodes["node_valid"]),
            nodes["node_labels"].T,
            nodes["node_taints"].T,
            nodes["node_aff"].T,
            nodes["node_pref"].T,
            nodes["node_taints_soft"].T,
            pallas_interpret,
            cons_node,
        )

    choose_keys = _CHOOSE_KEYS + (_CONSTRAINT_KEYS if round_masks is not None else ())
    if "pod_ex_carry" in ps:
        choose_keys = choose_keys + _EXCLUSIVE_KEYS
    if topo_t is not None:
        choose_keys = choose_keys + ("pod_gang_id",)
    if block >= p:
        return _choose_block(
            avail, nodes, weights, {k: ps[k] for k in choose_keys}, pallas_pack, round_masks, salt, topo_t
        )

    nb_occupied = (n_active + block - 1) // block  # traced; caller pads p % block == 0

    def cond(s):
        i = s[0]
        return i < nb_occupied

    def body(s):
        i, choice, has = s
        lo = i * block
        blk = {k: lax.dynamic_slice_in_dim(ps[k], lo, block) for k in choose_keys}
        bc, bh = _choose_block(avail, nodes, weights, blk, pallas_pack, round_masks, salt, topo_t)
        choice = lax.dynamic_update_slice_in_dim(choice, bc, lo, axis=0)
        has = lax.dynamic_update_slice_in_dim(has, bh, lo, axis=0)
        return i + 1, choice, has

    _, choice, has = lax.while_loop(cond, body, (jnp.int32(0), jnp.zeros((p,), jnp.int32), jnp.zeros((p,), bool)))
    return choice, has


# shape: (v: any, extra: int) -> any
def _pad0(v, extra):
    return jnp.pad(v, ((0, extra),) + ((0, 0),) * (v.ndim - 1))


# Shrink-chain floor: below this the accept phase is negligible and further
# steps would only multiply compiled variants.
_MIN_EPOCH_SIZE = 256


# shape: (target: int, block: int) -> int
def _chain_size(target: int, block: int) -> int:
    """Align one size of assign_cycle's static in-jit shrinking chain:
    block multiples while above ``block`` (the blockwise choose requires
    it), floored at _MIN_EPOCH_SIZE."""
    if target > block:
        target = ((target + block - 1) // block) * block
    return max(_MIN_EPOCH_SIZE, target)


# shape: (ps: dict) -> dict
def _compact(ps):
    """Stable active-first packing — relative (priority) order preserved.

    Implemented as a cumsum PARTITION, not a sort (PERF.md headroom item,
    measured ~0.6 ms vs ~1.45 ms per round at the north-star shape): each
    row's destination is its rank within its class (actives first), which
    is exactly the permutation a stable argsort of ``~active`` yields — so
    results stay bit-identical while dropping the O(P log P) sort."""
    active = ps["active"]
    n_act = jnp.cumsum(active.astype(jnp.int32))
    n_inact = jnp.cumsum((~active).astype(jnp.int32))
    dest = jnp.where(active, n_act - 1, n_act[-1] + n_inact - 1)
    return {k: jnp.zeros_like(v).at[dest].set(v) for k, v in ps.items()}


# shape: (pods: dict, block: int) -> ([P] i64, dict)
def _prepare_pods(pods, block: int):
    """Cycle setup — permute to priority order, pad to a block multiple,
    init the auction bookkeeping, compact actives to the front.

    Priority order (priority desc, FIFO index asc); stable sort keeps FIFO.
    The permutation happens BEFORE any block padding: rank positions feed
    the score-jitter hash and must equal the native backend's (which never
    pads) for binding parity — padding first would shift ranks whenever a
    pod has negative priority.  Padding rows sit at ranks ≥ p_out
    (inactive), leaving real ranks intact.
    """
    p_out = pods["pod_req"].shape[0]
    perm = jnp.argsort(-pods["pod_prio"], stable=True)
    ps = {k: v[perm] for k, v in pods.items() if k != "pod_prio"}
    p = p_out
    if block < p and p % block != 0:
        extra = block - p % block
        ps = {k: _pad0(v, extra) for k, v in ps.items()}
        p = p + extra
    ps["ranks"] = jnp.arange(p, dtype=jnp.uint32)
    ps["assigned"] = jnp.full((p,), -1, jnp.int32)
    ps["acc_round"] = jnp.full((p,), -1, jnp.int32)  # round each pod was accepted in
    ps["active"] = ps.pop("pod_valid")
    return perm, _compact(ps)


# shape: (nodes: dict, weights: [W] f32, block: int, use_pallas: bool,
#   pallas_interpret: bool, cmeta: dict, soft_spread: bool, soft_pa: bool,
#   hard_pa: bool, tmeta: dict) -> fn
def _make_round_body(nodes, weights, block, use_pallas, pallas_interpret, cmeta, soft_spread, soft_pa=False, hard_pa=True, tmeta=None):
    """One auction round as a while_loop body (every stage of
    assign_cycle's size chain runs it).

    ``tmeta`` (topology/locality.TopologySet.meta_arrays) switches on the
    rank-aware gang co-placement term: each round derives the per-(gang,
    node) score tensor from the loop-carried placement counts ``tst`` and
    the live capacity, and commit folds the round's accepted gang members
    back into those counts.  Gang-count state is [G, N] — NOT pod-indexed —
    so the size-chain slicing never loses placed-member information."""
    n = nodes["node_avail"].shape[0]
    exclusive = cmeta is not None and "ex_node_dom" in cmeta  # exclusive families (ops/constraints.py)

    def body(state):
        avail, ps, n_active, rounds, cst, tst = state
        p = ps["pod_req"].shape[0]
        round_masks = None
        if cmeta is not None:
            from .constraints import constraint_commit, constraint_filter, round_blocked_masks

            round_masks = round_blocked_masks(jnp, cst, cmeta, soft_spread=soft_spread, soft_pa=soft_pa, hard_pa=hard_pa)
        topo_t = None
        if tmeta is not None:
            topo_t = gang_topology_term(
                jnp, tst["gang_nodes"], tmeta, avail, ps["pod_gang_id"], ps["pod_req"], ps["active"], weights[6]
            )
        choice, has = _choose(
            avail, ps, n_active, nodes, weights, block, use_pallas, pallas_interpret, round_masks, salt=rounds,
            topo_t=topo_t,
        )
        cand = ps["active"] & has
        ch = jnp.where(cand, choice, n).astype(jnp.int32)  # sentinel segment n for non-claimants
        claim = jnp.where(cand[:, None], ps["pod_req"], 0)

        # Group claimants per node; the stable sort preserves the compacted
        # (= priority) order among each node's claimants.  On constrained
        # cycles a pod the filter has deferred claims after the others: a
        # deferred claimant holds no capacity, yet at the head of a node's
        # prefix it would block every claimant behind it, round after round,
        # until the stall stop stranded them all.
        key = ch * 2 + ps["deferred"].astype(jnp.int32) if cmeta is not None else ch
        order = jnp.argsort(key, stable=True)
        ch_s = ch[order]
        claim_s = claim[order]
        is_start = jnp.concatenate([jnp.ones((1,), bool), ch_s[1:] != ch_s[:-1]])[:, None]
        _, within = lax.associative_scan(_seg_scan_op, (is_start, claim_s))

        avail_ext = jnp.concatenate([avail, jnp.zeros((1, avail.shape[1]), avail.dtype)], axis=0)
        fits_prefix = (within <= avail_ext[ch_s]).all(-1)
        acc_s = fits_prefix & (ch_s < n)
        accepted = jnp.zeros((p,), bool).at[order].set(acc_s)

        if cmeta is not None:
            # Within-round conflict resolution + domain-state commit
            # (deferred pods stay active and retry next round).
            prefix = accepted
            accepted = constraint_filter(jnp, accepted, choice, ps["ranks"], ps, cst, cmeta, hard_pa=hard_pa)
            ps["deferred"] = ps["deferred"] | (prefix & ~accepted)
            stall = jnp.where(accepted.any(), jnp.int32(0), cst["stall"] + 1)
            cst = constraint_commit(jnp, accepted, choice, ps, cst, cmeta, soft_spread=soft_spread, soft_pa=soft_pa, hard_pa=hard_pa)
            cst["stall"] = stall

        ps["assigned"] = jnp.where(accepted, choice, ps["assigned"])
        ps["acc_round"] = jnp.where(accepted, rounds, ps["acc_round"])
        dec = jnp.zeros((n + 1, avail.shape[1]), jnp.int32).at[ch].add(jnp.where(accepted[:, None], ps["pod_req"], 0))
        avail = avail - dec[:n]
        was_active = ps["active"]
        ps["active"] = cand & ~accepted
        if cmeta is not None and (hard_pa or exclusive):
            # Positive affinity breaks the "feasibility only shrinks" rule
            # the no-feasible-node drop-out relies on: a pod placed THIS
            # round can activate a declarer's term and open nodes for it.
            # Keep blocked-everywhere PA declarers active while ANY pending
            # PA term gained a match this round: activations cascade (a
            # multi-hop chain A->B->C inside a GANG needs A alive until B
            # places — and the gang mop-up exclusion means a dropped gang
            # member livelocks, round-5 review finding), but a round where
            # NO term progressed cannot open anyone's nodes (AA masks only
            # grow, capacity only shrinks), so the hopeless stragglers that
            # round 4's any-pod-placed rule pinned through the whole
            # flagship tail (measured: ~1.3k pods blocking the size chain)
            # drain as soon as PA progress stops.
            from .constraints import pa_progress

            ps["active"] = ps["active"] | (was_active & ~has & pa_progress(jnp, ps, accepted, cmeta, hard_pa))
        if tmeta is not None:
            # Commit accepted gang members into the [G, N] placement counts
            # (non-claimants carry the sentinel column, gangless pods row 0 —
            # neither is ever read back).
            tst = {"gang_nodes": gang_state_update(jnp, tst["gang_nodes"], accepted, ch, ps["pod_gang_id"])}
        ps = _compact(ps)
        return avail, ps, ps["active"].sum(dtype=jnp.int32), rounds + 1, cst, tst

    return body


# shape: (nodes: dict, pods: dict, weights: [W] f32, max_rounds: int,
#   block: int, use_pallas: bool, pallas_interpret: bool, cmeta: dict,
#   cstate: dict, soft_spread: bool, soft_pa: bool, hard_pa: bool,
#   tmeta: dict, tstate: dict)
#   -> ([P] i32, scalar i32, [N, R] i32, [P] i32, [P] i32)
@partial(jax.jit, static_argnames=("max_rounds", "block", "use_pallas", "pallas_interpret", "soft_spread", "soft_pa", "hard_pa"))
def assign_cycle(
    nodes: dict,
    pods: dict,
    weights,
    max_rounds: int = 32,
    block: int = 4096,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    cmeta: dict | None = None,
    cstate: dict | None = None,
    soft_spread: bool = False,
    soft_pa: bool = False,
    hard_pa: bool = True,
    tmeta: dict | None = None,
    tstate: dict | None = None,
):
    """Assign all pending pods to nodes in one on-device cycle.

    ``nodes``/``pods`` are the PackedCluster device arrays split by prefix
    (see :func:`split_device_arrays`).  Returns (assigned [P] int32 — node
    index or −1, rounds int32, remaining node_avail [N,2] int32).

    ``cmeta``/``cstate`` (ops/constraints.py meta_arrays/state_arrays) switch
    on the anti-affinity + topology-spread path: choose gains the blocked-
    domain matmuls, accept gains the within-round conflict filter, and the
    domain state threads through the loop carry.  ``pods`` must then also
    carry the constraint pod bitmaps (ConstraintSet.pod_arrays).  The fused
    Pallas kernel covers constraint cycles too: the per-round blocked/penalty
    node masks ride in as extra node-side kernel operands (choose_block_pallas
    ``cons_pod``/``cons_node``), while accept/commit stay in jnp.

    The auction runs as a STATIC SIZE CHAIN inside the one jit program: the
    same round body at shrinking pod-array sizes (quartering, block-aligned,
    floored at _MIN_EPOCH_SIZE), advancing to the next size once the active
    count fits it.  Compaction keeps actives in a prefix, so each stage
    transition folds the finished rows' results into full-size rank-space
    buffers and takes a static prefix slice — all on device, zero host
    syncs.  Results are bit-identical to a single full-size loop because
    dropped rows are exactly the inactive ones and padding rows never
    influence a round (sentinel cells, rank-keyed jitter).
    """
    p_out = pods["pod_req"].shape[0]
    perm, ps = _prepare_pods(pods, block)
    p = ps["pod_req"].shape[0]
    if cmeta is not None:
        from .constraints import augment_round_state

        # Round-carried conflict state (spread water line, per-cell counts,
        # PA bootstrap flags) derived once at cycle start and updated
        # incrementally by constraint_commit inside the round body.
        cstate = {**augment_round_state(jnp, cstate, cmeta), "stall": jnp.int32(0)}
        ps["deferred"] = jnp.zeros((p,), bool)

    body = _make_round_body(
        nodes, weights, block, use_pallas, pallas_interpret, cmeta, soft_spread, soft_pa, hard_pa, tmeta
    )

    # Static size chain: p, p/4, p/16, … — aligned and floored by
    # _chain_size.  A stage is only appended when it
    # at least halves the previous one: a near-no-op tail stage (e.g. 300 →
    # 256) would pay a full extra while_loop + compiled round-body variant
    # for negligible savings.
    sizes = [p]
    while True:
        nxt = _chain_size(sizes[-1] // 4, block)
        if nxt > sizes[-1] // 2:
            break
        sizes.append(nxt)

    def make_cond(next_size, done):
        def cond(state):
            _, _, n_active, rounds, cst, _tst = state
            go = (rounds < max_rounds) & (n_active > 0) & ~done
            if cmeta is not None:
                go = go & (cst["stall"] < STALL_ROUNDS)
            if next_size:
                # Hand off to the next (smaller) stage once actives fit it.
                go = go & (n_active > next_size)
            return go

        return cond

    assigned_rank = jnp.zeros((p,), jnp.int32)
    acc_round_rank = jnp.zeros((p,), jnp.int32)
    avail = nodes["node_avail"]
    n_active = ps["active"].sum(dtype=jnp.int32)
    rounds = jnp.int32(0)
    cst = cstate
    tst = tstate
    # Terminal-exit latch: the stage-transition slice below is only safe
    # because a stage that exits via the round cap / stall / drained-pool
    # conditions (rather than the size handoff) guarantees every LATER stage
    # runs zero rounds — the slice may drop rows that are still active, and
    # the pre-slice fold preserves their unassigned state only if nothing
    # ever touches them again.  That used to be an implicit cross-stage
    # invariant riding on later conds re-checking the same rounds/stall
    # terms; ``done`` makes it explicit and robust against future per-stage
    # cond changes (e.g. resetting stall between stages).
    done = jnp.bool_(False)
    for i, size in enumerate(sizes):
        if i > 0:
            # Fold the rows about to be dropped (all inactive when the
            # previous stage exited via the size handoff — actives sit in
            # the compacted prefix and fit ``size``; on a terminal exit the
            # ``done`` latch keeps this stage at zero rounds), then slice.
            assigned_rank = assigned_rank.at[ps["ranks"]].set(ps["assigned"])
            acc_round_rank = acc_round_rank.at[ps["ranks"]].set(ps["acc_round"])
            ps = {k: v[:size] for k, v in ps.items()}
        next_size = sizes[i + 1] if i + 1 < len(sizes) else 0
        avail, ps, n_active, rounds, cst, tst = lax.while_loop(
            make_cond(next_size, done), body, (avail, ps, n_active, rounds, cst, tst)
        )
        terminal = (rounds >= max_rounds) | (n_active <= 0)
        if cmeta is not None:
            terminal = terminal | (cst["stall"] >= STALL_ROUNDS)
        done = done | terminal

    # Undo compaction (rank space), then the priority permutation (original
    # pod order), dropping block padding.
    assigned_rank = assigned_rank.at[ps["ranks"]].set(ps["assigned"])
    out = jnp.full((p_out,), -1, jnp.int32).at[perm].set(assigned_rank[:p_out])
    acc_round_rank = acc_round_rank.at[ps["ranks"]].set(ps["acc_round"])
    acc_round = jnp.full((p_out,), -1, jnp.int32).at[perm].set(acc_round_rank[:p_out])
    rank_of = jnp.zeros((p_out,), jnp.int32).at[perm].set(jnp.arange(p_out, dtype=jnp.int32))
    return out, rounds, avail, acc_round, rank_of


# Constraint cycles stop after STALL_ROUNDS consecutive ZERO-acceptance
# rounds (constant in ops/pack.py — jax-free for the native backend):
# unconstrained rounds always accept >=1 claimant (progress guarantee), but
# the within-round constraint filter can defer the same pods forever (e.g. a
# spread water line frozen by a capacity-full minimum domain) — measured 48
# wasted rounds to the cap at 5k pods.  Jitter re-rolls each round, so a few
# zero rounds may still unstick; after STALL_ROUNDS identical-state rounds
# the stragglers requeue to the next cycle instead (reference main.rs:122-125
# semantics — a retry later, never a crash or a spin).
