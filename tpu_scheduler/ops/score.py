"""Priority scoring — LeastRequested + BalancedAllocation as dense tensor ops
(BASELINE.json config 4).

The reference has *no* scoring (first feasible random candidate wins,
``src/main.rs:51-71``); this implements the standard kube-scheduler pair the
north star mandates, over the packed tensors:

  used_after[p,n,r] = (alloc[n,r] − avail[n,r]) + req[p,r]
  frac              = used_after / alloc              (1.0 where alloc == 0)
  least_requested   = mean_r(1 − frac) · 100
  balanced          = (1 − |frac_cpu − frac_mem|) · 100
  score             = w_lr · least_requested + w_ba · balanced

A third term breaks score ties deterministically: pods with identical
requests see identical LeastRequested/Balanced rows, so a whole batch would
herd onto one argmax node per auction round (the reference never hits this
because it samples randomly, ``main.rs:56``).  A hash-based per-(pod, node)
jitter — uint32 Knuth-multiplicative, identical wraparound semantics in
NumPy and XLA — spreads near-ties across near-tied nodes while leaving
materially different scores ordered.  Deterministic, so native/TPU/sharded
parity is preserved bitwise.

xp-generic (numpy / jax.numpy): one expression tree for both backends, all
float32 elementwise, so native and TPU scores agree bitwise.
"""

from __future__ import annotations

__all__ = ["score_block"]


# shape: (pod_req: [B, R] i32, node_alloc: [N, R] i32, node_avail: [N, R] i32,
#   weights: [W] f32, pod_idx: [B] u32, node_idx: [N] u32,
#   pod_pref_w: [B, A2] f32, node_pref: [N, A2] f32,
#   pod_ntol_soft: [B, Ts] f32, node_taints_soft: [N, Ts] f32,
#   pod_sps_declares: [B, Ss] f32, sp_penalty_node: [Ss, N] f32,
#   pod_sp_declares: [B, S] f32, sp_level_node: [S, N] f32,
#   pod_ppa_w: [B, Tp] f32, ppa_cnt_node: [Tp, N] f32,
#   salt: scalar any, pod_gang_id: [B] i32, topo_gang_node: [G, N] f32) -> [B, N] f32
def score_block(
    xp,
    pod_req,
    node_alloc,
    node_avail,
    weights,
    pod_idx=None,
    node_idx=None,
    pod_pref_w=None,
    node_pref=None,
    pod_ntol_soft=None,
    node_taints_soft=None,
    pod_sps_declares=None,
    sp_penalty_node=None,
    pod_sp_declares=None,
    sp_level_node=None,
    pod_ppa_w=None,
    ppa_cnt_node=None,
    salt=None,
    pod_gang_id=None,
    topo_gang_node=None,
):
    """[B, N] combined priority score of a block of pods against all nodes.

    pod_req [B,2] int32; node_alloc, node_avail [N,2] int32;
    weights [7] f32 — (least_requested_w, balanced_allocation_w, jitter,
    preferred_affinity_w, soft_taint_w, topology_w, gang_locality_w —
    models/profiles.py ``weights()`` order; index 6 is consumed upstream by
    topology/locality.gang_topology_term, not here); pod_idx [B] /
    node_idx [N] uint32 — global indices for the jitter hash (optional;
    jitter term is skipped when either is None).

    Soft terms (each optional-together, zero-width tensors are no-ops):
      • preferred node affinity: +w₃ · Σ matching-term weights
        (pod_pref_w [B,A2] · node_pref [N,A2], kube NodeAffinity scoring);
      • PreferNoSchedule taints: −w₄ per untolerated soft taint
        (pod_ntol_soft [B,Ts] · node_taints_soft [N,Ts], kube
        TaintToleration scoring);
      • ScheduleAnyway topology spread: −w₅ per matching placed pod already
        in the node's domain, per declared soft constraint
        (pod_sps_declares [B,Ss] · sp_penalty_node [Ss,N],
        ops/constraints.round_blocked_masks) — emptier domains score higher;
      • preferred inter-pod (anti-)affinity: ± term-weight per matching pod
        in the node's domain (pod_ppa_w [B,Tp] SIGNED weights ·
        ppa_cnt_node [Tp,N] domain match counts, kube InterPodAffinity
        scoring; anti-preference rides the same matmul with negative
        weights, so no extra global knob — the 1-100 term weights rule).
    """
    f32 = xp.float32
    # Scoring reads cpu/mem only (columns 0-1) — slice BEFORE the [B,N,·]
    # broadcast so extended-resource columns (R > 2) never materialize in
    # the hot path; bit-identical at R == 2.
    pod_req = pod_req[:, :2]
    node_alloc = node_alloc[:, :2]
    node_avail = node_avail[:, :2]
    used_after = (node_alloc - node_avail)[None, :, :] + pod_req[:, None, :]  # [B,N,2] int32
    safe = (node_alloc > 0)[None, :, :]
    denom = xp.where(safe, node_alloc.astype(f32)[None, :, :], f32(1.0))
    frac = xp.where(safe, used_after.astype(f32) / denom, f32(1.0))
    least_requested = ((f32(1.0) - frac[..., 0]) + (f32(1.0) - frac[..., 1])) * f32(50.0)
    balanced = (f32(1.0) - xp.abs(frac[..., 0] - frac[..., 1])) * f32(100.0)
    score = weights[0] * least_requested + weights[1] * balanced
    if pod_pref_w is not None and node_pref is not None:
        score = score + weights[3] * (pod_pref_w @ node_pref.T)
    if pod_ntol_soft is not None and node_taints_soft is not None:
        score = score - weights[4] * (pod_ntol_soft @ node_taints_soft.T)
    if pod_idx is not None and node_idx is not None:
        u32 = xp.uint32
        h = pod_idx.astype(u32)[:, None] * u32(2654435761) + node_idx.astype(u32)[None, :] * u32(2246822519)
        if salt is not None:
            # Auction-round salt: deferred pods re-roll their tie-break each
            # round instead of re-herding onto the same near-tied nodes —
            # spreads retries, cutting rounds.  Same wraparound semantics in
            # NumPy and XLA (uint32), so cross-backend parity is preserved.
            h = h + xp.asarray(salt).astype(u32) * u32(3266489917)
        h = (h ^ (h >> u32(15))) & u32(0xFFFF)
        # BUCKET-QUANTIZED tie-break: scores within one jitter-amplitude
        # bucket are treated as exact ties and ordered by the hash alone, so
        # claimants spread UNIFORMLY across the whole near-tied band instead
        # of clustering around its additive-jitter-weighted top.  Measured
        # motivation (round 5, a per-round kill diagnostic): with additive
        # jitter the flagship constrained tail's ~16k claimants chose only
        # ~11 distinct nodes per term — a few leader nodes sat just above
        # the ±32-point band and the capacity prefix killed 15k claimants a
        # round.  Same floor/div ops in numpy and XLA → parity holds; w₂=0
        # keeps the raw score (jitter off).
        jw = weights[2]
        safe = xp.where(jw > 0, jw, f32(1.0))
        score = xp.where(jw > 0, xp.floor(score / safe) * safe, score) + jw * (h.astype(f32) / f32(65536.0))
    if pod_sps_declares is not None and sp_penalty_node is not None:
        score = score - weights[5] * (pod_sps_declares @ sp_penalty_node)
    if pod_sp_declares is not None and sp_level_node is not None:
        # HARD-spread declarer steering: −2·jitter-amplitude per level the
        # node's domain sits above the constraint's water line
        # (ops/constraints.round_blocked_masks ``sp_level_node``).  Levels
        # dominate the ±jitter tie-break, so declarers target the domains
        # the admission filter can actually accept; nodes within one level
        # stay jitter-spread.  Score-neutral for everyone else.
        score = score - (f32(2.0) * weights[2]) * (pod_sp_declares @ sp_level_node)
    if pod_ppa_w is not None and ppa_cnt_node is not None:
        score = score + pod_ppa_w @ ppa_cnt_node
    if pod_gang_id is not None and topo_gang_node is not None:
        # Rank-aware gang co-placement (topology/locality.py): the per-round
        # [G+1, N] anchor/fit/herd tensor is SHARED by every member of a
        # gang, so the whole batched all-ranks term is one row gather here.
        # Added after the jitter quantization, like the hard-spread steering:
        # its herd component is sized to dominate the per-pod tie-break so a
        # gang converges on one domain instead of scattering across
        # near-ties.  Row 0 is pinned to zero — score-neutral for gangless
        # pods (and block padding, which lands in gang 0).
        score = score + topo_gang_node[pod_gang_id]
    return score.astype(f32)
