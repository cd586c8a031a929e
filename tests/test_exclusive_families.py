"""Exclusive-placement families (ops/constraints.ExclusiveFamilies): JobSet's
``exclusive-topology`` term pair folded per (namespace, topology key, label
key) instead of one row per job.

The contract: on fleets the per-term rows can hold, the families bind
exactly what the rows bind, on both backends; past the budgets the cycle
stays on the tensor path, native and TPU agree bit for bit, and every job
lands whole and alone in one pool.  Each exactness rule flips one verdict.
"""

import random
from dataclasses import replace

import numpy as np
import pytest

from tpu_scheduler.api.objects import Toleration
from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.backends.tpu import TpuBackend
from tpu_scheduler.core.snapshot import ClusterSnapshot
from tpu_scheduler.models.profiles import DEFAULT_PROFILE
from tpu_scheduler.ops.constraints import (
    EX_MANY,
    EX_NONE,
    MAX_AA_TERMS,
    UntensorizableConstraints,
    pack_constraints,
)
from tpu_scheduler.ops.pack import pack_snapshot
from tpu_scheduler.testing import (
    JOBSET_JOB_KEY,
    TPU_RESOURCE,
    jobset_job,
    jobset_terms,
    make_pod,
    tpu_slice_nodes,
)
from tpu_scheduler.topology.model import GKE_NODEPOOL_KEY

POOL, JOB = GKE_NODEPOOL_KEY, JOBSET_JOB_KEY
_nodes, _job = tpu_slice_nodes, jobset_job


def _anti(job: str):
    return jobset_terms(job)[1][0]


def _aff(job: str):
    return jobset_terms(job)[0][0]


def _fleet(n_pools: int, running: int, pending: int, seed: int, hosts: int = 2, gang: bool = True):
    """``n_pools`` pools of ``hosts`` hosts (two topologies), the first
    ``running`` holding a job each, ``pending`` jobs waiting."""
    rng = random.Random(seed)
    topos = [("2x4" if s % 3 else "4x4", hosts) for s in range(n_pools)]
    nodes = _nodes(topos)
    pods = []
    for j in range(running):
        on = [f"slice-{j}-{h}" for h in range(hosts)]
        pods += _job(f"run-{j}", topos[j][0], hosts, rng.randrange(3), on, gang)
    for j in range(pending):
        pods += _job(f"new-{j}", rng.choice(["2x4", "4x4"]), hosts, rng.randrange(3), gang=gang)
    return ClusterSnapshot.build(nodes, pods)


def _packed(snap, **kw):
    packed = pack_snapshot(snap)
    cons = pack_constraints(snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes, **kw)
    return replace(packed, constraints=cons) if cons is not None else packed


def _pools_of(snap, bindings) -> dict:
    pool = {n.name: n.metadata.labels[POOL] for n in snap.nodes}
    jobs: dict = {}
    for q, node in snap.placed_pods():
        jobs.setdefault(q.metadata.labels[JOB], set()).add(pool[node.name])
    for pf, node in bindings:
        job = pf.split("/", 1)[1].rsplit("-", 1)[0]
        jobs.setdefault(job, set()).add(pool[node])
    return jobs


def _assert_exclusive(snap, bindings):
    """Every job in one pool, and no pool holding two jobs."""
    jobs = _pools_of(snap, bindings)
    owners: dict = {}
    for job, pools in jobs.items():
        assert len(pools) == 1, (job, pools)
        (p,) = pools
        assert owners.setdefault(p, job) == job, (p, owners[p], job)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_families_bind_what_the_per_term_rows_bind(seed):
    snap = _fleet(n_pools=24, running=10, pending=14, seed=seed)
    rows = _packed(snap, exclusive=False)
    fams = _packed(snap, exclusive=True)
    assert rows.constraints.n_families == 0 and fams.constraints.n_families == 1
    assert fams.constraints.n_terms == 0 and fams.constraints.n_pa_terms == 0
    for backend in (NativeBackend(), TpuBackend()):
        r_rows = backend.schedule(rows, DEFAULT_PROFILE)
        r_fams = backend.schedule(fams, DEFAULT_PROFILE)
        assert r_rows.bindings == r_fams.bindings
        assert np.array_equal(r_rows.assigned, r_fams.assigned)
    _assert_exclusive(snap, r_fams.bindings)


def test_past_both_budgets_native_and_tpu_agree_bit_for_bit():
    # 300 pools (> MAX_COARSE_DOMAINS) and 290 jobs (> MAX_AA_TERMS terms).
    snap = _fleet(n_pools=300, running=270, pending=20, seed=11)
    assert len({n.metadata.labels[POOL] for n in snap.nodes}) > 256 and 290 > MAX_AA_TERMS
    with pytest.raises(UntensorizableConstraints):
        _packed(snap, exclusive=False)
    packed = _packed(snap, exclusive=True)
    assert packed.constraints.n_families == 1 and packed.constraints.n_terms == 0
    rn = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    rt = TpuBackend().schedule(packed, DEFAULT_PROFILE)
    assert rn.bindings == rt.bindings and rn.rounds == rt.rounds
    assert np.array_equal(rn.assigned, rt.assigned)
    assert rn.bindings
    _assert_exclusive(snap, rn.bindings)


def _one_node_pools(n: int) -> list:
    return _nodes([("4x4", 1)] * n)


def _pod(name, label=None, anti=None, aff=None, on=None):
    """A one-host pod: labelled ``job-key = label``, carrying the exclusive
    anti-affinity of ``anti`` and the pod affinity of ``aff``."""
    return make_pod(name, cpu="8", memory="32Gi", node_name=on, phase="Running" if on else "Pending",
                    labels={JOB: label} if label else {"app": "x"}, extended={TPU_RESOURCE: "4"},
                    tolerations=[Toleration(key=TPU_RESOURCE, operator="Exists", effect="NoSchedule")],
                    anti_affinity=[_anti(anti)] if anti else None, pod_affinity=[_aff(aff)] if aff else None)


def _blocked(snap, exclusive: bool) -> np.ndarray:
    """[pending, nodes] constraint-blocked verdicts at round start."""
    from tpu_scheduler.ops.constraints import augment_round_state, blocked_block, round_blocked_masks

    packed = _packed(snap, exclusive=exclusive)
    cons = packed.constraints
    meta = cons.meta_arrays()
    state = augment_round_state(np, cons.state_arrays(), meta)
    masks = round_blocked_masks(np, state, meta, hard_pa=cons.n_pa_terms > 0)
    b = blocked_block(np, cons.pod_arrays(), masks)
    return b[: len(snap.pending_pods()), : len(snap.nodes)]


def _verdicts(placed: list, pending: list) -> np.ndarray:
    """Blocked verdicts of ``pending`` against three one-node pools, with the
    families and with per-term rows; they must agree."""
    snap = ClusterSnapshot.build(_one_node_pools(3), placed + pending)
    fams, rows = _blocked(snap, True), _blocked(snap, False)
    assert np.array_equal(fams, rows)
    return fams


def test_a_labelled_pod_without_a_term_is_kept_from_another_jobs_pool():
    carrier = _pod("run-x", label="x", anti="x", on="slice-0-0")
    base = _verdicts([carrier], [_pod("q", label="x")])
    flip = _verdicts([carrier], [_pod("q", label="y")])
    assert not base.any()
    assert (base != flip).sum() == 1 and flip[0, 0]  # pool 0 holds x's carrier


def test_a_carrier_whose_value_differs_from_its_label_is_kept_from_its_own_label():
    labelled = _pod("lab-x", label="x", on="slice-0-0")
    base = _verdicts([labelled], [_pod("q", label="x", anti="x")])
    flip = _verdicts([labelled], [_pod("q", label="x", anti="z")])
    assert not base.any()
    assert (base != flip).sum() == 1 and flip[0, 0]  # its own term matches x's label


def test_a_many_domain_keeps_pod_affinity_on_per_term_rows():
    one = [_pod("a-x", label="x", on="slice-0-0")]
    many = one + [_pod("b-y", label="y", on="slice-0-0")]
    pending = [_pod("q", label="x", aff="x")]
    for placed, folded in ((one, True), (many, False)):
        cons = _packed(ClusterSnapshot.build(_one_node_pools(3), placed + pending)).constraints
        assert bool(cons.exclusive is not None and cons.exclusive.folded_pa) == folded
        assert cons.n_pa_terms == (0 if folded else 1)  # the per-term row is back
    assert (_verdicts(one, pending) == _verdicts(many, pending)).all()  # either way only pool 0 holds x


def test_the_self_match_bootstrap_waives_an_affinity_nothing_carries():
    alone = _verdicts([], [_pod("q", label="a", aff="a")])
    assert not alone.any()  # no pod carries a, and q does: anywhere
    other = _verdicts([], [_pod("q", label="b", aff="a")])
    assert other.all()  # q does not carry a: nowhere
    anchored = _verdicts([_pod("run-a", label="a", on="slice-1-0")], [_pod("q", label="a", aff="a")])
    assert (alone != anchored).sum() == 2 and not anchored[0, 1]  # a is placed: only its pool


def test_state_summaries_read_none_one_value_and_many():
    placed = [_pod("a", label="x", on="slice-0-0"), _pod("b", label="y", on="slice-0-0"),
              _pod("c", label="x", anti="x", on="slice-1-0")]
    snap = ClusterSnapshot.build(_one_node_pools(3), placed + [_pod("q", label="z", anti="z")])
    ex = _packed(snap).constraints.exclusive
    m, c = ex.state["ex_m"][0], ex.state["ex_c"][0]
    dom = ex.meta["ex_node_dom"][0, :3]
    assert m[dom[0]] == EX_MANY and m[dom[2]] == EX_NONE and c[dom[0]] == EX_NONE and c[dom[2]] == EX_NONE
    assert m[dom[1]] == c[dom[1]] >= 0  # x, labelled and carried


@pytest.mark.parametrize("backend", [NativeBackend, TpuBackend])
def test_two_jobs_ranked_into_one_free_pool_bind_only_the_first(backend):
    nodes = _nodes([("4x4", 2), ("2x4", 2)])
    pods = _job("run-0", "2x4", 2, on=["slice-1-0", "slice-1-1"])
    pods += _job("hi", "4x4", 1, priority=10, gang=False) + _job("lo", "4x4", 1, priority=5, gang=False)
    snap = ClusterSnapshot.build(nodes, pods)
    for exclusive in (True, False):
        r = backend().schedule(_packed(snap, exclusive=exclusive), DEFAULT_PROFILE)
        assert [pf for pf, _ in r.bindings] == ["default/hi-0"]
        assert r.stats["acc_round"][[i for i, p in enumerate(snap.pending_pods()) if p.metadata.name == "hi-0"][0]] == 0


def test_sharded_backend_raises_for_families_and_is_not_given_them():
    from tpu_scheduler.parallel.sharded import ShardedBackend

    backend = ShardedBackend(tp=2)
    assert not backend.supports_exclusive_families
    assert NativeBackend.supports_exclusive_families and TpuBackend.supports_exclusive_families
    packed = _packed(_fleet(n_pools=6, running=2, pending=2, seed=3))
    with pytest.raises(UntensorizableConstraints, match="exclusive families"):
        backend.schedule(packed, DEFAULT_PROFILE)


def test_family_cycles_keep_the_widest_pod_axis():
    widths: dict = {}
    big = _fleet(n_pools=40, running=0, pending=30, seed=4)
    small = _fleet(n_pools=40, running=0, pending=3, seed=4)
    first = _packed(big, widths=widths).constraints
    second = _packed(small, widths=widths).constraints
    assert first.padded_pods == second.padded_pods == 1 << (first.padded_pods - 1).bit_length()
    assert second.exclusive.pod["pod_ex_label"].shape[0] == second.padded_pods


def test_served_path_keeps_a_fleet_past_the_budgets_on_the_device():
    from tpu_scheduler import cli

    snap = _fleet(n_pools=320, running=270, pending=40, seed=5, hosts=1)
    args = cli.build_parser().parse_args(["--backend", "tpu", "--no-fallback", "--nodes", "0", "--pods", "0"])
    api, sched = cli.build_scheduler(args)
    api.load(list(snap.nodes), list(snap.pods))
    m = sched.run_cycle()
    counters = sched.metrics.snapshot()
    assert counters.get("scheduler_constraint_host_fallbacks_total", 0) == 0
    assert counters.get("scheduler_exclusive_terms_folded_total", 0) >= 310  # 310 anti-affinity terms, 40 affinity
    assert sched.metrics._snapshot_full()["gauges"].get("scheduler_exclusive_families") == 1
    assert m.bound > 0 and m.exclusive_seconds > 0
    placed = [(p, p.spec.node_name) for p in api.list_pods() if p.spec.node_name]
    final = ClusterSnapshot.build(list(snap.nodes), [p for p, _ in placed])
    _assert_exclusive(final, [])
    free = {("4x4" if s % 3 == 0 else "2x4") for s in range(270, 320)}
    pending_jobs = {p.metadata.labels[JOB] for p in api.list_pods() if not p.spec.node_name}
    assert len(_pools_of(final, [])) == 270 + 40 - len(pending_jobs) and free
