"""Incremental delta-scheduling engine (tpu_scheduler/delta): verdict skip
+ invalidation closure, capacity-ledger exactness (incl. breaker-deferred
flush exactly-once), escalation triggers, shards/takeover composition,
checkpoint v4, candidate-node compaction, and the shadow-solve parity gate
on the churn-steady-state scenario (record→replay bit-identity, seeds 0/1).
"""

import json
import os

import pytest

from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.delta import DeltaEngine
from tpu_scheduler.runtime.controller import Scheduler
from tpu_scheduler.runtime.fake_api import FakeApiServer
from tpu_scheduler.testing import make_node, make_pod, synth_cluster

from conftest import FakeClock


def _sched(api, clock=None, **kw):
    return Scheduler(api, NativeBackend(), clock=clock or FakeClock(), requeue_seconds=0.0, **kw)


def _audit_capacity(sched) -> None:
    """The engine's carried used64 must equal a fresh exact sweep over the
    live API state — the ledger-truth invariant every fold rule preserves."""
    from tpu_scheduler.core.snapshot import ClusterSnapshot
    from tpu_scheduler.ops.pack import _alloc_and_used64

    st = sched.delta.state
    assert st is not None, "engine has no SolveState to audit"
    snap = ClusterSnapshot.build(sched.api.list_nodes(), sched.api.list_pods())
    # Overlay deferred/assumed commitments the API does not show yet.
    extra = dict(sched.deferred_binds)
    extra.update(sched._assumed)
    alloc64, used64, row = _alloc_and_used64(snap, st.alloc64.shape[0], None, st.res_vocab)
    for pf, node in extra.items():
        ns, _, name = pf.rpartition("/")
        p = {f"{q.metadata.namespace or 'default'}/{q.metadata.name}": q for q in snap.pods}.get(pf)
        if p is not None and (p.spec is None or p.spec.node_name is None) and node in row:
            from tpu_scheduler.delta.state import req64_of

            used64[row[node]] += req64_of(p, st.res_vocab)
    assert (st.alloc64 == alloc64).all(), "alloc drifted from the live truth"
    assert (st.used64 == used64).all(), "used64 drifted from the live truth"


# -- verdict skip + invalidation closure ------------------------------------


def test_standing_verdict_skips_until_capacity_frees():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="4", memory="8Gi"))
    api.create_pod(make_pod("filler", cpu="3", memory="1Gi"))
    sched = _sched(api)
    assert sched.run_cycle().bound == 1  # cold full wave
    api.create_pod(make_pod("big", cpu="3", memory="1Gi"))
    m = sched.run_cycle()
    assert m.unschedulable == 1  # delta cycle solved the dirty pod, proved it stuck
    assert sched.delta.stats()["standing_verdicts"] == 1
    # Nothing changed: the verdict stands, the futile re-solve is elided.
    m2 = sched.run_cycle()
    assert m2.unschedulable == 0 and m2.bound == 0
    assert sched.delta.stats()["skipped_total"] >= 1
    # Capacity frees -> the closure retires the verdict -> the pod binds.
    api.delete_pod("default", "filler")
    m3 = sched.run_cycle()
    assert m3.bound == 1
    assert sched.delta.stats()["standing_verdicts"] == 0
    assert sched.delta.stats()["full_solves"] == 1  # only the cold start
    _audit_capacity(sched)


def test_unrelated_node_churn_leaves_standing_verdicts_untouched():
    """ISSUE 11 satellite (ROADMAP): per-node blocking sets — freed
    capacity on a node the verdict's pod could never land on (selector
    excluded) must NOT retire the verdict, while a free on a blocking
    node still does."""
    api = FakeApiServer()
    api.create_node(make_node("a1", cpu="4", memory="8Gi", labels={"zone": "zone-a"}))
    api.create_node(make_node("b1", cpu="8", memory="16Gi", labels={"zone": "zone-b"}))
    api.create_pod(make_pod("fill-a", cpu="3", memory="1Gi", node_selector={"zone": "zone-a"}))
    api.create_pod(make_pod("fill-b", cpu="2", memory="1Gi", node_selector={"zone": "zone-b"}))
    sched = _sched(api)
    assert sched.run_cycle().bound == 2  # cold full wave
    api.create_pod(make_pod("pinned", cpu="3", memory="1Gi", node_selector={"zone": "zone-a"}))
    m = sched.run_cycle()
    assert m.unschedulable == 1
    st = sched.delta.state
    _pa, _g, blocked, constrained = st.unsched["default/pinned"]
    assert not constrained and blocked == frozenset({"a1"})
    # Churn on the UNRELATED node: capacity frees on b1, but b1 is outside
    # the blocking set — the verdict stands and the re-solve stays elided.
    api.delete_pod("default", "fill-b")
    m2 = sched.run_cycle()
    assert m2.bound == 0 and m2.unschedulable == 0
    assert sched.delta.stats()["standing_verdicts"] == 1
    assert sched.delta.stats()["skipped_total"] >= 1
    # A free on the BLOCKING node retires the verdict and the pod binds.
    api.delete_pod("default", "fill-a")
    m3 = sched.run_cycle()
    assert m3.bound == 1
    assert sched.delta.stats()["standing_verdicts"] == 0
    assert sched.delta.stats()["full_solves"] == 1  # only the cold start
    _audit_capacity(sched)


def test_constrained_verdict_still_retires_on_any_free():
    """The per-node narrowing must NOT apply to cross-node-entangled
    verdicts: an anti-affinity-blocked pod retires on any freed capacity
    (a placed-pod deletion anywhere can shift its domain state)."""
    from tpu_scheduler.api.objects import PodAntiAffinityTerm

    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="4", memory="8Gi", labels={"zone": "zone-a"}))
    api.create_node(make_node("n2", cpu="4", memory="8Gi", labels={"zone": "zone-a"}))
    carrier = make_pod("carrier", cpu="1", memory="1Gi", labels={"app": "x"})
    api.create_pod(carrier)
    sched = _sched(api)
    assert sched.run_cycle().bound == 1
    # A pod anti-affine to app=x over the zone key: with the carrier
    # placed, no zone-a node is feasible.
    api.create_pod(
        make_pod(
            "anti",
            cpu="1",
            memory="1Gi",
            anti_affinity=[PodAntiAffinityTerm(topology_key="zone", match_labels={"app": "x"})],
        )
    )
    m = sched.run_cycle()
    assert m.unschedulable == 1
    ent = sched.delta.state.unsched["default/anti"]
    assert ent[3] is True  # constrained: the coarse any-free rule applies
    api.delete_pod("default", "carrier")
    m2 = sched.run_cycle()
    assert m2.bound == 1
    assert sched.delta.stats()["standing_verdicts"] == 0


def test_modified_pod_re_dirties_its_own_verdict():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="2", memory="4Gi"))
    api.create_pod(make_pod("wants-too-much", cpu="8", memory="1Gi"))
    sched = _sched(api)
    sched.run_cycle()
    assert sched.delta.stats()["standing_verdicts"] == 1
    # The pod object is replaced with a satisfiable spec: MODIFIED event.
    api.delete_pod("default", "wants-too-much")
    api.create_pod(make_pod("wants-too-much", cpu="1", memory="1Gi"))
    m = sched.run_cycle()
    assert m.bound == 1
    assert sched.delta.stats()["full_solves"] == 1


def test_gang_closure_re_dirties_gang_mates():
    clock = FakeClock()
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="5", memory="16Gi"))
    api.create_pod(make_pod("g-0", cpu="3", memory="1Gi", gang="g"))
    api.create_pod(make_pod("g-1", cpu="3", memory="1Gi", gang="g"))
    sched = Scheduler(api, NativeBackend(), clock=clock, requeue_seconds=5.0)
    sched.run_cycle()  # 6 > 5: gang rejected whole, both verdicts stand
    assert sched.delta.stats()["standing_verdicts"] == 2
    # A FRESH member arrives while the mates sit in backoff: the gang
    # closure must retire their verdicts (membership changed), even though
    # no capacity freed and no node changed.
    clock.t = 1.0
    api.create_pod(make_pod("g-2", cpu="3", memory="1Gi", gang="g"))
    sched.run_cycle()
    assert sched.delta.stats()["standing_verdicts"] == 1  # only g-2's fresh verdict
    # Once every member is eligible again the whole gang re-solves (and is
    # re-proven stuck as a unit: 9 > 5).
    clock.t = 200.0
    m = sched.run_cycle()
    assert m.unschedulable == 3
    # Shrink the gang until it fits: pending deletes retire the verdicts.
    api.delete_pod("default", "g-1")
    api.delete_pod("default", "g-2")
    clock.t = 600.0
    m2 = sched.run_cycle()
    assert m2.bound == 1  # g-0 alone is a whole gang and fits
    assert sched.delta.stats()["full_solves"] == 1  # cold only — all delta cycles
    _audit_capacity(sched)


def test_pod_affinity_seeker_re_dirties_on_new_placement():
    from tpu_scheduler.api.objects import PodAffinityTerm

    term = [PodAffinityTerm(match_labels={"app": "anchor"}, topology_key="zone")]
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="8", memory="16Gi", labels={"zone": "a"}))
    sched = _sched(api)
    sched.run_cycle()
    api.create_pod(make_pod("seeker", cpu="1", memory="1Gi", labels={"app": "web"}, pod_affinity=term))
    sched.run_cycle()
    assert sched.delta.stats()["standing_verdicts"] == 1  # no anchor anywhere
    api.create_pod(make_pod("anchor", cpu="1", memory="1Gi", labels={"app": "anchor"}))
    m = sched.run_cycle()  # anchor binds; its placement retires the seeker's verdict
    m2 = sched.run_cycle()
    assert m.bound + m2.bound == 2, "the seeker must co-locate once the anchor placed"
    # An empty-pending first cycle stays cold (no packed axis to rebuild
    # against); what matters is that no NON-cold escalation was needed.
    assert set(sched.delta.stats()["full_solve_reasons"]) <= {"cold"}


# -- capacity ledger exactness ----------------------------------------------


def test_capacity_ledger_tracks_churn_exactly():
    api = FakeApiServer()
    base = synth_cluster(n_nodes=20, n_pending=100, n_bound=40, seed=3)
    api.load(base.nodes, base.pods)
    sched = _sched(api)
    sched.run_cycle()
    _audit_capacity(sched)
    # Churn: completions + fresh arrivals across several delta cycles.
    bound = [p for p in api.list_pods() if p.spec is not None and p.spec.node_name]
    for i, p in enumerate(bound[:10]):
        api.delete_pod(p.metadata.namespace or "default", p.metadata.name)
        if i % 2 == 0:
            api.create_pod(make_pod(f"fresh-{i}", cpu="1", memory="1Gi"))
        sched.run_cycle()
        _audit_capacity(sched)
    s = sched.delta.stats()
    assert s["delta_cycles"] >= 10 and s["full_solves"] == 1


def test_breaker_deferred_flush_commits_exactly_once():
    clock = FakeClock()
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="16", memory="32Gi"))
    sched = _sched(api, clock=clock)
    sched.run_cycle()
    # Open the breaker with bind failures, then defer a real placement.
    api.fail_next_bindings = 10
    for i in range(6):
        api.create_pod(make_pod(f"fail-{i}", cpu="1", memory="1Gi"))
        clock.t += 1.0
        sched.run_cycle()
    assert sched.breaker.state == "open"
    api.create_pod(make_pod("held", cpu="2", memory="2Gi"))
    clock.t += 0.1
    sched.run_cycle()
    assert "default/held" in sched.deferred_binds
    assert "default/held" in sched.delta.state.placements  # committed ONCE at defer
    _audit_capacity(sched)
    # Recovery: the flush POSTs, the watch confirms, the ledger must not
    # double-count — and the recovery itself forces one full-wave rebuild.
    api.fail_next_bindings = 0  # blackout over
    clock.t += 120.0
    for _ in range(8):
        clock.t += 10.0
        sched.run_cycle()
    assert not sched.deferred_binds
    held = [p for p in api.list_pods() if p.metadata.name == "held"]
    assert held and held[0].spec.node_name == "n1"
    _audit_capacity(sched)
    assert "breaker-recovery" in sched.delta.stats()["full_solve_reasons"]


def test_failed_async_bind_uncommits():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="4", memory="8Gi"))
    sched = Scheduler(api, NativeBackend(), clock=FakeClock(), requeue_seconds=0.0, pipeline=True)
    sched.run_cycle()
    api.fail_next_bindings = 1
    api.create_pod(make_pod("p1", cpu="1", memory="1Gi"))
    sched.run_cycle()  # dispatches the bind; the failure folds next cycle
    sched._join_binds()
    sched.run_cycle()  # failure requeued -> uncommit
    sched.run_cycle()  # retry succeeds
    sched._join_binds()
    sched.run_cycle()  # fold the confirm
    _audit_capacity(sched)
    sched.close()


# -- escalation triggers -----------------------------------------------------


def test_node_change_escalates_to_full_wave():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="4", memory="8Gi"))
    api.create_pod(make_pod("p1", cpu="1", memory="1Gi"))
    sched = _sched(api)
    sched.run_cycle()
    api.create_node(make_node("n2", cpu="4", memory="8Gi"))
    api.create_pod(make_pod("p2", cpu="1", memory="1Gi"))
    m = sched.run_cycle()
    assert m.bound == 1
    assert "node-change" in sched.delta.stats()["full_solve_reasons"]
    _audit_capacity(sched)


def test_epoch_refresh_escalates_periodically():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="64", memory="128Gi"))
    sched = _sched(api)
    sched.delta.epoch_refresh = 3
    sched.run_cycle()
    for i in range(12):
        api.create_pod(make_pod(f"p-{i}", cpu="100m", memory="64Mi"))
        sched.run_cycle()
    assert sched.delta.stats()["full_solve_reasons"].get("epoch-refresh", 0) >= 2


def test_closure_overflow_escalates(monkeypatch):
    from tpu_scheduler.delta.index import DeltaIndex

    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="64", memory="128Gi"))
    sched = _sched(api)
    sched.delta.OVERFLOW_MIN = 2
    sched.run_cycle()
    api.create_pod(make_pod("a", cpu="100m", memory="64Mi"))
    sched.run_cycle()
    for i in range(8):  # dirty wave > max(2, half the cluster's pods)
        api.create_pod(make_pod(f"wave-{i}", cpu="100m", memory="64Mi"))
    # The overflow is certain before the fold (8 pending, no standing
    # verdicts): the plan escalates without folding and drops the buffer.
    plans = []
    plan = sched.delta.plan

    def watched_plan(*a, **k):
        buffered = sched.delta.index.pending_events()
        out = plan(*a, **k)
        plans.append((out, buffered, sched.delta.index.pending_events()))
        return out

    def no_fold(self, state, events):
        raise AssertionError("the fold ran on a certain overflow")

    monkeypatch.setattr(sched.delta, "plan", watched_plan)
    monkeypatch.setattr(DeltaIndex, "fold", no_fold)
    m = sched.run_cycle()
    assert m.bound == 8
    assert "closure-overflow" in sched.delta.stats()["full_solve_reasons"]
    assert sched.delta.stats()["folds_skipped"] == 1
    assert sched.metrics.snapshot()["scheduler_delta_fold_skipped_total"] == 1
    assert len(plans) == 1 and plans[0][0] is None and plans[0][1] > 0 and plans[0][2] == 0
    monkeypatch.undo()

    # Counter-case: standing verdicts keep pending − standing under the
    # threshold, so the fold runs; freed capacity retires the verdicts and
    # the post-close test decides the overflow.
    folds = []
    fold = DeltaIndex.fold

    def counted_fold(self, state, events):
        folds.append(len(events))
        return fold(self, state, events)

    monkeypatch.setattr(DeltaIndex, "fold", counted_fold)
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="4", memory="8Gi"))
    api.create_pod(make_pod("filler", cpu="3", memory="1Gi"))
    sched = _sched(api)
    sched.delta.OVERFLOW_MIN = 2
    sched.run_cycle()
    for i in range(8):
        api.create_pod(make_pod(f"big-{i}", cpu="2", memory="1Gi"))
    sched.run_cycle()  # 8 unschedulable verdicts from a (certain) overflow
    assert sched.delta.stats()["standing_verdicts"] == 8
    assert sched.delta.stats()["folds_skipped"] == 1
    sched.run_cycle()  # nothing changed: a delta cycle skips all eight
    api.delete_pod("default", "filler")
    folds.clear()
    m = sched.run_cycle()
    assert folds == [1]  # the delete folded, and retired all eight verdicts
    assert sched.delta.stats()["folds_skipped"] == 1
    assert sched.delta.stats()["full_solve_reasons"] == {"closure-overflow": 2, "cold": 1}
    assert m.bound == 2


def test_cycle_aborted_after_a_skipped_fold_leaves_the_engine_cold(monkeypatch):
    """A cycle that raises between a pre-fold overflow escalation and its
    commit must not leave the engine holding placements the dropped watch
    events changed: the next small cycle rebuilds instead of solving
    against a ledger that still counts the deleted wave."""
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="4", memory="8Gi"))
    for i in range(8):
        api.create_pod(make_pod(f"w1-{i}", cpu="500m", memory="64Mi"))
    sched = _sched(api)
    sched.delta.OVERFLOW_MIN = 2
    assert sched.run_cycle().bound == 8  # cold: the node is full
    for i in range(8):
        api.delete_pod("default", f"w1-{i}")
        api.create_pod(make_pod(f"w2-{i}", cpu="100m", memory="64Mi"))

    def boom(*a, **k):
        raise RuntimeError("cycle ends before its commit")

    monkeypatch.setattr(sched, "_run_batch_cycle", boom)
    with pytest.raises(RuntimeError):
        sched.run_cycle()
    monkeypatch.undo()
    stats = sched.delta.stats()
    assert stats["folds_skipped"] == 1 and not stats["valid"]
    assert sched.delta.index.pending_events() == 0
    for i in range(8):
        api.delete_pod("default", f"w2-{i}")
    api.create_pod(make_pod("solo", cpu="4", memory="64Mi"))
    m = sched.run_cycle()  # one pending pod: no overflow, but no state either
    assert m.bound == 1
    assert sched.delta.stats()["full_solve_reasons"] == {"cold": 2}
    assert set(sched.delta.state.placements) == {"default/solo"}
    _audit_capacity(sched)


class _FoldFirstEngine(DeltaEngine):
    """The engine whose plan always folds before the overflow test (the
    path without the pre-check)."""

    @staticmethod
    def _dirty_floor(st, pending, preempting):
        return 0


def _wave_script(api, clock, sched):
    """Waves created, cycled, deleted and created again, with requeued
    pods (failed binds) and standing verdicts (an unmatched selector) in
    between; yields after every cycle."""

    def cycle():
        clock.t += 1.0
        sched.run_cycle()
        return True

    def wave(tag, n):
        for i in range(n):
            api.create_pod(make_pod(f"{tag}-{i}", cpu="250m", memory="256Mi", labels={"wave": tag}))

    def retire(tag):
        for p in api.list_pods():
            if (p.metadata.labels or {}).get("wave") == tag:
                api.delete_pod(p.metadata.namespace or "default", p.metadata.name)

    for i in range(5):
        api.create_pod(make_pod(f"stuck-{i}", cpu="100m", memory="64Mi", node_selector={"zone": "nowhere"}))
    yield cycle()  # cold
    wave("w1", 24)
    yield cycle()
    retire("w1")
    wave("w2", 24)
    api.fail_next_bindings = 4
    yield cycle()
    yield cycle()  # the four failed binds retry
    api.create_pod(make_pod("late", cpu="250m", memory="256Mi"))
    yield cycle()  # a small delta cycle
    api.create_pod(make_pod("stuck-5", cpu="100m", memory="64Mi", node_selector={"zone": "nowhere"}))
    yield cycle()
    retire("w2")
    wave("w3", 30)
    yield cycle()
    retire("w3")
    yield cycle()
    wave("w4", 6)
    yield cycle()


def _engine_view(sched) -> dict:
    st = sched.delta.state
    return {
        "placements": {pf: (r, n, req.tolist()) for pf, (r, n, req) in st.placements.items()},
        "used64": st.used64.tolist(),
        "unsched": dict(st.unsched),
        "reasons": sched.delta.stats()["full_solve_reasons"],
        "bindings": sorted((p.metadata.name, p.spec.node_name) for p in sched.api.list_pods() if p.spec.node_name),
    }


@pytest.mark.parametrize("preempting", [False, True])
def test_overflow_precheck_matches_the_fold_first_path(preempting, monkeypatch):
    """The pre-fold overflow escalation does the same work: after every
    cycle of a scripted wave sequence, the SolveState, the escalation
    tally and the bindings equal those of the engine that folds first."""
    import random

    import tpu_scheduler.delta as delta_pkg
    from tpu_scheduler.models.profiles import DEFAULT_PROFILE

    def build(engine_cls):
        monkeypatch.setattr(delta_pkg, "DeltaEngine", engine_cls)
        api = FakeApiServer()
        for i in range(4):
            api.create_node(make_node(f"n{i}", cpu="8", memory="16Gi", labels={"zone": "a" if i % 2 else "b"}))
        clock = FakeClock()
        sched = Scheduler(
            api,
            NativeBackend(),
            profile=DEFAULT_PROFILE.with_(preemption=preempting),
            clock=clock,
            requeue_seconds=0.0,
            rng=random.Random(7),
        )
        sched.delta.OVERFLOW_MIN = 4
        return _wave_script(api, clock, sched), sched

    new_steps, new = build(DeltaEngine)
    old_steps, old = build(_FoldFirstEngine)
    assert type(old.delta) is _FoldFirstEngine and type(new.delta) is DeltaEngine
    for n_cycle, _ in enumerate(zip(new_steps, old_steps)):
        assert _engine_view(new) == _engine_view(old), f"diverged after cycle {n_cycle}"
    # No verdict retires in this script, so the pre-check takes every
    # overflow; the fold-first engine never skips a fold.
    assert new.delta.folds_skipped == new.delta.full_solve_reasons["closure-overflow"] >= 3
    assert old.delta.folds_skipped == 0
    assert new.delta.delta_cycles >= 2


def test_preempting_profile_keeps_eligible_pods_dirty():
    from tpu_scheduler.models.profiles import DEFAULT_PROFILE

    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="2", memory="4Gi"))
    api.create_pod(make_pod("low", cpu="2", memory="1Gi", priority=0))
    sched = Scheduler(
        api, NativeBackend(), profile=DEFAULT_PROFILE.with_(preemption=True), clock=FakeClock(), requeue_seconds=0.0
    )
    sched.run_cycle()
    api.create_pod(make_pod("high", cpu="2", memory="1Gi", priority=100))
    m = sched.run_cycle()  # preempts low immediately
    assert m.bound == 1
    # The next cycles keep re-solving (no verdict skip under preemption).
    sched.run_cycle()
    assert sched.delta.stats()["skipped_total"] == 0


# -- shards / takeover composition ------------------------------------------


def test_replica_kill_rebuilds_solve_state_on_takeover():
    """The ISSUE-10 acceptance pin: the delta path composes with the
    sharded control plane — a survivor absorbing a crashed owner's shards
    must escalate to a full wave (never trust pre-takeover residuals) and
    the scenario's availability + incremental verdicts must both hold."""
    from tpu_scheduler.sim.harness import run_scenario

    card = run_scenario("replica-kill-mid-cycle", seed=0)
    assert card["pass"], json.dumps(card["availability"])
    inc = card["incremental"]
    assert inc["enabled"] and inc["delta_cycles"] > 0
    assert "takeover" in inc["escalations"], inc["escalations"]


# -- checkpoint v4 -----------------------------------------------------------


def test_checkpoint_v4_roundtrip_forces_full_wave(tmp_path):
    from tpu_scheduler.runtime.checkpoint import restore_scheduler, save_scheduler

    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="8", memory="16Gi"))
    api.create_pod(make_pod("p1", cpu="1", memory="1Gi"))
    sched = _sched(api)
    sched.run_cycle()
    api.create_pod(make_pod("p2", cpu="1", memory="1Gi"))
    sched.run_cycle()
    assert sched.delta.stats()["delta_cycles"] == 1
    save_scheduler(sched, str(tmp_path))
    state = json.load(open(os.path.join(str(tmp_path), "state.json")))
    assert state["version"] == 5
    assert state["delta"]["delta_cycles"] == 1 and state["delta"]["full_solve_reasons"] == {"cold": 1}

    sched2 = _sched(api)
    assert restore_scheduler(sched2, str(tmp_path)) is True
    # Counters survived; residuals did NOT — the first cycle goes full.
    assert sched2.delta.delta_cycles == 1
    api.create_pod(make_pod("p3", cpu="1", memory="1Gi"))
    m = sched2.run_cycle()
    assert m.bound == 1
    assert sched2.delta.stats()["full_solve_reasons"].get("restore") == 1
    _audit_capacity(sched2)


def test_checkpoint_v3_file_migrates_engine_cold(tmp_path):
    """A v3 checkpoint (no delta key) restores cleanly: the engine starts
    cold and the first cycle full-waves — the v3 -> v4 migration pin."""
    from tpu_scheduler.runtime.checkpoint import restore_scheduler

    v3_state = {
        "version": 3,
        "cycle_count": 5,
        "counters": {},
        "shard_count": 1,
        "shards": {"0": {"requeue": {"default/a": [10.0, "no-node", 2]}}},
        "deferred_binds": [],
        "noexecute_elapsed": [],
        "pdb_peaks": {},
        "pdb_disruptions": {},
        "node_sig": None,
    }
    os.makedirs(str(tmp_path), exist_ok=True)
    with open(os.path.join(str(tmp_path), "state.json"), "w") as f:
        json.dump(v3_state, f)
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="8", memory="16Gi"))
    sched = _sched(api)
    assert restore_scheduler(sched, str(tmp_path)) is True
    assert sched.requeue_at.attempts("default/a") == 2
    assert sched.delta.delta_cycles == 0
    api.create_pod(make_pod("p1", cpu="1", memory="1Gi"))
    m = sched.run_cycle()
    assert m.bound == 1
    # Cold-or-restore: either way the first cycle was a full wave.
    assert sched.delta.stats()["full_solves"] == 1


# -- candidate-node compaction ----------------------------------------------


def test_compact_candidate_nodes_preserves_placed_set():
    from tpu_scheduler.delta.repack import compact_candidate_nodes
    from tpu_scheduler.models.profiles import DEFAULT_PROFILE
    from tpu_scheduler.ops.pack import pack_snapshot

    # Saturate most nodes: only 4 of 20 can host anything.
    nodes = [make_node(f"full-{i}", cpu="1", memory="1Gi") for i in range(16)]
    nodes += [make_node(f"open-{i}", cpu="16", memory="32Gi") for i in range(4)]
    pods = [make_pod(f"p-{i}", cpu="2", memory="2Gi") for i in range(8)]
    from tpu_scheduler.core.snapshot import ClusterSnapshot

    snap = ClusterSnapshot.build(nodes, pods)
    packed = pack_snapshot(snap)
    compacted = compact_candidate_nodes(packed)
    assert compacted is not packed
    assert set(compacted.node_names) == {f"open-{i}" for i in range(4)}
    full = NativeBackend().schedule(packed, DEFAULT_PROFILE)
    comp = NativeBackend().schedule(compacted, DEFAULT_PROFILE)
    assert {pf for pf, _ in full.bindings} == {pf for pf, _ in comp.bindings}
    assert sorted(full.unschedulable) == sorted(comp.unschedulable)


def test_compact_skips_when_not_paying():
    from tpu_scheduler.delta.repack import compact_candidate_nodes
    from tpu_scheduler.core.snapshot import ClusterSnapshot
    from tpu_scheduler.ops.pack import pack_snapshot

    nodes = [make_node(f"n-{i}", cpu="16", memory="32Gi") for i in range(8)]
    pods = [make_pod("p", cpu="1", memory="1Gi")]
    packed = pack_snapshot(ClusterSnapshot.build(nodes, pods))
    assert compact_candidate_nodes(packed) is packed  # everything fits: keep the warm shape


# -- the parity gate ---------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_churn_steady_state_shadow_parity_and_replay(seed, tmp_path):
    """The tentpole's correctness gate: churn-steady-state must pass with
    zero shadow mismatches and full_solve_fraction <= 0.10, and the whole
    run (delta decisions included) must record→replay bit-identically."""
    from tpu_scheduler.sim.harness import run_scenario

    trace = str(tmp_path / f"trace-{seed}.jsonl")
    card = run_scenario("churn-steady-state", seed=seed, record=trace)
    inc = card["incremental"]
    assert card["pass"], json.dumps(inc)
    assert inc["required"] and inc["ok"]
    assert inc["shadow_checks"] > 0 and inc["shadow_mismatches"] == 0
    assert inc["full_solve_fraction"] <= 0.10
    assert inc["dirty_p95"] <= inc["dirty_max"]
    replayed = run_scenario("churn-steady-state", seed=seed, replay=trace)
    assert replayed["fingerprint"] == card["fingerprint"]
    assert replayed["incremental"] == inc


def test_reduced_view_shares_placed_state():
    api = FakeApiServer()
    base = synth_cluster(n_nodes=5, n_pending=10, n_bound=10, seed=1)
    api.load(base.nodes, base.pods)
    from tpu_scheduler.core.snapshot import ClusterSnapshot

    snap = ClusterSnapshot.build(api.list_nodes(), api.list_pods())
    sub = snap.pending_pods()[:3]
    view = Scheduler._reduced_view(snap, sub)
    assert view.pending_pods() == sub
    assert view.placed_pods() is snap.placed_pods()
    assert view.pods_on_node(snap.nodes[0].name) == snap.pods_on_node(snap.nodes[0].name)
