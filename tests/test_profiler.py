"""Cycle cost-attribution profiler (utils/profiler.py + tracing upgrades):
hierarchical spans, the derived phase set (a new phase can't silently land
in `other`), the continuous ring, SLO burn tracking, the /debug/profile
route, nested Chrome-trace slices, the spans as profiler annotations,
collector pauses, the bind API time, and the two tier-1 gates — attribution
coverage ≥ 0.9 and span+ring overhead < 2% on a steady-state scenario."""

from __future__ import annotations

import gc
import glob
import json
import os
import threading
import time
import urllib.request

import pytest

from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.runtime.controller import Scheduler
from tpu_scheduler.runtime.fake_api import FakeApiServer
from tpu_scheduler.runtime.http_api import HttpApiServer
from tpu_scheduler.testing import make_node, make_pod, synth_cluster
from tpu_scheduler.utils.metrics import CycleMetrics, MetricsRegistry, cycle_phases
from tpu_scheduler.utils.profiler import (
    SPAN_CATALOGUE,
    ProfileRing,
    ReplicaProfileRegistry,
    build_tree,
    gc_pauses,
    install_gc_listener,
    record_transfer,
    span_cost_estimate,
    tier_of,
    transfer_bytes_total,
)
from tpu_scheduler.utils import tracing
from tpu_scheduler.utils.tracing import Trace, base_name, span


def _get(url: str) -> dict:
    with urllib.request.urlopen(url) as r:
        return json.loads(r.read())


# --- hierarchical tracing ----------------------------------------------------


def test_nested_spans_record_paths_and_top_level():
    t = Trace()
    with t:
        with span("solve"):
            with span("round[00]"):
                with span("score"):
                    pass
            with span("round[01]"):
                pass
        with span("bind"):
            pass
    assert set(t.durations) == {"solve", "solve/round[00]", "solve/round[00]/score", "solve/round[01]", "bind"}
    assert set(t.top_level()) == {"solve", "bind"}
    # A parent's duration contains its children's.
    assert t.durations["solve"] >= t.durations["solve/round[00]"] + t.durations["solve/round[01]"]
    assert t.counts["solve/round[00]"] == 1


def test_record_lands_under_open_span():
    t = Trace()
    with t:
        with span("solve"):
            t.record("compile", 0.5)
    assert t.durations["solve/compile"] == 0.5


def test_spans_on_other_threads_do_not_touch_the_trace():
    """The active-trace stack is thread-local: a worker thread (routed
    per-pool solves) sees no trace, so its spans cannot race the owner's
    tree — the THRD stance for the profiler."""
    t = Trace()
    seen = []

    def worker():
        with span("worker-span"):
            seen.append(True)

    with t:
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert seen == [True]
    assert "worker-span" not in t.durations


def test_base_name_strips_index():
    assert base_name("round[03]") == "round"
    assert base_name("epoch[1]") == "epoch"
    assert base_name("solve") == "solve"


def test_build_tree_self_time_is_disjoint():
    t = Trace()
    with t:
        with span("solve"):
            with span("round[00]"):
                pass
            with span("round[01]"):
                pass
    tree = build_tree(t, wall=t.durations["solve"] * 2)
    solve = tree["children"]["solve"]
    kids = sum(c["total_s"] for c in solve["children"].values())
    assert solve["self_s"] == pytest.approx(solve["total_s"] - kids)
    assert solve["self_s"] >= 0
    # Self-times over the whole tree sum to the attributed wall.
    def self_sum(node):
        return node["self_s"] + sum(self_sum(c) for c in node["children"].values())

    assert sum(self_sum(c) for c in tree["children"].values()) == pytest.approx(tree["attributed_s"])
    assert tree["coverage"] == pytest.approx(0.5, abs=1e-6)


# --- phase drift gate (satellite: other_seconds can't silently absorb) ------


def test_phase_series_matches_breakdown_fields_exactly():
    """Every CycleMetrics ``*_seconds`` field (except wall) must surface as
    a ``scheduler_phase_seconds{phase=}`` series and vice versa — the set is
    DERIVED (metrics.cycle_phases), so this pins the derivation, and a new
    phase field is a new series by construction."""
    phases = cycle_phases()
    assert "other" in phases and "wall" not in phases
    m = CycleMetrics(
        cycle=1, backend="native", pending=1, bound=1, unschedulable=0, rounds=1, wall_seconds=1.0,
        **{f"{ph}_seconds": 0.01 for ph in phases},
    )
    r = MetricsRegistry()
    r.observe_cycle(m)
    text = r.to_prometheus()
    observed = set()
    for line in text.splitlines():
        if line.startswith("scheduler_phase_seconds_count{"):
            label = line.split('phase="', 1)[1].split('"', 1)[0]
            observed.add(label)
    assert observed == set(phases)


def test_live_cycle_top_level_spans_are_all_phase_fields():
    """A real cycle's depth-0 span names must all be CycleMetrics phase
    fields (scheduler_unattributed_spans_total == 0), and the breakdown must
    reconstruct: wall == sum(phases) + other."""
    snap = synth_cluster(n_nodes=16, n_pending=64, n_bound=8, seed=3, anti_affinity_fraction=0.2, spread_fraction=0.2)
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    s = Scheduler(api, NativeBackend(), requeue_seconds=0.0)
    m = s.run_cycle()
    assert "scheduler_unattributed_spans_total" not in s.metrics.snapshot()
    total = sum(getattr(m, f"{ph}_seconds") for ph in cycle_phases())
    assert total == pytest.approx(m.wall_seconds, abs=2e-3)
    # The ring saw the same cycle and every recorded path uses catalogued names.
    census = s.profile_ring.span_census()
    assert census
    for path in census:
        for seg in path.split("/"):
            assert base_name(seg) in SPAN_CATALOGUE, path


def test_unknown_top_level_span_is_counted_not_silent():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu=4, memory="8Gi"))
    api.create_pod(make_pod("p1", cpu="100m", memory="64Mi"))
    s = Scheduler(api, NativeBackend(), requeue_seconds=0.0)
    orig = s._run_batch_cycle

    def noisy(snapshot, trace):
        with span("phantom-phase"):
            pass
        return orig(snapshot, trace)

    s._run_batch_cycle = noisy
    s.run_cycle()
    assert s.metrics.snapshot().get("scheduler_unattributed_spans_total", 0) >= 1


# --- continuous ring ---------------------------------------------------------


def test_ring_aggregates_counts_totals_and_quantiles():
    ring = ProfileRing(window=16)
    for i in range(40):
        t = Trace()
        with t:
            with span("solve"):
                pass
        t.durations["solve"] = 0.01 * (i + 1)  # deterministic synthetic totals
        ring.ingest(t, wall=0.02 * (i + 1))
    snap = ring.snapshot()
    assert snap["cycles"] == 40
    node = snap["tree"]["solve"]
    assert node["count"] == 40
    # The recent window is bounded at 16: quantiles come from the last 16.
    assert node["p50_s"] >= 0.01 * 25
    assert snap["coverage"] == pytest.approx(0.5, abs=0.01)
    brief = ring.brief()
    assert brief["top_phases"][0]["phase"] == "solve"
    census = ring.span_census()
    assert census["solve"] == 40


def test_ring_snapshot_is_threadsafe_under_concurrent_ingest():
    ring = ProfileRing()
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            t = Trace()
            with t:
                with span("solve"):
                    pass
            ring.ingest(t, 0.001)

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(200):
            snap = ring.snapshot()
            assert snap["cycles"] >= 0
    finally:
        stop.set()
        th.join()


# --- SLO burn ----------------------------------------------------------------


def test_tier_mapping():
    assert tier_of(1000) == "critical"
    assert tier_of(150) == "high"
    assert tier_of(0) == "default"
    assert tier_of(-1) == "best-effort"


def test_pending_age_tracked_and_observed_on_exit():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu=16, memory="32Gi"))
    # One bindable pod and one impossible one (selector no node matches).
    api.create_pod(make_pod("fast", cpu="100m", memory="64Mi", priority=150))
    api.create_pod(make_pod("stuck", cpu="100m", memory="64Mi", node_selector={"zone": "nowhere"}))
    fake_now = [100.0]
    s = Scheduler(api, NativeBackend(), requeue_seconds=0.0, clock=lambda: fake_now[0])
    s.run_cycle()
    # Both pods entered the tracker (the cycle's pending snapshot predates
    # the binds; exits are observed at the NEXT cycle boundary).
    age = s.pending_age_debug("default/stuck")
    assert age is not None and age["tier"] == "default" and age["age_seconds"] == 0.0
    assert s.pending_age_debug("default/fast") is not None
    fake_now[0] = 101.0
    s.run_cycle()
    # "fast" bound last cycle: it left the tracker and observed its final
    # time-in-queue (≤ one cycle interval late, by design) under its tier.
    assert s.pending_age_debug("default/fast") is None
    text = s.metrics.to_prometheus()
    assert 'scheduler_pending_age_seconds_count{gang="solo",tier="high"} 1' in text
    fake_now[0] = 160.0
    s.run_cycle()
    age = s.pending_age_debug("default/stuck")
    assert age["age_seconds"] == pytest.approx(60.0)
    assert age["burn_rate"] == pytest.approx(60.0 / age["target_seconds"])
    text = s.metrics.to_prometheus()
    # The survivor drives the per-tier oldest/burn gauges.
    assert 'scheduler_pending_oldest_age_seconds{tier="default"} 60.0' in text
    assert 'scheduler_slo_burn_rate{tier="default"}' in text
    slo = s.slo_snapshot()
    assert slo["default"]["pending"] == 1 and slo["default"]["oldest_age_s"] == pytest.approx(60.0)


# --- compile / transfer split ------------------------------------------------


def test_device_transfer_bytes_counted_once_per_upload():
    import numpy as np

    from tpu_scheduler.backends.tpu import TpuBackend

    b = TpuBackend()
    arr = np.zeros((64, 64), dtype=np.float32)
    before = transfer_bytes_total()
    b._put(arr)
    assert transfer_bytes_total() - before == arr.nbytes
    b._put(arr)  # cache hit: no second upload, no second count
    assert transfer_bytes_total() - before == arr.nbytes


def test_record_transfer_accumulates():
    before = transfer_bytes_total()
    record_transfer(123)
    assert transfer_bytes_total() == before + 123


# --- /debug/profile + replica registry ---------------------------------------


def test_debug_profile_route_and_replica_selection():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu=4, memory="8Gi"))
    api.create_pod(make_pod("p1", cpu="100m", memory="64Mi"))
    s = Scheduler(api, NativeBackend(), requeue_seconds=0.0, identity="replica-a")
    s.run_cycle()
    reg = ReplicaProfileRegistry()
    reg.register("replica-a", s.profile_snapshot)
    reg.register("replica-b", lambda: {"replica": "replica-b", "profile": {"cycles": 2, "wall_total_s": 1.0, "other_total_s": 0.5}})
    srv = HttpApiServer(api, metrics=s.metrics, recorder=s.recorder, profile=reg.snapshot,
                        pending_ages=s.pending_age_debug).start()
    try:
        merged = _get(srv.base_url + "/debug/profile")
        assert set(merged["replicas"]) == {"replica-a", "replica-b"}
        assert merged["merged"]["cycles"] == s.profile_ring.snapshot()["cycles"] + 2
        one = _get(srv.base_url + "/debug/profile?replica=replica-a")
        assert one["replica"] == "replica-a"
        assert one["profile"]["tree"]["sync"]["count"] >= 1
        assert "slo" in one and "compile" in one
        missing = _get(srv.base_url + "/debug/profile?replica=ghost")
        assert "error" in missing
    finally:
        srv.stop()


def test_debug_profile_404_when_not_attached():
    api = FakeApiServer()
    srv = HttpApiServer(api).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.base_url + "/debug/profile")
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_debug_pod_why_pending_carries_age_and_tier():
    """Satellite bugfix: the why-pending payload shows elapsed pending age
    and the SLO tier it burns against, not just the event timeline."""
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu=1, memory="1Gi"))
    api.create_pod(make_pod("big", cpu="64", memory="256Gi", priority=1500))
    fake_now = [10.0]
    s = Scheduler(api, NativeBackend(), requeue_seconds=0.0, clock=lambda: fake_now[0])
    s.run_cycle()
    fake_now[0] = 25.0
    s.run_cycle()
    srv = HttpApiServer(api, metrics=s.metrics, recorder=s.recorder, pending_ages=s.pending_age_debug).start()
    try:
        doc = _get(srv.base_url + "/debug/pods/default/big")
        assert doc["age"] is not None
        assert doc["age"]["tier"] == "critical"
        assert doc["age"]["age_seconds"] == pytest.approx(15.0)
        assert doc["age"]["burn_rate"] == pytest.approx(0.5)  # 15s of a 30s target
        assert doc["why_pending"] is not None  # the existing block survives
    finally:
        srv.stop()


def test_debug_shards_carries_perf_block():
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu=4, memory="8Gi"))
    api.create_pod(make_pod("p1", cpu="100m", memory="64Mi"))
    s = Scheduler(api, NativeBackend(), requeue_seconds=0.0)
    s.run_cycle()
    snap = s.shards_snapshot()
    assert snap["perf"]["cycles"] == 1
    assert 0.0 <= snap["perf"]["coverage"] <= 1.0
    assert snap["perf"]["top_phases"]


# --- nested Chrome trace (satellite) -----------------------------------------


def test_chrome_trace_nested_slices_with_disjoint_self_time():
    """/debug/trace must emit parent/child slices whose children sit INSIDE
    the parent interval and whose self-time (dur − direct children) is
    non-negative — the nesting contract Perfetto renders from."""
    snap = synth_cluster(n_nodes=12, n_pending=48, n_bound=6, seed=1, anti_affinity_fraction=0.25, spread_fraction=0.2)
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    s = Scheduler(api, NativeBackend(), requeue_seconds=0.0)
    s.run_cycle()
    trace = json.loads(json.dumps(s.recorder.chrome_trace(1)))
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    by_path = {e["args"].get("path", e["name"]): e for e in slices}
    nested = [p for p in by_path if "/" in p]
    assert nested, "a constrained cycle must record nested spans"
    tol = 1.0  # µs — endpoint rounding
    for path, ev in by_path.items():
        if "/" not in path:
            continue
        parent = by_path.get(path.rsplit("/", 1)[0])
        assert parent is not None, f"no parent slice for {path}"
        assert ev["ts"] >= parent["ts"] - tol
        assert ev["ts"] + ev["dur"] <= parent["ts"] + parent["dur"] + tol
    # Non-overlapping self-time: direct children never exceed the parent.
    for path, ev in by_path.items():
        kids = [c for p2, c in by_path.items() if p2.rsplit("/", 1)[0] == path and "/" in p2]
        if kids:
            assert sum(k["dur"] for k in kids) <= ev["dur"] + tol * (len(kids) + 1)
    # Leaf names, full path in args (the Perfetto-friendly shape).
    sample = by_path[nested[0]]
    assert "/" not in sample["name"] and sample["args"]["path"] == nested[0]


# --- the tier-1 acceptance gates --------------------------------------------


def test_steady_state_coverage_and_overhead_gates():
    """THE acceptance criteria: on a steady-state sim scenario, attribution
    coverage ≥ 0.9 and the measured span+ring overhead estimate < 2% of the
    cycle wall; the scorecard profile block is pass-gated and carries only
    deterministic data."""
    from dataclasses import replace

    from tpu_scheduler.sim.harness import run_scenario
    from tpu_scheduler.sim.scenarios import SCENARIOS

    sc = replace(SCENARIOS["steady-state"], duration=30.0)  # short, same family
    gates: dict = {}
    card = run_scenario(sc, seed=0, profile_gates=gates)
    assert card["pass"], json.dumps(card["invariants"])
    prof = card["profile"]
    assert prof["enabled"] and prof["required"] and prof["coverage_ok"]
    assert gates["coverage"] >= 0.9, gates
    assert gates["overhead_frac"] < 0.02, gates
    # The scorecard block is deterministic-only: census + booleans, no walls.
    assert set(prof) == {"enabled", "required", "coverage_ok", "cycles", "span_census"}
    assert all(isinstance(v, int) for v in prof["span_census"].values())
    assert "solve/round" in prof["span_census"]


def test_profiled_scenario_is_deterministic_in_census():
    """The profiler must not perturb determinism: two runs of the same
    (scenario, seed) produce identical span censuses and profile blocks —
    span presence/counts are pure control flow."""
    from dataclasses import replace

    from tpu_scheduler.sim.harness import run_scenario
    from tpu_scheduler.sim.scenarios import SCENARIOS

    sc = replace(SCENARIOS["steady-state"], duration=15.0)
    c1 = run_scenario(sc, seed=7)
    c2 = run_scenario(sc, seed=7)
    assert c1["profile"] == c2["profile"]
    assert c1["fingerprint"] == c2["fingerprint"]


def test_span_cost_microbench_is_sane():
    per = span_cost_estimate(n=500)
    assert 0 < per < 50e-6  # a span is microseconds, not milliseconds


# --- spans on the profiler's clock -------------------------------------------


def _host_events(trace_dir: str) -> list[tuple[str, int, int]]:
    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    ]


def test_cycle_phases_land_in_the_profiler_trace(tmp_path):
    """Under a profiler session every depth-0 phase of a cycle is a
    ``sched:<phase>`` host event inside the cycle's interval; recorded sums
    (``bind/post``) stay out of the profiler trace."""
    import jax

    snap = synth_cluster(n_nodes=16, n_pending=64, n_bound=8, seed=3)
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    s = Scheduler(api, NativeBackend(), requeue_seconds=0.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.cycle"):
            m = s.run_cycle()
    finally:
        jax.profiler.stop_trace()
    assert m.bound > 0
    events = _host_events(str(tmp_path))
    ((_, lo, hi),) = [e for e in events if e[0] == "test.cycle"]
    annotated = set()
    for name, start, end in events:
        if name.startswith("sched:") and name != "sched:gc":
            assert lo <= start <= end <= hi, name
            annotated.add(name[len("sched:"):])
    paths = {sp["name"] for sp in s.recorder.cycles(1)[0]["spans"]}
    phases = {p for p in paths if "/" not in p}
    assert {"sync", "queue", "pack", "solve", "bind"} <= phases
    assert phases <= annotated
    assert "bind/post" in paths and "bind/post" not in annotated


class _CountingAnnotation:
    enabled = False
    made: list = []

    @staticmethod
    def is_enabled() -> bool:
        return _CountingAnnotation.enabled

    def __init__(self, name, **_metadata):
        _CountingAnnotation.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def test_no_annotation_is_made_without_a_session(monkeypatch):
    monkeypatch.setattr(tracing, "_annotation_cls", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "made", [])
    t = Trace()
    with t:
        with span("solve"):
            with span("round[00]"):
                pass
    assert _CountingAnnotation.made == []
    monkeypatch.setattr(_CountingAnnotation, "enabled", True)
    with t:
        with span("solve"):
            with span("round[00]"):
                pass
        t.record("compile", 0.1)
    assert _CountingAnnotation.made == ["sched:solve", "sched:solve/round[00]"]


# --- collector pauses --------------------------------------------------------


def test_gc_pause_is_recorded_and_folded_into_the_histogram():
    s = Scheduler(FakeApiServer(), NativeBackend(), requeue_seconds=0.0)
    t0 = time.perf_counter()
    gc.collect()
    mine = [p for p in gc_pauses() if p[0] >= t0]
    assert any(gen == 2 and seconds > 0 for _, seconds, gen in mine), mine
    key = 'scheduler_gc_pause_seconds_count{generation="2"}'

    def gen2_count() -> float:
        for line in s.metrics.to_prometheus().splitlines():
            if line.startswith(key):
                return float(line.split(" ")[1])
        return 0.0

    s.run_cycle()
    first = gen2_count()
    assert first >= 1
    # Folded once: the next cycle adds only the full collections after the
    # last pause already folded.
    folded = s._gc_folded
    s.run_cycle()
    assert gen2_count() == first + sum(1 for t, _, gen in gc_pauses() if gen == 2 and t > folded)


def test_gc_under_the_registry_lock_returns():
    """A collection can fire while its thread holds MetricsRegistry._lock;
    the listener takes no lock, so it cannot deadlock there."""
    install_gc_listener()
    reg = MetricsRegistry()
    done = threading.Event()

    def hold_and_collect():
        with reg._lock:
            gc.collect()
        done.set()

    th = threading.Thread(target=hold_and_collect, daemon=True)
    th.start()
    assert done.wait(timeout=30)
    th.join(timeout=5)
    assert not th.is_alive()


# --- the bind API time -------------------------------------------------------


class _SlowBindApi(FakeApiServer):
    def __init__(self, delay: float):
        super().__init__()
        self.delay = delay

    def create_binding(self, namespace, pod_name, target):
        time.sleep(self.delay)
        super().create_binding(namespace, pod_name, target)


def _slow_bind_cluster(delay: float, n: int) -> _SlowBindApi:
    api = _SlowBindApi(delay)
    api.load(
        nodes=[make_node(f"n{i}", cpu="8", memory="16Gi") for i in range(4)],
        pods=[make_pod(f"p{i}", cpu="100m", memory="64Mi") for i in range(n)],
    )
    return api


def test_bind_post_is_the_api_time_under_bind():
    delay, n = 0.005, 10
    s = Scheduler(_slow_bind_cluster(delay, n), NativeBackend(), requeue_seconds=0.0)
    m = s.run_cycle()
    assert m.bound == n
    spans = s.recorder.cycles(1)[0]["spans"]
    post = sum(sp["end"] - sp["start"] for sp in spans if sp["name"] == "bind/post")
    bind = sum(sp["end"] - sp["start"] for sp in spans if sp["name"] == "bind")
    assert n * delay <= post <= bind
    assert post == pytest.approx(n * delay, rel=1.0)
    assert bind == pytest.approx(m.bind_seconds, abs=1e-5)  # intervals are wall stamps


def test_pipelined_bind_time_records_under_bind_post():
    """The bind worker's time is all API time: drained inside the overlay
    phase it still records as ``bind/post``, never as a depth-0 ``post``."""
    delay, n = 0.002, 8
    s = Scheduler(_slow_bind_cluster(delay, n), NativeBackend(), pipeline=True, requeue_seconds=0.0)
    s.run_cycle()
    assert s._bind_inflight[1].wait(timeout=30)
    s.run_cycle()  # its overlay phase folds the finished batch
    census = s.profile_ring.span_census()
    assert census.get("bind/post", 0) >= 1 and "post" not in census
    spans = s.recorder.cycles(1)[0]["spans"]
    post = sum(sp["end"] - sp["start"] for sp in spans if sp["name"] == "bind/post")
    assert post >= n * delay
    s.close()
