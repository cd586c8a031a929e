"""Flight recorder (utils/events.py) + tracing upgrades: bounded timelines,
LRU eviction, Chrome trace-event export schema, span intervals, JSON log
formatter."""

import json
import logging

from tpu_scheduler.ops.masks import feasibility_breakdown, reason_rejection_counts
from tpu_scheduler.utils.events import EVENT_KINDS, SEGMENT_OF_KIND, SEGMENTS, FlightRecorder, waterfall
from tpu_scheduler.utils.tracing import (
    JsonLogFormatter,
    Trace,
    configure_logging,
    set_log_cycle,
    span,
)

import numpy as np
import pytest


# --- recorder bounds ---------------------------------------------------------


def test_timeline_bounded_per_pod():
    fr = FlightRecorder(max_pods=8, per_pod=3)
    for i in range(10):
        fr.record("default/p", "requeued", i)
    tl = fr.timeline("default/p")
    assert len(tl) == 3 and [e["cycle"] for e in tl] == [7, 8, 9]


def test_lru_eviction_at_max_pods():
    fr = FlightRecorder(max_pods=2)
    fr.record("default/a", "seen-pending", 1)
    fr.record("default/b", "seen-pending", 1)
    fr.record("default/a", "bound", 2, node="n1")  # refreshes a
    fr.record("default/c", "seen-pending", 2)  # evicts b (least recent)
    assert fr.tracked_pods() == ["default/a", "default/c"]
    assert fr.evicted_timelines == 1
    assert fr.timeline("default/b") == []


def test_disabled_recorder_is_a_noop():
    fr = FlightRecorder(max_pods=0)
    fr.record("default/a", "bound", 1)
    fr.seen("default/a", 1)
    fr.record_cycle({"cycle": 1}, [])
    assert not fr.enabled
    assert fr.tracked_pods() == [] and fr.cycles() == []
    assert fr.chrome_trace()["traceEvents"] == []


def test_seen_records_only_first_sight():
    fr = FlightRecorder()
    fr.seen("default/a", 1)
    fr.seen("default/a", 2)
    assert [e["cycle"] for e in fr.timeline("default/a")] == [1]


def test_record_packed_only_touches_tracked_pods():
    fr = FlightRecorder()
    fr.seen("default/a", 1)
    fr.record_packed(["default/a", "default/ghost"], 1, "native")
    assert [e["kind"] for e in fr.timeline("default/a")] == ["seen-pending", "packed"]
    assert fr.timeline("default/ghost") == []


def test_event_kinds_vocabulary():
    assert {"seen-pending", "packed", "bound", "requeued", "unschedulable"} <= set(EVENT_KINDS)
    # The waterfall's terminal + reservation edge (PR 16): watch-confirm
    # time was previously dropped, making the confirm segment unmeasurable.
    assert {"bind-confirmed", "reservation-opened"} <= set(EVENT_KINDS)


# --- time-to-bind waterfall --------------------------------------------------


def test_events_stamp_wall_and_scheduler_clock():
    """Every event carries both a wall ``ts`` and a scheduler-clock ``t``
    (the virtual clock in sim) — the waterfall reads ``t``, so latency
    decomposition is deterministic under record/replay."""
    now = [10.0]
    fr = FlightRecorder(clock=lambda: now[0])
    fr.seen("default/a", 1)
    now[0] = 12.5
    fr.record("default/a", "bound", 2, node="n1")
    tl = fr.timeline("default/a")
    assert [e["t"] for e in tl] == [10.0, 12.5]
    assert all(isinstance(e["ts"], float) for e in tl)
    # Without a clock, t falls back to the wall stamp.
    fr2 = FlightRecorder()
    fr2.seen("default/b", 1)
    (ev,) = fr2.timeline("default/b")
    assert ev["t"] == ev["ts"]


def test_deferred_bind_entry_and_flush_stamps_attribute_to_breaker_deferred():
    """A bind-deferred event stamps buffer entry, bind-flushed stamps the
    flush — the interval between them is the breaker-deferred segment."""
    now = [0.0]
    fr = FlightRecorder(clock=lambda: now[0])
    fr.seen("default/a", 1)
    now[0] = 1.0
    fr.record("default/a", "bind-deferred", 1, node="n1", detail="circuit open")
    now[0] = 7.0
    fr.record("default/a", "bind-flushed", 5, node="n1")
    now[0] = 7.5
    fr.record("default/a", "bound", 5, node="n1")
    tl = fr.timeline("default/a")
    entry = next(e for e in tl if e["kind"] == "bind-deferred")
    flush = next(e for e in tl if e["kind"] == "bind-flushed")
    assert entry["t"] == 1.0 and flush["t"] == 7.0  # entry/flush stamps
    wf = waterfall(tl)
    assert wf["segments"]["breaker-deferred"] == 6.0
    assert wf["segments"]["solve"] == 1.0  # seen-pending -> deferred
    assert wf["segments"]["bind-post"] == 0.5  # flushed -> bound
    assert wf["ttb"] == 7.5 and wf["unattributed"] == 0.0


def test_waterfall_segments_sum_to_ttb():
    now = [0.0]
    fr = FlightRecorder(clock=lambda: now[0])
    fr.seen("default/a", 1)
    now[0] = 0.25
    fr.record("default/a", "requeued", 1, detail="create-binding-failed")
    now[0] = 3.25
    fr.record("default/a", "packed", 4, detail="native")
    now[0] = 3.5
    fr.record("default/a", "bound", 4, node="n1")
    now[0] = 4.5
    fr.record("default/a", "bind-confirmed", 5)
    wf = waterfall(fr.timeline("default/a"), arrival_t=-1.0)
    assert wf["segments"]["cadence-wait"] == 1.0  # arrival -1.0 -> seen 0.0
    assert wf["segments"]["solve"] == 0.25 + 0.25  # seen->requeued + packed->bound
    assert wf["segments"]["backoff"] == 3.0
    assert wf["segments"]["confirm"] == 1.0
    assert wf["ttb"] == 5.5
    assert abs(sum(wf["segments"].values()) + wf["unattributed"] - wf["ttb"]) < 1e-9
    assert set(wf["segments"]) == set(SEGMENTS)


def test_waterfall_unmapped_kind_leaks_to_unattributed():
    """An interval opened by a kind outside SEGMENT_OF_KIND must surface as
    unattributed — the leak the scorecard's sum-to-TTB audit catches."""
    assert "preempted" not in SEGMENT_OF_KIND
    now = [0.0]
    fr = FlightRecorder(clock=lambda: now[0])
    fr.seen("default/a", 1)
    now[0] = 1.0
    fr.record("default/a", "preempted", 2, detail="victim")
    now[0] = 4.0
    fr.record("default/a", "bound", 3, node="n1")
    wf = waterfall(fr.timeline("default/a"))
    assert wf["unattributed"] == 3.0 and wf["segments"]["solve"] == 1.0
    assert wf["ttb"] == 4.0


def test_waterfall_terminal_fallback_and_empty():
    """Terminal = last bind-confirmed, else last bound, else no waterfall."""
    now = [0.0]
    fr = FlightRecorder(clock=lambda: now[0])
    fr.seen("default/pending", 1)
    assert waterfall(fr.timeline("default/pending")) is None
    assert waterfall([]) is None
    fr.seen("default/a", 1)
    now[0] = 2.0
    fr.record("default/a", "bound", 2, node="n1")  # never confirmed
    wf = waterfall(fr.timeline("default/a"))
    assert wf["ttb"] == 2.0 and wf["segments"]["confirm"] == 0.0


def test_chrome_trace_pod_waterfall_tracks():
    """Pod timelines export as pid-2 X slices named by segment, one tid per
    pod, so Perfetto shows the admission waterfall beside the cycle spans."""
    now = [0.0]
    fr = FlightRecorder(clock=lambda: now[0])
    fr.seen("default/a", 1)
    now[0] = 1.0
    fr.record("default/a", "bound", 1, node="n1")
    now[0] = 2.0
    fr.record("default/a", "bind-confirmed", 2)
    trace = json.loads(json.dumps(fr.chrome_trace()))
    pod_slices = [e for e in trace["traceEvents"] if e["ph"] == "X" and e["pid"] == 2]
    assert {e["name"] for e in pod_slices} == {"solve", "confirm"}
    for e in pod_slices:
        assert e["args"]["pod"] == "default/a" and e["dur"] >= 0
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M" and e["pid"] == 2]
    assert {e["args"]["name"] for e in meta} == {"pod admission waterfall", "default/a"}


# --- chrome trace export -----------------------------------------------------


def test_chrome_trace_schema():
    fr = FlightRecorder()
    t = Trace()
    with t:
        with span("pack"):
            pass
        with span("solve"):
            pass
    fr.record_cycle({"cycle": 7, "bound": 3}, t.events, notes=["backend-fallback: tpu -> native"])
    trace = fr.chrome_trace(1)
    # Round-trips as JSON (the wire contract of /debug/trace).
    trace = json.loads(json.dumps(trace))
    assert isinstance(trace["traceEvents"], list)
    assert trace["displayTimeUnit"] == "ms"
    complete = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"pack", "solve"}
    for e in complete:
        assert isinstance(e["ts"], (int, float)) and isinstance(e["dur"], (int, float))
        assert e["dur"] >= 0 and e["pid"] == 1 and e["tid"] == 1
        assert e["args"]["cycle"] == 7
    # Cycle records (and their notes) surface through cycles().
    recs = fr.cycles(1)
    assert recs[0]["metrics"]["cycle"] == 7
    assert recs[0]["notes"] == ["backend-fallback: tpu -> native"]
    assert recs[0]["spans"][0]["name"] == "pack"


def test_trace_span_intervals_are_ordered_wall_times():
    t = Trace()
    with t:
        with span("a"):
            pass
        with span("b"):
            pass
    assert [name for name, _, _ in t.events] == ["a", "b"]
    for name, start, end in t.events:
        assert end >= start > 1e9  # wall-clock epoch seconds, not perf deltas
    # Duration-only records (the overlapped-bind drain) synthesize an interval.
    t.record("bind", 0.25)
    name, start, end = t.events[-1]
    assert name == "bind" and abs((end - start) - 0.25) < 1e-9


# --- structured logging ------------------------------------------------------


def test_json_log_formatter_fields_and_cycle_tag():
    fmt = JsonLogFormatter()
    rec = logging.LogRecord("tpu_scheduler.x", logging.WARNING, "f.py", 1, "pod %s failed", ("a",), None)
    obj = json.loads(fmt.format(rec))
    assert obj["level"] == "WARNING" and obj["logger"] == "tpu_scheduler.x"
    assert obj["msg"] == "pod a failed" and isinstance(obj["ts"], float)
    assert "cycle" not in obj
    set_log_cycle(42)
    try:
        obj = json.loads(fmt.format(rec))
        assert obj["cycle"] == 42
    finally:
        set_log_cycle(None)


def test_configure_logging_rejects_unknown_format():
    with pytest.raises(ValueError):
        configure_logging("INFO", fmt="xml")


# --- per-reason mask exposure (ops/masks.py) ---------------------------------


def test_feasibility_breakdown_counts():
    """The per-predicate masks feasibility_block ANDs together, exposed
    named — per-reason candidate counts must attribute each rejection."""
    pod_req = np.array([[2, 2], [8, 2]], dtype=np.int64)  # pod1 over-asks cpu
    node_avail = np.array([[4, 4], [4, 4]], dtype=np.int64)
    pod_sel = np.array([[1.0, 0.0], [0.0, 0.0]], dtype=np.float32)  # pod0 selects label0
    pod_sel_count = np.array([1.0, 0.0], dtype=np.float32)
    node_labels = np.array([[1.0, 0.0], [0.0, 1.0]], dtype=np.float32)  # only node0 has label0
    bd = feasibility_breakdown(np, pod_req, pod_sel, pod_sel_count, node_avail, node_labels)
    assert bd["NotEnoughResources"].tolist() == [[True, True], [False, False]]
    assert bd["NodeSelectorMismatch"].tolist() == [[True, False], [True, True]]
    node_valid = np.array([True, True])
    counts = reason_rejection_counts(np, bd, node_valid)
    assert counts["NotEnoughResources"].tolist() == [0, 2]
    assert counts["NodeSelectorMismatch"].tolist() == [1, 0]
