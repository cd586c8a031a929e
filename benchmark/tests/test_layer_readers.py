"""The readers of the collector, bind-API and delta-engine metrics, on
synthetic runs: two waves inside the window, one before it."""

import os

import pytest

from benchmark import harness
from benchmark.tests.conftest import REPO


def reader(name: str):
    return harness.load_module(os.path.join(REPO, "benchmark", "metrics", name + ".py")).read


def synthetic_run():
    from tpu_scheduler.utils.metrics import CycleMetrics

    run = harness.Run("wave-plain-100k", {}, {}, {}, 1, 30.0, True, 0.0, "test", 10.0)
    run.window = (10.0, 40.0)
    run.waves = [
        {"index": 0, "t_created": 1.0, "t_done": 9.0},
        {"index": 1, "t_created": 10.5, "t_done": 24.0},
        {"index": 2, "t_created": 24.5, "t_done": 39.0},
    ]
    # Two cycles a wave, numbered 1-6; wave k's cycles spend k + 0.5 s in delta.
    for n in range(1, 7):
        wave = (n - 1) // 2
        m = CycleMetrics(cycle=n, backend="tpu", pending=1, bound=1, unschedulable=0, rounds=1, wall_seconds=1.0,
                         delta_seconds=wave + 0.5)
        run.cycles.append({"t0": 0.0, "t1": 0.0, "m": m, "wave": wave})
        spans = [{"name": "bind", "start": 100.0 * n, "end": 100.0 * n + 3.0},
                 {"name": "bind/post", "start": 100.0 * n + 1.0, "end": 100.0 * n + 1.0 + 0.25 * n}]
        run.rec_cycles.append({"metrics": {"cycle": n}, "spans": spans})
    return run


def test_delta_s_wave_is_the_delta_phase_of_window_waves():
    # Waves 1 and 2: two cycles each of 1.5 s and 2.5 s.
    assert reader("delta_s.wave")(synthetic_run()) == pytest.approx((2 * 1.5 + 2 * 2.5) / 2)


def test_bind_api_s_wave_sums_bind_post_of_window_cycles():
    run = synthetic_run()
    # Cycles 3-6 belong to waves 1 and 2: 0.25 * (3 + 4 + 5 + 6) s over two waves.
    assert reader("bind_api_s.wave")(run) == pytest.approx(0.25 * 18 / 2)


def test_bind_api_s_wave_is_none_without_the_span_or_with_cycles_missing():
    run = synthetic_run()
    run.rec_cycles = [rec for rec in run.rec_cycles if rec["metrics"]["cycle"] != 4]
    assert reader("bind_api_s.wave")(run) is None  # the recorder lost cycle 4
    run = synthetic_run()
    for rec in run.rec_cycles:
        rec["spans"] = [s for s in rec["spans"] if s["name"] != "bind/post"]
    assert reader("bind_api_s.wave")(run) is None  # a program that records no bind/post


def test_gc_s_wave_sums_pauses_begun_in_the_window(monkeypatch):
    from tpu_scheduler.utils import profiler

    pauses = [(5.0, 1.0, 2), (12.0, 0.5, 0), (30.0, 2.0, 2), (39.5, 0.25, 1), (41.0, 4.0, 2)]
    monkeypatch.setattr(profiler, "gc_pauses", lambda: list(pauses))
    assert reader("gc_s.wave")(synthetic_run()) == pytest.approx((0.5 + 2.0 + 0.25) / 2)
    # A full record that starts after the window opened has lost pauses.
    monkeypatch.setattr(profiler, "GC_PAUSE_CAPACITY", 4)
    monkeypatch.setattr(profiler, "gc_pauses", lambda: list(pauses[1:]))
    assert reader("gc_s.wave")(synthetic_run()) is None


def test_gc_s_wave_is_none_for_a_program_without_the_record(monkeypatch):
    from tpu_scheduler.utils import profiler

    monkeypatch.delattr(profiler, "gc_pauses")
    assert reader("gc_s.wave")(synthetic_run()) is None
