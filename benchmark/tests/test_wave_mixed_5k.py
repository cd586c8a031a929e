"""The wave-mixed-5k cell's own pieces: the mop-up and explainer readers, the
constrained roofline's counts, the shapes the guarded loop counts from the
pods, and the loop's watchdog."""

import os
import subprocess
import sys
import textwrap
import time
from types import SimpleNamespace

import pytest

from benchmark import roofline, roofline_constrained
from benchmark.loops import wave_guarded
from benchmark.tests.conftest import REPO
from benchmark.tests.test_layer_readers import reader, synthetic_run


def test_mopup_s_wave_is_the_mopup_phase_of_window_waves():
    run = synthetic_run()
    for c in run.cycles:
        c["m"].mopup_seconds = 0.5 * c["m"].cycle  # cycles 3-6 belong to waves 1 and 2
    assert reader("mopup_s.wave")(run) == pytest.approx(0.5 * (3 + 4 + 5 + 6) / 2)


def test_explain_s_wave_sums_every_phase_explain_of_window_cycles():
    run = synthetic_run()
    for rec in run.rec_cycles:
        n = rec["metrics"]["cycle"]
        rec["spans"] += [{"name": "bind/explain", "start": 0.0, "end": 0.1 * n},
                         {"name": "mopup/explain", "start": 1.0, "end": 1.0 + 0.01 * n}]
    assert reader("explain_s.wave")(run) == pytest.approx((0.1 + 0.01) * (3 + 4 + 5 + 6) / 2)


def test_explain_s_wave_is_zero_without_explaining_and_none_without_the_span(monkeypatch):
    assert reader("explain_s.wave")(synthetic_run()) == 0.0  # the program explained no pod
    run = synthetic_run()
    run.rec_cycles = [rec for rec in run.rec_cycles if rec["metrics"]["cycle"] != 5]
    assert reader("explain_s.wave")(run) is None  # the recorder lost cycle 5
    from tpu_scheduler.utils import profiler

    monkeypatch.setattr(profiler, "SPAN_CATALOGUE", tuple(s for s in profiler.SPAN_CATALOGUE if s != "explain"))
    assert reader("explain_s.wave")(synthetic_run()) is None  # a program without the span


def test_constrained_work_adds_the_constraint_terms_to_the_plain_count():
    ops, nbytes = roofline_constrained.choose_constrained_work(
        pods=5000, nodes=5000, resources=3, label_terms=1, blocked=0.25, steered=0.1, soft=0.5, node_rows=300)
    # A pair: 5*3 + 1 plain, 1 blocked comparison, 2 * (0.25 + 0.1 + 0.5) for the terms.
    assert ops == pytest.approx(5000 * 5000 * (16 + 1 + 2 * 0.85))
    plain_words = 5000 * (3 + 1) + 5000 * (2 * 3 + 1) + 5000
    assert nbytes == pytest.approx(4 * (plain_words + 5000 * 0.85 + 5000 * 300))
    # Reading each node's 300 constraint rows once outweighs the pairs' work at this shape.
    least, bound = roofline.least_seconds(ops, nbytes, roofline.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)


def test_choose_constrained_roofline_reads_the_constrained_kernel_per_wave():
    run = synthetic_run()
    run.device_kind = "TPU v5 lite"
    run.shapes = {"pods": 5000, "nodes": 5000, "resources": 3, "label_terms": 1,
                  "blocked": 0.25, "steered": 0.1, "soft": 0.5, "node_rows": 300}
    run.trace = {"window_start_ns": 10.0e9, "kernel_events": [  # the window opens at 10 s: 10e9 ns
        ("choose_block_constrained.1", 11.0e9, 11.0e9 + 2e6),  # wave 1: 2 ms
        ("choose_block", 12.0e9, 12.0e9 + 5e6),  # the plain kernel: not counted
        ("choose_block_constrained", 25.0e9, 25.0e9 + 4e6),  # wave 2: 4 ms
    ]}
    least, _ = roofline.least_seconds(*roofline_constrained.choose_constrained_work(5000, 5000, 3, 1, 0.25, 0.1, 0.5, 300),
                                      roofline.peaks("TPU v5 lite"))
    assert reader("choose_constrained_roofline")(run) == pytest.approx(100 * least / 3e-3)  # a mean of 3 ms a wave
    run.trace["kernel_events"] = run.trace["kernel_events"][:2]  # no constrained kernel in wave 2
    assert reader("choose_constrained_roofline")(run) is None


def test_constraint_shapes_count_the_pods_terms():
    from benchmark import cluster

    pods = cluster.gen_pods(__import__("random").Random(3), 400, {"anti_affinity_fraction": 0.5, "spread_fraction": 0.5,
                                                                  "schedule_anyway_fraction": 0.5, "pod_affinity_fraction": 0.5})
    s = wave_guarded.constraint_shapes([pods])
    anti = sum(len(p.spec.anti_affinity or []) for p in pods)
    pa = sum(len(p.spec.pod_affinity or []) for p in pods)
    hard = sum(1 for p in pods for c in p.spec.topology_spread or [] if c.is_hard)
    loose = sum(1 for p in pods for c in p.spec.topology_spread or [] if not c.is_hard)
    assert anti and pa and hard and loose
    assert s["blocked"] == pytest.approx((anti + pa + hard) / 400)
    assert s["steered"] == pytest.approx(hard / 400) and s["soft"] == pytest.approx(loose / 400)
    apps_aa = {(p.spec.anti_affinity[0].match_labels["app"]) for p in pods if p.spec.anti_affinity}
    assert s["node_rows"] >= 2 * len(apps_aa)


def test_tiny_traced_run_of_the_cell_reports_its_layers(tiny_root):
    """The cell as BENCHMARK.json gives it (the guarded loop, its own traffic
    file), cut to the tiny size: correct, and its new readers report (the
    constrained roofline finds no Pallas kernel on the CPU and says None)."""
    import json

    from benchmark import harness

    spec = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    cut = json.load(open(os.path.join(REPO, "benchmark", "tests", "tiny", "wave-mixed-5k.json")))
    _cell, _config, traffic = harness.resolve(spec, "wave-mixed-5k", tiny_root)
    assert traffic["loop"] == "wave_guarded" and traffic["wave_pods"] == cut["traffic"]["wave_pods"]
    result = harness.run_cell("wave-mixed-5k", 2**31 + 25, 3.0, True, time.perf_counter(), require_tpu=False, root=tiny_root,
                              drain_seconds=10)
    assert result["correct"], result["checks"]
    assert {"mopup_s.wave", "explain_s.wave"} <= set(result["metrics"])
    assert "choose_constrained_roofline" not in result["metrics"]


@pytest.mark.parametrize("stage", ["set-up", "window"])
def test_watchdog_ends_a_run_whose_cycle_never_returns(stage):
    script = textwrap.dedent(f"""
        import time
        from types import SimpleNamespace
        from benchmark.loops import wave_guarded
        now = time.perf_counter()
        run = SimpleNamespace(traffic={{"setup_limit_s": 1.5}}, t_process_start=now, seconds=0.5, drain_seconds=0.5,
                              window=(now, None) if {stage == "window"} else None)
        wave_guarded.watch(run, poll=0.05)
        while True:  # a cycle that never returns, holding the interpreter
            pass
    """)
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, "-c", script], cwd=REPO, capture_output=True, text=True, timeout=60,
                       env={**os.environ, "PYTHONPATH": REPO})
    took = time.perf_counter() - t0
    assert p.returncode == wave_guarded.EXIT_CODE and "watchdog" in p.stderr
    assert took < 30  # the limit is 1.5 s (set-up) or 2.5 s (window), plus the interpreter's start


def test_watchdog_stops_watching_once_the_window_closed():
    ended = []
    run = SimpleNamespace(traffic={"setup_limit_s": 0.0}, t_process_start=0.0, seconds=0.0, drain_seconds=0.0,
                          window=(0.0, 1.0))
    t = wave_guarded.watch(run, poll=0.01, exit=ended.append)
    t.join(timeout=5)
    assert not t.is_alive() and ended == []
