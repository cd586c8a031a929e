"""The jobset-fleet-4k cell's own pieces: the fleet's generator at its full
census, the tiny cut correct and caught by the control and every fault, and
the readers of the constraint layer's two metrics."""

import json
import os
import random
import time
from collections import Counter

import pytest

from benchmark import harness
from benchmark.tests.conftest import REPO
from benchmark.tests.test_faults import FAULTS
from benchmark.tests.test_layer_readers import reader, synthetic_run

CELL = "jobset-fleet-4k"


def _generator():
    return harness.load_module(os.path.join(REPO, "benchmark", "clusters", "gke_tpu_slices.py"))


def test_generator_makes_the_stated_fleet_and_its_standing_jobs():
    gke = _generator()
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    _cell, config, traffic = harness.resolve(spec, CELL)
    rng = random.Random(f"{2**31 + 101}/cluster")
    nodes = gke.gen_nodes(rng, config["nodes"])
    pools = {}
    for n in nodes:
        labels = n.metadata.labels
        assert not any(k.startswith("topology.tpu-scheduler/") for k in labels)
        assert labels[gke.HOSTNAME] == n.metadata.name and labels[gke.ACCELERATOR] == gke.V5E
        assert n.status.allocatable[gke.TPU] == "4" and [t.key for t in n.spec.taints] == [gke.TPU]
        pools.setdefault(labels[gke.POOL], []).append(labels[gke.TOPOLOGY])
    assert len(nodes) == 4096 and len(pools) == 472
    assert Counter(t[0] for t in pools.values()) == {"4x4": 256, "4x8": 128, "8x8": 64, "8x16": 16, "16x16": 8}
    assert all(len(ts) == gke.hosts(ts[0]) for ts in pools.values())
    standing = gke.gen_standing(rng, config["standing"], nodes)
    assert len(standing) == 3072 and all(p.spec.node_name for p in standing)
    pool_of = {n.metadata.name: n.metadata.labels[gke.POOL] for n in nodes}
    jobs = {}
    for p in standing:
        jobs.setdefault(p.metadata.labels[gke.JOB], set()).add(pool_of[p.spec.node_name])
    assert len(jobs) == 354 and all(len(ps) == 1 for ps in jobs.values())
    owner = Counter(next(iter(ps)) for ps in jobs.values())
    assert max(owner.values()) == 1  # one job a pool
    busy = Counter(pools[p][0] for p in owner)
    assert busy == {"4x4": 192, "4x8": 96, "8x8": 48, "8x16": 12, "16x16": 6}
    assert {p.spec.priority for p in standing} <= {t["priority"] for t in config["standing"]["priority_tiers"].values()}
    wave = gke.wave_pods(gke.wave_jobs(random.Random(3), traffic), "w0-")
    assert len({p.metadata.labels[gke.JOB] for p in wave}) == 96


def test_the_last_warm_wave_runs_the_largest_window_wave():
    from types import SimpleNamespace

    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    _cell, config, traffic = harness.resolve(spec, CELL)
    loop = harness.load_module(os.path.join(REPO, "benchmark", "loops", "jobset_fleet_waves.py"))
    run = SimpleNamespace(traffic=traffic, seed=2**31 + 77, seconds=51.0, window=None, t_process_start=time.perf_counter())
    try:
        loop.prepare(run)
    finally:
        run.window = (0.0, 0.0)  # a closed window ends the loop's watchdog
    warm = traffic["warm_waves"]
    sizes = [len(w) for w in run.prebuilt]
    assert len(sizes) == warm + run.window_waves and sizes[warm - 1] == max(sizes[warm:])
    names = {p.metadata.name for w in run.prebuilt for p in w}
    assert len(names) == sum(sizes)  # the copy runs under names of its own


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_fleet_is_correct_and_reports_its_metrics(tiny_root, traced):
    spec = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    result = harness.run_cell(CELL, 2**31 + 4243 + traced, 2.0, traced, time.perf_counter(), require_tpu=False,
                              root=tiny_root, drain_seconds=3)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in harness.metrics_of(spec, CELL, traced)}
    if traced:
        assert {"exclusive_s.wave", "host_fallbacks.wave", "solve_s.wave"} <= want
        assert want - {"device_idle_pct.wave"} <= set(result["metrics"])
        assert result["metrics"]["host_fallbacks.wave"]["value"] == 0
        assert result["metrics"]["exclusive_s.wave"]["value"] > 0
    else:
        assert want == {"setup_s", "pods_per_s", "ttb_p99_s"} == set(result["metrics"])


@pytest.mark.parametrize("fault", FAULTS)
def test_tiny_fleet_fault_is_caught(tiny_root, fault):
    result = harness.run_cell(CELL, 2**31 + 4242, 2.0, False, time.perf_counter(), fault=fault, require_tpu=False,
                              root=tiny_root, drain_seconds=3)
    assert not result["correct"]
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_exclusive_s_wave_is_the_exclusive_subphase_of_window_waves():
    run = synthetic_run()
    for c in run.cycles:
        c["m"].exclusive_seconds = 0.01 * c["m"].cycle  # cycles 3-6 belong to waves 1 and 2
    assert reader("exclusive_s.wave")(run) == pytest.approx(0.01 * (3 + 4 + 5 + 6) / 2)


def test_exclusive_s_wave_is_none_for_a_program_without_the_field(monkeypatch):
    from tpu_scheduler.utils.metrics import CycleMetrics

    fields = dict(CycleMetrics.__dataclass_fields__)
    del fields["exclusive_seconds"]
    monkeypatch.setattr(CycleMetrics, "__dataclass_fields__", fields)
    assert reader("exclusive_s.wave")(synthetic_run()) is None


def test_host_fallbacks_wave_counts_window_waves_and_is_none_unrecorded():
    run = synthetic_run()
    assert reader("host_fallbacks.wave")(run) is None  # a loop that records no count
    for w, n in zip(run.waves, (5, 0, 3)):
        w["host_fallbacks"] = n
    assert reader("host_fallbacks.wave")(run) == pytest.approx((0 + 3) / 2)


def test_a_program_that_leaves_the_device_path_in_warm_up_ends_the_run(tiny_root, monkeypatch, capsys):
    """A program whose constrained cycles take the host sequential chain (as
    one without exclusive families does past the term budget) cannot run the
    cell: the first warm wave that falls back ends the process with exit
    code 3 and no result."""
    from benchmark.loops import wave_guarded
    from tpu_scheduler.ops.constraints import UntensorizableConstraints
    from tpu_scheduler.runtime.controller import Scheduler

    batch = Scheduler._schedule_batch

    def host_only(self, snapshot, placed, with_constraints=False, **kw):
        if with_constraints:
            raise UntensorizableConstraints("held to the host chain")
        return batch(self, snapshot, placed, **kw)

    class Ended(Exception):
        pass

    def ended(code):
        raise Ended(code)

    monkeypatch.setattr(Scheduler, "_schedule_batch", host_only)
    monkeypatch.setattr(wave_guarded, "watch", lambda run: None)  # no watchdog thread outlives the test
    monkeypatch.setattr(os, "_exit", ended)
    with pytest.raises(Ended) as e:
        harness.run_cell(CELL, 2**31 + 4244, 2.0, True, time.perf_counter(), require_tpu=False, root=tiny_root, drain_seconds=3)
    assert e.value.args == (wave_guarded.EXIT_CODE,)
    assert "warm wave 0: 1 cycle(s) left the device path" in capsys.readouterr().err
