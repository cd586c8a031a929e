"""The harness: found by name, refuses to run without a chip, and keeps
BENCHMARK.json within the characters its readers accept."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from benchmark import harness
from benchmark.tests.conftest import REPO
from benchmark.tests.test_faults import FAULTS

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")


def test_names_units_and_text_fields():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    names = []
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert TEXT.match(c["source"]) and TEXT.match(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.isfile(os.path.join(REPO, c["file"])) and c["file"].startswith("benchmark/")
        names.append(c["name"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4) and TEXT.match(w["why"])
        assert os.path.isfile(os.path.join(REPO, "benchmark", "traffic", w["traffic"] + ".json"))
        names.append(w["name"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(REPO, "benchmark", "metrics", m["name"] + ".py"))
        names.append(m["name"])
    for m in spec["per_layer"]:
        assert TEXT.match(m["layer"]) and m["moves"] in {e["name"] for e in spec["end_to_end"]}
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    assert all(TEXT.match(word) for word in spec["command"])


def test_new_cell_mix_config_and_metric_are_found_by_name(tiny_root, tmp_path):
    """Adding files and entries is enough: no existing file changes."""
    root = str(tmp_path)
    import shutil

    shutil.copytree(tiny_root, root, dirs_exist_ok=True)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(root, "benchmark", "configs", "envelope-5k.json")))
    cfg["nodes"]["count"], cfg["standing"]["count"] = 120, 600
    json.dump(cfg, open(os.path.join(root, "benchmark", "configs", "small-new.json"), "w"))
    json.dump({"loop": "wave", "wave_pods": 300, "warm_waves": 1, "wave_seconds": 1, "mix": {"selector_fraction": 0.5}},
              open(os.path.join(root, "benchmark", "traffic", "wave-new.json"), "w"))
    with open(os.path.join(root, "benchmark", "metrics", "waves_done.new.py"), "w") as f:
        f.write("def read(run):\n    return len(run.waves_in_window())\n")
    spec["configs"].append({"name": "small-new", "source": "test", "file": "benchmark/configs/small-new.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new-cell", "config": "small-new", "traffic": "wave-new", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "waves_done.new", "unit": "waves", "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "pods_per_s", "workloads": ["new-cell"]})
    for m in spec["end_to_end"]:
        if m["name"] == "pods_per_s":
            m["workloads"].append("new-cell")
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell, config, traffic = harness.resolve(spec, "new-cell", root)
    assert config["nodes"]["count"] == 120 and traffic["wave_pods"] == 300
    assert [m["name"] for m in harness.metrics_of(spec, "new-cell", True)] == ["waves_done.new"]
    result = harness.run_cell("new-cell", 11, 2.0, True, time.perf_counter(), require_tpu=False, root=root, drain_seconds=5)
    assert result["correct"] and result["metrics"]["waves_done.new"]["value"] == 2  # ceil(2 s / 1 s a wave)
    assert result["attempted"] == 2 * 300  # every pod of every wave of the window


def test_no_tpu_exits_nonzero_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "wave-plain-100k", "--seed", "3000000001", "--seconds", "1"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_without_the_program_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "wave-plain-100k", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and "correct" not in p.stdout


@pytest.mark.parametrize("workload", ["wave-plain-100k", "wave-mixed-5k"])
def test_tiny_run_is_correct_and_reports_its_metrics(tiny_root, workload):
    spec = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    result = harness.run_cell(workload, 2**31 + 12345, 3.0, False, time.perf_counter(), require_tpu=False, root=tiny_root, drain_seconds=10)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    want = {m["name"] for m in harness.metrics_of(spec, workload, False)}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())



FOUND_BY_NAME = os.path.join(REPO, "benchmark", "tests", "found_by_name")


def _with_fleet_cell(src: str) -> str:
    """A copy of the repo's benchmark in ``src`` with a cell of its own
    cluster generator, loop, traffic, configuration and tiny cut added as new
    files (``benchmark/tests/found_by_name``) and new entries alone."""
    import shutil

    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(src, "benchmark"), ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), src)
    for dirpath, _dirs, files in os.walk(FOUND_BY_NAME):
        rel = os.path.relpath(dirpath, FOUND_BY_NAME)
        for f in files:
            assert not os.path.exists(os.path.join(REPO, "benchmark", rel, f)), "a new file, not an edit"
            os.makedirs(os.path.join(src, "benchmark", rel), exist_ok=True)
            shutil.copy(os.path.join(dirpath, f), os.path.join(src, "benchmark", rel, f))
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tpu-fleet", "source": "test", "file": "benchmark/configs/tpu-fleet.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "jobset-fleet", "config": "tpu-fleet", "traffic": "jobset-waves", "chips": 1, "why": "test"})
    next(m for m in spec["end_to_end"] if m["name"] == "pods_per_s")["workloads"].append("jobset-fleet")
    with open(os.path.join(src, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return src


@pytest.fixture(scope="module")
def fleet_root(tmp_path_factory):
    from benchmark.tests.conftest import make_tiny_root

    src = _with_fleet_cell(str(tmp_path_factory.mktemp("fleet-src")))
    return make_tiny_root(str(tmp_path_factory.mktemp("fleet-tiny")), src)


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_a_cell_with_its_own_cluster_loop_and_cut_is_found_by_name(fleet_root, fault):
    """Slice node pools and standing JobSet jobs from the configuration's own
    generator, exclusive-placement gangs from the traffic's own loop, the
    size from the cell's own tiny cut: correct, and the control and every
    fault are caught."""
    spec = json.load(open(os.path.join(fleet_root, "BENCHMARK.json")))
    _cell, config, traffic = harness.resolve(spec, "jobset-fleet", fleet_root)
    assert config["nodes"]["slice_topologies"] == ["4x4", "4x4", "4x4", "4x8", "4x8"] and traffic["wave_jobs"] == 2
    result = harness.run_cell("jobset-fleet", 2**31 + 29, 2.0, False, time.perf_counter(), fault=fault, require_tpu=False,
                              root=fleet_root, drain_seconds=5)
    if fault is None:
        assert result["correct"], result["checks"]
        assert result["attempted"] > 0 and set(result["metrics"]) == {"setup_s", "pods_per_s", "ttb_p99_s"}
    else:
        assert not result["correct"]
        assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_a_cell_without_a_tiny_cut_is_named(tmp_path):
    from benchmark.tests.conftest import make_tiny_root

    src = _with_fleet_cell(str(tmp_path / "src"))
    os.remove(os.path.join(src, "benchmark", "tests", "tiny", "jobset-fleet.json"))
    os.mkdir(tmp_path / "tiny")
    with pytest.raises(FileNotFoundError, match="'jobset-fleet'"):
        make_tiny_root(str(tmp_path / "tiny"), src)


@pytest.mark.parametrize("config", ["northstar-100k", "envelope-5k"])
def test_build_cluster_without_a_generator_is_the_benchmarks_own(config):
    """A configuration that names no generator gets ``benchmark/cluster.py``'s
    nodes and standing pods, drawn from the same seed in the same order."""
    import random

    from benchmark import cluster
    from benchmark.tests.test_cluster import _strip

    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(REPO, next(c["file"] for c in spec["configs"] if c["name"] == config))))
    assert "generator" not in cfg
    run = harness.Run("w", {}, cfg, {}, 2**31 + 7, 1.0, False, 0.0, "test", 1.0)
    standing = harness.build_cluster(run)
    rng = random.Random(f"{2**31 + 7}/cluster")
    nodes = cluster.gen_nodes(rng, cfg["nodes"])
    assert _strip(run.nodes) == _strip(nodes)
    assert _strip(standing) == _strip(cluster.gen_standing(rng, cfg["standing"], nodes))
