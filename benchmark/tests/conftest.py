"""CPU tests of the benchmark: ``python3 -m pytest benchmark/tests -q``.

The harness's look for a chip is skipped (``require_tpu=False``); every
other part of a run is driven on the CPU at a size a test can hold.
"""

import json
import os
import shutil

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def merge(base: dict, cut: dict) -> dict:
    """``base`` with ``cut`` laid over it: objects merge key by key, any
    other value of ``cut`` replaces the one in ``base``."""
    out = dict(base)
    for k, v in cut.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def make_tiny_root(dest: str, src: str = REPO) -> str:
    """A checkout of the benchmark in ``src`` with every cell cut to a size a
    CPU test can run in seconds.  Each cell's cut is found by name,
    ``benchmark/tests/tiny/<cell>.json``: ``{"config": {...}, "traffic":
    {...}}``, merged into the cell's configuration and traffic files.  Cells
    that share a file must cut it alike."""
    shutil.copytree(os.path.join(src, "benchmark"), os.path.join(dest, "benchmark"), ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    files = {c["name"]: c["file"] for c in spec["configs"]}
    written: dict = {}  # path -> (cell, what was written there)
    for cell in spec["workloads"]:
        path = os.path.join(src, "benchmark", "tests", "tiny", cell["name"] + ".json")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"cell {cell['name']!r} has no tiny cut: add benchmark/tests/tiny/{cell['name']}.json")
        with open(path) as f:
            cut = json.load(f)
        for part, rel in (("config", files[cell["config"]]), ("traffic", os.path.join("benchmark", "traffic", cell["traffic"] + ".json"))):
            with open(os.path.join(src, rel)) as f:
                data = merge(json.load(f), cut.get(part, {}))
            out = os.path.join(dest, rel)
            if out in written and written[out][1] != data:
                raise ValueError(f"cells {written[out][0]!r} and {cell['name']!r} cut {rel} differently")
            written[out] = (cell["name"], data)
            with open(out, "w") as f:
                json.dump(data, f)
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))
