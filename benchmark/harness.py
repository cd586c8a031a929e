"""One run of one cell through the served path.

The scheduler is built by ``tpu_scheduler.cli.build_scheduler`` from the
arguments ``python -m tpu_scheduler.cli --backend tpu --no-fallback`` takes,
with every other option at its default, against the in-process
``FakeApiServer``.  The benchmark generates the cluster and the traffic from
the seed and loads them through the API.  It wraps three methods of that
server instance (``create_pod``, ``delete_pod``, ``create_binding``) to log
what the server accepted, in order and with the host clock, and wraps the
scheduler instance's ``run_cycle`` to time each cycle.  Nothing else of the
program is touched.

Everything a cell needs is found by name:

- ``BENCHMARK.json``: the cell, its configuration and traffic, its metrics;
- the configuration's ``file``: nodes and standing pods, made by
  ``benchmark/clusters/<generator>.py`` where the file names a
  ``generator``, else by ``benchmark/cluster.py``; ``gangs_placed_when_they_fit``
  true where the deployment guarantees that a gang that fits is not left
  pending, so that the reference judges its pending gangs;
- ``benchmark/traffic/<traffic>.json``: the mix, read by the loop it names;
- ``benchmark/loops/<loop>.py``: ``prepare``, ``warm`` and ``measure``;
- ``benchmark/metrics/<metric>.py``: ``read(run)``, a number or None.
"""

from __future__ import annotations

import bisect
import importlib.util
import json
import os
import random
import subprocess
import sys
import tempfile
import threading
import time
import zlib

import numpy as np

from . import audit, cluster, trace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
KERNELS = ("choose_block", "choose_block_constrained")  # the fused placement kernel, plain and constrained
DRAIN_SECONDS = 60.0  # how long past the window's close a pod created in it may take to bind


class NoChip(Exception):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location("benchmark_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: str = ROOT) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic) of ``workload``, each read by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(spec: dict, workload: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer ones."""
    return [m for m in spec["per_layer" if traced else "end_to_end"] if workload in m.get("workloads", [workload])]


def tpu_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"the benchmark runs on a TPU; jax found {devices}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; jax found {devices}")
    return devices


class Run:
    """The state of one run: what the loops record and the readers read."""

    def __init__(self, workload, cell, config, traffic, seed, seconds, traced, t_process_start, device_kind, drain_seconds):
        self.workload, self.cell, self.config, self.traffic = workload, cell, config, traffic
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.t_process_start = t_process_start
        self.device_kind = device_kind
        self.drain_seconds = drain_seconds
        self.log: list = []  # (perf time, kind, object) in the order the API server accepted them
        self.log_lock = threading.Lock()
        self.cycles: list[dict] = []  # {"t0", "t1", "m": CycleMetrics, "wave"}
        self.waves: list[dict] = []  # wave loops: {"index", "t_created", "t_done", "keys"}
        self.activity: list = []  # (name, t0, t1) of the loops' own work, for the idle-gap labels
        self.start_time: dict = {}  # pod key -> when its time to bind starts
        self.window_keys: list = []  # pods created while the window was open
        self.bound: set = set()  # keys of the pods whose binding the server accepted
        self.current_wave = None
        self.window = None  # (open, close) perf times; close is None while it is open
        self.drain_end = None
        self.setup_s = None
        self.shapes: dict = {}  # wave loops: what the roofline reader needs (pods, nodes, resources, label_terms)
        self.trace = None  # trace.reduce() of the window, traced runs only
        self.rec_cycles: list = []  # the scheduler's recorded cycles with their phase spans (wall clock)
        self.wall_minus_perf = time.time() - time.perf_counter()
        self.compiles_in_window = None
        self._trace_dir = None
        self._annotation = None
        self.api = self.sched = None
        self.nodes: list = []

    # -- the window --------------------------------------------------------

    def open_window(self) -> None:
        from tpu_scheduler.utils.profiler import compile_stats

        if self.traced:
            import jax

            self._trace_dir = tempfile.TemporaryDirectory()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the host's Python work would swamp the trace
            opts.host_tracer_level = 1  # annotations only: the window's own marks it
            jax.profiler.start_trace(self._trace_dir.name, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation(trace.WINDOW)
            self._annotation.__enter__()
        self._compiles0 = compile_stats().get("compiles", 0)
        t = time.perf_counter()
        self.window = (t, None)
        self.setup_s = t - self.t_process_start

    def close_window(self) -> None:
        from tpu_scheduler.utils.profiler import compile_stats

        self.window = (self.window[0], time.perf_counter())
        self.compiles_in_window = compile_stats().get("compiles", 0) - self._compiles0
        if self.traced:
            import jax

            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()

    # -- what the loops call ------------------------------------------------

    def create(self, pod) -> None:
        """Create ``pod``; its time to bind starts now."""
        self.api.create_pod(pod)
        key = (pod.metadata.namespace or "default", pod.metadata.name)
        self.start_time[key] = time.perf_counter()
        if self.window is not None and self.window[1] is None:
            self.window_keys.append(key)

    def delete(self, key) -> None:
        self.api.delete_pod(*key)

    def checkpoint(self, keys) -> None:
        with self.log_lock:
            self.log.append((time.perf_counter(), "checkpoint", list(keys)))

    def cycle(self):
        return self.sched.run_cycle()

    # -- reading back -------------------------------------------------------

    def bind_times(self) -> dict:
        return {obj[0]: t for t, kind, obj in self.log if kind == "bind"}

    def binds_in_window(self) -> int:
        lo, hi = self.window
        return sum(1 for t, kind, _ in self.log if kind == "bind" and lo <= t <= hi)

    def waves_in_window(self) -> list[dict]:
        lo, hi = self.window
        return [w for w in self.waves if w["t_done"] is not None and w["t_created"] >= lo and w["t_done"] <= hi]

    def cycles_in_window(self) -> list[dict]:
        lo, hi = self.window
        return [c for c in self.cycles if c["t0"] >= lo and c["t1"] <= hi]

    def phase_per_wave(self, phase: str) -> float | None:
        """Mean over the waves inside the window of the seconds their cycles
        spent in ``phase`` (a ``CycleMetrics`` field ``<phase>_seconds``)."""
        waves = self.waves_in_window()
        if not waves:
            return None
        return sum(getattr(c["m"], f"{phase}_seconds") for c in self.cycles if c["wave"] in {w["index"] for w in waves}) / len(waves)

    def trace_ns(self, perf_t: float) -> float:
        """A host perf_counter time on the trace's clock."""
        return self.trace["window_start_ns"] + (perf_t - self.window[0]) * 1e9

    def kernel_ns_between(self, t0: float, t1: float) -> float | None:
        if self.trace is None or not self.trace["kernel_events"]:
            return None
        lo, hi = self.trace_ns(t0), self.trace_ns(t1)
        return sum(min(e, hi) - max(s, lo) for _, s, e in self.trace["kernel_events"] if e > lo and s < hi)


def instrument(run: Run, fault: str | None) -> None:
    """Log what the API server accepts, time each cycle, and plant ``fault``
    (tests and the control only) in the path under test."""
    api, sched = run.api, run.sched
    create_pod, delete_pod, create_binding, run_cycle = api.create_pod, api.delete_pod, api.create_binding, sched.run_cycle
    node_names = [n.metadata.name for n in run.nodes]

    def logged_create(pod):
        with run.log_lock:
            create_pod(_strip_labels_constraints(pod) if fault == "control" else pod)
            run.log.append((time.perf_counter(), "create", pod))

    def logged_delete(namespace, name):
        with run.log_lock:
            delete_pod(namespace, name)
            run.log.append((time.perf_counter(), "delete", (namespace, name)))

    def logged_bind(namespace, name, target):
        if fault == "half" and zlib.crc32(name.encode()) % 2:
            return  # acknowledged, never made
        if fault == "altered":
            target = type(target)(name=node_names[(node_names.index(target.name) + 1) % len(node_names)])
        with run.log_lock:
            create_binding(namespace, name, target)
            run.log.append((time.perf_counter(), "bind", ((namespace, name), target.name)))
            run.bound.add((namespace, name))

    def timed_cycle():
        t0 = time.perf_counter()
        m = run_cycle()
        run.cycles.append({"t0": t0, "t1": time.perf_counter(), "m": m, "wave": run.current_wave})
        return m

    def unchanged_cycle():
        from tpu_scheduler.utils.metrics import CycleMetrics

        t0 = time.perf_counter()
        m = CycleMetrics(cycle=len(run.cycles) + 1, backend="unchanged", pending=0, bound=0, unschedulable=0, rounds=0, wall_seconds=0.0)
        run.cycles.append({"t0": t0, "t1": time.perf_counter(), "m": m, "wave": run.current_wave})
        return m

    api.create_pod, api.delete_pod, api.create_binding = logged_create, logged_delete, logged_bind
    sched.run_cycle = unchanged_cycle if fault == "unchanged" else timed_cycle


def _strip_labels_constraints(pod):
    """The control: the pod as a scheduler that skips the node-label and
    taint predicates would see it (no nodeSelector, no required node
    affinity, every taint tolerated)."""
    from dataclasses import replace

    from tpu_scheduler.api.objects import Toleration

    spec = replace(pod.spec, node_selector=None, node_affinity=None, tolerations=[Toleration(operator="Exists")])
    return replace(pod, metadata=replace(pod.metadata), spec=spec)


def build_cluster(run: Run, root: str = ROOT) -> list:
    """Nodes and standing pods of the configuration, from the seed, by the
    generator the configuration names (``benchmark/clusters/<generator>.py``),
    or by ``benchmark/cluster.py`` where it names none."""
    generator = cluster
    if "generator" in run.config:
        generator = load_module(os.path.join(root, "benchmark", "clusters", run.config["generator"] + ".py"))
    rng = random.Random(f"{run.seed}/cluster")
    run.nodes = generator.gen_nodes(rng, run.config["nodes"])
    return generator.gen_standing(rng, run.config["standing"], run.nodes)


def build_scheduler(run: Run, fault: str | None) -> None:
    from tpu_scheduler import cli

    args = cli.build_parser().parse_args(["--backend", "tpu", "--no-fallback", "--nodes", "0", "--pods", "0"])
    run.api, run.sched = cli.build_scheduler(args)
    instrument(run, fault)


def group_of(cycles: list[dict]):
    starts = [c["t0"] for c in cycles]

    def find(t: float):
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and t <= cycles[i]["t1"] else ("outside", t)

    return find


def time_to_bind(run: Run, fitting: set) -> list[float]:
    """Seconds from each window pod's start to its binding.  A pod never bound
    that the audit found room for (it judges every one) counts until the end
    of the drain."""
    bound = run.bind_times()
    out = []
    for key in run.window_keys:
        if key in bound:
            out.append(bound[key] - run.start_time[key])
        elif key in fitting:
            out.append(run.drain_end - run.start_time[key])
    return out


def idle_segments(run: Run, gaps, top: int = 10) -> list:
    """The device's idle gaps cut at what the host was doing in them: the
    scheduler's cycle phases (its recorded spans), the benchmark's own work
    (creating and deleting waves), or neither (between cycles).  The ``top`` longest pieces, ``[label, seconds]``."""
    host = [(s["start"] - run.wall_minus_perf, s["end"] - run.wall_minus_perf, "cycle:" + s["name"])
            for rec in run.rec_cycles for s in rec["spans"] if "/" not in s["name"]]
    host += [(t0, t1, "benchmark:" + name) for name, t0, t1 in run.activity]
    host += [(c["t0"], c["t1"], "cycle:other") for c in run.cycles]  # under the phases: what they leave
    pieces = []
    for lo_ns, hi_ns in gaps:
        lo, hi = (run.window[0] + (x - run.trace["window_start_ns"]) / 1e9 for x in (lo_ns, hi_ns))
        covered = []
        for t0, t1, label in host:
            a, b = max(t0, lo), min(t1, hi)
            if b > a and not any(a < cb and ca < b for ca, cb in covered):
                pieces.append([label, b - a])
                covered.append((a, b))
        rest = (hi - lo) - sum(b - a for a, b in covered)
        if rest > 1e-6:
            pieces.append(["between cycles", rest])
    return sorted(pieces, key=lambda p: -p[1])[:top]


def run_cell(workload: str, seed: int, seconds: float, traced: bool, t_process_start: float, fault: str | None = None,
             require_tpu: bool = True, root: str = ROOT, drain_seconds: float = DRAIN_SECONDS) -> dict:
    """One run; returns the result line's object."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cell, config, traffic = resolve(spec, workload, root)
    import jax

    devices = tpu_devices(cell["chips"]) if require_tpu else jax.devices()
    say(f"{workload}: seed {seed}, {seconds}s window, trace {int(traced)}, device {devices[0].device_kind}")
    if os.path.isdir(os.path.join(root, "native")):
        # The program's native packing shim, built in this checkout from its
        # source (a no-op once built).
        subprocess.run(["make", "-s", "-C", os.path.join(root, "native"), "all"], check=True)
    from tpu_scheduler.utils.gc_tuning import enable_daemon_gc_tuning
    from tpu_scheduler.utils.tracing import configure_logging

    enable_daemon_gc_tuning()  # as the CLI's main does
    configure_logging("ERROR", "text")  # a 100k-pod wave logs a line per pod at INFO

    run = Run(workload, cell, config, traffic, seed, seconds, traced, t_process_start, devices[0].device_kind, drain_seconds)
    loop = load_module(os.path.join(root, "benchmark", "loops", traffic["loop"] + ".py"))
    standing = build_cluster(run, root)
    loop.prepare(run)
    build_scheduler(run, fault)
    t0 = time.perf_counter()
    run.api.load(run.nodes, standing)
    del standing
    loop.warm(run)
    say(f"set-up: cluster loaded and warmed in {time.perf_counter() - t0:.3f}s")
    loop.measure(run)
    run.rec_cycles = run.sched.recorder.cycles()
    counters = run.sched.metrics.snapshot()
    run.sched.close()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[: cell["chips"]])
    run.api = run.sched = None  # the program's state goes before the reference runs
    say(f"compiles inside the window: {run.compiles_in_window}")
    pending = [c["m"].pending for c in run.cycles_in_window()]
    say(f"cycles inside the window: {len(pending)}, pods considered a cycle: max {max(pending, default=0)}, "
        f"padded sizes {sorted({-(-p // 128) * 128 for p in pending})}")
    fallbacks = counters.get("scheduler_backend_fallbacks_total", 0)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    breakdown = None
    if traced:
        data = trace.load(run._trace_dir.name)
        run._trace_dir.cleanup()
        run.trace = trace.reduce(data, KERNELS)
        first = min(data["devices"]) if data["devices"] else None
        run.trace["kernel_events"] = [ev for ev in data["devices"].get(first, []) if trace.op_name(ev[0]) in KERNELS]
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_ns"] / 1e9
        breakdown = {
            "device_ops": [[name, ns / 1e9] for name, ns in run.trace["top_ops"]],
            "idle_gaps": idle_segments(run, run.trace["gaps"]),
        }

    t_ref = time.perf_counter()
    verdict = audit.replay(run.nodes, run.log, group_of(run.cycles), bool(config.get("gangs_placed_when_they_fit")))
    run.ttb = time_to_bind(run, verdict.fitting)
    say(f"reference: {len(run.log)} accepted operations replayed in {time.perf_counter() - t_ref:.3f}s")
    for check, examples in verdict.examples.items():
        for ex in examples:
            say(f"{check}: {ex}")

    result_metrics = {}
    for m in metrics_of(spec, workload, traced):
        value = load_module(os.path.join(root, "benchmark", "metrics", m["name"] + ".py")).read(run)
        if value is not None:
            result_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks = {**verdict.report(), "backend_fallbacks": {"value": fallbacks, "limit": 0}}
    result = {
        "correct": verdict.correct and fallbacks == 0,
        "attempted": len(run.window_keys),
        "failed": sum(verdict.counts.values()),
        "metrics": result_metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def percentile(values, q: float) -> float | None:
    return float(np.percentile(np.asarray(values, dtype=float), q)) if len(values) else None
