"""Closed-loop waves of JobSet jobs with exclusive placement over the node
pool key, under ``wave_guarded``'s watchdog.

A wave is ``wave_jobs`` jobs, each on a slice of a topology drawn with the
traffic's ``job_topologies`` weights (``benchmark/clusters/gke_tpu_slices.py``
makes the pods, as it makes the standing jobs).  The waves and the window
are ``benchmark.loops.wave``'s; only the pods and the warm-up's check differ.  A job
drawn beyond its topology's free pools waits, which is correct: the wave
ends when a cycle binds none of it.

The waves' sizes move with the slice shapes their jobs draw, so the last
warm wave runs the jobs of the window's largest wave (by pods) under other
names: set-up then meets the largest shape the window will, and nothing
compiles inside the window.

Each window wave also records ``host_fallbacks``: how many of its cycles
left the device path for the host sequential chain, read from the
scheduler's ``scheduler_constraint_host_fallbacks_total`` before and after.

The cell measures the fleet on the device path, so a program whose warm-up
leaves it cannot run the cell: at the end of the first warm wave in which
the counter moved, the loop prints a ``bench:`` line saying so and ends the
process with ``wave_guarded``'s exit code 3, before the window opens and
with no result line.
"""

from __future__ import annotations

import math
import os
import random
import sys
import time

from benchmark.harness import load_module
from benchmark.loops import wave, wave_guarded

gke = load_module(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "clusters", "gke_tpu_slices.py"))

FALLBACKS = "scheduler_constraint_host_fallbacks_total"


def prepare(run) -> None:
    wave_guarded.watch(run)
    t = run.traffic
    rng = random.Random(f"{run.seed}/traffic")
    run.window_waves = max(1, math.ceil(run.seconds / t["wave_seconds"]))
    jobs = [gke.wave_jobs(rng, t) for _ in range(t["warm_waves"] + run.window_waves)]
    warm = t["warm_waves"]
    jobs[warm - 1] = max(jobs[warm:], key=lambda wave: sum(gke.hosts(topo) for topo, _p in wave))
    run.prebuilt = [gke.wave_pods(wave, prefix=f"w{k}-") for k, wave in enumerate(jobs)]


def _host_fallbacks(run) -> int:
    return run.sched.metrics.snapshot().get(FALLBACKS, 0)


def warm(run) -> None:
    for k in range(run.traffic["warm_waves"]):
        before = _host_fallbacks(run)
        wave._retire(run, wave._wave(run, k, None))
        left = _host_fallbacks(run) - before
        if left:
            print(f"bench: warm wave {k}: {left} cycle(s) left the device path for the host sequential chain; "
                  f"this cell measures the device path, ending the run with exit code {wave_guarded.EXIT_CODE}",
                  file=sys.stderr, flush=True)
            os._exit(wave_guarded.EXIT_CODE)


def measure(run) -> None:
    first = run.traffic["warm_waves"]
    run.open_window()
    deadline = run.window[0] + run.seconds + run.drain_seconds
    for k in range(first, first + run.window_waves):
        before = _host_fallbacks(run)
        done = wave._wave(run, k, deadline)
        done["host_fallbacks"] = _host_fallbacks(run) - before
        wave._retire(run, done)
        if time.perf_counter() > deadline:
            break
    run.close_window()
    run.drain_end = time.perf_counter()
