"""Closed-loop waves of JobSet jobs with exclusive placement, ``wave_jobs``
a wave, each on a slice of a topology drawn from ``job_topologies``; the
pods are ``benchmark/clusters/tpu_slices.py``'s ``job_pods``, as the
standing jobs are.  The waves, the window and the warm-up are
``benchmark.loops.wave``'s; only the pods differ.
"""

import math
import os
import random

from benchmark.harness import load_module
from benchmark.loops import wave

tpu_slices = load_module(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "clusters", "tpu_slices.py"))


def prepare(run) -> None:
    t = run.traffic
    rng = random.Random(f"{run.seed}/traffic")
    run.window_waves = max(1, math.ceil(run.seconds / t["wave_seconds"]))
    run.prebuilt = [
        [p for j in range(t["wave_jobs"]) for p in tpu_slices.job_pods(f"w{k}-job-{j}", rng.choice(t["job_topologies"]), rng.randrange(3))]
        for k in range(t["warm_waves"] + run.window_waves)
    ]


warm = wave.warm
measure = wave.measure
