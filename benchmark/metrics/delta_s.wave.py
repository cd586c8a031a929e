"""Seconds per wave the scheduler's cycles spent in their ``delta`` phase
(``CycleMetrics.delta_seconds``: the incremental engine's plan and commit),
over the waves begun and decided inside the window."""


def read(run):
    return run.phase_per_wave("delta")
