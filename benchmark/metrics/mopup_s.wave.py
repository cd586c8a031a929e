"""Seconds per wave the scheduler's cycles spent in their ``mopup`` phase
(``CycleMetrics.mopup_seconds``: the sequential pass over a constrained
auction's residue, its explaining included), over the waves begun and
decided inside the window."""


def read(run):
    return run.phase_per_wave("mopup")
