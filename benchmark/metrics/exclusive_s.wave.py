"""Seconds per wave the scheduler's cycles spent building the exclusive
placement families' state (``CycleMetrics.exclusive_seconds``, the
``pack/exclusive`` span), over the waves begun and decided inside the
window.  None where the program has no such field."""


def read(run):
    from tpu_scheduler.utils.metrics import CycleMetrics

    if "exclusive_seconds" not in CycleMetrics.__dataclass_fields__:
        return None
    return run.phase_per_wave("exclusive")
