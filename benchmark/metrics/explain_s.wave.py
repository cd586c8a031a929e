"""Seconds per wave the scheduler spent explaining why pods stay pending:
the ``<phase>/explain`` records the flight recorder kept of the cycles of
the waves begun and decided inside the window (matched by cycle number), in
every phase.  None where the program records no such span (its profiler's
span catalogue lacks ``explain``) or the recorder no longer holds every one
of those cycles; 0 where it explained nothing in those waves."""


def read(run):
    try:
        from tpu_scheduler.utils.profiler import SPAN_CATALOGUE
    except ImportError:
        return None
    waves = {w["index"] for w in run.waves_in_window()}
    cycles = {c["m"].cycle for c in run.cycles if c["wave"] in waves}
    recs = [rec for rec in run.rec_cycles if rec["metrics"].get("cycle") in cycles]
    if "explain" not in SPAN_CATALOGUE or not waves or len(recs) < len(cycles):
        return None
    return sum(s["end"] - s["start"] for rec in recs for s in rec["spans"] if s["name"].endswith("/explain")) / len(waves)
