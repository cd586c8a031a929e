"""Seconds per wave the scheduler spent inside the API server's binding
calls: the ``bind/post`` spans the flight recorder kept of the cycles of the
waves begun and decided inside the window (matched by cycle number).  None
where the program records no such span or the recorder no longer holds every
one of those cycles."""


def read(run):
    waves = {w["index"] for w in run.waves_in_window()}
    cycles = {c["m"].cycle for c in run.cycles if c["wave"] in waves}
    recs = [rec for rec in run.rec_cycles if rec["metrics"].get("cycle") in cycles]
    posts = [s["end"] - s["start"] for rec in recs for s in rec["spans"] if s["name"] == "bind/post"]
    if not waves or not posts or len(recs) < len(cycles):
        return None
    return sum(posts) / len(waves)
