"""Share of its roofline the constrained placement kernel reaches per wave:
the least time of one pass of every pod of the wave against every node with
the constraint terms the pods carry (``benchmark/roofline_constrained.py``,
shapes from ``benchmark/loops/wave_guarded.py``) over the device time of
``choose_block_constrained`` in that wave, averaged over the waves begun and
decided inside the window.  None where the trace holds no such kernel in one
of those waves, or the loop recorded no constraint shapes."""

from benchmark import roofline, roofline_constrained, trace

KERNEL = "choose_block_constrained"


def read(run):
    s = run.shapes
    waves = run.waves_in_window()
    if run.trace is None or "blocked" not in s or not waves:
        return None
    events = [(st, en) for name, st, en in run.trace["kernel_events"] if trace.op_name(name) == KERNEL]
    per_wave = []
    for w in waves:
        lo, hi = run.trace_ns(w["t_created"]), run.trace_ns(w["t_done"])
        per_wave.append(sum(min(en, hi) - max(st, lo) for st, en in events if en > lo and st < hi))
    if not all(per_wave):
        return None
    ops, nbytes = roofline_constrained.choose_constrained_work(
        s["pods"], s["nodes"], s["resources"], s["label_terms"], s["blocked"], s["steered"], s["soft"], s["node_rows"])
    least, _bound = roofline.least_seconds(ops, nbytes, roofline.peaks(run.device_kind))
    return 100.0 * least / (sum(per_wave) / len(per_wave) / 1e9)
