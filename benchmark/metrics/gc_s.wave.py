"""Seconds per wave the program's garbage collector paused
(``tpu_scheduler.utils.profiler.gc_pauses``): every pause that began inside
the window, the waves' creation and deletion included, over the waves the
window ran.  None where the program keeps no such record, or where its
record no longer reaches back to the window's opening."""


def read(run):
    try:
        from tpu_scheduler.utils.profiler import GC_PAUSE_CAPACITY, gc_pauses
    except ImportError:
        return None
    waves = run.waves_in_window()
    pauses = gc_pauses()
    if not waves or (len(pauses) >= GC_PAUSE_CAPACITY and pauses[0][0] > run.window[0]):
        return None
    lo, hi = run.window
    return sum(seconds for start, seconds, _ in pauses if lo <= start <= hi) / len(waves)
