"""Cycles per wave that left the device path for the host sequential chain
(``scheduler_constraint_host_fallbacks_total``, read by the loop before and
after each window wave), over the waves begun and decided inside the
window.  None where the loop recorded no such count."""


def read(run):
    waves = run.waves_in_window()
    if not waves or any("host_fallbacks" not in w for w in waves):
        return None
    return sum(w["host_fallbacks"] for w in waves) / len(waves)
