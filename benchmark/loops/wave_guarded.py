"""Closed-loop admission waves (``benchmark.loops.wave``) under a watchdog.

The waves, the window and the warm-up are ``wave``'s own.  Two things are
added:

- A watchdog thread.  When set-up runs past the traffic's ``setup_limit_s``
  seconds from the process's start, or the window past ``--seconds`` plus
  the drain plus ``setup_limit_s`` from its opening, it prints a ``bench:``
  line saying so and ends the process with exit code 3.  A program that
  cannot decide a wave (a cycle that never returns) then fails the run, and
  does not hang it.
- The constrained roofline's shapes (``benchmark/roofline_constrained.py``),
  counted from the window waves' pods themselves: the mean number a pod
  carries of required inter-pod terms (anti-affinity, pod affinity, hard
  spread), of hard spread constraints, and of soft terms (ScheduleAnyway
  spread, preferred pod affinity and anti-affinity), and the mean number of
  distinct constraint rows a wave puts on the node side.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from benchmark.loops import wave

POLL_SECONDS = 1.0
EXIT_CODE = 3


def _deadline(run) -> tuple[str, float] | None:
    """What is being watched, and until when; None once the window closed."""
    limit = run.traffic["setup_limit_s"]
    if run.window is None:
        return "set-up", run.t_process_start + limit
    if run.window[1] is None:
        return "the window and its drain", run.window[0] + run.seconds + run.drain_seconds + limit
    return None


def watch(run, poll: float = POLL_SECONDS, exit=os._exit) -> threading.Thread:
    """Start the watchdog thread of ``run``."""

    def loop():
        while True:
            watched = _deadline(run)
            if watched is None:
                return
            what, until = watched
            if time.perf_counter() > until:
                print(f"bench: watchdog: {what} ran past its limit (setup_limit_s {run.traffic['setup_limit_s']}); "
                      f"ending the run with exit code {EXIT_CODE}", file=sys.stderr, flush=True)
                exit(EXIT_CODE)
                return
            time.sleep(poll)

    t = threading.Thread(target=loop, name="bench-watchdog", daemon=True)
    t.start()
    return t


def _selector(term) -> tuple:
    return tuple(sorted((term.match_labels or {}).items())), repr(getattr(term, "match_expressions", None)), term.topology_key


def constraint_shapes(waves: list) -> dict:
    """Per-pod means of the constraint terms of ``waves`` (lists of pods), and
    the mean count a wave of distinct node-side constraint rows: two a
    distinct anti-affinity term (matched and carrier), two a distinct hard
    spread constraint (blocked and level), one each other distinct term."""
    blocked = steered = soft = 0
    rows = 0
    pods = 0
    for pods_of_wave in waves:
        distinct: set = set()
        for p in pods_of_wave:
            s = p.spec
            ns = p.metadata.namespace
            hard = [c for c in (s.topology_spread or []) if c.is_hard]
            loose = [c for c in (s.topology_spread or []) if not c.is_hard]
            preferred = [w.term for w in (s.preferred_pod_affinity or []) + (s.preferred_pod_anti_affinity or [])]
            blocked += len(s.anti_affinity or []) + len(s.pod_affinity or []) + len(hard)
            steered += len(hard)
            soft += len(loose) + len(preferred)
            distinct.update(("aa-matched", ns, _selector(t)) for t in s.anti_affinity or [])
            distinct.update(("aa-carrier", ns, _selector(t)) for t in s.anti_affinity or [])
            distinct.update(("pa", ns, _selector(t)) for t in s.pod_affinity or [])
            distinct.update(("spread", ns, _selector(c), c.max_skew) for c in hard)
            distinct.update(("spread-level", ns, _selector(c), c.max_skew) for c in hard)
            distinct.update(("spread-soft", ns, _selector(c)) for c in loose)
            distinct.update(("preferred", ns, _selector(t)) for t in preferred)
            pods += 1
        rows += len(distinct)
    n = max(1, pods)
    return {"blocked": blocked / n, "steered": steered / n, "soft": soft / n, "node_rows": rows / max(1, len(waves))}


def prepare(run) -> None:
    watch(run)
    wave.prepare(run)
    run.shapes.update(constraint_shapes(run.prebuilt[run.traffic["warm_waves"]:]))


warm = wave.warm
measure = wave.measure
