"""The least time of the constrained placement kernel's work, from shapes.

``choose_block_constrained`` does for every (pod, node) pair what
``choose_block`` does (``benchmark/roofline.py``: ``5 * resources +
label_terms`` operations), and on top of that the work its constraint
operands imply for the pair:

- the blocked test: for each required inter-pod term the pod carries (a
  required anti-affinity term, a required pod-affinity term, a hard
  ``DoNotSchedule`` spread constraint), the pod's bit times the node's
  blocked-domain bit, added to the pair's sum (2 operations a term), and one
  comparison of that sum against zero for the pair;
- the spread level steering: for each hard spread constraint, the pod's bit
  times the node's domain height above the water line, added (2 operations);
- the soft scores: for each ``ScheduleAnyway`` spread constraint and each
  preferred pod (anti-)affinity term, the pod's weight times the node's match
  count, added (2 operations).

So ``5 * resources + label_terms + 1 + 2 * (blocked + steered + soft)``
operations a pair, with the per-pod counts the means over a wave's pods,
counted from the pods themselves (``benchmark/loops/wave_guarded.py``).

Bytes: what ``roofline.choose_work`` counts, plus one 4-byte word a pod for
each of its constraint terms and one 4-byte row a node for each distinct
constraint row of the wave (``node_rows``), each read once.

The least time is the larger of operations over the chip's bf16 peak and
bytes over its memory bandwidth (``roofline.least_seconds``).
"""

from __future__ import annotations

from benchmark import roofline


def choose_constrained_work(pods: int, nodes: int, resources: int, label_terms: int, blocked: float, steered: float,
                            soft: float, node_rows: float) -> tuple[float, float]:
    """(operations, bytes) of checking and scoring ``pods`` against ``nodes``
    with the constraint terms a pod carries on average (``blocked``,
    ``steered``, ``soft``) and the wave's distinct constraint rows."""
    ops, nbytes = roofline.choose_work(pods, nodes, resources, label_terms)
    terms = blocked + steered + soft
    ops += float(pods) * nodes * (1 + 2 * terms)
    nbytes += 4.0 * (pods * terms + nodes * node_rows)
    return ops, nbytes
