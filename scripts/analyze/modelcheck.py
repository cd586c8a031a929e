"""MODL — bounded explicit-state model checking of ``# protocol:`` specs.

PROT proves the code stays inside each machine's declared transition
relation; this pass proves the MACHINE ITSELF keeps its promises when the
world misbehaves.  Each spec's ``action``/``env`` lines compose the
protocol with its crash/retry/timeout environment — the actor dies
between any two steps, a message is delivered twice (an enabled action
can always re-fire) or never (the explorer also takes the path where it
doesn't), TTLs fire — and the explorer exhaustively enumerates every
reachable composite state ``(state, var values)``:

* ``invariant`` lines are safety properties: checked in every reachable
  state; a violation is reported with the minimal action trace that
  reaches it (BFS with deterministic, declaration-ordered successors).
* ``progress`` lines are no-stuck properties: any reachable state whose
  condition holds must have at least one enabled action — otherwise the
  protocol has wedged (e.g. an expired lease nobody can ever reclaim).

Vars saturate at their declared bounds, so the composite space is finite
by construction; a runaway spec trips MAX_STATES and reports that instead
of hanging the 5s analyze budget.  The pass is full-context (not
FILE_SCOPED, like EXCP): a spec edit anywhere re-verifies that machine
regardless of which files changed.

``LAST_STATS`` exposes per-machine exploration stats (states, transitions,
violations) after each run; the driver folds it into ``--json-out``.
"""

from __future__ import annotations

from collections import deque

from .core import Context, Finding
from . import protocol
from .protocol import MachineSpec, eval_cond

CODES = {
    "MODL": "a # protocol: machine composed with its crash/retry environment reaches a state violating a declared invariant or progress property (minimal trace in the finding)",
}

FILE_SCOPED = False

# Composite-space cap per machine.  The committed specs sit around 10-40
# states each; 20k is a runaway-spec backstop, not a tuning knob.
MAX_STATES = 20_000

# Per-machine exploration stats from the most recent run(), keyed by
# machine name: {"file", "states", "transitions", "invariants",
# "progress", "violations"}.  The driver folds this into --json-out.
LAST_STATS: dict = {}


def _apply_effects(action, env: dict, bounds: dict) -> dict:
    out = dict(env)
    for var, op, val in action.effects:
        cur = out[var]
        nxt = val if op == "=" else (cur + val if op == "+=" else cur - val)
        lo, hi = bounds[var]
        out[var] = min(hi, max(lo, nxt))  # saturating
    return out


def _successors(spec: MachineSpec, state: str, env: dict, bounds: dict):
    """Enabled actions in declaration order — determinism gives every
    violation a stable, minimal trace."""
    for a in spec.actions:
        if a.frm != "*" and a.frm != state:
            continue
        if a.requires is not None and not eval_cond(a.requires, state, env):
            continue
        to = state if a.to == "*" else a.to
        yield a, to, _apply_effects(a, env, bounds)


def explore(spec: MachineSpec) -> dict:
    """Exhaustive BFS over the composite space.

    Returns {"states": int, "transitions": int, "violations":
    [(kind, name, trace, line)], "capped": bool} where trace is the
    minimal action-name sequence from the initial state.
    """
    bounds = {v.name: (v.lo, v.hi) for v in spec.vars}
    init = (spec.init, tuple(v.init for v in spec.vars))
    var_names = [v.name for v in spec.vars]

    def as_env(values: tuple) -> dict:
        return dict(zip(var_names, values))

    parent: dict = {init: None}  # composite -> (prev composite, action name)
    queue = deque([init])
    transitions = 0
    capped = False
    violations: list = []
    seen_violation: set = set()  # (kind, name) — first (minimal) trace only

    def trace_to(node) -> list:
        steps: list = []
        while parent[node] is not None:
            prev, aname = parent[node]
            steps.append(aname)
            node = prev
        steps.reverse()
        return steps

    def check(node) -> None:
        state, values = node
        env = as_env(values)
        for name, cond, line in spec.invariants:
            if ("invariant", name) not in seen_violation and not eval_cond(cond, state, env):
                seen_violation.add(("invariant", name))
                violations.append(("invariant", name, trace_to(node), line))
        if spec.progress:
            stuck = not any(True for _ in _successors(spec, state, env, bounds))
            if stuck:
                for name, cond, line in spec.progress:
                    if ("progress", name) not in seen_violation and eval_cond(cond, state, env):
                        seen_violation.add(("progress", name))
                        violations.append(("progress", name, trace_to(node), line))

    check(init)
    while queue:
        node = queue.popleft()
        state, values = node
        env = as_env(values)
        for action, to, nenv in _successors(spec, state, env, bounds):
            transitions += 1
            nxt = (to, tuple(nenv[n] for n in var_names))
            if nxt not in parent:
                if len(parent) >= MAX_STATES:
                    capped = True
                    queue.clear()
                    break
                parent[nxt] = (node, action.name)
                check(nxt)
                queue.append(nxt)

    return {
        "states": len(parent),
        "transitions": transitions,
        "violations": violations,
        "capped": capped,
    }


def _fmt_state(spec: MachineSpec, trace: list) -> str:
    return " -> ".join(trace) if trace else "(initial state)"


def run(ctx: Context) -> list:
    findings: list[Finding] = []
    LAST_STATS.clear()
    for f in ctx.parsed():
        # Parse errors are PROT's to report; here broken specs are absent.
        machines, _ = protocol.collect_machines(f)
        for spec, _cls in machines:
            result = explore(spec)
            LAST_STATS[spec.name] = {
                "file": spec.rel,
                "states": result["states"],
                "transitions": result["transitions"],
                "invariants": len(spec.invariants),
                "progress": len(spec.progress),
                "violations": len(result["violations"]),
            }
            if result["capped"]:
                findings.append(
                    Finding(
                        "MODL", spec.rel, spec.line,
                        f"machine '{spec.name}': composite state space exceeds {MAX_STATES} states — tighten var bounds",
                    )
                )
                continue
            for kind, name, trace, line in result["violations"]:
                what = "violated" if kind == "invariant" else "stuck (no enabled action)"
                findings.append(
                    Finding(
                        "MODL", spec.rel, line,
                        f"machine '{spec.name}': {kind} '{name}' {what} after: {_fmt_state(spec, trace)}",
                    )
                )
    return findings
