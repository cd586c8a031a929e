"""Hygiene passes ported from the monolithic ``scripts/lint.py``: the error
classes a round-2 regression shipped with (stale imports, phantom exports)
plus basic mechanical hygiene, on the stdlib so the gate runs in the build
image (which carries no installable linter).

Scope: the WHOLE analyzed tree — ``tpu_scheduler/``, ``tests/``,
``scripts/``, ``__graft_entry__.py`` (every file the driver
loads; there is no package filter here, and tests/test_analyze.py pins that
a violation seeded under tests/ or scripts/ is flagged)."""

from __future__ import annotations

import ast
import re

from .core import Context, Finding, SourceFile, module_all, top_level_defs

CODES = {
    "E999": "syntax errors (ast.parse) — nothing else is checkable past one",
    "W291": "trailing whitespace — diff noise that masks real changes",
    "W191": "tabs in indentation — one indentation currency repo-wide",
    "E711": "comparison to None with ==/!= — use is / is not",
    "E712": "comparison to True/False with ==/!= — use the value or is",
    "E722": "bare except: — swallows KeyboardInterrupt/SystemExit and hides real faults; name the exception",
    "E741": "ambiguous single-char binding (l/O/I) — unreadable in most fonts, a classic transcription bug",
    "B006": "mutable default argument — shared across calls, a classic aliasing bug",
    "F841": "local assigned once and never read — dead stores hide logic errors",
    "F401": "imported name never used in the module — stale-import rot",
    "F822": "__all__ names a symbol the module does not define — phantom export",
}

# Per-file rules only — safe under the driver's --changed-only fast path.
FILE_SCOPED = True

_AMBIGUOUS = ("l", "O", "I")


class _FnScope:
    """One function's F841 state, filled during the single module walk.

    STORES are collected from the function's OWN scope only (nested
    function/lambda/class bodies get their own record — counting them here
    would double-report their dead stores against the outer scope); READS
    come from the full subtree so a closure's use of an outer local still
    counts (conservative: an inner local shadowing an outer name can mask
    an outer dead store — false negatives over false positives).
    AugAssign targets count as READS — the ledger-accumulator pattern is a
    use, not a dead store."""

    __slots__ = ("relpath", "assigned", "reads", "exempt", "args")

    def __init__(self, relpath: str, node) -> None:
        self.relpath = relpath
        self.assigned: dict[str, int] = {}
        self.reads: set[str] = set()
        self.exempt: set[str] = set()
        self.args = {a.arg for a in node.args.args + node.args.kwonlyargs + node.args.posonlyargs}

    def finalize(self, findings: list[Finding]) -> None:
        for name, lineno in sorted(self.assigned.items(), key=lambda kv: kv[1]):
            if name in self.reads or name in self.exempt or name in self.args or name.startswith("_"):
                continue
            if name in ("self", "cls"):
                continue
            findings.append(Finding("F841", self.relpath, lineno, f"local variable '{name}' assigned but never used"))


def _check_module(f: SourceFile, findings: list[Finding]) -> None:
    tree = f.tree
    assert tree is not None
    rel = f.rel
    imports: dict[str, int] = {}  # bound name -> lineno
    used: set[str] = set()
    # ONE walk of the module drives every rule — E722/E741 (bare except,
    # ambiguous bindings), E711/E712 (None/bool compares, both sides so
    # Yoda comparisons are caught too), import collection for F401, AND
    # the per-function scope state (B006/F841).  The F841 reads/stores
    # used to be two more full traversals (subtree reads per function,
    # own-scope stores per function); here each node is visited exactly
    # once carrying its enclosing-function context: ``fscopes`` is the
    # stack of _FnScope records whose subtree contains the node (a Name
    # Load feeds every one of them — that is exactly the old full-subtree
    # reads semantics), and ``own`` says whether plain stores at this node
    # belong to ``fscopes[-1]``'s own scope (False under a lambda/class
    # barrier — a class attribute is not a function local — and at module
    # level).
    records: list[_FnScope] = []
    stack: list = [(node, (), False) for node in ast.iter_child_nodes(tree)]
    while stack:
        node, fscopes, own = stack.pop()
        t = type(node)
        if t is ast.Name:
            if isinstance(node.ctx, ast.Load):
                used.add(node.id)
                for r in fscopes:
                    r.reads.add(node.id)
            elif isinstance(node.ctx, ast.Store):
                if node.id in _AMBIGUOUS:
                    findings.append(Finding("E741", rel, node.lineno, f"ambiguous variable name '{node.id}'"))
                if own:
                    r = fscopes[-1]
                    # Earliest store wins (stack order is not document
                    # order, so keep the min lineno explicitly).
                    prev = r.assigned.get(node.id)
                    if prev is None or node.lineno < prev:
                        r.assigned[node.id] = node.lineno
            continue  # Name nodes are leaves bar the ctx
        if t is ast.Constant:
            continue
        if t in (ast.FunctionDef, ast.AsyncFunctionDef):
            # B006 — mutable literals/constructors as parameter defaults.
            for default in list(node.args.defaults) + [d for d in node.args.kw_defaults if d is not None]:
                if isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(default, ast.Call)
                    and isinstance(default.func, ast.Name)
                    and default.func.id in ("list", "dict", "set")
                ):
                    findings.append(Finding("B006", rel, default.lineno, "mutable default argument"))
            r = _FnScope(rel, node)
            records.append(r)
            inner = fscopes + (r,)
            stack.extend((child, inner, True) for child in ast.iter_child_nodes(node))
            continue
        if t in (ast.Lambda, ast.ClassDef):
            # Scope barrier: reads still reach the enclosing functions (a
            # closure use counts), but stores are no longer their locals.
            stack.extend((child, fscopes, False) for child in ast.iter_child_nodes(node))
            continue
        if t is ast.Compare:
            # Operand i of op i is left for i == 0, else comparators[i-1].
            operands = [node.left] + list(node.comparators)
            for i, op in enumerate(node.ops):
                if not isinstance(op, (ast.Eq, ast.NotEq)):
                    continue
                for side in (operands[i], operands[i + 1]):
                    if not isinstance(side, ast.Constant):
                        continue
                    if side.value is None:
                        findings.append(Finding("E711", rel, node.lineno, "comparison to None (use 'is'/'is not')"))
                    elif side.value is True or side.value is False:
                        findings.append(
                            Finding("E712", rel, node.lineno, f"comparison to {side.value} (use the value or 'is')")
                        )
        elif t is ast.arg:
            if node.arg in _AMBIGUOUS:
                findings.append(Finding("E741", rel, node.lineno, f"ambiguous argument name '{node.arg}'"))
        elif t is ast.ExceptHandler:
            if node.type is None:
                findings.append(Finding("E722", rel, node.lineno, "bare 'except:' — name the exception"))
        elif t is ast.Import:
            for a in node.names:
                imports[a.asname or a.name.split(".")[0]] = node.lineno
        elif t is ast.ImportFrom:
            # future imports act by existing, never by reference
            if node.module != "__future__":
                for a in node.names:
                    if a.name != "*":
                        imports[a.asname or a.name] = node.lineno
        elif t is ast.AugAssign:
            # In-place mutation is a USE of the target, not a dead store.
            if isinstance(node.target, ast.Name):
                for r in fscopes:
                    r.reads.add(node.target.id)
        elif own:
            # global/nonlocal writes are module/outer-scope effects, and
            # loop induction variables are iteration plumbing (ruff would
            # file them under B007) — neither is an unused LOCAL.
            r = fscopes[-1]
            if t in (ast.Global, ast.Nonlocal):
                r.exempt.update(node.names)
            elif t in (ast.For, ast.AsyncFor):
                r.exempt.update(n.id for n in ast.walk(node.target) if isinstance(n, ast.Name))
            elif t is ast.comprehension:
                r.exempt.update(n.id for n in ast.walk(node.target) if isinstance(n, ast.Name))
            elif t in (ast.With, ast.AsyncWith):
                # `with ... as x:` targets are context handles pyflakes/ruff
                # never file under F841 (e.g. pytest.raises(...) as exc).
                for item in node.items:
                    if item.optional_vars is not None:
                        r.exempt.update(n.id for n in ast.walk(item.optional_vars) if isinstance(n, ast.Name))
            elif t is ast.Assign:
                # Tuple-unpack targets document structure — exempt them.
                for tgt in node.targets:
                    if isinstance(tgt, (ast.Tuple, ast.List)):
                        r.exempt.update(n.id for n in ast.walk(tgt) if isinstance(n, ast.Name))
        stack.extend((child, fscopes, own) for child in ast.iter_child_nodes(node))
    for r in records:
        r.finalize(findings)
    exported = set(module_all(tree))
    # Names referenced in string annotations / docstring doctests are out
    # of scope; __init__ re-exports are legitimate when listed in __all__.
    is_init = f.path.name == "__init__.py"
    for name, lineno in imports.items():
        if name in used or name == "_":
            continue
        if is_init or name in exported:
            continue
        # A conservative text check catches usage forms the AST walk does
        # not model (e.g. inside f-string format specs).
        if len(re.findall(rf"\b{re.escape(name)}\b", f.text)) > 1:
            continue
        findings.append(Finding("F401", f.rel, lineno, f"'{name}' imported but unused"))
    defined = top_level_defs(tree)
    for name in exported:
        if name not in defined:
            findings.append(Finding("F822", f.rel, 1, f"undefined name '{name}' in __all__"))


def run(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    for f in ctx.files:
        for i, line in enumerate(f.lines, 1):
            if line != line.rstrip():
                findings.append(Finding("W291", f.rel, i, "trailing whitespace"))
            if line.startswith("\t"):
                findings.append(Finding("W191", f.rel, i, "tab in indentation"))
        if f.tree is not None:
            _check_module(f, findings)
    return findings
