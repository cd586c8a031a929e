"""Single-parse driver: load + parse every file once, run every pass over
the shared ``Context``, compare against the baseline, report.

Usage (also reachable through the ``scripts/lint.py`` shim):

    python -m scripts.analyze [paths...] [options]

Options:
    --rule CODE[,CODE]   run only the named rule(s); baseline comparison is
                         scoped to them
    --changed-only       fast path for pre-commit: analyze only the files
                         git reports changed (staged, unstaged, untracked),
                         running only the passes that are sound on a
                         partial context (each pass declares FILE_SCOPED)
    --json               machine-readable report on stdout (findings with a
                         baselined flag, plus new/stale arrays) for CI
                         annotation
    --json-out FILE      additionally write the JSON report to FILE
    --budget SECONDS     fail (exit 1) when the whole run exceeds SECONDS —
                         the make-check guarantee that analysis never
                         becomes the slow part of the gate
    --write-baseline     pin the current findings as the new baseline
                         (reasons start as a review placeholder)
    --no-baseline        report raw findings, ignore baseline.json
    --list-rules         print the rule catalogue and exit

Exit status: 0 iff there are no NEW findings, no STALE baseline entries,
and the budget (when given) was met.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import time

from . import catalogues, determinism, excp, exports, hygiene, jitc, jitpure, locks, modelcheck, protocol, shapes
from .baseline import BASELINE_PATH, compare, load_baseline, write_baseline
from .core import DEFAULT_PATHS, ROOT, Context, Finding, load_files

# Fixed pass order: cheap mechanical hygiene first, repo-invariant passes
# last (their reports are the ones a human digs into).  protocol precedes
# modelcheck so spec parse errors surface as PROT before MODL explores.
PASSES = (hygiene, exports, catalogues, excp, locks, jitpure, jitc, determinism, shapes, protocol, modelcheck)


def all_codes() -> dict[str, str]:
    """Every registered rule code -> one-line rationale (the ANLZ surface)."""
    out: dict[str, str] = {}
    for p in PASSES:
        out.update(p.CODES)
    return out


def file_scoped_codes() -> set[str]:
    """Rules sound on a partial file set (the --changed-only pass subset).
    E999 rides along: it is reported per file by the driver itself."""
    out = {"E999"}
    for p in PASSES:
        if getattr(p, "FILE_SCOPED", False):
            out.update(p.CODES)
    return out


def changed_paths(root: pathlib.Path = ROOT) -> list[str] | None:
    """Repo-relative paths git reports as changed (unstaged + staged +
    untracked), filtered to the analyzed extensions.  None when git itself
    fails (not a repo, no git) — the caller falls back to a full run."""
    try:
        out = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=all"],
            cwd=root,
            capture_output=True,
            text=True,
            timeout=30,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    paths: list[str] = []
    for line in out.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:].strip()
        if " -> " in path:  # rename: analyze the new side
            path = path.split(" -> ", 1)[1]
        path = path.strip('"')
        if path.endswith(".py") or path == "README.md":
            if (root / path).exists():
                paths.append(path)
    return sorted(set(paths))


def run_passes(ctx: Context, rules: set[str] | None = None, file_scoped_only: bool = False) -> list[Finding]:
    findings: list[Finding] = []
    for f in ctx.files:
        if f.tree is None:
            try:
                import ast

                ast.parse(f.text, filename=str(f.path))
            except SyntaxError as e:
                findings.append(Finding("E999", f.rel, e.lineno or 1, f"syntax error: {e.msg}"))
    for p in PASSES:
        if rules is not None and not (set(p.CODES) & rules):
            continue
        if file_scoped_only and not getattr(p, "FILE_SCOPED", False):
            continue
        findings.extend(p.run(ctx))
    if rules is not None:
        findings = [f for f in findings if f.rule in rules]
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule, f.message))


def main(argv: list[str]) -> int:
    t0 = time.perf_counter()
    args = list(argv)
    rules: set[str] | None = None
    as_json = write = no_baseline = changed_only = False
    json_out: str | None = None
    budget: float | None = None
    paths: list[str] = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--rule":
            i += 1
            if i >= len(args):
                print("--rule requires a CODE argument", file=sys.stderr)
                return 2
            rules = (rules or set()) | {c.strip().upper() for c in args[i].split(",") if c.strip()}
        elif a.startswith("--rule="):
            rules = (rules or set()) | {c.strip().upper() for c in a.split("=", 1)[1].split(",") if c.strip()}
        elif a == "--json":
            as_json = True
        elif a == "--json-out":
            i += 1
            if i >= len(args):
                print("--json-out requires a FILE argument", file=sys.stderr)
                return 2
            json_out = args[i]
        elif a.startswith("--json-out="):
            json_out = a.split("=", 1)[1]
        elif a == "--budget":
            i += 1
            if i >= len(args):
                print("--budget requires a SECONDS argument", file=sys.stderr)
                return 2
            budget = float(args[i])
        elif a.startswith("--budget="):
            budget = float(a.split("=", 1)[1])
        elif a == "--changed-only":
            changed_only = True
        elif a == "--write-baseline":
            write = True
        elif a == "--no-baseline":
            no_baseline = True
        elif a == "--list-rules":
            for code, rationale in sorted(all_codes().items()):
                print(f"{code}  {rationale}")
            return 0
        elif a in ("-h", "--help"):
            print(__doc__)
            return 0
        elif a.startswith("-"):
            print(f"unknown option {a!r}", file=sys.stderr)
            return 2
        else:
            paths.append(a)
        i += 1

    if rules is not None:
        unknown = rules - set(all_codes())
        if unknown:
            print(f"unknown rule(s): {', '.join(sorted(unknown))} (see --list-rules)", file=sys.stderr)
            return 2

    if changed_only:
        changed = changed_paths()
        if changed is None:
            print("analyze: --changed-only could not read git status; running the full set", file=sys.stderr)
        else:
            # Only files under the analyzed roots — a stray .py elsewhere in
            # the repo is not this gate's business.
            roots = tuple(p for p in DEFAULT_PATHS if (ROOT / p).is_dir())
            files = tuple(p for p in DEFAULT_PATHS if not (ROOT / p).is_dir())
            paths = [
                p
                for p in changed
                if p.endswith(".py") and (p.startswith(tuple(r + "/" for r in roots)) or p in files)
            ]
            if not paths:
                print("analyze: 0 changed files, nothing to check")
                return 0
            # Restrict the rule set to passes sound on a partial context, so
            # the baseline comparison cannot cry NEW or STALE on rules that
            # did not (or could not correctly) run.
            scoped = file_scoped_codes()
            rules = (rules & scoped) if rules is not None else scoped

    files = load_files(paths or DEFAULT_PATHS)
    readme = (ROOT / "README.md").read_text() if (ROOT / "README.md").exists() else ""
    ctx = Context(files=files, root=ROOT, readme=readme)
    findings = run_passes(ctx, rules, file_scoped_only=changed_only)

    if write:
        write_baseline(findings)
        print(f"analyze: wrote {len(findings)} baseline entr{'y' if len(findings) == 1 else 'ies'} to {BASELINE_PATH}")
        return 0

    if no_baseline:
        entries: list[dict] = []
    else:
        entries = load_baseline()
    # Scope the stale check to the analyzed files (plus README, which the
    # catalogue gates report against) so a partial run cannot cry stale.
    scope_paths = {f.rel for f in files} | {"README.md"}
    new, stale, baselined = compare(findings, entries, rules=rules, paths=scope_paths)

    elapsed = time.perf_counter() - t0
    over_budget = budget is not None and elapsed > budget

    report = None
    if as_json or json_out:
        report = {
            "files": len(files),
            "findings": [
                {**f.__dict__, "baselined": f.key in {b.key for b in baselined}} for f in findings
            ],
            "new": [f.__dict__ for f in new],
            "stale": stale,
            "elapsed_s": round(elapsed, 3),
            "budget_s": budget,
            "changed_only": changed_only,
            # Per-machine model-check stats (empty when MODL did not run,
            # e.g. --changed-only or a --rule subset).
            "modelcheck": dict(modelcheck.LAST_STATS),
            # Bucket/hotpath contract coverage (empty when JITC did not
            # run).
            "jitc": dict(jitc.LAST_STATS),
        }
    if json_out and report is not None:
        pathlib.Path(json_out).write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    if as_json and report is not None:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        for f in new:
            print(f.render())
        for e in stale:
            print(
                f"{e['path']}:1: STALE baseline entry — {e['rule']} \"{e['message']}\" no longer found; "
                f"remove it from scripts/analyze/baseline.json (reason was: {e['reason']})"
            )
        mode = " (changed-only)" if changed_only else ""
        print(
            f"analyze{mode}: {len(files)} files, {len(findings)} findings "
            f"({len(baselined)} baselined), {len(new)} new, {len(stale)} stale, {elapsed:.2f}s"
        )
    if over_budget:
        print(
            f"analyze: BUDGET EXCEEDED — {elapsed:.2f}s > {budget:.2f}s; the analysis gate must stay "
            "the fast part of make check (profile the passes, see scripts/analyze/exports.py for the pattern)",
            file=sys.stderr,
        )
    return 1 if new or stale or over_budget else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
