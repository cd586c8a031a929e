"""Shared analysis substrate: one parse per file, consumed by every pass.

``SourceFile`` carries the path, raw text, split lines, and the parsed AST
(``None`` when the file does not parse — the driver reports E999 and the
passes skip it).  ``Context`` is the whole-repo view a pass runs against;
cross-file rules (DEAD, THRD's lock-order graph, JAXP's call graph) read it
directly instead of re-globbing.
"""

from __future__ import annotations

import ast
import pathlib
from dataclasses import dataclass

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
DEFAULT_PATHS = ["tpu_scheduler", "tests", "__graft_entry__.py", "scripts"]


@dataclass(frozen=True)
class Finding:
    """One rule violation.  Identity for baseline matching is
    ``(rule, path, message)`` — deliberately line-free, so editing an
    unrelated part of a file cannot stale a pinned finding."""

    rule: str
    path: str  # repo-relative posix path
    line: int
    message: str

    @property
    def key(self) -> tuple[str, str, str]:
        return (self.rule, self.path, self.message)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


@dataclass
class SourceFile:
    path: pathlib.Path
    rel: str
    text: str
    lines: list[str]
    tree: ast.Module | None  # None => syntax error (E999, reported by driver)

    def in_package(self, *parts: str) -> bool:
        return tuple(self.rel.split("/")[: len(parts)]) == parts


@dataclass
class Context:
    files: list[SourceFile]
    root: pathlib.Path
    readme: str

    def parsed(self) -> list[SourceFile]:
        return [f for f in self.files if f.tree is not None]


def iter_py(paths: list[str], root: pathlib.Path = ROOT) -> list[pathlib.Path]:
    out: list[pathlib.Path] = []
    for p in paths:
        path = root / p
        if path.is_dir():
            out.extend(sorted(path.rglob("*.py")))
        elif path.suffix == ".py":
            out.append(path)
    return out


def load_files(paths: list[str], root: pathlib.Path = ROOT) -> list[SourceFile]:
    files: list[SourceFile] = []
    for f in iter_py(paths, root):
        text = f.read_text()
        try:
            tree = ast.parse(text, filename=str(f))
        except SyntaxError:
            tree = None
        files.append(
            SourceFile(path=f, rel=f.relative_to(root).as_posix(), text=text, lines=text.splitlines(), tree=tree)
        )
    return files


# -- small AST helpers shared by several passes -----------------------------


def module_all(tree: ast.Module) -> list[str]:
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__" and isinstance(node.value, (ast.List, ast.Tuple)):
                    return [e.value for e in node.value.elts if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def top_level_defs(tree: ast.Module) -> set[str]:
    names: set[str] = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, ast.Tuple):
                    names.update(e.id for e in t.elts if isinstance(e, ast.Name))
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target, ast.Name):
            names.add(node.target.id)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                if a.name != "*":
                    names.add(a.asname or a.name.split(".")[0])
    return names


def self_attr_path(node: ast.expr) -> str | None:
    """Dotted attribute path rooted at ``self`` (``self._a._b`` -> "_a._b"),
    or None when the expression is not a pure self-attribute chain."""
    parts: list[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name) and cur.id == "self" and parts:
        return ".".join(reversed(parts))
    return None
