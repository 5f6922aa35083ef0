"""tpulint framework: findings, rule registry, suppressions, file walking.

The rules themselves live in sibling modules (``rules_code`` for the AST
rules, ``rules_config`` for the knob-registry cross-checks,
``checker_metrics``/``checker_manifests`` for the migrated PR-1/PR-3
linters).  This module is the machinery they all plug into:

- :class:`Finding` — one violation: rule code, file, line, message.
- :func:`file_rule` / :func:`repo_rule` — registration decorators.  A
  *file rule* runs per parsed Python file (AST + source in a
  :class:`FileContext`); a *repo rule* runs once per lint invocation
  against the repo root (doc/registry/manifest cross-checks).
- **Scoping** — each file rule declares the repo-relative glob(s) it
  applies to (engine files for trace-safety, serving+models for exception
  hygiene, everything for config discipline).  ``unscoped=True`` (CLI
  ``--no-scope``) disables scoping so fixture tests can exercise any rule
  on any file.
- **Suppressions** — ``# tpulint: disable=CODE[,CODE]`` on the offending
  line suppresses those codes there; ``# tpulint: disable-file=CODE`` on
  any line suppresses the codes for the whole file.  Suppressions are for
  *reviewed, intentional* violations (the documented host-sync fetch
  points in the engine); each should carry a justification comment.

Exit-code contract (``__main__``): 0 clean, 1 findings, 2 internal/usage
error — the same shape as lint_metrics/lint_manifests before they became
checkers here.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import os
import re
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence

REPO = Path(__file__).resolve().parents[2]

#: the python trees a full-repo lint walks (tests are excluded: fixture
#: snippets deliberately violate rules, and tests may poke raw env vars)
DEFAULT_SCAN = ("tpustack", "tools", "scripts", "bench.py", "chip_smoke.py")

#: never linted: the registry itself (it IS the env boundary) and caches
EXCLUDE_PARTS = ("__pycache__",)
EXCLUDE_FILES = ("tpustack/utils/knobs.py",)

# the code list ends at the first token that is not a comma-joined code, so
# a justification may follow on the same line ("disable=TPL201 OK: reviewed")
_CODE_LIST = r"([A-Z0-9]+(?:\s*,\s*[A-Z0-9]+)*)"
_SUPPRESS_RE = re.compile(r"#\s*tpulint:\s*disable=" + _CODE_LIST)
_SUPPRESS_FILE_RE = re.compile(r"#\s*tpulint:\s*disable-file=" + _CODE_LIST)


#: one parse per file per process: ``lint_repo`` walks the scan set for the
#: AST rules and TPL402's accessor cross-check walks it again — keyed on
#: (path, mtime, size) so a rewritten fixture file is never served stale
_AST_CACHE: Dict[tuple, ast.AST] = {}


def parse_cached(path: Path, src: str) -> ast.AST:
    try:
        st = path.stat()
        key = (str(path.resolve()), st.st_mtime_ns, st.st_size)
    except OSError:
        return ast.parse(src, filename=str(path))
    tree = _AST_CACHE.get(key)
    if tree is None:
        tree = _AST_CACHE[key] = ast.parse(src, filename=str(path))
    return tree


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str
    path: str  # repo-relative (or as given for out-of-repo fixtures)
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"

    def as_json(self) -> Dict[str, object]:
        return {"code": self.code, "path": self.path, "line": self.line,
                "message": self.message}


@dataclasses.dataclass(frozen=True)
class Rule:
    code: str
    name: str
    summary: str
    scope: Optional[Sequence[str]]  # globs; None = every scanned file
    fn: Callable


FILE_RULES: List[Rule] = []
REPO_RULES: List[Rule] = []


def file_rule(code: str, name: str, summary: str,
              scope: Optional[Sequence[str]] = None):
    def wrap(fn):
        FILE_RULES.append(Rule(code, name, summary, scope, fn))
        return fn
    return wrap


def repo_rule(code: str, name: str, summary: str):
    def wrap(fn):
        REPO_RULES.append(Rule(code, name, summary, None, fn))
        return fn
    return wrap


def all_rules() -> List[Rule]:
    return sorted(FILE_RULES + REPO_RULES, key=lambda r: r.code)


class FileContext:
    """One parsed Python file, shared by every file rule that runs on it:
    source lines (for suppression + annotation comments), the AST with
    parent links, and the repo-relative path rules scope against."""

    def __init__(self, path: Path, rel: str, src: str):
        self.path = path
        self.rel = rel
        self.src = src
        self.lines = src.splitlines()
        self.tree = parse_cached(path, src)
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                child._tpulint_parent = node  # type: ignore[attr-defined]
        self._file_suppressed = set()
        for line in self.lines:
            m = _SUPPRESS_FILE_RE.search(line)
            if m:
                self._file_suppressed.update(
                    c.strip() for c in m.group(1).split(",") if c.strip())

    # ------------------------------------------------------------ AST helpers
    @staticmethod
    def parent(node: ast.AST) -> Optional[ast.AST]:
        return getattr(node, "_tpulint_parent", None)

    def parents(self, node: ast.AST) -> Iterable[ast.AST]:
        p = self.parent(node)
        while p is not None:
            yield p
            p = self.parent(p)

    def enclosing_function(self, node: ast.AST):
        for p in self.parents(node):
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                return p
        return None

    def in_loop(self, node: ast.AST) -> bool:
        """Lexically inside a for/while body without an intervening
        function boundary (comprehensions don't count — their iteration is
        usually over already-fetched host data)."""
        for p in self.parents(node):
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                return False
            if isinstance(p, (ast.For, ast.While, ast.AsyncFor)):
                return True
        return False

    def held_locks(self, node: ast.AST) -> List[str]:
        """Unparsed context expressions of every enclosing ``with`` /
        ``async with`` item that looks like a lock (name contains 'lock'),
        up to the enclosing function boundary."""
        held: List[str] = []
        for p in self.parents(node):
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                break
            if isinstance(p, (ast.With, ast.AsyncWith)):
                for item in p.items:
                    expr = ast.unparse(item.context_expr)
                    if "lock" in expr.lower():
                        held.append(expr)
        return held

    # --------------------------------------------------------- suppressions
    def suppressed(self, code: str, line: int) -> bool:
        if code in self._file_suppressed:
            return True
        if 1 <= line <= len(self.lines):
            m = _SUPPRESS_RE.search(self.lines[line - 1])
            if m and code in [c.strip() for c in m.group(1).split(",")]:
                return True
        return False


def _rel(path: Path, root: Path) -> str:
    try:
        return path.resolve().relative_to(root.resolve()).as_posix()
    except ValueError:
        return path.as_posix()


def iter_python_files(paths: Sequence[str], root: Path = REPO):
    """Yield every .py file under ``paths`` (files or directories),
    skipping caches and the excluded registry module."""
    for p in paths:
        base = Path(p)
        if not base.is_absolute():
            base = root / p
        if base.is_file():
            candidates = [base]
        else:
            candidates = sorted(base.rglob("*.py"))
        for f in candidates:
            if any(part in EXCLUDE_PARTS for part in f.parts):
                continue
            if _rel(f, root) in EXCLUDE_FILES:
                continue
            yield f


def _in_scope(rule: Rule, rel: str, unscoped: bool) -> bool:
    if unscoped or rule.scope is None:
        return True
    return any(fnmatch.fnmatch(rel, pat) for pat in rule.scope)


def _selected(rule: Rule, select: Optional[Sequence[str]]) -> bool:
    if not select:
        return True
    return any(rule.code.startswith(s) for s in select)


def lint_files(paths: Sequence[str], root: Path = REPO,
               select: Optional[Sequence[str]] = None,
               unscoped: bool = False) -> List[Finding]:
    """Run the AST file rules over ``paths``.  Unparseable files are a
    finding (code TPL000), not a crash — the lint must not be silently
    blind to a syntax error."""
    findings: List[Finding] = []
    for f in iter_python_files(paths, root):
        rel = _rel(f, root)
        try:
            ctx = FileContext(f, rel, f.read_text())
        except (SyntaxError, UnicodeDecodeError) as e:
            findings.append(Finding("TPL000", rel, getattr(e, "lineno", 1)
                                    or 1, f"unparseable: {e}"))
            continue
        for rule in FILE_RULES:
            if not _selected(rule, select) or not _in_scope(rule, rel,
                                                            unscoped):
                continue
            for fd in rule.fn(ctx):
                if not ctx.suppressed(fd.code, fd.line):
                    findings.append(fd)
    return findings


def lint_repo(root: Path = REPO,
              select: Optional[Sequence[str]] = None,
              scan: Sequence[str] = DEFAULT_SCAN) -> List[Finding]:
    """Full lint: AST rules over the default scan set plus every repo
    checker (metrics catalog, manifests, knob registry cross-checks)."""
    findings = lint_files(scan, root, select=select)
    for rule in REPO_RULES:
        if _selected(rule, select):
            findings.extend(rule.fn(root))
    return sorted(findings, key=lambda f: (f.path, f.line, f.code))
