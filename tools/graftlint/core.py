"""graftlint core: project model, findings, baseline, runner."""

from __future__ import annotations

import ast
import hashlib
import json
from dataclasses import dataclass, field
from functools import cached_property
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set

from tools.graftlint.astutil import (ImportMap, build_parent_map,
                                     collect_callback_functions,
                                     collect_jitted_names,
                                     collect_traced_functions, defs_by_name)

SEVERITIES = ("error", "warning")

_SKIP_DIR_NAMES = {"__pycache__", ".git", ".pytest_cache", ".hypothesis",
                   "node_modules", ".venv"}


@dataclass
class Finding:
    """One diagnostic, anchored to ``path:line``."""

    rule: str                 # "GL001".."GL005" (or "GL000" parse error)
    severity: str             # "error" | "warning"
    path: str                 # repo-root-relative posix path
    line: int
    col: int
    message: str
    hint: str = ""            # how to fix (or legitimately suppress)
    fingerprint: str = ""     # stable id for baseline suppression

    def location(self) -> str:
        return f"{self.path}:{self.line}:{self.col}"

    def to_dict(self) -> Dict[str, object]:
        return {"rule": self.rule, "severity": self.severity,
                "path": self.path, "line": self.line, "col": self.col,
                "message": self.message, "hint": self.hint,
                "fingerprint": self.fingerprint}


@dataclass
class ParsedFile:
    """One parsed file and the analyses its rules share, each made once
    a lint run: ``nodes`` (the tree in ``ast.walk`` order, walked once),
    ``parents``, ``imports``, and on first use ``defs``, ``traced``,
    ``callback_fns`` and ``jitted_names``."""

    path: Path                          # absolute
    rel: str                            # repo-root-relative, posix
    tree: ast.Module
    lines: List[str]
    nodes: List[ast.AST]
    imports: ImportMap
    parents: Dict[ast.AST, ast.AST] = field(default_factory=dict)

    @cached_property
    def defs(self) -> Dict[str, List[ast.AST]]:
        return defs_by_name(self.nodes)

    @cached_property
    def traced(self) -> Set[ast.AST]:
        """Function nodes whose bodies run under jax tracing."""
        return collect_traced_functions(self.nodes, self.imports,
                                        self.defs)

    @cached_property
    def callback_fns(self) -> Set[ast.AST]:
        """Function nodes passed to a host-callback primitive."""
        return collect_callback_functions(self.nodes, self.imports,
                                          self.defs)

    @cached_property
    def jitted_names(self) -> Set[str]:
        """Names bound to jitted callables."""
        return collect_jitted_names(self.nodes, self.imports, self.traced)

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""


class Project:
    """The scanned file set plus the repo context (pyproject root) the
    cross-file checkers need."""

    def __init__(self, paths: Sequence[Path],
                 repo_root: Optional[Path] = None):
        self.scan_paths = [Path(p).resolve() for p in paths]
        self.repo_root = (Path(repo_root).resolve() if repo_root
                          else _find_repo_root(self.scan_paths))
        self.files: List[ParsedFile] = []
        self.parse_failures: List[Finding] = []
        for py in _iter_python_files(self.scan_paths):
            self._load(py)
        self.files.sort(key=lambda pf: pf.rel)

    def _relpath(self, path: Path) -> str:
        try:
            return path.relative_to(self.repo_root).as_posix()
        except ValueError:
            return path.as_posix()

    def _load(self, path: Path) -> None:
        rel = self._relpath(path)
        try:
            source = path.read_text(encoding="utf-8")
            tree = ast.parse(source, filename=str(path))
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            line = getattr(e, "lineno", 1) or 1
            self.parse_failures.append(Finding(
                rule="GL000", severity="error", path=rel, line=line,
                col=0, message=f"file does not parse: {e}",
                hint="fix the syntax error; graftlint checks nothing "
                     "else in an unparseable file"))
            return
        nodes = list(ast.walk(tree))
        pf = ParsedFile(path=path, rel=rel, tree=tree,
                        lines=source.splitlines(), nodes=nodes,
                        imports=ImportMap(nodes),
                        parents=build_parent_map(nodes))
        self.files.append(pf)

    def file_ending_with(self, suffix: str) -> Optional[ParsedFile]:
        for pf in self.files:
            if pf.rel.endswith(suffix):
                return pf
        return None


def _iter_python_files(paths: Sequence[Path]):
    seen: Set[Path] = set()
    for p in paths:
        if p.is_file() and p.suffix == ".py":
            if p not in seen:
                seen.add(p)
                yield p
        elif p.is_dir():
            for sub in sorted(p.rglob("*.py")):
                if any(part in _SKIP_DIR_NAMES for part in sub.parts):
                    continue
                if sub not in seen:
                    seen.add(sub)
                    yield sub


def _find_repo_root(paths: Sequence[Path]) -> Path:
    start = paths[0] if paths else Path.cwd()
    cur = start if start.is_dir() else start.parent
    for _ in range(12):
        if (cur / "pyproject.toml").exists():
            return cur
        if cur.parent == cur:
            break
        cur = cur.parent
    return start if start.is_dir() else start.parent


# --- fingerprints / baseline ----------------------------------------------

def _fingerprint(finding: Finding, line_text: str) -> str:
    # keyed on the line's *text*, not its number, so unrelated edits
    # above a suppressed finding don't invalidate the baseline entry
    blob = "|".join((finding.rule, finding.path, line_text,
                     finding.message))
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:16]


def stamp_fingerprints(project: Project,
                       findings: List[Finding]) -> None:
    by_rel = {pf.rel: pf for pf in project.files}
    for f in findings:
        pf = by_rel.get(f.path)
        if pf is not None:
            text = pf.line_text(f.line)
        else:
            text = _doc_line_text(project, f.path, f.line)
        f.fingerprint = _fingerprint(f, text)


def _doc_line_text(project: Project, rel: str, line: int) -> str:
    path = project.repo_root / rel
    try:
        lines = path.read_text(encoding="utf-8").splitlines()
        return lines[line - 1].strip() if 1 <= line <= len(lines) else ""
    except OSError:
        return ""


def load_baseline(path: Path) -> Set[str]:
    try:
        data = json.loads(Path(path).read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError):
        return set()
    return {e.get("fingerprint", "") for e in data.get("findings", [])}


def write_baseline(path: Path, findings: Sequence[Finding]) -> None:
    data = {
        "version": 1,
        "comment": "graftlint suppressions: remove entries as the "
                   "underlying findings are fixed. An empty list means "
                   "the tree is clean.",
        "findings": [
            {"fingerprint": f.fingerprint, "rule": f.rule,
             "path": f.path, "message": f.message}
            for f in findings
        ],
    }
    Path(path).write_text(json.dumps(data, indent=2) + "\n",
                          encoding="utf-8")


# --- inline suppression -----------------------------------------------------

_SUPPRESS_RE = None  # compiled lazily; module stays import-light

KNOWN_RULES = frozenset(
    {"GL000"} | {f"GL{n:03d}" for n in range(1, 17)})


def _suppress_regex():
    global _SUPPRESS_RE
    if _SUPPRESS_RE is None:
        import re
        _SUPPRESS_RE = re.compile(
            r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\s]+)")
    return _SUPPRESS_RE


def _line_suppressions(pf: ParsedFile):
    """line number -> set of rule codes disabled on that line, plus
    warning findings for unknown codes."""
    out: Dict[int, Set[str]] = {}
    warnings: List[Finding] = []
    rx = _suppress_regex()
    for lineno, text in enumerate(pf.lines, start=1):
        if "graftlint" not in text:
            continue
        m = rx.search(text)
        if not m:
            continue
        codes = {c.strip().upper() for c in m.group(1).split(",")
                 if c.strip()}
        for code in sorted(codes):
            if code not in KNOWN_RULES and code != "ALL":
                warnings.append(Finding(
                    rule="GL000", severity="warning", path=pf.rel,
                    line=lineno, col=text.index("#"),
                    message=f"unknown rule code {code!r} in graftlint "
                            f"suppression comment",
                    hint=f"known codes are "
                         f"{', '.join(sorted(KNOWN_RULES))} (or 'all')"))
        out[lineno] = codes
    return out, warnings


def apply_inline_suppressions(project: Project,
                              findings: List[Finding]) -> List[Finding]:
    """Honor ``# graftlint: disable=GL00N`` end-of-line comments: a
    finding anchored to an annotated line is dropped; unknown codes
    produce a GL000 warning so typos don't silently disable nothing."""
    by_rel: Dict[str, ParsedFile] = {pf.rel: pf for pf in project.files}
    maps: Dict[str, Dict[int, Set[str]]] = {}
    kept: List[Finding] = []
    extra: List[Finding] = []
    for rel, pf in by_rel.items():
        maps[rel], warns = _line_suppressions(pf)
        extra.extend(warns)
    for f in findings:
        codes = maps.get(f.path, {}).get(f.line)
        if codes and (f.rule in codes or "ALL" in codes):
            continue
        kept.append(f)
    return kept + extra


# --- runner ----------------------------------------------------------------

def run_checks(paths: Sequence[Path],
               select: Optional[Sequence[str]] = None,
               repo_root: Optional[Path] = None):
    """Parse ``paths`` and run the (selected) checkers.

    Returns ``(project, findings)``; findings are fingerprint-stamped,
    inline-suppression-filtered and sorted by (path, line, rule).
    Baseline filtering is the CLI's job — callers see everything
    else."""
    from tools.graftlint.checkers import all_checkers

    project = Project(paths, repo_root=repo_root)
    findings: List[Finding] = list(project.parse_failures)
    wanted = {s.upper() for s in select} if select else None
    for checker in all_checkers():
        if wanted is not None and checker.rule not in wanted:
            continue
        findings.extend(checker.check_project(project))
    findings = apply_inline_suppressions(project, findings)
    stamp_fingerprints(project, findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return project, findings


class Checker:
    """Base checker: subclasses set ``rule``/``name``/``description``
    and override ``check_file`` (per-file rules) or ``check_project``
    (cross-file rules like GL004)."""

    rule = "GL000"
    name = "base"
    description = ""

    def check_project(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        for pf in project.files:
            out.extend(self.check_file(pf, project))
        return out

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        return []
