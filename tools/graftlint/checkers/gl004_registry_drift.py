"""GL004 — registry drift.

Two registries keep the fault-tolerance and configuration surfaces
honest, and both can silently drift from the code:

  - fault points: every production ``fault_point("name")`` call site
    must name an entry in ``core/faults.py``'s ``KNOWN_POINTS`` (the
    fuzzing suite arms points from that dict via
    ``tests/fuzzing/registry.py``), and every registered point must
    have at least one call site — an orphaned registration means the
    chaos suite reports false coverage;
  - env vars: every ``MMLSPARK_TPU_*`` variable must be (a) read
    through the typed helpers in ``core/env.py``, (b) declared in that
    module's registry, and (c) documented in PARAMS.md or README.md —
    and every documented variable must still exist in code. This is
    the checker that caught the 5 undocumented knobs this tool was
    built for.

All parsing is AST/regex — nothing is imported.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from tools.graftlint.astutil import dotted
from tools.graftlint.core import Checker, Finding, ParsedFile, Project

_ENV_NAME = re.compile(r"^MMLSPARK_TPU_[A-Z0-9]+(?:_[A-Z0-9]+)*$")
_ENV_IN_DOCS = re.compile(r"MMLSPARK_TPU_[A-Z0-9_]*[A-Z0-9]")
_TYPED_READERS = {"env_flag", "env_int", "env_str", "env_raw",
                  "env_override"}
_ENVIRON_METHODS = {"get", "pop", "setdefault"}


class RegistryDriftChecker(Checker):
    rule = "GL004"
    name = "registry-drift"
    description = ("fault points vs KNOWN_POINTS; MMLSPARK_TPU_* env "
                   "vars vs core/env.py registry vs PARAMS.md/README")

    def check_project(self, project: Project) -> List[Finding]:
        return (self._check_fault_points(project)
                + self._check_env_vars(project))

    # --- fault points ----------------------------------------------------

    def _check_fault_points(self, project: Project) -> List[Finding]:
        faults_pf = project.file_ending_with("core/faults.py")
        if faults_pf is None:
            return []
        known = _known_points(faults_pf)
        if known is None:
            return [Finding(
                rule=self.rule, severity="error", path=faults_pf.rel,
                line=1, col=0,
                message="KNOWN_POINTS dict literal not found in "
                        "core/faults.py",
                hint="keep KNOWN_POINTS a module-level dict literal so "
                     "the fuzzing registry and this checker can "
                     "enumerate it")]
        out: List[Finding] = []
        sites: Dict[str, List[Tuple[ParsedFile, int, int]]] = {}
        for pf in project.files:
            if pf is faults_pf:
                continue   # the harness's own docs/examples
            for call in pf.nodes:
                if not isinstance(call, ast.Call):
                    continue
                fname = (dotted(call.func) or "").split(".")[-1]
                if fname != "fault_point" or not call.args:
                    continue
                arg = call.args[0]
                if isinstance(arg, ast.Constant) and isinstance(
                        arg.value, str):
                    sites.setdefault(arg.value, []).append(
                        (pf, call.lineno, call.col_offset))
        for name, where in sorted(sites.items()):
            if name not in known:
                pf, line, col = where[0]
                out.append(Finding(
                    rule=self.rule, severity="error", path=pf.rel,
                    line=line, col=col,
                    message=f"fault_point({name!r}) is not registered "
                            f"in core/faults.py KNOWN_POINTS",
                    hint="add the point (name -> what arming it "
                         "simulates) to KNOWN_POINTS so the fuzzing "
                         "suite can arm it"))
        for name, line in sorted(known.items()):
            if name not in sites:
                out.append(Finding(
                    rule=self.rule, severity="error",
                    path=faults_pf.rel, line=line, col=0,
                    message=f"KNOWN_POINTS entry {name!r} has no "
                            f"fault_point() call site",
                    hint="thread the point through production code or "
                         "remove the registration — an orphaned entry "
                         "is false chaos coverage"))
        out.extend(self._check_fuzzing_registry(project, faults_pf))
        return out

    def _check_fuzzing_registry(self, project: Project,
                                faults_pf: ParsedFile) -> List[Finding]:
        tests_dir = project.repo_root / "tests"
        if not tests_dir.is_dir():
            return []   # fixture project without a test tree
        reg = tests_dir / "fuzzing" / "registry.py"
        try:
            text = reg.read_text(encoding="utf-8")
        except OSError:
            return [Finding(
                rule=self.rule, severity="error",
                path="tests/fuzzing/registry.py", line=1, col=0,
                message="fuzzing registry missing: fault points are "
                        "not exposed to the fuzzing suite",
                hint="re-export core.faults.KNOWN_POINTS from "
                     "tests/fuzzing/registry.py")]
        if "KNOWN_POINTS" not in text:
            return [Finding(
                rule=self.rule, severity="error",
                path="tests/fuzzing/registry.py", line=1, col=0,
                message="fuzzing registry does not reference "
                        "KNOWN_POINTS; armable points have drifted "
                        "out of the fuzzing surface",
                hint="source the registry's fault-point list from "
                     "core.faults.KNOWN_POINTS")]
        return []

    # --- env vars --------------------------------------------------------

    def _check_env_vars(self, project: Project) -> List[Finding]:
        env_pf = project.file_ending_with("core/env.py")
        out: List[Finding] = []

        typed_reads: Dict[str, Tuple[ParsedFile, int, int]] = {}
        raw_reads: List[Tuple[str, ParsedFile, int, int]] = []
        for pf in project.files:
            for name, line, col, raw in _env_references(pf):
                if raw and pf is not env_pf:
                    raw_reads.append((name, pf, line, col))
                typed_reads.setdefault(name, (pf, line, col))

        registered: Dict[str, int] = (
            _registered_vars(env_pf) if env_pf is not None else {})

        for name, pf, line, col in raw_reads:
            out.append(Finding(
                rule=self.rule, severity="error", path=pf.rel,
                line=line, col=col,
                message=f"raw os.environ access to {name}; framework "
                        f"knobs must go through core/env.py",
                hint="use env_flag/env_int/env_str/env_override from "
                     "mmlspark_tpu.core.env (typed, registered, "
                     "warn-once on bad values)"))

        if env_pf is not None:
            for name, (pf, line, col) in sorted(typed_reads.items()):
                if name not in registered and pf is not env_pf:
                    out.append(Finding(
                        rule=self.rule, severity="error", path=pf.rel,
                        line=line, col=col,
                        message=f"{name} is read but not declared in "
                                f"the core/env.py registry",
                        hint="add a register(...) declaration with "
                             "kind/default/description"))

        doc_names = self._documented_vars(project)
        code_names = set(typed_reads) | set(registered)
        if doc_names is None:
            return out
        docs, doc_set = doc_names
        for name in sorted(code_names - doc_set):
            pf, line, col = typed_reads.get(name, (None, 0, 0))
            if pf is None and env_pf is not None:
                pf, line, col = env_pf, registered.get(name, 1), 0
            out.append(Finding(
                rule=self.rule, severity="error",
                path=pf.rel if pf else "PARAMS.md", line=line or 1,
                col=col,
                message=f"{name} is read in code but undocumented",
                hint="add it to the PARAMS.md env-var tables (default "
                     "+ effect); GL004 keeps the table honest"))
        if env_pf is None:
            # partial scan (single files outside the package): without
            # the registry in scope, "documented but never read" would
            # fire for every documented knob
            return out
        for name, (doc_rel, doc_line) in sorted(docs.items()):
            if name not in code_names and _ENV_NAME.match(name):
                out.append(Finding(
                    rule=self.rule, severity="error", path=doc_rel,
                    line=doc_line, col=0,
                    message=f"{name} is documented but never read in "
                            f"code",
                    hint="remove the stale doc row or restore the "
                         "knob"))
        return out

    def _documented_vars(self, project: Project):
        """{name: (doc rel path, first line)} over PARAMS.md/README.md;
        None when neither doc exists (fixture scans)."""
        docs: Dict[str, Tuple[str, int]] = {}
        found_any = False
        for doc in ("PARAMS.md", "README.md"):
            path = project.repo_root / doc
            try:
                text = path.read_text(encoding="utf-8")
            except OSError:
                continue
            found_any = True
            for i, line in enumerate(text.splitlines(), 1):
                for m in _ENV_IN_DOCS.finditer(line):
                    docs.setdefault(m.group(0), (doc, i))
        if not found_any:
            return None
        return docs, set(docs)


def _known_points(pf: ParsedFile) -> Optional[Dict[str, int]]:
    for stmt in pf.nodes:
        if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
            continue
        targets = stmt.targets if isinstance(stmt, ast.Assign) \
            else [stmt.target]
        names = [t.id for t in targets if isinstance(t, ast.Name)]
        if "KNOWN_POINTS" not in names:
            continue
        value = stmt.value
        if isinstance(value, ast.Dict):
            out = {}
            for k in value.keys:
                if isinstance(k, ast.Constant) and isinstance(
                        k.value, str):
                    out[k.value] = k.lineno
            return out
    return None


def _registered_vars(pf: ParsedFile) -> Dict[str, int]:
    """Literal first arguments of register(...) calls in core/env.py."""
    out: Dict[str, int] = {}
    for call in pf.nodes:
        if not isinstance(call, ast.Call):
            continue
        if (dotted(call.func) or "").split(".")[-1] != "register":
            continue
        if call.args and isinstance(call.args[0], ast.Constant) \
                and isinstance(call.args[0].value, str):
            name = call.args[0].value
            if _ENV_NAME.match(name):
                out[name] = call.lineno
    return out


def _env_references(pf: ParsedFile):
    """Yield (name, line, col, is_raw) for every MMLSPARK_TPU_* literal
    used as an env read/write in this file. ``is_raw`` marks direct
    os.environ access (vs the typed core/env.py helpers)."""
    for node in pf.nodes:
        if isinstance(node, ast.Call):
            fname = dotted(node.func) or ""
            last = fname.split(".")[-1]
            resolved = pf.imports.resolve_node(node.func) or ""
            if last in _TYPED_READERS or (
                    last == "register"
                    and pf.rel.endswith("core/env.py")):
                name = _literal_arg0(node)
                if name:
                    yield name, node.lineno, node.col_offset, False
            elif resolved == "os.getenv":
                name = _literal_arg0(node)
                if name:
                    yield name, node.lineno, node.col_offset, True
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _ENVIRON_METHODS
                  and _is_environ(pf, node.func.value)):
                name = _literal_arg0(node)
                if name:
                    yield name, node.lineno, node.col_offset, True
        elif isinstance(node, ast.Subscript) and _is_environ(
                pf, node.value):
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(
                    sl.value, str) and _ENV_NAME.match(sl.value):
                yield sl.value, node.lineno, node.col_offset, True
        elif isinstance(node, ast.Compare):
            if any(_is_environ(pf, c) for c in node.comparators):
                left = node.left
                if isinstance(left, ast.Constant) and isinstance(
                        left.value, str) and _ENV_NAME.match(left.value):
                    yield (left.value, left.lineno, left.col_offset,
                           True)


def _is_environ(pf: ParsedFile, node: ast.AST) -> bool:
    return (pf.imports.resolve_node(node) or "") == "os.environ"


def _literal_arg0(call: ast.Call) -> Optional[str]:
    if call.args and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, str) \
            and _ENV_NAME.match(call.args[0].value):
        return call.args[0].value
    return None
