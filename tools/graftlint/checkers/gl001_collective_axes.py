"""GL001 — collective-axis consistency.

Every axis name handed to a collective (``jax.lax.psum`` and friends)
or spelled as a ``PartitionSpec`` literal must be an axis the framework
declares: the ``*_AXIS`` constants in ``parallel/mesh.py`` (dp/fp/mp/sp)
or a module-local ``*_AXIS = "..."`` constant. A typo'd axis inside a
``shard_map`` body is exactly the bug class that silently corrupts
data-parallel training — the collective either fails at trace time in a
test that happens to cover it, or reduces over the wrong axis.

Resolution is conservative: a name that cannot be statically resolved
to a string (a bare parameter, a computed value) is skipped, never
guessed — GL001 reports only provably-unknown axis names.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.graftlint.astutil import (dotted, enclosing_functions,
                                     module_str_constants, param_default)
from tools.graftlint.core import Checker, Finding, ParsedFile, Project

# collective -> positional index of its axis-name argument.
# reduce_scatter covers external spellings of jax's psum_scatter (the
# XLA/paper name for the same op); kept in sync with graftsan's
# KNOWN_COLLECTIVES (core/sanitizer.py) so runtime-recorded kinds and
# statically-checked kinds never drift.
COLLECTIVES: Dict[str, int] = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "all_gather": 1,
    "all_to_all": 1, "ppermute": 1, "pshuffle": 1, "psum_scatter": 1,
    "reduce_scatter": 1, "axis_index": 0, "pbroadcast": 1, "pcast": 1,
}

_PSPEC_NAMES = ("jax.sharding.PartitionSpec",
                "jax.experimental.PartitionSpec")


class CollectiveAxisChecker(Checker):
    rule = "GL001"
    name = "collective-axes"
    description = ("collective/PartitionSpec axis names must match the "
                   "axes declared in parallel/mesh.py")

    def check_project(self, project: Project) -> List[Finding]:
        declared = _declared_axes(project)
        out: List[Finding] = []
        for pf in project.files:
            out.extend(self._check_file(pf, declared))
        return out

    def _check_file(self, pf: ParsedFile,
                    declared: Dict[str, str]) -> List[Finding]:
        local_consts = {k: v for k, v in
                        module_str_constants(pf.tree).items()
                        if k.endswith("_AXIS")}
        axis_by_name = {**declared, **local_consts}
        valid = set(axis_by_name.values())
        out: List[Finding] = []
        for call in pf.nodes:
            if not isinstance(call, ast.Call):
                continue
            resolved = pf.imports.resolve_node(call.func) or ""
            last = resolved.split(".")[-1]
            if last in COLLECTIVES and _is_collective_namespace(resolved):
                axis_expr = _axis_argument(call, COLLECTIVES[last])
                if axis_expr is not None:
                    out.extend(self._check_axis_expr(
                        pf, call, last, axis_expr, axis_by_name, valid))
            elif resolved in _PSPEC_NAMES:
                for arg in call.args:
                    for lit in _pspec_literals(arg):
                        if lit.value not in valid:
                            out.append(self._finding(
                                pf, lit, lit.value, "PartitionSpec",
                                valid))
        out.extend(self._check_rule_tables(pf, valid))
        return out

    def _check_rule_tables(self, pf: ParsedFile,
                           valid: Set[str]) -> List[Finding]:
        """Module-level ``*_RULES`` tables — lists of ``(regex, spec)``
        pairs consumed by ``parallel/shard_rules.py`` — carry axis names
        in their spec halves exactly like PartitionSpec literals; a typo
        there silently downgrades a whole model family to replication."""
        out: List[Finding] = []
        for stmt in pf.tree.body:
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            else:
                continue
            names = [t.id for t in targets if isinstance(t, ast.Name)
                     and t.id.endswith("_RULES")]
            if not names or not isinstance(value, (ast.List, ast.Tuple)):
                continue
            for entry in value.elts:
                if not (isinstance(entry, (ast.Tuple, ast.List))
                        and len(entry.elts) == 2):
                    continue
                for lit in _spec_literals(entry.elts[1]):
                    if lit.value not in valid:
                        out.append(self._finding(
                            pf, lit, lit.value,
                            f"rule table {names[0]}", valid))
        return out

    def _check_axis_expr(self, pf: ParsedFile, call: ast.Call, op: str,
                         expr: ast.AST, axis_by_name: Dict[str, str],
                         valid: Set[str]) -> List[Finding]:
        out: List[Finding] = []
        for value, node in _axis_candidates(pf, call, expr, axis_by_name):
            if value not in valid:
                out.append(self._finding(pf, node, value, op, valid))
        return out

    def _finding(self, pf: ParsedFile, node: ast.AST, value: str,
                 where: str, valid: Set[str]) -> Finding:
        return Finding(
            rule=self.rule, severity="error", path=pf.rel,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=f"axis name {value!r} in {where} is not a declared "
                    f"mesh axis",
            hint=f"declared axes are {sorted(valid)} (parallel/mesh.py "
                 f"*_AXIS constants); use the constant, not a string "
                 f"literal, or declare the new axis in mesh.py")


def _declared_axes(project: Project) -> Dict[str, str]:
    mesh = project.file_ending_with("parallel/mesh.py")
    if mesh is not None:
        axes = {k: v for k, v in
                module_str_constants(mesh.tree).items()
                if k.endswith("_AXIS")}
        if axes:
            return axes
    return {"DATA_AXIS": "dp", "FEATURE_AXIS": "fp",
            "MODEL_AXIS": "mp", "SEQUENCE_AXIS": "sp"}


def _is_collective_namespace(resolved: str) -> bool:
    """Only flag the jax.lax family (or names imported from it, which
    the import map rewrites to the full path) — ``mylib.psum`` with
    unrelated semantics must not trip GL001."""
    return resolved.startswith(("jax.lax.", "lax."))


def _axis_argument(call: ast.Call, pos: int) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg in ("axis_name", "axis_names"):
            return kw.value
    if len(call.args) > pos:
        return call.args[pos]
    return None


def _axis_candidates(pf: ParsedFile, call: ast.Call, expr: ast.AST,
                     axis_by_name: Dict[str, str],
                     depth: int = 0) -> List[Tuple[str, ast.AST]]:
    """Statically-resolvable axis strings in ``expr`` (with the node to
    anchor a finding to). Unresolvable parts yield nothing."""
    if depth > 3:
        return []
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return [(expr.value, expr)]
    if isinstance(expr, (ast.Tuple, ast.List)):
        out: List[Tuple[str, ast.AST]] = []
        for el in expr.elts:
            out.extend(_axis_candidates(pf, call, el, axis_by_name,
                                        depth + 1))
        return out
    if isinstance(expr, ast.Name):
        name = expr.id
        if name.endswith("_AXIS"):
            # declared constant (imported or local); mesh.py's values
            # are authoritative, unknown *_AXIS names resolve to valid
            # by construction of axis_by_name or are skipped
            return []
        for fn in enclosing_functions(pf.parents, call):
            from tools.graftlint.astutil import has_param
            if has_param(fn, name):
                default = param_default(fn, name)
                if default is not None:
                    return _axis_candidates(pf, call, default,
                                            axis_by_name, depth + 1)
                return []  # runtime-supplied: unresolvable, skip
        value = module_str_constants(pf.tree).get(name)
        if value is not None:
            return [(value, expr)]
    return []


def _spec_literals(spec: ast.AST, depth: int = 0) -> List[ast.Constant]:
    """String literals in one rule-table spec (an axis name or an
    arbitrarily nested tuple of axis names; None means replicated,
    ``*_AXIS`` constants are valid by construction and skipped)."""
    if depth > 3:
        return []
    if isinstance(spec, ast.Constant) and isinstance(spec.value, str):
        return [spec]
    out: List[ast.Constant] = []
    if isinstance(spec, (ast.Tuple, ast.List)):
        for el in spec.elts:
            out.extend(_spec_literals(el, depth + 1))
    return out


def _pspec_literals(arg: ast.AST) -> List[ast.Constant]:
    """String literals inside one PartitionSpec argument (an axis name
    or a tuple of axis names; None means replicated)."""
    out: List[ast.Constant] = []
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        out.append(arg)
    elif isinstance(arg, (ast.Tuple, ast.List)):
        for el in arg.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el)
    return out
