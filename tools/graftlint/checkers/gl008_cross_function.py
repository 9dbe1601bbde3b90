"""GL008 — cross-function collective-context propagation.

GL001/GL002 see one function at a time: a typo'd axis name or host
impurity hiding in a helper *called from* a shard_map body escapes
them. GL008 builds a project-wide index of module-level functions,
follows calls out of traced bodies (depth <= 3, through module
boundaries via the import map), binds statically-known string
arguments to the helper's parameters, and re-checks the helper's own
top-level statements:

* **axis propagation** — a collective inside the helper whose axis
  argument is a parameter bound at the call site to a string that is
  not a declared mesh axis;
* **tracer hygiene** — print/time.*/os.environ/.item() in the helper's
  executed path, plus host-numpy and float()/int() calls *that mention
  the helper's parameters* (which carry tracers when called from a
  traced body). The parameter-mention requirement keeps trace-time
  shape math (``np.ceil(n / block)`` grid computations) legal.

Sanctioned infrastructure modules (env/faults/sanitizer/jax_compat/
logging/native bindings) are skipped: they are the framework's own
trace-time escape hatches, each individually audited. Nested
functions inside a helper are opaque here — if the helper passes them
to shard_map or a callback primitive, GL002 covers them in that
helper's own file.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.graftlint.astutil import dotted, module_str_constants
from tools.graftlint.checkers.gl001_collective_axes import (
    COLLECTIVES, _axis_argument, _declared_axes,
    _is_collective_namespace)
from tools.graftlint.core import Checker, Finding, ParsedFile, Project
from tools.graftlint.dataflow import own_body_walk

_MAX_DEPTH = 3

# framework escape hatches: trace-time env/fault/sanitizer plumbing is
# their audited, documented purpose
_SKIP_MODULE_SUFFIXES = (
    "core/env.py", "core/faults.py", "core/sanitizer.py",
    "core/jax_compat.py", "core/logging_utils.py", "core/fabric.py",
    "native/bindings.py",
)

_NP_STATIC_OK_LAST = {
    "float16", "float32", "float64", "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64", "bool_", "complex64",
    "complex128", "pi", "e", "inf", "nan", "newaxis", "euler_gamma",
    "ndarray", "dtype", "generic", "integer", "floating", "issubdtype",
    "result_type", "promote_types", "iinfo", "finfo", "asarray",
}


class _Helper:
    __slots__ = ("pf", "fn", "module")

    def __init__(self, pf: ParsedFile, fn: ast.FunctionDef,
                 module: str):
        self.pf = pf
        self.fn = fn
        self.module = module


class CrossFunctionChecker(Checker):
    rule = "GL008"
    name = "cross-function-context"
    description = ("axis-name and tracer-hygiene checks follow helper "
                   "functions called from shard_map/jit bodies across "
                   "module boundaries")

    def check_project(self, project: Project) -> List[Finding]:
        index = _build_index(project)
        declared = set(_declared_axes(project).values())
        out: List[Finding] = []
        reported: Set[Tuple[int, int]] = set()
        for pf in project.files:
            traced = pf.traced
            if not traced:
                continue
            callback_fns = pf.callback_fns
            own_traced = {id(f) for f in traced}
            for root in traced:
                if root in callback_fns:
                    continue
                root_name = getattr(root, "name", "<lambda>")
                root_tracers = _tracer_names(root)
                visited: Set[int] = set()
                for call in ast.walk(root):
                    if not isinstance(call, ast.Call):
                        continue
                    helper = _resolve_call(pf, call, index)
                    if helper is None or id(helper.fn) in own_traced:
                        continue
                    out.extend(self._follow(
                        project, helper, call, pf,
                        caller_tracers=root_tracers,
                        chain=f"{pf.rel}:{root_name}",
                        declared=declared, depth=1, visited=visited,
                        reported=reported))
        return out

    def _follow(self, project: Project, helper: _Helper,
                call_site: ast.Call, caller_pf: ParsedFile,
                caller_tracers: Set[str], chain: str,
                declared: Set[str], depth: int, visited: Set[int],
                reported: Set[Tuple[int, int]]) -> List[Finding]:
        if depth > _MAX_DEPTH or id(helper.fn) in visited:
            return []
        visited.add(id(helper.fn))
        if helper.pf.rel.endswith(_SKIP_MODULE_SUFFIXES):
            return []
        if helper.fn in helper.pf.traced:
            return []   # GL001/GL002 already own it
        if helper.fn in helper.pf.callback_fns:
            return []   # host code by design
        bindings = _bind_str_args(caller_pf, call_site, helper.fn)
        # interprocedural tracer propagation: a helper parameter
        # carries a tracer only when the call site binds it from an
        # expression that reads one of the caller's tracer names as
        # data — static config (ints, cfg objects from the closure)
        # stays host-legal through the chain
        traced_params = _traced_param_bindings(call_site, helper.fn,
                                               caller_tracers)
        chain = f"{chain} -> {helper.module}.{helper.fn.name}"
        out: List[Finding] = []
        for node in own_body_walk(helper.fn):
            f = self._check_axis(helper, node, bindings, declared,
                                 chain)
            if f is None:
                f = self._check_hygiene(helper, node, traced_params,
                                        chain)
            if f is not None:
                key = (id(helper.fn), node.lineno)
                if key not in reported:
                    reported.add(key)
                    out.append(f)
            if isinstance(node, ast.Call):
                nxt = _resolve_call(helper.pf, node,
                                    _build_index(project))
                if nxt is not None:
                    out.extend(self._follow(
                        project, nxt, node, helper.pf,
                        caller_tracers=traced_params, chain=chain,
                        declared=declared, depth=depth + 1,
                        visited=visited, reported=reported))
        return out

    # -- axis propagation ---------------------------------------------------

    def _check_axis(self, helper: _Helper, node: ast.AST,
                    bindings: Dict[str, str], declared: Set[str],
                    chain: str) -> Optional[Finding]:
        if not isinstance(node, ast.Call) or not bindings:
            return None
        resolved = helper.pf.imports.resolve_node(node.func) or ""
        last = resolved.split(".")[-1]
        if last not in COLLECTIVES or not _is_collective_namespace(
                resolved):
            return None
        axis_expr = _axis_argument(node, COLLECTIVES[last])
        if not isinstance(axis_expr, ast.Name):
            return None
        value = bindings.get(axis_expr.id)
        if value is None or value in declared:
            return None
        local = {v for v in module_str_constants(helper.pf.tree).values()}
        if value in local:
            return None
        return Finding(
            rule=self.rule, severity="error", path=helper.pf.rel,
            line=node.lineno, col=node.col_offset,
            message=f"axis name {value!r} reaches {last!r} through "
                    f"parameter {axis_expr.id!r} (call chain {chain}) "
                    f"and is not a declared mesh axis",
            hint=f"declared axes are {sorted(declared)}; pass a "
                 f"parallel/mesh.py *_AXIS constant through the "
                 f"helper, not a literal")

    # -- tracer hygiene through the call chain ------------------------------

    def _check_hygiene(self, helper: _Helper, node: ast.AST,
                       params: Set[str],
                       chain: str) -> Optional[Finding]:
        pf = helper.pf
        if isinstance(node, ast.Call):
            resolved = pf.imports.resolve_node(node.func) or ""
            if resolved == "print":
                return self._hy(pf, node, chain,
                                "print() fires at trace time only")
            if resolved.startswith("time."):
                return self._hy(pf, node, chain,
                                f"{resolved}() runs at trace time only")
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                return self._hy(pf, node, chain,
                                ".item() forces a device sync and "
                                "fails on tracers")
            if resolved in ("float", "int", "bool") and node.args \
                    and _mentions_params(node.args[0], params):
                return self._hy(pf, node, chain,
                                f"{resolved}() on a traced argument "
                                f"forces concretization")
            if resolved.startswith("numpy."):
                attr = resolved.split(".")[-1]
                if attr not in _NP_STATIC_OK_LAST and any(
                        _mentions_params(a, params)
                        for a in list(node.args)
                        + [kw.value for kw in node.keywords]):
                    return self._hy(pf, node, chain,
                                    f"host numpy ({resolved}) applied "
                                    f"to a traced argument")
        if isinstance(node, ast.Attribute):
            parent = pf.parents.get(node)
            if isinstance(parent, ast.Attribute) and parent.value is node:
                return None
            resolved = pf.imports.resolve_node(node) or ""
            if resolved == "os.environ" or resolved.startswith(
                    "os.environ."):
                return self._hy(pf, node, chain,
                                "os.environ read is baked in at trace "
                                "time and never re-read")
        return None

    def _hy(self, pf: ParsedFile, node: ast.AST, chain: str,
            what: str) -> Finding:
        return Finding(
            rule=self.rule, severity="error", path=pf.rel,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=f"{what} — in a helper reached from a traced body "
                    f"(call chain {chain})",
            hint="the helper executes under tracing; move the host "
                 "code out of the call chain or route it through "
                 "jax.pure_callback (see core/jax_compat.py)")


# --- project indexing -------------------------------------------------------

_INDEX_CACHE: Dict[int, Dict[str, "_Helper"]] = {}


def _build_index(project: Project) -> Dict[str, _Helper]:
    cached = _INDEX_CACHE.get(id(project))
    if cached is not None:
        return cached
    index: Dict[str, _Helper] = {}
    for pf in project.files:
        module = _module_name(pf.rel)
        for stmt in pf.tree.body:
            if isinstance(stmt, ast.FunctionDef):
                index[f"{module}.{stmt.name}"] = _Helper(pf, stmt,
                                                         module)
    _INDEX_CACHE.clear()
    _INDEX_CACHE[id(project)] = index
    return index


def _module_name(rel: str) -> str:
    mod = rel[:-3] if rel.endswith(".py") else rel
    if mod.endswith("/__init__"):
        mod = mod[: -len("/__init__")]
    return mod.replace("/", ".")


def _resolve_call(pf: ParsedFile, call: ast.Call,
                  index: Dict[str, _Helper]) -> Optional[_Helper]:
    resolved = pf.imports.resolve_node(call.func)
    if not resolved:
        return None
    hit = index.get(resolved)
    if hit is not None:
        return hit
    # bare local name: resolve against this file's module
    if "." not in resolved:
        return index.get(f"{_module_name(pf.rel)}.{resolved}")
    # relative import (`from .helpers import f` keeps a short module):
    # match by dotted suffix, unambiguous only
    matches = [h for full, h in index.items()
               if full.endswith("." + resolved)]
    return matches[0] if len(matches) == 1 else None


def _bind_str_args(caller_pf: ParsedFile, call: ast.Call,
                   fn: ast.FunctionDef) -> Dict[str, str]:
    """param name -> statically-known string argument at this site."""
    consts = module_str_constants(caller_pf.tree)
    args = fn.args
    pos = [a.arg for a in (list(getattr(args, "posonlyargs", []))
                           + list(args.args))]
    out: Dict[str, str] = {}

    def value_of(expr: ast.AST) -> Optional[str]:
        if isinstance(expr, ast.Constant) and isinstance(expr.value,
                                                         str):
            return expr.value
        if isinstance(expr, ast.Name):
            return consts.get(expr.id)
        d = dotted(expr)
        if d and d.split(".")[-1].endswith("_AXIS"):
            return None   # declared constant: trusted, GL001 territory
        return None

    for i, a in enumerate(call.args):
        if i < len(pos):
            v = value_of(a)
            if v is not None:
                out[pos[i]] = v
    for kw in call.keywords:
        if kw.arg:
            v = value_of(kw.value)
            if v is not None:
                out[kw.arg] = v
    return out


def _tracer_names(root: ast.AST) -> Set[str]:
    from tools.graftlint.checkers.gl002_tracer_hygiene import (
        _tracer_param_names)
    return _tracer_param_names(root)


def _traced_param_bindings(call: ast.Call, fn: ast.FunctionDef,
                           caller_tracers: Set[str]) -> Set[str]:
    """Helper parameters bound at this call site from expressions that
    read a caller tracer as data (shape/dtype reads don't count)."""
    args = fn.args
    pos = [a.arg for a in (list(getattr(args, "posonlyargs", []))
                           + list(args.args))]
    out: Set[str] = set()
    for i, a in enumerate(call.args):
        if isinstance(a, ast.Starred):
            continue
        if i < len(pos) and _mentions_params(a, caller_tracers):
            out.add(pos[i])
    for kw in call.keywords:
        if kw.arg and _mentions_params(kw.value, caller_tracers):
            out.add(kw.arg)
    return out


def _mentions_params(expr: ast.AST, params: Set[str]) -> bool:
    """True when the expression reads a parameter *as data* — uses
    under .shape/.dtype/.ndim/.size are trace-static and don't count."""
    def rec(node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in params
        if isinstance(node, ast.Attribute) and node.attr in (
                "shape", "dtype", "ndim", "size"):
            return False
        return any(rec(c) for c in ast.iter_child_nodes(node))
    return rec(expr)
