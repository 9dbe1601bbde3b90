"""GL002 — tracer hygiene.

Host-side impurity inside a traced body is either a silent staleness
bug (``os.environ`` read at trace time, baked into the compiled
executable and never re-read), a per-trace side effect (``print``,
``time.*`` fire once at trace time, not per execution), or a
concretization error waiting for the first non-trivial input
(``np.*`` on tracers, ``.item()``, ``float()/int()``).

Scope is lexical: functions decorated with ``@jax.jit``/``@pmap``
(directly or via ``partial``) or passed to ``jax.jit``/``pmap``/
``shard_map``, including their nested functions. The sanctioned escape
hatches — ``jax.pure_callback``, ``io_callback``,
``emit_python_callback``, ``jax.debug.*`` and the native-kernel
bindings (``mmlspark_tpu/native/bindings.py``) — are allowlisted, and
functions passed *to* a callback primitive are host code by design, so
their bodies are skipped entirely.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from tools.graftlint.astutil import (dotted, is_callback_primitive,
                                     walk_skipping)
from tools.graftlint.core import Checker, Finding, ParsedFile, Project

# numpy attributes that are static metadata, legal inside a trace
_NP_STATIC_OK = {
    "float16", "float32", "float64", "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64", "bool_", "complex64",
    "complex128", "pi", "e", "inf", "nan", "newaxis", "euler_gamma",
    "ndarray", "dtype", "generic", "integer", "floating",
}

# call targets always allowed inside traced code
_ALLOWED_CALL_PREFIXES = ("mmlspark_tpu.native.bindings.",)
_ALLOWED_CALL_LAST = {"fault_point"}


class TracerHygieneChecker(Checker):
    rule = "GL002"
    name = "tracer-hygiene"
    description = ("no host impurity (np.*, print, time.*, os.environ, "
                   ".item(), float()/int()) inside jit/shard_map bodies")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        traced = pf.traced
        if not traced:
            return []
        callback_fns = pf.callback_fns
        out: List[Finding] = []
        seen: Set[int] = set()   # dedupe nodes reachable from 2 roots
        for fn in traced:
            skip = callback_fns - {fn}
            tracer_names = _tracer_param_names(fn)
            for node in walk_skipping(fn, skip):
                if id(node) in seen:
                    continue
                f = self._check_node(pf, node, tracer_names)
                if f is not None:
                    seen.add(id(node))
                    out.append(f)
        return out

    def _check_node(self, pf: ParsedFile, node: ast.AST,
                    tracer_names: Set[str]) -> Optional[Finding]:
        if isinstance(node, ast.Call):
            resolved = pf.imports.resolve_node(node.func) or ""
            if self._is_allowed_call(resolved):
                return None
            if resolved == "print":
                return self._finding(
                    pf, node, "print() inside a traced body fires at "
                    "trace time, not per execution",
                    "use jax.debug.print for per-execution output")
            if resolved in ("float", "int", "bool") and node.args \
                    and _mentions_names(node.args[0], tracer_names):
                # only when the argument references a traced-function
                # parameter — int()/round() over static closure config
                # (e.g. feature-fraction math in the trainer step) is
                # legal trace-time Python
                return self._finding(
                    pf, node, f"{resolved}() on a traced value forces "
                    "concretization",
                    "keep the value as a jax array (astype / jnp "
                    "casts); pull to host outside the traced function")
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args):
                return self._finding(
                    pf, node, ".item() forces a device sync and "
                    "fails on tracers",
                    "return the array and convert outside the trace")
            if resolved.startswith("time."):
                return self._finding(
                    pf, node, f"{resolved}() runs at trace time only",
                    "time outside the traced function (the compiled "
                    "step never re-executes host code)")
        if isinstance(node, ast.Attribute):
            # only the outermost link of a chain: np.random.seed must
            # produce one finding, not one per attribute hop
            parent = pf.parents.get(node)
            if isinstance(parent, ast.Attribute) and parent.value is node:
                return None
            resolved = pf.imports.resolve_node(node) or ""
            if resolved.startswith("numpy."):
                attr = resolved.split(".", 1)[1].split(".")[0]
                if attr not in _NP_STATIC_OK:
                    return self._finding(
                        pf, node, f"host numpy ({resolved}) inside a "
                        "traced body",
                        "use jax.numpy, or move the computation out of "
                        "the traced function (host results are baked "
                        "in at trace time)")
            if resolved == "os.environ" or resolved.startswith(
                    "os.environ."):
                return self._finding(
                    pf, node, "os.environ read inside a traced body is "
                    "baked in at trace time and never re-read",
                    "read the env var outside the trace and pass the "
                    "value in (see mmlspark_tpu/core/env.py), or fold "
                    "it into the compilation cache key")
        return None

    def _is_allowed_call(self, resolved: str) -> bool:
        if is_callback_primitive(resolved):
            return True
        if resolved.startswith(_ALLOWED_CALL_PREFIXES):
            return True
        return resolved.split(".")[-1] in _ALLOWED_CALL_LAST

    def _finding(self, pf: ParsedFile, node: ast.AST, message: str,
                 hint: str) -> Finding:
        return Finding(rule=self.rule, severity="error", path=pf.rel,
                       line=getattr(node, "lineno", 1),
                       col=getattr(node, "col_offset", 0),
                       message=message, hint=hint)


def _tracer_param_names(fn: ast.AST) -> Set[str]:
    """Parameter names of the traced function and every function nested
    in it — the names that (statically) carry tracers."""
    names: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            args = node.args
            for a in (list(getattr(args, "posonlyargs", []))
                      + list(args.args) + list(args.kwonlyargs)):
                names.add(a.arg)
            if args.vararg:
                names.add(args.vararg.arg)
            if args.kwarg:
                names.add(args.kwarg.arg)
    return names


def _mentions_names(expr: ast.AST, names: Set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in names
               for n in ast.walk(expr))
