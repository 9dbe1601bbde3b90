"""GL016 — host/device width drift.

Host numpy defaults to float64; a jitted callable narrows every
operand to float32 (x64 off); the native kernels behind
``emit_python_callback`` demand *exact* dtypes and will mis-read a
buffer whose width drifted. Two sub-rules patrol the crossings:

1. **float64-contracted helpers feeding device code.** The split-gain
   helpers deliberately compute in float64 (exact integer-weight
   bincounts below 2^53) — that is a *host* contract. When such a
   helper's result flows, uncast, into a jitted callable or a
   ``native.bindings`` kernel, the width decision is made silently by
   the boundary instead of the author. The rule marks local functions
   whose returns carry np.float64 evidence, taints their call results,
   and flags tainted arguments crossing either boundary. An explicit
   cast (``astype(np.float32)``) kills the taint: stating the width
   IS the fix. (Distinct from GL007's narrowing rule, which taints
   *casts* — this one taints *helper contracts*, so the two never
   double-report one flow.)

2. **default-dtype numpy constructors in callback operands.** An
   ``np.zeros``/``arange``/``asarray``/… built inline in the operands
   of a ``pure_callback``/``io_callback``/``emit_python_callback``
   call takes numpy's default dtype (int64/float64) while the device
   side of the boundary speaks jnp defaults (int32/float32) — and
   ``bindings.py`` requires exact dtypes. Constructors with an
   explicit dtype pass.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from tools.graftlint.astutil import dotted, is_callback_primitive
from tools.graftlint.core import Checker, Finding, ParsedFile, Project
from tools.graftlint.dataflow import ExprTokens, Tokens, own_body_walk
from tools.graftlint.checkers.dtypemodel import DtypeModel, dtype_model

_NP_CTORS = frozenset({"zeros", "ones", "empty", "full", "arange",
                       "asarray", "array", "ascontiguousarray"})


class HostWidthDriftChecker(Checker):
    rule = "GL016"
    name = "host-width-drift"
    description = ("float64-contracted host helper results crossing "
                   "into jitted callables or native.bindings kernels "
                   "uncast, and default-dtype numpy constructors in "
                   "host-callback operands where bindings.py requires "
                   "exact dtypes")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        model = dtype_model(pf)
        helpers = _f64_helpers(pf, model)
        out: List[Finding] = []
        for fn in pf.nodes:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            out.extend(self._check_function(pf, model, fn, helpers))
        out.extend(self._check_callback_operands(pf, model))
        return out

    # -- sub-rule 1: f64 helper contracts crossing the boundary -------------

    def _check_function(self, pf, model: DtypeModel, fn: ast.AST,
                        helpers: Set[str]) -> List[Finding]:
        if not helpers:
            return []
        calls = [n for n in own_body_walk(fn)
                 if isinstance(n, ast.Call)]
        if not calls:
            return []
        hostf64 = model.analysis(
            fn, "hostf64",
            ExprTokens(source=_hostf64_source(pf, model, helpers)))
        out: List[Finding] = []
        for call in calls:
            boundary = _boundary_kind(pf, model, call)
            if boundary is None:
                continue
            stmt = model.enclosing_stmt(call, fn)
            if stmt is None:
                continue
            env = hostf64.env_at(stmt)
            for arg in list(call.args) + [kw.value
                                          for kw in call.keywords]:
                if "hostf64" not in hostf64.eval_expr(arg, env):
                    continue
                out.append(Finding(
                    rule=self.rule, severity="error", path=pf.rel,
                    line=call.lineno, col=call.col_offset,
                    message=f"result of a float64-contracted host "
                            f"helper crosses into {boundary} uncast "
                            f"({pf.line_text(call.lineno)[:48]!r}) — "
                            f"the boundary decides the width "
                            f"silently (jit narrows to f32, native "
                            f"kernels require exact dtypes)",
                    hint="make the width decision explicit at the "
                         "boundary: astype(np.float32) (accepting "
                         "the narrowing) or keep the value host-side"))
        return out

    # -- sub-rule 2: default-dtype np constructors in callback operands -----

    def _check_callback_operands(self, pf,
                                 model: DtypeModel) -> List[Finding]:
        out: List[Finding] = []
        for call in pf.nodes:
            if not isinstance(call, ast.Call):
                continue
            if not is_callback_primitive(
                    pf.imports.resolve_node(call.func)):
                continue
            operands = list(call.args) + [kw.value
                                          for kw in call.keywords]
            for op in operands:
                for inner in ast.walk(op):
                    ctor = _bare_np_ctor(pf, model, inner)
                    if ctor is None:
                        continue
                    out.append(Finding(
                        rule=self.rule, severity="error", path=pf.rel,
                        line=inner.lineno, col=inner.col_offset,
                        message=f"np.{ctor} without an explicit dtype "
                                f"in host-callback operands — numpy "
                                f"defaults (int64/float64) drift from "
                                f"the device side's jnp defaults, and "
                                f"the native kernels require exact "
                                f"dtypes",
                        hint=f"pin it: np.{ctor}(..., "
                             f"dtype=np.float32) (or the exact dtype "
                             f"the kernel signature declares)"))
        return out


def _f64_helpers(pf, model: DtypeModel) -> Set[str]:
    """Local function names whose returns carry np.float64 evidence."""
    names: Set[str] = set()
    for fn in pf.nodes:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in own_body_walk(fn):
            if not (isinstance(node, ast.Return)
                    and node.value is not None):
                continue
            if _returns_f64(pf, model, node.value):
                names.add(fn.name)
                break
    return names


def _returns_f64(pf, model: DtypeModel, expr: ast.AST) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Call) and model.cast_dtype(n) == "float64":
            return True
        d = dotted(n)
        if d and (pf.imports.resolve(d) or d) == "numpy.float64":
            return True
    return False


def _hostf64_source(pf, model: DtypeModel, helpers: Set[str]):
    def source(expr: ast.AST) -> Optional[Tokens]:
        if not isinstance(expr, ast.Call):
            return None
        if (isinstance(expr.func, ast.Name)
                and expr.func.id in helpers):
            return frozenset({"hostf64"})
        if model.cast_dtype(expr) is not None:
            return frozenset()   # explicit width decision: kill
        return None
    return source


def _boundary_kind(pf, model: DtypeModel,
                   call: ast.Call) -> Optional[str]:
    if (isinstance(call.func, ast.Name)
            and call.func.id in model.jitted_names):
        return f"jitted callable {call.func.id!r}"
    resolved = pf.imports.resolve_node(call.func) or ""
    if resolved.startswith("mmlspark_tpu.native.bindings."):
        return "a native.bindings kernel"
    return None


def _bare_np_ctor(pf, model: DtypeModel,
                  node: ast.AST) -> Optional[str]:
    if not isinstance(node, ast.Call):
        return None
    resolved = pf.imports.resolve_node(node.func) or ""
    last = resolved.split(".")[-1]
    if last not in _NP_CTORS or not resolved.startswith("numpy."):
        return None
    if model.explicit_dtype(node) is not None:
        return None
    return last
