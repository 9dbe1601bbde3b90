"""GL015 — unsafe low-precision accumulation.

The TF-serving playbook treats reduced-precision serving as safe only
when the *accumulation* width is pinned: bf16/f16 operands are fine in
element-wise math, but a contraction or reduction that inherits the
operand dtype accumulates its rounding error over every term — ~100
boosted trees of bf16 leaf values lose ~2^-8 relative accuracy per
term, and a 2M-row mean in f16 is garbage. Two sub-rules:

1. **low-precision operands reaching an accumulating op.** A value
   tainted by a cast to bf16/f16 feeding ``matmul``/``dot``/
   ``einsum``/``tensordot``/``dot_general``/``sum``/``mean`` (call or
   method form, plus the ``@`` operator) without a
   ``preferred_element_type=`` flags. An explicit upcast
   (``astype(jnp.float32)``) kills the taint — that IS the fix.

2. **bf16 casts outside the sanctioned seam.** The one blessed
   autocast path is ``shard_rules``' dtype_specs placement cast
   (``placement_cast``): weights are cast once at shard/placement
   time, behind the resolve/warn-once policy, and every consumer
   upcasts before accumulating. Any other ``.astype(jnp.bfloat16)``
   (or bf16-pinned constructor) in the package is an ad-hoc autocast
   that bypasses the policy, the runtime dtype contract, and the
   bench accounting — it flags wherever it appears.
"""

from __future__ import annotations

import ast
from typing import List

from tools.graftlint.core import Checker, Finding, ParsedFile, Project
from tools.graftlint.dataflow import own_body_walk
from tools.graftlint.checkers.dtypemodel import (
    DtypeModel, dtype_model, low_prec_source)
from tools.graftlint.dataflow import ExprTokens

_ACCUM_CALLS = frozenset({"matmul", "dot", "einsum", "tensordot",
                          "dot_general", "sum", "mean"})
_ACCUM_METHODS = frozenset({"matmul", "dot", "sum", "mean"})
_SEAM_FILE = "mmlspark_tpu/parallel/shard_rules.py"


class LowPrecAccumulationChecker(Checker):
    rule = "GL015"
    name = "lowprec-accumulation"
    description = ("matmul/dot/einsum/sum/mean on bf16/f16-tainted "
                   "operands without preferred_element_type or an f32 "
                   "upcast, and astype(jnp.bfloat16) outside the "
                   "shard_rules placement-cast seam")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        model = dtype_model(pf)
        out: List[Finding] = []
        for fn in pf.nodes:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            out.extend(self._check_function(pf, model, fn))
        if pf.rel != _SEAM_FILE:
            out.extend(self._check_seam(pf, model))
        return out

    # -- sub-rule 1: accumulation on low-precision taint --------------------

    def _check_function(self, pf, model: DtypeModel,
                        fn: ast.AST) -> List[Finding]:
        nodes = list(own_body_walk(fn))
        if not any(isinstance(n, (ast.Call, ast.BinOp)) for n in nodes):
            return []
        lowp = model.analysis(
            fn, "lowp", ExprTokens(source=low_prec_source(model)))
        out: List[Finding] = []
        for node in nodes:
            if isinstance(node, ast.Call):
                out.extend(self._check_accum_call(pf, model, fn, lowp,
                                                  node))
            elif (isinstance(node, ast.BinOp)
                  and isinstance(node.op, ast.MatMult)):
                out.extend(self._check_matmult(pf, model, fn, lowp,
                                               node))
        return out

    def _check_accum_call(self, pf, model, fn, lowp,
                          call: ast.Call) -> List[Finding]:
        resolved = pf.imports.resolve_node(call.func) or ""
        last = resolved.split(".")[-1]
        operands: List[ast.expr] = []
        if (last in _ACCUM_CALLS
                and resolved.startswith(("jax.numpy.", "jax.lax."))):
            operands = list(call.args)
        elif (isinstance(call.func, ast.Attribute)
              and call.func.attr in _ACCUM_METHODS
              and not resolved.startswith(("jax.", "numpy."))):
            operands = [call.func.value] + list(call.args)
        if not operands:
            return []
        if any(kw.arg == "preferred_element_type"
               for kw in call.keywords):
            return []
        stmt = model.enclosing_stmt(call, fn)
        if stmt is None:
            return []
        env = lowp.env_at(stmt)
        if not any("lowp" in lowp.eval_expr(op, env)
                   for op in operands):
            return []
        name = last if last in _ACCUM_CALLS else call.func.attr
        return [Finding(
            rule=self.rule, severity="error", path=pf.rel,
            line=call.lineno, col=call.col_offset,
            message=f"{name} accumulates bf16/f16-tainted operands at "
                    f"operand precision "
                    f"({pf.line_text(call.lineno)[:48]!r}) — rounding "
                    f"error compounds per term; the accumulator width "
                    f"must be pinned",
            hint="pass preferred_element_type=jnp.float32, or upcast "
                 "the operand (astype(jnp.float32)) before the "
                 "reduction")]

    def _check_matmult(self, pf, model, fn, lowp,
                       binop: ast.BinOp) -> List[Finding]:
        stmt = model.enclosing_stmt(binop, fn)
        if stmt is None:
            return []
        env = lowp.env_at(stmt)
        if not any("lowp" in lowp.eval_expr(op, env)
                   for op in (binop.left, binop.right)):
            return []
        return [Finding(
            rule=self.rule, severity="error", path=pf.rel,
            line=binop.lineno, col=binop.col_offset,
            message=f"'@' contraction on bf16/f16-tainted operands "
                    f"({pf.line_text(binop.lineno)[:48]!r}) "
                    f"accumulates at operand precision",
            hint="use jnp.matmul(..., "
                 "preferred_element_type=jnp.float32) or upcast the "
                 "operands first")]

    # -- sub-rule 2: bf16 casts outside the placement seam ------------------

    def _check_seam(self, pf, model: DtypeModel) -> List[Finding]:
        out: List[Finding] = []
        for node in pf.nodes:
            if not isinstance(node, ast.Call):
                continue
            if model.cast_dtype(node) != "bfloat16":
                continue
            out.append(Finding(
                rule=self.rule, severity="error", path=pf.rel,
                line=node.lineno, col=node.col_offset,
                message="cast to bfloat16 outside the shard_rules "
                        "placement-cast seam — ad-hoc autocast "
                        "bypasses the resolve/warn-once policy and "
                        "the runtime dtype contract",
                hint="route low-precision placement through "
                     "shard_rules.placement_cast (the dtype_specs "
                     "seam) so the bf16 arm stays policy-gated and "
                     "contract-checked"))
        return out
