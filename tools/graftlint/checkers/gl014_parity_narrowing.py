"""GL014 — parity-boundary narrowing.

Every headline contract in this repo is a *parity pin* — bitwise trees,
bitwise failover replies, bitwise OOC/spill/checkpoint resume — and
each pin rides on a value whose exact bits matter: pow2-exact quant
scales (``_pow2_scale`` outputs), uint8 binned planes, spill/checkpoint
payloads, native-callback operands. A narrowing ``.astype``/``.view``
on any of those destroys the pin silently: the fit still runs, the
trees just stop matching their replayed/resumed twins.

The rule taints values produced by a parity-pinned source and flags a
cast/view to a sub-32-bit target (``float16``/``bfloat16``/``int16``/
``int8``/``uint16``) on a tainted value. Two deliberate exclusions keep
the blessed idioms quiet:

* casts to **float32** never flag — f64→f32 at a jit boundary is
  GL007/GL016 territory, and f32 is the pinned accumulator width;
* casts to **uint8** never flag — binning *produces* the uint8 plane;
  it is a parity source here, not a narrowing sink.

Unlike the dtype-evidence taints, parity taint is **not** killed by an
intermediate cast: widening a pinned value does not un-pin it, so the
taint must survive to catch a later narrowing. It does NOT flow through
the *predicate* of a ``jnp.where``/``lax.select`` (selection never
moves the predicate's bits into the output — an int8 decision-bits
enum selected by a quant-derived mask is not a narrowed quant value)
nor through comparison results, which are booleans.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from tools.graftlint.core import Checker, Finding, ParsedFile, Project
from tools.graftlint.dataflow import ExprTokens, Tokens, own_body_walk
from tools.graftlint.checkers.dtypemodel import DtypeModel, dtype_model

NARROW_TARGETS = frozenset({"float16", "bfloat16", "int16", "int8",
                            "uint16"})

# call names (last dotted segment) whose results are parity-pinned
_PARITY_CALL_NAMES = frozenset({
    "_pow2_scale", "pow2_scale",            # pow2-exact quant scales
    "read_chunk", "iter_chunks",            # spill payloads
    "load_checkpoint", "read_checkpoint",   # checkpoint payloads
})


class ParityNarrowingChecker(Checker):
    rule = "GL014"
    name = "parity-narrowing"
    description = ("narrowing .astype/.view on a parity-pinned value "
                   "(pow2 quant scales, uint8 binned planes, "
                   "spill/checkpoint payloads, native-callback "
                   "operands) — silently breaks a bitwise contract")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        model = dtype_model(pf)
        out: List[Finding] = []
        for fn in pf.nodes:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            out.extend(self._check_function(pf, model, fn))
        return out

    def _check_function(self, pf, model: DtypeModel,
                        fn: ast.AST) -> List[Finding]:
        calls = [n for n in own_body_walk(fn)
                 if isinstance(n, ast.Call)]
        if not calls:
            return []
        parity = model.analysis(
            fn, "parity", _ParityTokens(pf, model))
        out: List[Finding] = []
        for call in calls:
            target = _narrow_target(pf, model, call)
            if target is None:
                continue
            stmt = model.enclosing_stmt(call, fn)
            if stmt is None:
                continue
            env = parity.env_at(stmt)
            operand = call.func.value  # the x in x.astype(...)
            toks = parity.eval_expr(operand, env)
            if "parity" not in toks:
                continue
            verb = call.func.attr
            out.append(Finding(
                rule=self.rule, severity="error", path=pf.rel,
                line=call.lineno, col=call.col_offset,
                message=f".{verb}({target}) narrows a parity-pinned "
                        f"value "
                        f"({pf.line_text(call.lineno)[:48]!r}) — quant "
                        f"scales, binned planes and spill/checkpoint "
                        f"payloads are bitwise contracts; a sub-32-bit "
                        f"cast silently breaks resume/failover parity",
                hint="keep pinned values at their contract width "
                     "(float32/uint8); if a low-precision copy is "
                     "needed, derive it from the unpinned source data, "
                     "not from the pinned value"))
        return out


def _narrow_target(pf, model: DtypeModel,
                   call: ast.Call) -> Optional[str]:
    """The narrow dtype a ``.astype``/``.view`` call lands on, or
    None when the call is not a narrowing cast."""
    if not isinstance(call.func, ast.Attribute):
        return None
    if call.func.attr not in ("astype", "view"):
        return None
    d = model.explicit_dtype(call)
    if d is None and call.args:
        d = model.dtype_name(call.args[0])
    return d if d in NARROW_TARGETS else None


def _parity_source(pf, model: DtypeModel):
    def source(expr: ast.AST) -> Optional[Tokens]:
        if isinstance(expr, ast.Compare):
            return frozenset()             # booleans carry no payload
        if not isinstance(expr, ast.Call):
            return None
        resolved = pf.imports.resolve_node(expr.func) or ""
        last = resolved.split(".")[-1]
        if last in _PARITY_CALL_NAMES:
            return frozenset({"parity"})
        if resolved.startswith("mmlspark_tpu.native.bindings."):
            return frozenset({"parity"})   # native-callback operands
        if model.cast_dtype(expr) == "uint8":
            return frozenset({"parity"})   # the binned plane itself
        return None                        # casts do NOT kill the pin
    return source


class _ParityTokens(ExprTokens):
    """ExprTokens whose selection calls (``jnp.where``/``lax.select``)
    take taint only from their branch values, never the predicate."""

    def __init__(self, pf, model: DtypeModel):
        super().__init__(source=_parity_source(pf, model))
        self._pf = pf

    def __call__(self, node, env):
        if isinstance(node, ast.Call) and node.args:
            resolved = self._pf.imports.resolve_node(node.func) or ""
            if resolved in ("jax.numpy.where", "jax.lax.select",
                            "jax.lax.select_n"):
                out = frozenset()
                for branch in node.args[1:]:
                    out |= self(branch, env)   # nested selections too
                return out
        return super().__call__(node, env)
