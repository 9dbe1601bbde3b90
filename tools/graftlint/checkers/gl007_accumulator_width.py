"""GL007 — accumulator width / dtype dataflow.

Two silent-corruption classes in the histogram and scatter paths:

1. **int32 flat-index overflow.** Flat indices of the shape
   ``rows * F * B`` overflow int32 at pod-scale row counts
   (2^31 / (F*B) rows), and jax's default integer dtype inside a jit
   is int32. The checker uses row-scale taint (anything derived from
   ``.shape``/``.size``) plus reaching definitions to find products of
   **three or more factors** where at least one factor is row-scaled,
   feeding ``arange``/``segment_sum``/``.at[...].add`` index positions
   with int32 evidence (explicit int32, or no widening). An
   ``int64``/``astype(int64)`` anywhere in the chain absolves — that is
   the fix the finding asks for. Two-factor products (``nb * r`` bin
   math) are deliberately below the radar: the rule targets the
   row×feature×bin class, not every shape product.

2. **silent float64→float32 narrowing across a jit boundary.** A value
   with float64 evidence (``np.float64`` casts/dtypes) passed bare into
   a jitted callable is narrowed to float32 without warning (jax x64 is
   disabled by default). An explicit ``astype``/``asarray`` to another
   dtype kills the taint — intentional narrowing is fine; *silent*
   narrowing is the bug.

3. **sub-32-bit accumulation.** ``segment_sum``/``.at[...].add`` keep
   the operand dtype as the accumulator dtype, so int16/int8 data
   (MMLSPARK_TPU_HIST_QUANT-style quantized gradients) summed over a
   large segment overflows silently — int16 holds only ~2 quantized
   values of magnitude qmax=32000 per bin. The fix is the periodic-
   rescale idiom (``trainer._level_histogram_quant``'s XLA mirror):
   chunk the rows so each chunk's int32 partial is exact, widen the
   operand (``astype(jnp.int32)``) per chunk, and fold partials into a
   float32/int64 accumulator. As with rule 1, any widening cast in the
   dataflow chain absolves — it IS the fix.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from tools.graftlint.astutil import dotted
from tools.graftlint.core import Checker, Finding, ParsedFile, Project
from tools.graftlint.dataflow import (Analysis, ExprTokens, Tokens,
                                      own_body_walk)

_MIN_FACTORS = 3


class AccumulatorWidthChecker(Checker):
    rule = "GL007"
    name = "accumulator-width"
    description = ("row-scaled int32 flat-index products (n*F*B) "
                   "feeding segment_sum/scatter, silent "
                   "float64->float32 narrowing across jit boundaries, "
                   "and sub-32-bit (int8/int16) accumulation into "
                   "segment_sum/.at[].add without a widening cast")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        jit_callables = pf.jitted_names
        out: List[Finding] = []
        for fn in pf.nodes:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            out.extend(self._check_function(pf, fn, jit_callables))
        return out

    def _check_function(self, pf: ParsedFile, fn: ast.AST,
                        jit_callables: Set[str]) -> List[Finding]:
        body_nodes = list(own_body_walk(fn))
        calls = [n for n in body_nodes if isinstance(n, ast.Call)]
        if not calls:
            return []
        row = Analysis(fn, ExprTokens(source=_row_source(pf),
                                      kill_static_attrs=False))
        defs = Analysis(fn, lambda e, env: frozenset({id(e)})
                        if e is not None else frozenset())
        def_nodes = {id(n): n for n in ast.walk(fn)}
        f64 = Analysis(fn, ExprTokens(source=_dtype_source(pf,
                                                           "float64")))
        i64 = Analysis(fn, ExprTokens(source=_dtype_source(pf,
                                                           "int64")))
        sub32 = Analysis(fn, ExprTokens(source=_sub32_source(pf)))
        out: List[Finding] = []
        seen: Set[int] = set()
        for call in calls:
            stmt = _enclosing_stmt(pf, call, fn)
            if stmt is None:
                continue
            out.extend(self._check_index_widths(
                pf, call, stmt, row, i64, defs, def_nodes, seen))
            out.extend(self._check_narrowing(
                pf, call, stmt, f64, jit_callables))
            out.extend(self._check_sub32_accumulation(
                pf, call, stmt, sub32))
        return out

    # -- rule 1: int32 flat-index products ---------------------------------

    def _check_index_widths(self, pf, call, stmt, row, i64, defs,
                            def_nodes, seen) -> List[Finding]:
        resolved = pf.imports.resolve_node(call.func) or ""
        last = resolved.split(".")[-1]
        index_exprs: List[ast.expr] = []
        if last == "arange" and resolved.startswith(
                ("jax.numpy.", "jnp.")):
            if call.args:
                index_exprs.append(call.args[0])
            if _explicit_dtype(pf, call) == "int64":
                return []
        elif last == "segment_sum":
            if len(call.args) > 1:
                index_exprs.append(call.args[1])
            index_exprs.extend(kw.value for kw in call.keywords
                               if kw.arg == "segment_ids")
        elif (isinstance(call.func, ast.Attribute)
              and call.func.attr in ("add", "set", "max", "min")
              and isinstance(call.func.value, ast.Subscript)
              and isinstance(call.func.value.value, ast.Attribute)
              and call.func.value.value.attr == "at"):
            index_exprs.append(call.func.value.slice)
        else:
            return []

        env = row.env_at(stmt)
        env64 = i64.env_at(stmt)
        out: List[Finding] = []
        for expr in index_exprs:
            candidates: List[ast.expr] = [expr]
            for name_node in ast.walk(expr):
                if isinstance(name_node, ast.Name):
                    for did in defs.env_at(stmt).get(name_node.id, ()):
                        d = def_nodes.get(did)
                        if d is not None:
                            candidates.append(d)
            for cand in candidates:
                hit = _row_product(cand, row.eval_expr, env, pf)
                if hit is None or id(hit) in seen:
                    continue
                if _has_int64(pf, cand) or _has_int64(pf, expr):
                    continue
                if "i64" in (i64.eval_expr(cand, env64)
                             | i64.eval_expr(expr, env64)):
                    continue   # widened upstream: that IS the fix
                seen.add(id(hit))
                n = len(_flatten_product(hit))
                out.append(Finding(
                    rule=self.rule, severity="error", path=pf.rel,
                    line=call.lineno, col=call.col_offset,
                    message=f"row-scaled {n}-factor int32 flat-index "
                            f"product feeds {pf.line_text(call.lineno)[:40]!r}"
                            f" — overflows int32 at pod-scale row "
                            f"counts (jax default int is int32 under "
                            f"jit)",
                    hint="widen the accumulator index: compute the "
                         "product in int64 (astype(jnp.int64) on the "
                         "row term) or restructure to per-feature "
                         "segment ids that stay < 2**31"))
        return out

    # -- rule 3: sub-32-bit accumulation -----------------------------------

    def _check_sub32_accumulation(self, pf, call, stmt,
                                  sub32) -> List[Finding]:
        """int16/int8-tainted DATA summed by segment_sum or
        ``.at[...].add``: the accumulator inherits the operand dtype,
        so the sum overflows long before the indices do. A widening
        cast anywhere on the data chain clears the taint (dtype-source
        kill), which is exactly the chunked periodic-rescale fix."""
        resolved = pf.imports.resolve_node(call.func) or ""
        data_exprs: List[ast.expr] = []
        if resolved.split(".")[-1] == "segment_sum":
            if call.args:
                data_exprs.append(call.args[0])
            data_exprs.extend(kw.value for kw in call.keywords
                              if kw.arg == "data")
        elif (isinstance(call.func, ast.Attribute)
              and call.func.attr == "add"
              and isinstance(call.func.value, ast.Subscript)
              and isinstance(call.func.value.value, ast.Attribute)
              and call.func.value.value.attr == "at"):
            data_exprs.extend(call.args)
        else:
            return []
        env = sub32.env_at(stmt)
        out: List[Finding] = []
        for expr in data_exprs:
            if "sub32" not in sub32.eval_expr(expr, env):
                continue
            out.append(Finding(
                rule=self.rule, severity="error", path=pf.rel,
                line=call.lineno, col=call.col_offset,
                message=f"sub-32-bit (int8/int16) data accumulated by "
                        f"{pf.line_text(call.lineno)[:40]!r} — the "
                        f"accumulator inherits the operand dtype and "
                        f"overflows within a few thousand quantized "
                        f"rows per bin",
                hint="apply the periodic-rescale idiom: chunk the rows "
                     "so an int32 partial is exact, widen per chunk "
                     "(astype(jnp.int32)) and fold partials into a "
                     "float32/int64 accumulator (see "
                     "trainer._level_histogram_quant)"))
        return out

    # -- rule 2: float64 narrowing ------------------------------------------

    def _check_narrowing(self, pf, call, stmt, f64,
                         jit_callables) -> List[Finding]:
        if not isinstance(call.func, ast.Name):
            return []
        if call.func.id not in jit_callables:
            return []
        env = f64.env_at(stmt)
        out: List[Finding] = []
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(arg, ast.Name) and "f64" in env.get(arg.id,
                                                              frozenset()):
                out.append(Finding(
                    rule=self.rule, severity="error", path=pf.rel,
                    line=call.lineno, col=call.col_offset,
                    message=f"float64 value {arg.id!r} passed into "
                            f"jitted callable {call.func.id!r} is "
                            f"silently narrowed to float32 (jax x64 "
                            f"is disabled by default)",
                    hint="cast explicitly (astype(np.float32)) before "
                         "the jit boundary, or enable jax x64 if the "
                         "precision is load-bearing"))
        return out


# --- taint sources ----------------------------------------------------------

def _row_source(pf: ParsedFile):
    def source(expr: ast.AST) -> Optional[Tokens]:
        # x.shape, x.shape[i], x.size are row-scale evidence
        if isinstance(expr, ast.Attribute) and expr.attr in ("shape",
                                                             "size"):
            return frozenset({"row"})
        if isinstance(expr, ast.Call):
            resolved = pf.imports.resolve_node(expr.func) or ""
            if resolved == "len":
                return frozenset({"row"})
        return None
    return source


def _dtype_source(pf: ParsedFile, want: str):
    """Taint source for dtype evidence: a cast *to* ``want`` seeds the
    taint ('f64'/'i64'), an explicit cast to anything else kills it."""
    label = {"float64": "f64", "int64": "i64"}[want]

    def source(expr: ast.AST) -> Optional[Tokens]:
        if not isinstance(expr, ast.Call):
            return None
        d = _cast_dtype(pf, expr)
        if d == want:
            return frozenset({label})
        if d is not None:
            return frozenset()   # explicit cast to something else: kill
        return None
    return source


def _sub32_source(pf: ParsedFile):
    """Taint source for sub-32-bit integer evidence: a cast to
    int16/int8 seeds 'sub32'; an explicit cast to any wider dtype
    kills it (that widening is the periodic-rescale fix)."""
    def source(expr: ast.AST) -> Optional[Tokens]:
        if not isinstance(expr, ast.Call):
            return None
        d = _cast_dtype(pf, expr)
        if d in ("int16", "int8", "uint16", "uint8"):
            return frozenset({"sub32"})
        if d is not None:
            return frozenset()   # widened (or float): kill
        return None
    return source


def _cast_dtype(pf: ParsedFile, call: ast.Call) -> Optional[str]:
    """The target dtype of an explicit cast call, or None if the call
    is not a cast. Recognizes astype, asarray/array(dtype=...),
    np.float64(x)-style constructors."""
    resolved = pf.imports.resolve_node(call.func) or ""
    last = resolved.split(".")[-1]
    if (not last and isinstance(call.func, ast.Attribute)
            and call.func.attr == "astype"):
        last = "astype"   # astype on a call result: dotted() can't
        # resolve through the Call, but the method name is decisive
    if last in ("float64", "float32", "float16", "int32", "int64",
                "bfloat16") and resolved.startswith(
                    ("numpy.", "jax.numpy.")):
        return last
    if last == "astype" or last in ("asarray", "array", "full", "zeros",
                                    "ones", "arange", "linspace"):
        d = _explicit_dtype(pf, call)
        if d is None and last == "astype" and call.args:
            d = _dtype_name(pf, call.args[0])
        if d is None and last == "asarray" and len(call.args) > 1:
            d = _dtype_name(pf, call.args[1])
        return d
    return None


def _explicit_dtype(pf: ParsedFile, call: ast.Call) -> Optional[str]:
    for kw in call.keywords:
        if kw.arg == "dtype":
            return _dtype_name(pf, kw.value)
    return None


def _dtype_name(pf: ParsedFile, expr: ast.AST) -> Optional[str]:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    d = dotted(expr)
    if d:
        resolved = pf.imports.resolve(d) or d
        return resolved.split(".")[-1]
    return None


# --- product analysis -------------------------------------------------------

def _flatten_product(expr: ast.AST) -> List[ast.AST]:
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Mult):
        return _flatten_product(expr.left) + _flatten_product(expr.right)
    return [expr]


def _row_product(expr: ast.AST, eval_expr, env,
                 pf: ParsedFile) -> Optional[ast.AST]:
    """The first maximal multiplication chain in ``expr`` with >=
    _MIN_FACTORS factors, at least one row-tainted; None otherwise."""
    def maximal_mults(node: ast.AST, under_mult: bool):
        is_mult = (isinstance(node, ast.BinOp)
                   and isinstance(node.op, ast.Mult))
        if is_mult and not under_mult:
            yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            yield from maximal_mults(child, is_mult)
    for mult in maximal_mults(expr, False):
        factors = _flatten_product(mult)
        if len(factors) < _MIN_FACTORS:
            continue
        if any("row" in eval_expr(f, env) for f in factors):
            return mult
    return None


def _has_int64(pf: ParsedFile, expr: ast.AST) -> bool:
    for n in ast.walk(expr):
        d = dotted(n)
        if d and (pf.imports.resolve(d) or d).endswith(".int64"):
            return True
        if (isinstance(n, ast.Constant) and n.value == "int64"):
            return True
    return False


def _enclosing_stmt(pf: ParsedFile, node: ast.AST,
                    fn: ast.AST) -> Optional[ast.stmt]:
    cur = node
    while cur is not None and cur is not fn:
        if isinstance(cur, ast.stmt):
            return cur
        cur = pf.parents.get(cur)
    return None
