"""GL013 — weak-type / promotion hazards in traced code.

Three silent-truncation classes, all rooted in jax's x64-off default
(every float64 becomes float32 without warning inside a trace):

1. **np.float64 constants entering traced arithmetic.** A
   ``np.float64(...)`` scalar built inside a jit/shard_map body is
   narrowed to float32 the moment it meets a tracer — the extra
   precision the author asked for is silently discarded.

2. **high-precision float literals in traced arithmetic.** A literal
   with more significant digits than float32 can hold (and that fails
   an exact float32 round-trip) is truncated at trace time. Common
   constants (``0.5``, ``1e-6``, ``0.1``) are deliberately below the
   radar — only literals written with > 8 significant digits flag,
   because those encode a precision intent the trace cannot honor.

3. **default-dtype constructors on kernel paths.** ``jnp.zeros/ones/
   arange/full/empty`` without an explicit dtype inherit whatever the
   global default-dtype config happens to be. Inside any traced body,
   and anywhere in the kernel modules (``models/gbdt/``, ``ops/``),
   that is a parity hazard: the quant accumulator paths must never
   depend on ambient config. A ``dtype=`` keyword or a positional
   dtype argument (``jnp.zeros(n, jnp.int32)``) absolves.

Host callback bodies (``pure_callback``/``emit_python_callback``
targets) are exempt from 1 and 2 — they are host code by design.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from tools.graftlint.astutil import walk_skipping
from tools.graftlint.core import Checker, Finding, ParsedFile, Project
from tools.graftlint.checkers.dtypemodel import (
    dtype_model, float32_roundtrips, significant_digits)

_CTORS = frozenset({"zeros", "ones", "arange", "full", "empty"})
_KERNEL_PREFIXES = ("mmlspark_tpu/models/gbdt/", "mmlspark_tpu/ops/")
_MAX_LITERAL_DIGITS = 8


class WeakTypeChecker(Checker):
    rule = "GL013"
    name = "weak-types"
    description = ("np.float64 constants and high-precision float "
                   "literals silently truncated to float32 inside "
                   "jit/shard_map bodies (x64 off), and default-dtype "
                   "jnp.zeros/ones/arange/full/empty on kernel paths")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        model = dtype_model(pf)
        out: List[Finding] = []
        seen: Set[int] = set()
        for root in model.traced:
            for node in walk_skipping(root, model.callback_fns):
                if id(node) in seen:
                    continue
                seen.add(id(node))
                out.extend(self._check_traced_node(pf, model, node))
        if pf.rel.startswith(_KERNEL_PREFIXES):
            for node in pf.nodes:
                if isinstance(node, ast.Call) and id(node) not in seen:
                    seen.add(id(node))
                    f = self._check_ctor(pf, model, node,
                                         where="kernel module")
                    if f is not None:
                        out.append(f)
        return out

    def _check_traced_node(self, pf, model, node) -> List[Finding]:
        out: List[Finding] = []
        if isinstance(node, ast.Call):
            resolved = pf.imports.resolve_node(node.func) or ""
            if resolved == "numpy.float64":
                out.append(Finding(
                    rule=self.rule, severity="error", path=pf.rel,
                    line=node.lineno, col=node.col_offset,
                    message="np.float64 constant built inside a traced "
                            "body is silently truncated to float32 "
                            "(jax x64 is disabled by default)",
                    hint="use np.float32 (or a plain float and accept "
                         "weak-type promotion); if float64 is "
                         "load-bearing, compute it in host code before "
                         "the trace boundary"))
            f = self._check_ctor(pf, model, node, where="traced body")
            if f is not None:
                out.append(f)
        elif (isinstance(node, ast.Constant)
              and type(node.value) is float):
            out.extend(self._check_literal(pf, node))
        return out

    def _check_literal(self, pf, node) -> List[Finding]:
        parent = pf.parents.get(node)
        if isinstance(parent, ast.UnaryOp):
            parent = pf.parents.get(parent)
        if not isinstance(parent, (ast.BinOp, ast.Compare)):
            return []
        text = self._literal_text(pf, node)
        if significant_digits(text) <= _MAX_LITERAL_DIGITS:
            return []
        if float32_roundtrips(node.value):
            return []
        return [Finding(
            rule=self.rule, severity="error", path=pf.rel,
            line=node.lineno, col=node.col_offset,
            message=f"float literal {text} carries more precision than "
                    f"float32 holds — inside a traced body it is "
                    f"silently truncated (jax x64 is disabled by "
                    f"default)",
            hint="round the literal to its float32 value, or hoist the "
                 "float64 math to host code before the trace boundary")]

    @staticmethod
    def _literal_text(pf, node) -> str:
        line = (pf.lines[node.lineno - 1]
                if 1 <= node.lineno <= len(pf.lines) else "")
        end = getattr(node, "end_col_offset", None)
        if node.lineno == getattr(node, "end_lineno", node.lineno) \
                and end is not None:
            return line[node.col_offset:end]
        return repr(node.value)

    def _check_ctor(self, pf, model, call,
                    where: str) -> Optional[Finding]:
        resolved = pf.imports.resolve_node(call.func) or ""
        last = resolved.split(".")[-1]
        if last not in _CTORS or not resolved.startswith("jax.numpy."):
            return None
        if model.explicit_dtype(call) is not None:
            return None
        return Finding(
            rule=self.rule, severity="error", path=pf.rel,
            line=call.lineno, col=call.col_offset,
            message=f"jnp.{last} without an explicit dtype in a "
                    f"{where} inherits the ambient default-dtype "
                    f"config — a parity hazard on quantized/binned "
                    f"paths",
            hint=f"pin the dtype: jnp.{last}(..., dtype=jnp.float32) "
                 f"(or the intended integer dtype)")
