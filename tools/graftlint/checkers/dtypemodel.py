"""Shared per-file dtype model for the graftdtype rules (GL013–GL016).

The four numeric-precision checkers all need the same facts about a
file: which function bodies run under jax tracing, which are host
callbacks, which names are bound to jitted callables, and what dtype
(if any) a call expression casts its operand to. The first three are
the ``ParsedFile``'s own (made once a lint run, shared with GL002 and
GL006-GL008); the model is memoized on the ``ParsedFile`` instance and
shared across the checkers, and each rule layers its own taint sources
on the common ``Analysis`` cache.

Generalizes the GL007 dtype helpers: where GL007 cares only about
float64/int64/sub-32 evidence, the model resolves the full dtype
vocabulary (including bfloat16/float16 and the unsigned bin-plane
types) and exposes a width table so rules can reason about narrowing.
"""

from __future__ import annotations

import ast
import struct
from typing import Dict, List, Optional, Tuple

from tools.graftlint.astutil import dotted
from tools.graftlint.core import ParsedFile
from tools.graftlint.dataflow import Analysis, Tokens

# every dtype name the model resolves; anything else is "not a dtype"
DTYPE_NAMES = frozenset({
    "float64", "float32", "float16", "bfloat16",
    "int64", "int32", "int16", "int8",
    "uint64", "uint32", "uint16", "uint8",
    "bool", "bool_", "complex64", "complex128",
})

# bit width per dtype — the narrowing rules compare these
DTYPE_WIDTHS: Dict[str, int] = {
    "float64": 64, "float32": 32, "float16": 16, "bfloat16": 16,
    "int64": 64, "int32": 32, "int16": 16, "int8": 8,
    "uint64": 64, "uint32": 32, "uint16": 16, "uint8": 8,
    "bool": 1, "bool_": 1, "complex64": 64, "complex128": 128,
}

LOW_PREC = frozenset({"bfloat16", "float16"})

# dtype-carrying constructor calls (dtype may be a keyword or trailing
# positional); astype is handled separately because its operand is the
# attribute base, not an argument
_CAST_CALLS = frozenset({"asarray", "array", "full", "zeros", "ones",
                         "empty", "arange", "linspace"})


class DtypeModel:
    """Memoized per-file dtype facts shared by GL013–GL016."""

    def __init__(self, pf: ParsedFile):
        self.pf = pf
        self.traced = pf.traced
        self.callback_fns = pf.callback_fns
        self.jitted_names = pf.jitted_names
        self._analyses: Dict[Tuple[int, str], Analysis] = {}

    # -- analysis cache -----------------------------------------------------

    def analysis(self, fn: ast.AST, key: str, eval_expr) -> Analysis:
        """One dataflow run per (function, taint-kind), shared across
        the checkers that ask for the same kind."""
        k = (id(fn), key)
        a = self._analyses.get(k)
        if a is None:
            a = Analysis(fn, eval_expr)
            self._analyses[k] = a
        return a

    # -- dtype resolution ---------------------------------------------------

    def dtype_name(self, expr: ast.AST) -> Optional[str]:
        """``'float32'`` for a dtype-denoting expression (string
        literal, ``jnp.float32``, ``np.uint8`` …), else None."""
        if isinstance(expr, ast.Constant) and isinstance(expr.value,
                                                         str):
            return expr.value if expr.value in DTYPE_NAMES else None
        d = dotted(expr)
        if d:
            resolved = self.pf.imports.resolve(d) or d
            last = resolved.split(".")[-1]
            if last in DTYPE_NAMES:
                return last
        return None

    def explicit_dtype(self, call: ast.Call) -> Optional[str]:
        """The dtype a constructor call pins, from ``dtype=`` or a
        positional dtype-denoting argument. ``'?'`` means "a dtype= is
        present but not statically resolvable" — still explicit."""
        for kw in call.keywords:
            if kw.arg == "dtype":
                return self.dtype_name(kw.value) or "?"
        for arg in call.args:
            d = self.dtype_name(arg)
            if d is not None:
                return d
            if isinstance(arg, ast.Attribute) and arg.attr == "dtype":
                return "?"   # jnp.zeros(n, x.dtype): explicitly pinned
        return None

    def cast_dtype(self, call: ast.Call) -> Optional[str]:
        """The target dtype of an explicit cast, or None if the call is
        not a cast. Recognizes ``x.astype(d)``, dtype-pinned
        constructors, and ``np.float64(x)``-style scalar casts."""
        resolved = self.pf.imports.resolve_node(call.func) or ""
        last = resolved.split(".")[-1]
        if (not last and isinstance(call.func, ast.Attribute)
                and call.func.attr == "astype"):
            last = "astype"   # astype on a call result: dotted() can't
            # resolve through the Call, but the method name is decisive
        if last in DTYPE_NAMES and resolved.startswith(
                ("numpy.", "jax.numpy.")):
            return last
        if last == "astype":
            d = self.explicit_dtype(call)
            if d is None and call.args:
                d = self.dtype_name(call.args[0])
            return d
        if last in _CAST_CALLS:
            return self.explicit_dtype(call)
        return None

    def enclosing_stmt(self, node: ast.AST,
                       fn: ast.AST) -> Optional[ast.stmt]:
        cur = node
        while cur is not None and cur is not fn:
            if isinstance(cur, ast.stmt):
                return cur
            cur = self.pf.parents.get(cur)
        return None


def dtype_model(pf: ParsedFile) -> DtypeModel:
    """The file's memoized model, built on first request."""
    model = getattr(pf, "_graftdtype_model", None)
    if model is None:
        model = DtypeModel(pf)
        pf._graftdtype_model = model
    return model


# --- shared taint sources ---------------------------------------------------

def low_prec_source(model: DtypeModel):
    """Taint source for bf16/f16 evidence: a cast to a low-precision
    float seeds 'lowp'; an explicit cast to anything else kills it
    (the upcast IS the fix GL015 asks for)."""
    def source(expr: ast.AST) -> Optional[Tokens]:
        if not isinstance(expr, ast.Call):
            return None
        d = model.cast_dtype(expr)
        if d in LOW_PREC:
            return frozenset({"lowp"})
        if d is not None and d != "?":
            return frozenset()
        return None
    return source


def float32_roundtrips(value: float) -> bool:
    """True when the literal survives a float32 round-trip exactly."""
    try:
        return struct.unpack("<f", struct.pack("<f", value))[0] == value
    except (OverflowError, struct.error):
        return False


def significant_digits(text: str) -> int:
    """Significant decimal digits in a float literal's source text."""
    mantissa = text.split("e")[0].split("E")[0]
    digits = "".join(c for c in mantissa if c.isdigit()).lstrip("0")
    return len(digits)
