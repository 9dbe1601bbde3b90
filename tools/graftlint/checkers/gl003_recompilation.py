"""GL003 — recompilation hazards.

Three patterns that make XLA recompile (or cache wrongly) without any
visible error:

  - a ``static_argnums``/``static_argnames`` parameter whose default is
    non-hashable (list/dict/set): jit raises only when the default is
    actually used, i.e. in the rarely-exercised call path;
  - f-string cache keys: two configs that format identically collide,
    and float formatting (``f"{lr}"``) is locale/precision-fragile —
    the compiled-step caches here key on tuples for this reason;
  - iterating a set to build traced inputs or cache keys: set order is
    not deterministic across processes (string-hash randomization), so
    the same logical config can produce differently-ordered operands —
    a fresh compile per process and a poisoned persistent cache.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from tools.graftlint.astutil import dotted
from tools.graftlint.core import Checker, Finding, ParsedFile, Project

_NONHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                ast.SetComp)
_NONHASHABLE_CALLS = {"list", "dict", "set", "bytearray"}


class RecompilationChecker(Checker):
    rule = "GL003"
    name = "recompilation-hazards"
    description = ("non-hashable static args, f-string cache keys, "
                   "set-iteration feeding traced code")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        out: List[Finding] = []
        defs = {n.name: n for n in pf.nodes
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        for node in pf.nodes:
            if isinstance(node, ast.Call):
                out.extend(self._check_static_args_call(pf, node, defs))
                out.extend(self._check_fstring_cache_call(pf, node))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(self._check_static_args_decorators(pf, node))
            elif isinstance(node, (ast.For, ast.comprehension)):
                it = node.iter
                f = self._check_set_iteration(pf, it)
                if f is not None:
                    out.append(f)
            elif isinstance(node, ast.Subscript):
                out.extend(self._check_fstring_cache_subscript(pf, node))
        return out

    # --- non-hashable static args ---------------------------------------

    def _static_keywords(self, call: ast.Call):
        return [kw for kw in call.keywords
                if kw.arg in ("static_argnums", "static_argnames")]

    def _check_static_args_call(self, pf: ParsedFile, call: ast.Call,
                                defs) -> List[Finding]:
        resolved = pf.imports.resolve_node(call.func) or ""
        if resolved not in ("jax.jit", "jax.pmap", "functools.partial"):
            return []
        statics = self._static_keywords(call)
        if not statics:
            return []
        target: Optional[ast.AST] = None
        if call.args:
            head = call.args[0]
            if resolved == "functools.partial":
                head_resolved = pf.imports.resolve_node(head) or ""
                if head_resolved not in ("jax.jit", "jax.pmap"):
                    return []
                # decorator form handled via _check_static_args_decorators
                return []
            if isinstance(head, ast.Name):
                target = defs.get(head.id)
        if target is None:
            return []
        return self._check_target_defaults(pf, target, statics)

    def _check_static_args_decorators(self, pf: ParsedFile,
                                      fn) -> List[Finding]:
        out: List[Finding] = []
        for dec in fn.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            resolved = pf.imports.resolve_node(dec.func) or ""
            is_partial_jit = (
                resolved == "functools.partial" and dec.args
                and (pf.imports.resolve_node(dec.args[0]) or "")
                in ("jax.jit", "jax.pmap"))
            if resolved in ("jax.jit", "jax.pmap") or is_partial_jit:
                statics = self._static_keywords(dec)
                if statics:
                    out.extend(self._check_target_defaults(pf, fn,
                                                           statics))
        return out

    def _check_target_defaults(self, pf: ParsedFile, fn,
                               statics) -> List[Finding]:
        args = fn.args
        pos = list(getattr(args, "posonlyargs", [])) + list(args.args)
        defaults = list(args.defaults)
        offset = len(pos) - len(defaults)
        static_params = set()
        for kw in statics:
            v = kw.value
            values = v.elts if isinstance(v, (ast.Tuple, ast.List)) \
                else [v]
            for el in values:
                if not isinstance(el, ast.Constant):
                    continue
                if isinstance(el.value, int) and 0 <= el.value < len(pos):
                    static_params.add(pos[el.value].arg)
                elif isinstance(el.value, str):
                    static_params.add(el.value)
        out: List[Finding] = []
        for i, a in enumerate(pos):
            if a.arg not in static_params or i < offset:
                continue
            d = defaults[i - offset]
            bad = isinstance(d, _NONHASHABLE) or (
                isinstance(d, ast.Call)
                and (dotted(d.func) or "") in _NONHASHABLE_CALLS)
            if bad:
                out.append(Finding(
                    rule=self.rule, severity="warning", path=pf.rel,
                    line=d.lineno, col=d.col_offset,
                    message=f"static argument {a.arg!r} has a "
                            f"non-hashable default; jit will raise "
                            f"TypeError only when the default is used",
                    hint="use a hashable default (tuple, frozenset, "
                         "None-sentinel) for static args"))
        return out

    # --- f-string cache keys --------------------------------------------

    def _check_fstring_cache_subscript(self, pf: ParsedFile,
                                       sub: ast.Subscript
                                       ) -> List[Finding]:
        name = (dotted(sub.value) or "").lower()
        if "cache" not in name:
            return []
        return [self._fstring_finding(pf, n)
                for n in ast.walk(sub.slice)
                if isinstance(n, ast.JoinedStr)]

    def _check_fstring_cache_call(self, pf: ParsedFile,
                                  call: ast.Call) -> List[Finding]:
        name = (dotted(call.func) or "").lower()
        if "cache" not in name:
            return []
        out = []
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(arg, ast.JoinedStr):
                out.append(self._fstring_finding(pf, arg))
        return out

    def _fstring_finding(self, pf: ParsedFile,
                         node: ast.JoinedStr) -> Finding:
        return Finding(
            rule=self.rule, severity="warning", path=pf.rel,
            line=node.lineno, col=node.col_offset,
            message="f-string used as a cache key: formatting collides "
                    "distinct configs and is precision-fragile for "
                    "floats",
            hint="key caches on a tuple of the raw values (see "
                 "trainer._hist_env_key)")

    # --- set iteration ---------------------------------------------------

    def _check_set_iteration(self, pf: ParsedFile,
                             it: ast.AST) -> Optional[Finding]:
        is_set = isinstance(it, (ast.Set, ast.SetComp)) or (
            isinstance(it, ast.Call)
            and (dotted(it.func) or "") in ("set", "frozenset"))
        if not is_set:
            return None
        return Finding(
            rule=self.rule, severity="warning", path=pf.rel,
            line=it.lineno, col=it.col_offset,
            message="iterating a set: order is not deterministic "
                    "across processes (hash randomization)",
            hint="wrap in sorted(...) so derived operand orders and "
                 "cache keys are stable")
