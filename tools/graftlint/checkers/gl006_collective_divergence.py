"""GL006 — collective divergence.

In SPMD code every rank must execute the *same sequence* of
collectives; a ``psum`` reachable only under control flow that differs
across ranks deadlocks the pod (each rank waits in a different
collective) or silently reduces over a subset. Three hazard shapes,
found with the dataflow engine's rank/data taint:

1. a collective inside an ``if``/``while``/``for`` whose predicate is
   tainted by **rank identity** (``jax.process_index``,
   ``lax.axis_index``, ``mesh.process_index``, host/device env vars,
   hostname/pid) — unless both branches of the ``if`` execute an
   identical collective sequence, in which case the collective runs on
   every rank regardless;
2. inside a traced body, a predicate tainted by **traced data** (the
   function's array arguments) — data-dependent control flow both
   fails to trace and, under ``disable_jit`` or host dispatch, makes
   ranks diverge on their local shard values;
3. an ``if`` inside a traced body whose two branches both perform
   collectives but with **mismatched sequences** — even when the
   predicate is trace-static today, the branches disagree on the
   collective protocol (warning).

Taint does not flow through ``.shape``/``.dtype``/``.ndim``/``.size``
(trace-static metadata) or ``is None`` tests, so the codebase's shape
math and config gating stay clean.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.graftlint.astutil import dotted
from tools.graftlint.checkers.gl001_collective_axes import (
    COLLECTIVES, _is_collective_namespace)
from tools.graftlint.core import Checker, Finding, ParsedFile, Project
from tools.graftlint.dataflow import (Analysis, ExprTokens,
                                      control_context,
                                      functions_in_traced_context,
                                      own_body_walk)

# wrapper collectives the framework itself defines (core/jax_compat.py)
_WRAPPER_COLLECTIVE_SUFFIXES = (".pcast_varying",)

# rank-identity sources: (last segment, allowed resolved prefixes)
_RANK_CALLS = {
    "process_index": ("jax.", "mmlspark_tpu.parallel.mesh.", "mesh."),
    "process_count": (),      # count itself is uniform; never a source
    "axis_index": ("jax.lax.", "lax."),
    "gethostname": ("socket.",),
    "getfqdn": ("socket.",),
    "node": ("platform.",),
    "getpid": ("os.",),
    "uuid4": ("uuid.",),
}


class CollectiveDivergenceChecker(Checker):
    rule = "GL006"
    name = "collective-divergence"
    description = ("collectives must not be control-dependent on rank "
                   "identity or traced data; sibling branches must "
                   "agree on their collective sequence")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        traced = pf.traced
        traced_ctx = functions_in_traced_context(pf.tree, traced)
        out: List[Finding] = []
        for fn in pf.nodes:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            collectives = [n for n in own_body_walk(fn)
                           if _collective_name(pf, n)]
            if not collectives:
                continue
            out.extend(self._check_function(
                pf, fn, collectives, in_trace=id(fn) in traced_ctx))
        return out

    def _check_function(self, pf: ParsedFile, fn: ast.AST,
                        collectives: List[ast.Call],
                        in_trace: bool) -> List[Finding]:
        analysis = Analysis(
            fn, ExprTokens(source=_rank_source(pf)),
            seed=_param_seed(fn) if in_trace else {})
        out: List[Finding] = []
        flagged_ifs: Set[int] = set()
        for call in collectives:
            op = _collective_name(pf, call) or "?"
            for ctl, branch in control_context(pf.parents, call, fn):
                labels = self._predicate_taint(analysis, ctl)
                if not labels:
                    continue
                kind = "rank" if "rank" in labels else "data"
                if kind == "data" and not in_trace:
                    continue
                if isinstance(ctl, ast.If):
                    if id(ctl) in flagged_ifs:
                        break
                    if _branch_sequences_match(pf, ctl):
                        continue
                    flagged_ifs.add(id(ctl))
                    out.append(self._finding(pf, call, op, ctl, kind,
                                             branch))
                else:
                    out.append(self._finding(pf, call, op, ctl, kind,
                                             branch))
                break  # innermost tainted control is enough
        if in_trace:
            out.extend(self._sibling_mismatches(pf, fn, flagged_ifs))
        return out

    def _predicate_taint(self, analysis: Analysis,
                         ctl: ast.stmt) -> Set[str]:
        env = analysis.env_at(ctl)
        if isinstance(ctl, (ast.If, ast.While)):
            toks = analysis.eval_expr(ctl.test, env)
        else:  # For/AsyncFor: divergence comes from the iterable
            toks = analysis.eval_expr(ctl.iter, env)
        return {t for t in toks if t in ("rank", "data")}

    def _sibling_mismatches(self, pf: ParsedFile, fn: ast.AST,
                            already: Set[int]) -> List[Finding]:
        """Rule 3: both branches collect, but differently (warning)."""
        out: List[Finding] = []
        for node in own_body_walk(fn):
            if not isinstance(node, ast.If) or id(node) in already:
                continue
            body_seq = _collective_sequence(pf, node.body)
            else_seq = _collective_sequence(pf, node.orelse)
            if body_seq and else_seq and body_seq != else_seq:
                out.append(Finding(
                    rule=self.rule, severity="warning", path=pf.rel,
                    line=node.lineno, col=node.col_offset,
                    message=f"sibling branches execute mismatched "
                            f"collective sequences "
                            f"({_fmt_seq(body_seq)} vs "
                            f"{_fmt_seq(else_seq)}) inside a traced "
                            f"body",
                    hint="every rank must run the same collectives in "
                         "the same order; hoist the collective out of "
                         "the branch or make both arms issue the same "
                         "sequence"))
        return out

    def _finding(self, pf: ParsedFile, call: ast.Call, op: str,
                 ctl: ast.stmt, kind: str, branch: str) -> Finding:
        where = {ast.If: "if", ast.While: "while"}.get(type(ctl), "for")
        if kind == "rank":
            message = (f"collective {op!r} is only reachable under a "
                       f"{where!r} predicate tainted by rank identity "
                       f"(line {ctl.lineno}) — ranks taking different "
                       f"branches will deadlock in the collective")
            hint = ("collectives must execute on every rank: compute "
                    "the rank-dependent value as data (jnp.where/mask) "
                    "and keep the collective unconditional")
        else:
            message = (f"collective {op!r} is control-dependent on "
                       f"traced data ({where!r} at line {ctl.lineno}) "
                       f"inside a traced body")
            hint = ("data-dependent Python control flow does not trace "
                    "and diverges across ranks; use lax.cond/jnp.where "
                    "with the collective outside the predicate")
        return Finding(rule=self.rule, severity="error", path=pf.rel,
                       line=call.lineno, col=call.col_offset,
                       message=message, hint=hint)


# --- helpers ----------------------------------------------------------------

def _collective_name(pf: ParsedFile, node: ast.AST) -> Optional[str]:
    if not isinstance(node, ast.Call):
        return None
    resolved = pf.imports.resolve_node(node.func) or ""
    last = resolved.split(".")[-1]
    if last in COLLECTIVES and last != "axis_index" \
            and _is_collective_namespace(resolved):
        return last
    if resolved.endswith(_WRAPPER_COLLECTIVE_SUFFIXES):
        return resolved.split(".")[-1]
    return None


def _rank_source(pf: ParsedFile):
    def source(expr: ast.AST):
        if isinstance(expr, ast.Call):
            resolved = pf.imports.resolve_node(expr.func) or ""
            last = resolved.split(".")[-1]
            prefixes = _RANK_CALLS.get(last)
            if prefixes:
                if resolved.startswith(prefixes) or resolved == last:
                    return frozenset({"rank"})
            if resolved in ("os.getenv", "os.environ.get"):
                return frozenset({"rank"})
        if isinstance(expr, (ast.Attribute, ast.Subscript)):
            d = dotted(expr.value if isinstance(expr, ast.Subscript)
                       else expr)
            r = pf.imports.resolve(d) if d else None
            if r == "os.environ" or (r or "").startswith("os.environ."):
                return frozenset({"rank"})
        return None
    return source


def _param_seed(fn: ast.AST) -> Dict[str, frozenset]:
    seed: Dict[str, frozenset] = {}
    args = fn.args
    for a in (list(getattr(args, "posonlyargs", [])) + list(args.args)
              + list(args.kwonlyargs)):
        seed[a.arg] = frozenset({"data"})
    if args.vararg:
        seed[args.vararg.arg] = frozenset({"data"})
    if args.kwarg:
        seed[args.kwarg.arg] = frozenset({"data"})
    return seed


def _collective_sequence(pf: ParsedFile,
                         stmts: List[ast.stmt]) -> Tuple:
    """(op, axis-repr) tuples in source order for one branch, not
    descending into nested functions."""
    seq: List[Tuple[str, str]] = []
    for stmt in stmts:
        for node in [stmt] + list(own_body_walk(stmt)):
            op = _collective_name(pf, node)
            if op:
                seq.append((op, _axis_repr(node)))
    return tuple(seq)


def _axis_repr(call: ast.Call) -> str:
    for kw in call.keywords:
        if kw.arg in ("axis_name", "axis_names"):
            return ast.dump(kw.value)
    if len(call.args) > 1:
        return ast.dump(call.args[1])
    return ""


def _branch_sequences_match(pf: ParsedFile, node: ast.If) -> bool:
    return (_collective_sequence(pf, node.body)
            == _collective_sequence(pf, node.orelse))


def _fmt_seq(seq: Tuple) -> str:
    return "[" + ", ".join(op for op, _ in seq) + "]"
