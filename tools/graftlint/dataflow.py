"""Intraprocedural CFG + dataflow for the GL006–GL008 checkers.

A function body is lowered to a statement-level control-flow graph
(If/While/For/Try/With/Break/Continue/Return aware) and a forward
may-analysis propagates an environment mapping variable names to a
join-semilattice value: a frozenset of opaque *tokens*. Two
instantiations are used by the checkers:

* **taint** — tokens are labels ("rank", "data", "row", "f64"); the
  transfer function derives an assignment's tokens from its RHS via a
  pluggable expression evaluator (`ExprTokens`), and
* **reaching definitions** — each assignment contributes ``id()`` of
  its RHS expression, so a use site can recover the set of defining
  expressions (GL007's flat-index products).

Like the rest of graftlint this is purely syntactic and deliberately
conservative: names with no visible definition stay bottom (empty
token set), ``global``/``nonlocal`` rebinding and attribute/subscript
stores are ignored, and nested function bodies are opaque — their
*names* are defined (untainted) and their bodies are analyzed when the
checker visits the nested function itself.
"""

from __future__ import annotations

import ast
from collections import deque
from typing import (Callable, Dict, FrozenSet, Iterable, List, Optional,
                    Tuple)

Tokens = FrozenSet[object]
Env = Dict[str, Tokens]
EMPTY: Tokens = frozenset()

_LOOP = (ast.While, ast.For, ast.AsyncFor)
_FUNC = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


class CFG:
    """Statement-level control-flow graph of one function body. Program
    points are the statement AST nodes themselves; a compound
    statement's point models the evaluation of its header (an ``if``'s
    test, a ``for``'s iterable + target binding)."""

    def __init__(self, body: List[ast.stmt]):
        self.points: List[ast.stmt] = []
        self.succ: Dict[int, List[int]] = {}
        self.entry: List[int] = []
        first = self._seq(body, frontier=None, loops=[])
        del first  # fall-off-the-end exits need no modelling
        self.by_id: Dict[int, ast.stmt] = {id(p): p for p in self.points}
        self.preds: Dict[int, List[int]] = {id(p): [] for p in self.points}
        for src, dsts in self.succ.items():
            for d in dsts:
                self.preds[d].append(src)
        # the walrus targets a point's header binds, in evaluation order
        self.walrus: Dict[int, List[ast.NamedExpr]] = {
            id(p): [n for e in _header_exprs(p) for n in ast.walk(e)
                    if isinstance(n, ast.NamedExpr)
                    and isinstance(n.target, ast.Name)]
            for p in self.points}

    # frontier: list of point ids with an edge to the next statement;
    # None means "function entry" (recorded in self.entry instead)
    def _add(self, node: ast.stmt) -> int:
        nid = id(node)
        if nid not in self.succ:
            self.succ[nid] = []
            self.points.append(node)
        return nid

    def _link(self, frontier: Optional[List[int]],
              node: ast.stmt) -> int:
        nid = self._add(node)
        if frontier is None:
            self.entry.append(nid)
        else:
            for f in frontier:
                if nid not in self.succ[f]:
                    self.succ[f].append(nid)
        return nid

    def _seq(self, stmts: List[ast.stmt],
             frontier: Optional[List[int]],
             loops: List[Dict[str, List[int]]]) -> Optional[List[int]]:
        for stmt in stmts:
            if isinstance(stmt, ast.If):
                nid = self._link(frontier, stmt)
                body_out = self._seq(stmt.body, [nid], loops)
                if stmt.orelse:
                    else_out = self._seq(stmt.orelse, [nid], loops)
                else:
                    else_out = [nid]
                frontier = _join_frontiers(body_out, else_out)
            elif isinstance(stmt, _LOOP):
                nid = self._link(frontier, stmt)
                ctl = {"breaks": [], "continues": []}
                body_out = self._seq(stmt.body, [nid], loops + [ctl])
                for f in (body_out or []) + ctl["continues"]:
                    if nid not in self.succ[f]:
                        self.succ[f].append(nid)
                exit_frontier: List[int] = [nid]
                if stmt.orelse:
                    exit_frontier = self._seq(stmt.orelse, [nid],
                                              loops) or []
                frontier = exit_frontier + ctl["breaks"]
            elif isinstance(stmt, ast.Try):
                body_in = frontier
                body_out = self._seq(stmt.body, frontier, loops)
                handler_outs: List[int] = []
                for h in stmt.handlers:
                    # the exception may fire anywhere in the body:
                    # approximate handler entry from both the try entry
                    # and the body exit
                    h_in = _join_frontiers(body_in, body_out)
                    h_out = self._seq(h.body, h_in, loops)
                    handler_outs.extend(h_out or [])
                if stmt.orelse:
                    body_out = self._seq(stmt.orelse, body_out, loops)
                frontier = _join_frontiers(body_out, handler_outs)
                if stmt.finalbody:
                    frontier = self._seq(stmt.finalbody, frontier, loops)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                nid = self._link(frontier, stmt)
                frontier = self._seq(stmt.body, [nid], loops)
            elif isinstance(stmt, ast.Break):
                nid = self._link(frontier, stmt)
                if loops:
                    loops[-1]["breaks"].append(nid)
                frontier = []
            elif isinstance(stmt, ast.Continue):
                nid = self._link(frontier, stmt)
                if loops:
                    loops[-1]["continues"].append(nid)
                frontier = []
            elif isinstance(stmt, (ast.Return, ast.Raise)):
                self._link(frontier, stmt)
                frontier = []
            else:
                nid = self._link(frontier, stmt)
                frontier = [nid]
        return frontier


def _join_frontiers(*fronts: Optional[Iterable[int]]) -> List[int]:
    out: List[int] = []
    for f in fronts:
        for nid in (f or []):
            if nid not in out:
                out.append(nid)
    return out


# --- expression token evaluation -------------------------------------------

# attribute reads that are trace-static metadata even on a tracer:
# branching on x.shape[0] is legal (resolved at trace time), so taint
# must not flow through them
STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "weak_type",
                "sharding"}

# calls whose results are trace-static regardless of argument taint
_STATIC_CALLS = {"len", "isinstance", "issubclass", "getattr", "hasattr",
                 "callable", "type", "id", "repr", "str"}


class ExprTokens:
    """Token evaluator for expressions: the union of the tokens of free
    names (looked up in the environment) plus whatever a pluggable
    ``source`` callback contributes.

    ``source(expr)`` may return a frozenset (authoritative: those are
    the expression's tokens, recursion stops — an empty frozenset is an
    explicit *kill*, e.g. an ``astype(float32)`` cast) or ``None`` (no
    opinion, recurse into children).
    """

    def __init__(self,
                 source: Optional[Callable[[ast.AST],
                                           Optional[Tokens]]] = None,
                 kill_static_attrs: bool = True):
        self.source = source
        self.kill_static_attrs = kill_static_attrs

    def __call__(self, node: Optional[ast.AST], env: Env) -> Tokens:
        if node is None:
            return EMPTY
        if isinstance(node, ast.expr):
            if self.source is not None:
                s = self.source(node)
                if s is not None:
                    return frozenset(s)
            if isinstance(node, ast.Name):
                return env.get(node.id, EMPTY)
            if (isinstance(node, ast.Attribute)
                    and self.kill_static_attrs
                    and node.attr in STATIC_ATTRS):
                return EMPTY
            if isinstance(node, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot))
                    for op in node.ops):
                # `x is None` is resolved at trace time, never a
                # data-dependent predicate
                return EMPTY
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in _STATIC_CALLS):
                return EMPTY
            if isinstance(node, ast.Lambda):
                return EMPTY
        out: Tokens = EMPTY
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FUNC):
                continue
            out |= self(child, env)
        return out


def cfg_of(fn: ast.AST) -> CFG:
    """``fn``'s CFG, built once a lint run and kept on the node, so that
    every analysis of ``fn`` (each rule's, each taint kind's) shares
    it."""
    cfg = getattr(fn, "_graftlint_cfg", None)
    if cfg is None:
        cfg = CFG([] if isinstance(fn, ast.Lambda) else list(fn.body))
        fn._graftlint_cfg = cfg
    return cfg


# --- the forward may-analysis ----------------------------------------------

class Analysis:
    """Forward dataflow over one function's CFG.

    ``eval_expr(expr, env) -> Tokens`` computes the tokens an RHS
    contributes to its target(s); ``seed`` is the environment on entry
    (typically parameter taints). After ``run()``, ``env_at(stmt)``
    gives the environment *before* the statement executes — for an
    ``if``, the environment its test is evaluated in.
    """

    def __init__(self, fn: ast.AST,
                 eval_expr: Callable[[Optional[ast.AST], Env], Tokens],
                 seed: Optional[Env] = None):
        self.cfg = cfg_of(fn)
        self.eval_expr = eval_expr
        self.seed: Env = dict(seed or {})
        self._in: Dict[int, Env] = {}
        self._out: Dict[int, Env] = {}
        self.run()

    def run(self) -> None:
        order = [id(p) for p in self.cfg.points]
        work = deque(order)
        in_work = set(order)
        entry = set(self.cfg.entry)
        while work:
            nid = work.popleft()
            in_work.discard(nid)
            env: Env = dict(self.seed) if nid in entry else {}
            for p in self.cfg.preds[nid]:
                for k, v in self._out.get(p, {}).items():
                    env[k] = env.get(k, EMPTY) | v
            self._in[nid] = env
            out = self._transfer(self.cfg.by_id[nid], env)
            if out != self._out.get(nid):
                self._out[nid] = out
                for s in self.cfg.succ[nid]:
                    if s not in in_work:
                        in_work.add(s)
                        work.append(s)

    def env_at(self, stmt: ast.stmt) -> Env:
        return self._in.get(id(stmt), dict(self.seed))

    # -- transfer ----------------------------------------------------------

    def _transfer(self, stmt: ast.stmt, env: Env) -> Env:
        env = dict(env)
        for n in self.cfg.walrus[id(stmt)]:
            env[n.target.id] = self.eval_expr(n.value, env)
        if isinstance(stmt, ast.Assign):
            toks = self.eval_expr(stmt.value, env)
            for t in stmt.targets:
                _bind_target(t, toks, env)
        elif isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                _bind_target(stmt.target,
                             self.eval_expr(stmt.value, env), env)
        elif isinstance(stmt, ast.AugAssign):
            if isinstance(stmt.target, ast.Name):
                old = env.get(stmt.target.id, EMPTY)
                env[stmt.target.id] = old | self.eval_expr(stmt.value,
                                                           env)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            _bind_target(stmt.target,
                         self.eval_expr(stmt.iter, env), env)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                if item.optional_vars is not None:
                    _bind_target(item.optional_vars,
                                 self.eval_expr(item.context_expr, env),
                                 env)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            env[stmt.name] = EMPTY
        elif isinstance(stmt, (ast.Import, ast.ImportFrom)):
            for a in stmt.names:
                env[(a.asname or a.name).split(".")[0]] = EMPTY
        elif isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    env.pop(t.id, None)
        return env

def _header_exprs(stmt: ast.stmt) -> List[ast.expr]:
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [i.context_expr for i in stmt.items]
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.ClassDef)):
        return []
    return [c for c in ast.iter_child_nodes(stmt)
            if isinstance(c, ast.expr)]


def _bind_target(target: ast.AST, toks: Tokens, env: Env) -> None:
    if isinstance(target, ast.Name):
        env[target.id] = toks
    elif isinstance(target, ast.Starred):
        _bind_target(target.value, toks, env)
    elif isinstance(target, (ast.Tuple, ast.List)):
        for el in target.elts:
            _bind_target(el, toks, env)
    # attribute/subscript stores are out of scope (conservative)


# --- shared helpers for the GL006-008 checkers -----------------------------

def own_body_walk(fn: ast.AST) -> Iterable[ast.AST]:
    """All nodes of ``fn``'s body, not descending into nested
    function/lambda bodies (which get their own analysis run)."""
    def rec(node: ast.AST) -> Iterable[ast.AST]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FUNC):
                yield child  # the def itself, not its body
                continue
            yield child
            yield from rec(child)
    return rec(fn)


def control_context(parents: Dict[ast.AST, ast.AST], node: ast.AST,
                    fn: ast.AST) -> List[Tuple[ast.stmt, str]]:
    """Innermost-first (control statement, branch) pairs enclosing
    ``node`` within ``fn``; branch is "body" or "orelse"."""
    out: List[Tuple[ast.stmt, str]] = []
    cur: ast.AST = node
    while cur is not fn:
        parent = parents.get(cur)
        if parent is None:
            break
        if isinstance(parent, (ast.If, ast.While, ast.For,
                               ast.AsyncFor)):
            branch = ("orelse" if cur in getattr(parent, "orelse", [])
                      else "body")
            if cur in parent.body or cur in getattr(parent, "orelse",
                                                    []):
                out.append((parent, branch))
        cur = parent
    return out


def functions_in_traced_context(tree: ast.AST, traced) -> set:
    """id()s of function nodes that run under tracing: the traced roots
    plus every function lexically nested inside one."""
    ids = set()
    for root in traced:
        for n in ast.walk(root):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                ids.add(id(n))
        ids.add(id(root))
    return ids
