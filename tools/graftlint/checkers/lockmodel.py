"""Shared per-class lock model for the graftlock rules (GL009–GL012).

The four concurrency checkers all need the same facts about a class:
which attributes are locks (and what kind), which methods spawn
threads (and what the threads are named), and — for the order/blocking
rules — a traversal of each method that tracks the held-lock stack
through ``with self._lock:`` / ``.acquire()`` nesting while following
same-class helper calls (GL008's depth-3 discipline, but over methods
instead of module functions). This module computes those once per
file; the checkers filter the events.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.graftlint.astutil import dotted

# canonical constructor -> lock kind. san_lock is matched by suffix so
# both `san_lock(...)` and `sanitizer.san_lock(...)` resolve.
_LOCK_CTORS = {
    "threading.Lock": "lock",
    "threading.RLock": "rlock",
    "threading.Condition": "condition",
    "threading.Semaphore": "semaphore",
    "threading.BoundedSemaphore": "semaphore",
}
_SAN_LOCK_SUFFIX = "san_lock"

# thread-safe container/signal constructors: attributes holding these
# are synchronization objects themselves, not shared state GL010
# should police
_THREADSAFE_CTORS = {
    "queue.Queue": "queue",
    "queue.SimpleQueue": "queue",
    "queue.LifoQueue": "queue",
    "queue.PriorityQueue": "queue",
    "threading.Event": "event",
    "threading.Thread": "thread",
    "threading.Barrier": "barrier",
    "threading.local": "tls",
}

# the repo-wide daemon-thread naming convention GL010's thread
# discovery keys off (unified in this PR: the old graft-watchdog-*
# spelling was the straggler)
THREAD_NAME_PREFIX = "mmlspark-"

_MAX_DEPTH = 3


@dataclass
class LockAttr:
    name: str           # attribute (or module global) name
    kind: str           # lock | rlock | condition | semaphore
    line: int
    san_name: str = ""  # the san_lock() name argument, if any


@dataclass
class ThreadSpawn:
    node: ast.Call
    method: str                      # method that constructs the Thread
    name_prefix: Optional[str]       # leading literal of name=, or None
    has_name: bool = False


@dataclass
class ClassModel:
    node: ast.ClassDef
    methods: Dict[str, ast.FunctionDef] = field(default_factory=dict)
    locks: Dict[str, LockAttr] = field(default_factory=dict)
    safe_attrs: Dict[str, str] = field(default_factory=dict)
    spawns: List[ThreadSpawn] = field(default_factory=list)

    def spawns_threads(self) -> bool:
        return bool(self.spawns)


def _resolve_ctor(call: ast.Call, imports) -> Optional[str]:
    """Canonical dotted name of a call's callee, via the import map."""
    name = imports.resolve_node(call.func)
    if name:
        return name
    return dotted(call.func)


def lock_kind_of_call(call: ast.Call, imports) -> Optional[str]:
    """``"lock"``/``"rlock"``/``"condition"``/``"semaphore"`` when the
    call constructs a lock (threading.* or san_lock), else None."""
    name = _resolve_ctor(call, imports)
    if not name:
        return None
    kind = _LOCK_CTORS.get(name)
    if kind:
        return kind
    if name == _SAN_LOCK_SUFFIX or name.endswith("." + _SAN_LOCK_SUFFIX):
        for kw in call.keywords:
            if kw.arg == "kind" and isinstance(kw.value, ast.Constant):
                return str(kw.value.value)
        if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
            return str(call.args[1].value)
        return "lock"
    return None


def _san_lock_name(call: ast.Call) -> str:
    if call.args and isinstance(call.args[0], ast.Constant):
        return str(call.args[0].value)
    return ""


def _threadsafe_kind(call: ast.Call, imports) -> Optional[str]:
    name = _resolve_ctor(call, imports)
    return _THREADSAFE_CTORS.get(name) if name else None


def _self_attr(node: ast.AST) -> Optional[str]:
    """``self.X`` -> ``"X"``; anything else -> None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _thread_name_prefix(call: ast.Call) -> Tuple[bool, Optional[str]]:
    """(has name kwarg, leading literal text of the name or None)."""
    for kw in call.keywords:
        if kw.arg != "name":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            return True, v.value
        if isinstance(v, ast.JoinedStr) and v.values:
            first = v.values[0]
            if (isinstance(first, ast.Constant)
                    and isinstance(first.value, str)):
                return True, first.value
            return True, ""
        return True, None    # dynamic name: can't prove the prefix
    return False, None


def build_class_models(pf, imports=None) -> List[ClassModel]:
    """One :class:`ClassModel` per top-level class in the file."""
    imports = imports if imports is not None else pf.imports
    out: List[ClassModel] = []
    for node in pf.nodes:
        if not isinstance(node, ast.ClassDef):
            continue
        model = ClassModel(node=node)
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                model.methods[item.name] = item
        for meth in model.methods.values():
            for sub in ast.walk(meth):
                if isinstance(sub, ast.Assign):
                    value = sub.value
                    if not isinstance(value, ast.Call):
                        continue
                    kind = lock_kind_of_call(value, imports)
                    safe = (None if kind else
                            _threadsafe_kind(value, imports))
                    for tgt in sub.targets:
                        attr = _self_attr(tgt)
                        if attr is None:
                            continue
                        if kind:
                            model.locks.setdefault(attr, LockAttr(
                                name=attr, kind=kind, line=sub.lineno,
                                san_name=_san_lock_name(value)))
                        elif safe:
                            model.safe_attrs.setdefault(attr, safe)
                elif isinstance(sub, ast.Call):
                    name = _resolve_ctor(sub, imports)
                    if name == "threading.Thread":
                        has_name, prefix = _thread_name_prefix(sub)
                        model.spawns.append(ThreadSpawn(
                            node=sub, method=meth.name,
                            name_prefix=prefix, has_name=has_name))
        out.append(model)
    return out


@dataclass
class FileLockModel:
    """Per-file bundle of everything the graftlock rules share. Built
    once and cached on the ParsedFile — four checkers read it."""
    classes: List[ClassModel]
    mod_locks: Dict[str, LockAttr]
    mod_functions: Dict[str, ast.FunctionDef]


def file_lock_model(pf) -> FileLockModel:
    """Memoized accessor: the four GL009–GL012 checkers all need the
    same class models / module locks / module function index, so it is
    computed once per file and stashed on the ParsedFile."""
    cached = getattr(pf, "_graftlock_model", None)
    if cached is None:
        cached = FileLockModel(classes=build_class_models(pf),
                               mod_locks=module_locks(pf),
                               mod_functions=module_functions(pf))
        pf._graftlock_model = cached
    return cached


def module_locks(pf, imports=None) -> Dict[str, LockAttr]:
    """Module-global ``_lock = threading.Lock()`` style assignments."""
    imports = imports if imports is not None else pf.imports
    out: Dict[str, LockAttr] = {}
    for stmt in pf.tree.body:
        if not (isinstance(stmt, ast.Assign)
                and isinstance(stmt.value, ast.Call)):
            continue
        kind = lock_kind_of_call(stmt.value, imports)
        if not kind:
            continue
        for tgt in stmt.targets:
            if isinstance(tgt, ast.Name):
                out[tgt.id] = LockAttr(
                    name=tgt.id, kind=kind, line=stmt.lineno,
                    san_name=_san_lock_name(stmt.value))
    return out


# --- held-lock traversal ----------------------------------------------------

@dataclass
class Acquisition:
    """One lock acquisition observed with a non-empty held stack."""
    held: Tuple[str, ...]            # lock names held, outermost first
    held_nodes: Tuple[ast.AST, ...]
    lock: str
    node: ast.AST                    # the acquiring with/acquire node
    chain: Tuple[str, ...]           # method call chain from the root


@dataclass
class HeldCall:
    """One call expression evaluated while locks are held."""
    held: Tuple[str, ...]
    held_nodes: Tuple[ast.AST, ...]
    node: ast.Call
    chain: Tuple[str, ...]


class LockTraversal:
    """Walks a function tracking the held-lock stack through ``with``
    blocks and ``.acquire()``/``.release()`` pairs, following
    same-class ``self.helper()`` calls (and bare-name module helpers)
    to depth ≤3. Produces :class:`Acquisition` and :class:`HeldCall`
    event lists for GL009/GL012 to filter."""

    def __init__(self, model: Optional[ClassModel],
                 mod_locks: Dict[str, LockAttr],
                 mod_functions: Dict[str, ast.FunctionDef]):
        self.model = model
        self.mod_locks = mod_locks
        self.mod_functions = mod_functions
        self.acquisitions: List[Acquisition] = []
        self.calls: List[HeldCall] = []

    # -- lock-expression recognition --

    def _lock_name(self, expr: ast.AST) -> Optional[str]:
        attr = _self_attr(expr)
        if attr is not None:
            if self.model and attr in self.model.locks:
                return attr
            return None
        if isinstance(expr, ast.Name) and expr.id in self.mod_locks:
            return expr.id
        return None

    def _lock_kind(self, name: str) -> str:
        if self.model and name in self.model.locks:
            return self.model.locks[name].kind
        return self.mod_locks[name].kind

    # -- traversal --

    def run(self, fn: ast.FunctionDef, chain: Tuple[str, ...] = ()
            ) -> None:
        self._visit_body(fn.body, held=[], chain=chain + (fn.name,),
                         depth=0, seen={fn.name})

    def _record_acquire(self, name: str, node: ast.AST,
                        held: List[Tuple[str, ast.AST]],
                        chain: Tuple[str, ...]) -> None:
        if held:
            self.acquisitions.append(Acquisition(
                held=tuple(h for h, _n in held),
                held_nodes=tuple(n for _h, n in held),
                lock=name, node=node, chain=chain))

    def _visit_body(self, body: Sequence[ast.stmt],
                    held: List[Tuple[str, ast.AST]],
                    chain: Tuple[str, ...], depth: int,
                    seen: Set[str]) -> None:
        for stmt in body:
            self._visit_stmt(stmt, held, chain, depth, seen)

    def _visit_stmt(self, stmt: ast.stmt,
                    held: List[Tuple[str, ast.AST]],
                    chain: Tuple[str, ...], depth: int,
                    seen: Set[str]) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return    # nested defs run later, under their own stack
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            pushed = 0
            for item in stmt.items:
                name = self._lock_name(item.context_expr)
                if name is not None:
                    self._record_acquire(name, stmt, held, chain)
                    held.append((name, stmt))
                    pushed += 1
                else:
                    self._scan_exprs(item.context_expr, held, chain,
                                     depth, seen)
            self._visit_body(stmt.body, held, chain, depth, seen)
            for _ in range(pushed):
                held.pop()
            return
        # acquire()/release() calls change the held stack in sequence
        call = self._bare_call(stmt)
        if call is not None and isinstance(call.func, ast.Attribute):
            name = self._lock_name(call.func.value)
            if name is not None and call.func.attr == "acquire":
                self._record_acquire(name, stmt, held, chain)
                held.append((name, stmt))
                return
            if name is not None and call.func.attr == "release":
                for i in range(len(held) - 1, -1, -1):
                    if held[i][0] == name:
                        del held[i]
                        break
                return
        # compound statements: visit sub-bodies with the same stack
        for fname in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, fname, None)
            if sub:
                self._visit_body(sub, held, chain, depth, seen)
        for handler in getattr(stmt, "handlers", []) or []:
            self._visit_body(handler.body, held, chain, depth, seen)
        # expressions hanging off this statement (tests, values, args)
        for expr in self._stmt_exprs(stmt):
            self._scan_exprs(expr, held, chain, depth, seen)

    @staticmethod
    def _bare_call(stmt: ast.stmt) -> Optional[ast.Call]:
        if (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Call)):
            return stmt.value
        return None

    @staticmethod
    def _stmt_exprs(stmt: ast.stmt) -> List[ast.expr]:
        out: List[ast.expr] = []
        for fname, value in ast.iter_fields(stmt):
            if fname in ("body", "orelse", "finalbody", "handlers"):
                continue
            if isinstance(value, ast.expr):
                out.append(value)
            elif isinstance(value, list):
                out.extend(v for v in value
                           if isinstance(v, ast.expr))
        return out

    def _scan_exprs(self, expr: ast.AST,
                    held: List[Tuple[str, ast.AST]],
                    chain: Tuple[str, ...], depth: int,
                    seen: Set[str]) -> None:
        for sub in ast.walk(expr):
            if not isinstance(sub, ast.Call):
                continue
            if held:
                self.calls.append(HeldCall(
                    held=tuple(h for h, _n in held),
                    held_nodes=tuple(n for _h, n in held),
                    node=sub, chain=chain))
            self._follow(sub, held, chain, depth, seen)

    def _follow(self, call: ast.Call,
                held: List[Tuple[str, ast.AST]],
                chain: Tuple[str, ...], depth: int,
                seen: Set[str]) -> None:
        """Descend into same-class / same-module helpers while holding
        locks, so nested acquisitions inside helpers contribute edges
        and blocking calls."""
        if not held or depth >= _MAX_DEPTH:
            return
        target: Optional[ast.FunctionDef] = None
        label = ""
        attr = _self_attr(call.func) if isinstance(
            call.func, ast.Attribute) else None
        if attr is not None and self.model is not None:
            target = self.model.methods.get(attr)
            label = attr
        elif isinstance(call.func, ast.Name):
            target = self.mod_functions.get(call.func.id)
            label = call.func.id
        if target is None or label in seen:
            return
        self._visit_body(target.body, held, chain + (label,),
                         depth + 1, seen | {label})


def module_functions(pf) -> Dict[str, ast.FunctionDef]:
    return {stmt.name: stmt for stmt in pf.tree.body
            if isinstance(stmt, ast.FunctionDef)}


def enclosing_function(parents: Dict[ast.AST, ast.AST],
                       node: ast.AST) -> Optional[ast.AST]:
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur
        cur = parents.get(cur)
    return None


def with_locks_held_at(pf, node: ast.AST, model: Optional[ClassModel],
                       mod_locks: Dict[str, LockAttr]) -> List[str]:
    """Lock names held at ``node`` per enclosing ``with`` statements
    (same function only) — the scope notion GL010/GL011 use."""
    held: List[str] = []
    cur = pf.parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            break
        if isinstance(cur, (ast.With, ast.AsyncWith)):
            for item in cur.items:
                attr = _self_attr(item.context_expr)
                if (attr is not None and model is not None
                        and attr in model.locks):
                    held.append(attr)
                elif (isinstance(item.context_expr, ast.Name)
                        and item.context_expr.id in mod_locks):
                    held.append(item.context_expr.id)
        cur = pf.parents.get(cur)
    return held
