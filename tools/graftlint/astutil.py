"""Shared AST analysis for the graftlint checkers.

Everything here is purely syntactic: graftlint never imports the code
it scans (scanning must work without jax installed and must not execute
module side effects like ``arm_from_env()``).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Sequence, Set


def dotted(node: ast.AST) -> Optional[str]:
    """``'jax.lax.psum'`` for a Name/Attribute chain, else ``None``."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ImportMap:
    """Alias -> canonical dotted path, from every import statement in
    the module (function-local imports included — the codebase imports
    jax lazily almost everywhere); ``nodes`` are the module's, in
    ``ast.walk`` order."""

    def __init__(self, nodes: Sequence[ast.AST]):
        self.aliases: Dict[str, str] = {}
        for n in nodes:
            if isinstance(n, ast.Import):
                for a in n.names:
                    if a.asname:
                        self.aliases[a.asname] = a.name
                    else:
                        head = a.name.split(".")[0]
                        self.aliases.setdefault(head, head)
            elif isinstance(n, ast.ImportFrom):
                mod = n.module or ""
                for a in n.names:
                    if a.name == "*":
                        continue
                    full = f"{mod}.{a.name}" if mod else a.name
                    self.aliases[a.asname or a.name] = full

    def resolve(self, name: Optional[str]) -> Optional[str]:
        """Map the first segment through the alias table:
        ``np.sum`` -> ``numpy.sum``, ``lax.psum`` -> ``jax.lax.psum``."""
        if not name:
            return None
        head, _, rest = name.partition(".")
        base = self.aliases.get(head, head)
        return f"{base}.{rest}" if rest else base

    def resolve_node(self, node: ast.AST) -> Optional[str]:
        return self.resolve(dotted(node))


def build_parent_map(nodes: Sequence[ast.AST]) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in nodes:
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


FunctionNode = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def enclosing_functions(parents: Dict[ast.AST, ast.AST],
                        node: ast.AST) -> List[ast.AST]:
    """Innermost-first chain of function nodes containing ``node``."""
    out: List[ast.AST] = []
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, FunctionNode):
            out.append(cur)
        cur = parents.get(cur)
    return out


def walk_skipping(node: ast.AST, skip: Set[ast.AST]) -> Iterator[ast.AST]:
    """``ast.walk`` that does not descend into nodes in ``skip``."""
    for child in ast.iter_child_nodes(node):
        if child in skip:
            continue
        yield child
        yield from walk_skipping(child, skip)


# --- traced-context discovery ---------------------------------------------

def _is_jit_name(resolved: Optional[str]) -> bool:
    return resolved in ("jax.jit", "jax.pmap")


def _is_shard_map_name(resolved: Optional[str]) -> bool:
    return bool(resolved) and resolved.split(".")[-1] == "shard_map"


def _is_partial_name(resolved: Optional[str]) -> bool:
    return resolved == "functools.partial"


def is_tracing_wrapper(resolved: Optional[str]) -> bool:
    return _is_jit_name(resolved) or _is_shard_map_name(resolved)


def defs_by_name(nodes: Sequence[ast.AST]) -> Dict[str, List[ast.AST]]:
    """Function definitions by name, of a file's ``nodes``
    (``ParsedFile.nodes``)."""
    out: Dict[str, List[ast.AST]] = {}
    for n in nodes:
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(n.name, []).append(n)
    return out


def _function_targets(arg: ast.AST, imports: ImportMap,
                      defs: Dict[str, List[ast.AST]],
                      depth: int = 0) -> List[ast.AST]:
    """Function nodes an expression refers to: a lambda, a local def by
    name, or either wrapped in partial/jit/shard_map."""
    if depth > 4:
        return []
    if isinstance(arg, ast.Lambda):
        return [arg]
    if isinstance(arg, ast.Name):
        return defs.get(arg.id, [])
    if isinstance(arg, ast.Call):
        f = imports.resolve_node(arg.func)
        if (_is_partial_name(f) or is_tracing_wrapper(f)) and arg.args:
            return _function_targets(arg.args[0], imports, defs, depth + 1)
    return []


def collect_traced_functions(nodes: Sequence[ast.AST], imports: ImportMap,
                             defs: Dict[str, List[ast.AST]]
                             ) -> Set[ast.AST]:
    """Function nodes whose bodies run under jax tracing: decorated with
    jit/pmap (directly or via ``partial(jax.jit, ...)``), or passed —
    possibly through ``functools.partial`` — to ``jax.jit``/``pmap``/
    ``shard_map``. Purely lexical: dynamically-built callables
    (``jax.jit(make_fn())``) are out of reach and skipped. ``nodes``
    and ``defs`` are the file's (``ParsedFile.nodes``, ``.defs``)."""
    marked: Set[ast.AST] = set()
    for fn in nodes:
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in fn.decorator_list:
                if isinstance(dec, ast.Call):
                    f = imports.resolve_node(dec.func)
                    if is_tracing_wrapper(f):
                        marked.add(fn)
                    elif (_is_partial_name(f) and dec.args
                          and is_tracing_wrapper(
                              imports.resolve_node(dec.args[0]))):
                        marked.add(fn)
                elif is_tracing_wrapper(imports.resolve_node(dec)):
                    marked.add(fn)
    for call in nodes:
        if isinstance(call, ast.Call):
            f = imports.resolve_node(call.func)
            if is_tracing_wrapper(f) and call.args:
                marked.update(
                    _function_targets(call.args[0], imports, defs))
    return marked


def collect_jitted_names(nodes: Sequence[ast.AST], imports: ImportMap,
                         traced: Set[ast.AST]) -> Set[str]:
    """Names bound to jitted callables: ``step = jax.jit(f)`` targets
    plus functions decorated with jit/pmap (of ``traced``, the file's
    ``collect_traced_functions``)."""
    names: Set[str] = set()
    for node in nodes:
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Call):
            resolved = imports.resolve_node(node.value.func) or ""
            if resolved in ("jax.jit", "jax.pmap"):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
    for fn in traced:
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in fn.decorator_list:
                r = (imports.resolve_node(
                        dec.func if isinstance(dec, ast.Call) else dec)
                     or "")
                if r in ("jax.jit", "jax.pmap"):
                    names.add(fn.name)
    return names


# --- host-callback escape hatches -----------------------------------------

_CALLBACK_LAST_SEGMENTS = ("pure_callback", "io_callback",
                           "emit_python_callback")


def is_callback_primitive(resolved: Optional[str]) -> bool:
    """The sanctioned host-callback primitives (the allowlist through
    which native/bindings.py kernels legally enter traced code)."""
    if not resolved:
        return False
    last = resolved.split(".")[-1]
    if last in _CALLBACK_LAST_SEGMENTS:
        return True
    return resolved in ("jax.debug.callback", "jax.debug.print",
                        "debug.callback", "debug.print")


def collect_callback_functions(nodes: Sequence[ast.AST],
                               imports: ImportMap,
                               defs: Dict[str, List[ast.AST]]
                               ) -> Set[ast.AST]:
    """Function nodes passed to a callback primitive: their bodies are
    host code by design, exempt from tracer-hygiene checks. ``nodes``
    and ``defs`` are the file's (``ParsedFile.nodes``, ``.defs``)."""
    out: Set[ast.AST] = set()
    for call in nodes:
        if not isinstance(call, ast.Call):
            continue
        if not is_callback_primitive(imports.resolve_node(call.func)):
            continue
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            out.update(_function_targets(arg, imports, defs))
    return out


# --- constant/string resolution -------------------------------------------

def param_default(fn: ast.AST, name: str) -> Optional[ast.AST]:
    """Default-value expression for parameter ``name``, if any."""
    if isinstance(fn, ast.Lambda):
        args = fn.args
    else:
        args = fn.args
    pos = list(getattr(args, "posonlyargs", [])) + list(args.args)
    defaults = list(args.defaults)
    # defaults align with the tail of the positional params
    offset = len(pos) - len(defaults)
    for i, a in enumerate(pos):
        if a.arg == name:
            if i >= offset:
                return defaults[i - offset]
            return None
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if a.arg == name and d is not None:
            return d
    return None


def has_param(fn: ast.AST, name: str) -> bool:
    args = fn.args
    pos = list(getattr(args, "posonlyargs", [])) + list(args.args)
    names = [a.arg for a in pos + list(args.kwonlyargs)]
    if args.vararg:
        names.append(args.vararg.arg)
    if args.kwarg:
        names.append(args.kwarg.arg)
    return name in names


def module_str_constants(tree: ast.AST) -> Dict[str, str]:
    """Module-level ``NAME = "literal"`` assignments."""
    out: Dict[str, str] = {}
    for stmt in tree.body:
        if (isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and isinstance(stmt.value, ast.Constant)
                and isinstance(stmt.value.value, str)):
            out[stmt.targets[0].id] = stmt.value.value
    return out


def string_literals_in(node: ast.AST) -> List[ast.Constant]:
    return [n for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)]
