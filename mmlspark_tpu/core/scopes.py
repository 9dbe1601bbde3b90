"""The optimized HLO of the programs this process dispatched, for
whoever joins a device trace to the program's ``jax.named_scope``s.

A trace's op events name instructions of the optimized HLO
(``fusion.17``); the scope path stands in the ``op_name`` of that
instruction's metadata, in a text only the party that compiled the
program can ask for. So the program remembers what it dispatched
(:func:`register`: the jitted function and its arguments' shapes, no
array, nothing lowered) and, only when asked (:func:`hlo_texts`),
lowers and compiles the same function over the same shapes again (in
the process that ran it JAX's own caches hand back the executable that
ran; in another the persistent compile cache does) and hands out its
text. What a scope is and which one an instruction belongs to is the
reader's to say (``benchmark/scope_time.py``). Nothing switches this:
with no call of :func:`hlo_texts` a registration costs microseconds and
that is all.

Temporary: it goes, with its three ``register`` calls, once the
benchmark's trace reader keeps each op's own ``tf_op``
(``ROADMAP.md`` S0).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List

# the entries a process keeps: a stage has a handful of programs (its
# row and length rungs, with and without logits), a fit one
_ENTRY_LIMIT = 64

_ENTRIES: Dict[Any, tuple] = {}     # key -> (module name, jitted, shapes)
_LOCK = threading.Lock()


def _signature(x):
    """What of an argument's leaf decides the compiled program."""
    if not hasattr(x, "shape"):
        return type(x)              # a Python scalar: traced, weakly typed
    return (x.shape, x.dtype, getattr(x, "weak_type", False),
            x.sharding if getattr(x, "committed", False) else None)


def _shape(x):
    import jax

    if not hasattr(x, "shape"):
        return x
    shape, dtype, weak, sharding = _signature(x)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding,
                                weak_type=weak)


def register(jitted, *args) -> None:
    """Remember that ``jitted(*args)`` is about to be dispatched, under
    the name the profiler's ``XLA Modules`` line shows for it
    (``jit_<function name>``). One entry a jitted function and
    arguments' shapes, dtypes and shardings, the longest unused dropped
    beyond ``_ENTRY_LIMIT``. Lowers nothing, touches no device and
    keeps no array, so the arguments may be donated afterwards."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    key = (id(jitted), treedef, tuple(map(_signature, leaves)))
    with _LOCK:                     # stages score from several threads
        entry = _ENTRIES.pop(key, None)
        if entry is None:
            if len(_ENTRIES) >= _ENTRY_LIMIT:
                del _ENTRIES[next(iter(_ENTRIES))]
            entry = ("jit_" + jitted.__name__, jitted,
                     jax.tree_util.tree_unflatten(
                         treedef, [_shape(x) for x in leaves]))
        _ENTRIES[key] = entry       # the newest last


def hlo_texts() -> Dict[str, List[str]]:
    """``{module name: [optimized HLO text, ...]}`` of every registered
    program, one text an entry, so two shapes of one program are two
    texts under one name. Each call lowers and compiles every entry
    again and keeps nothing."""
    with _LOCK:
        entries = list(_ENTRIES.values())
    out: Dict[str, List[str]] = {}
    for name, jitted, shapes in entries:
        out.setdefault(name, []).append(
            jitted.lower(*shapes).compile().as_text())
    return out
