"""Device seconds of a traced program by the program's own scopes.

The trace's op events name instructions of the optimized HLO
(``%fusion.17 = ...``); the ``jax.named_scope`` path an instruction was
written under stands in its ``op_name``, in the optimized HLO's text,
which the program hands out for what it dispatched
(``mmlspark_tpu.core.scopes.hlo_texts``: ``{module name: [text, ...]}``).
This module reads ``{instruction name: scope or None}`` off each text
(:func:`scope_table`) and joins it to device 0's trace: the ops that
lie inside the ``XLA Modules`` events whose name matches, each op's
*exclusive* time (a nested op, a fusion inside a ``while``, takes its
interval from its parent: a partition of the module's busy time, not a
ranking), each op looked up in the table of its own module (two
programs both have a ``fusion.17``; where a name has several texts,
the rungs of a ladder, a module takes the one that holds most of its
ops' time).

A scope is a component of an ``op_name`` of the form
``family.part[.part]``, named in full where scopes nest
(``lm.moe.experts``); the families are the first components of the
``scopes`` that the layer files of ``readers/scope_time.py`` ask for.

A plain module, imported once a process, so what it computed for one
reader is there for the next (``run.py`` executes a reader's file anew
for every metric). ``None`` wherever there is nothing to join: no
device trace, a program that hands out no texts (a parent commit), or
no registered program of that name.
"""

import bisect
import glob
import json
import os
import re
import time

from benchmark.lookup import HERE
from benchmark.trace_reduce import short_name

# the trace's whole nanoseconds come as float seconds: a module's last
# op may end a rounding past the module, an op a rounding past the
# start of the next
EDGE = 1e-9
UNSCOPED = "(no scope)"
UNLISTED = "(not in the table)"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")

_memo = {}      # id(trace) -> (trace, {match: result}, tables, seconds)


def families():
    """The first components of the scopes that the layer files of
    ``readers/scope_time.py`` ask for: ``("gbdt", "lm")``."""
    found = set()
    for path in glob.glob(os.path.join(HERE, "layers", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("reader") == "scope_time":
            params = spec.get("params", {})
            found.update(scope.partition(".")[0] for scope in
                         params.get("scopes", []) + params.get("exclude", []))
    return tuple(sorted(found))


def scope_pattern(names):
    """What finds the scopes of these families in an ``op_name``."""
    return re.compile(r"(?<![\w.])(?:%s)(?:\.[a-z_]+)+"
                      % "|".join(map(re.escape, names)))


def scope_of(op_name, pattern):
    """The innermost scope in an ``op_name``, or ``None``."""
    found = pattern.findall(op_name)
    return found[-1] if found else None


def shared_scope(scopes):
    """The innermost scope that holds every one of ``scopes``
    (``lm.moe`` for ``lm.moe.route`` and ``lm.moe.dispatch``), or
    ``None`` where they share a family at most."""
    parts = [scope.split(".") for scope in scopes]
    shared = []
    for column in zip(*parts):
        if len(set(column)) > 1:
            break
        shared.append(column[0])
    return ".".join(shared) if len(shared) > 1 else None


def scope_table(hlo_text, pattern):
    """``{instruction name: scope or None}`` over every instruction of
    an optimized HLO module's text. An instruction's scope is its
    ``op_name``'s innermost; one with none that calls computations (a
    ``fusion``, ``while``, ``call`` or ``conditional`` XLA made itself)
    takes the scope that the scoped instructions of those computations
    agree on (:func:`shared_scope`), looked for through their own such
    instructions; where they disagree (the ``while`` of a scan over
    whole layers) or there is none, ``None``."""
    own, calls, members = {}, {}, {}
    current = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            head = _COMPUTATION.match(line)
            if head:
                current = head.group(1)
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name = found.group(1)
        op_name = _OP_NAME.search(line)
        own[name] = scope_of(op_name.group(1), pattern) if op_name else None
        members.setdefault(current, []).append(name)
        called = _CALLED.findall(line)
        for group in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        if called:
            calls[name] = called

    seen = {}

    def inside(name):
        """The scopes of the instructions in the computations ``name``
        calls."""
        if name not in seen:
            seen[name] = found = set()      # set first: no cycle loops
            for computation in calls[name]:
                for inst in members.get(computation, ()):
                    if own[inst] is not None:
                        found.add(own[inst])
                    elif inst in calls:
                        found.update(inside(inst))
        return seen[name]

    table = dict(own)
    for name in calls:
        if own[name] is None and inside(name):
            table[name] = shared_scope(inside(name))
    return table


def instruction(op_name):
    """``%fusion.17 = f32[...] fusion(...)`` -> ``fusion.17``."""
    return op_name.partition(" = ")[0].lstrip("%")


def exclusive_seconds(ops):
    """``{op name: seconds}``: each event's length less the events
    nested in it, summed by name; the values add up to the length of
    the union of the events where every overlap is a nesting."""
    out, stack = {}, []             # stack: [name, start, end, nested]

    def close(upto):
        while stack and stack[-1][2] <= upto + EDGE / 2:
            name, s, e, nested = stack.pop()
            out[name] = out.get(name, 0.0) + (e - s) - nested
            if stack:
                stack[-1][3] += e - s

    for name, s, e in sorted(ops, key=lambda ev: (ev[1], ev[1] - ev[2])):
        close(s)
        stack.append([name, s, e, 0.0])
    close(float("inf"))
    return out


def ops_by_module(dev, pattern):
    """``{module event name: [op, ...]}`` for device ``dev``'s ops that
    lie inside an ``XLA Modules`` event matching ``pattern``; the name
    is the event's, number in brackets included."""
    modules = sorted(dev.matching(pattern, line="modules"),
                     key=lambda ev: ev[1])
    starts = [s for _, s, _ in modules]
    out = {name: [] for name, _, _ in modules}
    for op in dev.ops:
        i = bisect.bisect_right(starts, op[1] + EDGE) - 1
        if i >= 0 and op[2] <= modules[i][2] + EDGE:
            out[modules[i][0]].append(op)
    return out


def _tables():
    """``{module name: [table, ...]}`` of the programs the process
    dispatched, asked for once a trace, and the seconds that took;
    ``(None, 0.0)`` from a program that hands out no texts."""
    try:
        from mmlspark_tpu.core import scopes
    except ImportError:
        return None, 0.0
    start = time.perf_counter()
    pattern = scope_pattern(families())
    found = {name: [scope_table(text, pattern) for text in texts]
             for name, texts in scopes.hlo_texts().items()}
    return found, time.perf_counter() - start


def by_scope(ctx, match):
    """``{"device_s": busy seconds of the matching modules, "scopes":
    {scope: seconds}}`` over the traced calls, with ``UNSCOPED`` for
    what no scope claims and ``UNLISTED`` for instructions the
    program's table does not hold. Emits one ``scope_time`` fact a
    distinct module, the first time it is asked about ``match``: its
    seconds by scope, the sixteen instructions under no scope that took
    most (by name and result shape: ``PERF.md`` §5 lists them), the
    share of its time whose instruction the table does not hold, and
    what asking for the tables took."""
    if ctx.trace is None or not ctx.traced_calls:
        return None
    held = _memo.get(id(ctx.trace))
    if held is None or held[0] is not ctx.trace:
        held = _memo[id(ctx.trace)] = (ctx.trace, {}) + _tables()
    _, results, tables, tables_s = held
    if tables is None:
        return None
    if match not in results:
        results[match] = _join(ctx, match, tables, tables_s)
    return results[match]


def _join(ctx, match, tables, tables_s):
    total, scopes = 0.0, {}
    for module, ops in ops_by_module(ctx.trace.device(0), match).items():
        candidates = tables.get(module.partition("(")[0])
        if not candidates or not ops:
            continue
        spent, shown = {}, {}
        for line, seconds in exclusive_seconds(ops).items():
            name = instruction(line)
            spent[name] = spent.get(name, 0.0) + seconds
            shown[name] = short_name(line)      # with the result's shape
        # several shapes of one program: the table that knows this
        # module's instructions is its own
        table = max(candidates, key=lambda t: sum(
            s for name, s in spent.items() if name in t))
        mine, unscoped = {}, {}
        for name, seconds in spent.items():
            scope = (table[name] or UNSCOPED) if name in table else UNLISTED
            mine[scope] = mine.get(scope, 0.0) + seconds
            if scope == UNSCOPED:
                unscoped[shown[name]] = seconds
        busy = sum(mine.values())
        ctx.emit(scope_time=module, device_s=busy,
                 by_scope_s=dict(sorted(mine.items(), key=lambda kv: -kv[1])),
                 not_in_table_share=mine.get(UNLISTED, 0.0) / busy,
                 unscoped_ops_s=sorted(unscoped.items(),
                                       key=lambda kv: -kv[1])[:16],
                 tables_s=tables_s)
        total += busy
        for scope, seconds in mine.items():
            scopes[scope] = scopes.get(scope, 0.0) + seconds
    return {"device_s": total, "scopes": scopes} if total > 0 else None
