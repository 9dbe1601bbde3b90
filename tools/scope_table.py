"""Device seconds of jit(step) by gbdt.* scope: join a kept trace's op
events (named by instruction) to the op_name metadata of the step's
optimized HLO text (PERF.md §5's by-scope table; PRs 27 and 28).

usage: python3 tools/scope_table.py <xplane.pb> <hlo.txt> [trees]

The trace is a fit cell run with BENCH_KEEP_TRACE=<dir>; the HLO text is
XLA's dump of jit_step after optimizations, or the step compiled in the
sandbox for a described v5e (same instruction names). Run it where the
trace is: a kept fit trace is ~95 MB, more than a chip call brings back."""
import re
import sys
from collections import defaultdict

from jax.profiler import ProfileData

xplane, hlo, trees = sys.argv[1], sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3 else 4

# instruction name -> op_name, and the computations an instruction calls
op_name, calls, members = {}, {}, defaultdict(list)
current = None
for line in open(hlo):
    head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
    if head and not line.startswith(" "):
        current = head.group(1)
        continue
    m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", line)
    if not m:
        continue
    n = re.search(r'op_name="([^"]*)"', line)
    op_name[m.group(1)] = n.group(1) if n else ""
    members[current].append(m.group(1))
    calls[m.group(1)] = re.findall(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", line)


def own_scope(name):
    parts = [p for p in op_name.get(name, "").split("/") if p.startswith("gbdt.")]
    return parts[-1] if parts else None


def inner_scopes(name, depth=0):
    """Scopes of the instructions inside the computations ``name`` calls."""
    found = defaultdict(int)
    if depth > 4:
        return found
    for comp in calls.get(name, []):
        for inst in members.get(comp, []):
            sc = own_scope(inst)
            if sc:
                found[sc] += 1
            else:
                for k, v in inner_scopes(inst, depth + 1).items():
                    found[k] += v
    return found


def scope_of(name):
    if name not in op_name:
        return "(not in hlo)"
    sc = own_scope(name)
    if sc:
        return sc
    inner = inner_scopes(name)
    if inner:
        return max(inner, key=inner.get) + " (by its body)"
    return "(no scope)"


data = ProfileData.from_file(xplane)
plane = next(p for p in data.planes if p.name.startswith("/device:TPU:0"))
ops, modules = [], []
for line in plane.lines:
    if line.name == "XLA Ops":
        ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
    elif line.name == "XLA Modules":
        modules = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
steps = [(s, e) for n, s, e in modules if n.startswith("jit_step(")]
print("step programs:", len(steps), "seconds each:", [round((e - s) / 1e9, 3) for s, e in steps])
in_step = [(n, s, e) for n, s, e in ops if any(a <= s and e <= b for a, b in steps)]
print("ops in steps:", len(in_step), "of", len(ops))

# exclusive time: a nested op (inside a while/conditional parent) takes its
# interval from the parent.  Sweep over sorted events with a stack.
events = sorted(in_step, key=lambda ev: (ev[1], -(ev[2] - ev[1])))
excl = defaultdict(float)        # instruction -> exclusive ns
stack = []                       # (name, start, end, children_ns)


def close(upto):
    while stack and stack[-1][2] <= upto:
        name, s, e, kids = stack.pop()
        excl[name] += (e - s) - kids
        if stack:
            stack[-1][3] += (e - s)


for name, s, e in events:
    close(s)
    stack.append([name, s, e, 0.0])
close(float("inf"))

by_scope, by_inst = defaultdict(float), defaultdict(float)
for full, ns in excl.items():
    iname = full.split(" = ")[0].lstrip("%")
    by_scope[scope_of(iname)] += ns
    by_inst[(scope_of(iname), iname, full.split(" = ")[1].split(" ")[0][:40] if " = " in full else "")] += ns
total = sum(by_scope.values())
print(f"busy in steps (exclusive sum): {total / 1e9:.3f} s; per tree {total / 1e9 / trees:.3f} s")
print("| scope | s a tree | share |")
for scope, ns in sorted(by_scope.items(), key=lambda kv: -kv[1]):
    print(f"| {scope} | {ns / 1e9 / trees:.3f} | {100 * ns / total:.1f}% |")
print("top instructions:")
for (scope, iname, shape), ns in sorted(by_inst.items(), key=lambda kv: -kv[1])[:40]:
    print(f"  {ns / 1e9 / trees:8.3f} s/tree  {scope:16s} {iname:28s} {shape}  op_name={op_name.get(iname, '')[-90:]}")
kernel = [n for n in excl if "custom-call" in n]
print("custom calls:", sorted({n.split(' = ')[0] for n in kernel})[:12])
