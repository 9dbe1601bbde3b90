"""Of device 0's idle seconds inside the traced calls, the share that
lies under a program span below the call's root.

The program's spans are on ``time.perf_counter()``, the trace on the
profiler's clock. They are joined call by call: the i-th traced call's
annotation in the trace started microseconds before ``Call.start`` was
read (``run.py``), so the difference of the two is that call's offset,
against gaps of most of a second. Each idle stretch goes to the
innermost span that covers it. Also prints ``idle_by_span_s`` as a fact
line, and ``traced_call_s``, the traced calls' wall times, whose median
against the window's untraced calls is what the profiler costs a call.
Per cent; nothing without a device trace or without spans.
"""

from benchmark import program_spans


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    ctx.emit(traced_call_s=[c.seconds for c in ctx.traced_calls])
    pairs = program_spans.calls_with_roots(ctx.traced_calls)
    if not pairs:
        return None
    ops = [(s, e) for _, s, e in ctx.trace.device(0).ops]
    roots_of = {id(c): roots for c, roots in pairs}
    by_span = {}
    for name in {c.name for c, _ in pairs}:
        marks = sorted((s, e) for n, s, e in ctx.trace.annotations
                       if n == name)
        calls = [c for c in ctx.traced_calls if c.name == name]
        if len(marks) != len(calls):
            return None      # the trace lost an annotation: no join
        for call, (lo, hi) in zip(calls, marks):
            offset = lo - call.start
            spans = [(s["name"], s["start_s"] + offset, s["end_s"] + offset)
                     for record in roots_of.get(id(call), [])
                     for s in record["spans"]]
            for span, idle in program_spans.idle_by_span(
                    ops, spans, lo, hi).items():
                by_span[span] = by_span.get(span, 0.0) + idle
    total = sum(by_span.values())
    if total <= 0:
        return None
    ctx.emit(idle_by_span_s=dict(sorted(by_span.items(),
                                        key=lambda kv: -kv[1])))
    return 100.0 * (total - by_span.get(program_spans.UNNAMED, 0.0)) / total
