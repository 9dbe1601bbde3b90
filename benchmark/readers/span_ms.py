"""Milliseconds a call under the named program spans.

The spans are the program's own (``core/timer.py``), read from its
telemetry records inside the window's calls (``program_spans.py``);
``spans`` names them. Summed over the window's calls that carry spans,
over the number of those calls. Nothing where the program records none.
"""

from benchmark import program_spans


def read(ctx, params):
    pairs = program_spans.calls_with_roots(ctx.window_calls())
    if not pairs:
        return None
    spent = 0.0
    for _, roots in pairs:
        for record in roots:
            by_name = program_spans.seconds_by_name(record)
            spent += sum(by_name.get(n, 0.0) for n in params["spans"])
    return 1000.0 * spent / len(pairs)
