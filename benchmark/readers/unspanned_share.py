"""Share of the window's call time that no program span covers.

A call's wall time (the benchmark's clock) less the spans the program
opened directly beneath the root of each ``fit()``/``transform()``
inside it: what the program did and gave no name, and what the caller
did around the entry point. Per cent of the calls' summed wall time;
nothing where the program records no spans. Also prints
``program_spans`` as a fact line: seconds a call under each span, those
directly beneath the root (which with ``unspanned_s`` add up to
``wall_s``) apart from those nested deeper.
"""

from benchmark import program_spans


def read(ctx, params):
    pairs = program_spans.calls_with_roots(ctx.window_calls())
    if not pairs:
        return None
    wall = sum(c.seconds for c, _ in pairs)
    top, nested = {}, {}
    for _, roots in pairs:
        for record in roots:
            root = program_spans.root_name(record)
            for s in record["spans"]:
                into = top if s["parent"] == root else nested
                into[s["name"]] = (into.get(s["name"], 0.0)
                                   + s["end_s"] - s["start_s"])
    named = sum(top.values())
    n = len(pairs)
    ctx.emit(program_spans={
        "calls": n, "wall_s": wall / n, "unspanned_s": (wall - named) / n,
        "top_level_s": {k: v / n for k, v in top.items()},
        "nested_s": {k: v / n for k, v in nested.items()}})
    return 100.0 * (wall - named) / wall
