"""One count of the program's root records over another, per cent, over
the window's calls.

``count`` and ``of`` name two keys of the ``counts`` a stage's
``fit()``/``transform()`` put on its root span (``core/timer.py``; the
record's ``counts``): ``generate_prefill_run_share`` is
``prefill_visits_run`` over ``prefill_visits``, the share of a prefill's
visits (a group of rows in a step) that the group loop runs.

A layer file for it: ``{"reader": "root_count_share", "params":
{"count": "prefill_visits_run", "of": "prefill_visits"}}``.

Nothing where no record of the window carries ``of`` (a program that
does not count it).
"""

from benchmark import program_spans


def read(ctx, params):
    part = whole = 0
    for _, roots in program_spans.calls_with_roots(ctx.window_calls()):
        for record in roots:
            counts = record.get("counts") or {}
            if params["of"] in counts:
                part += counts.get(params["count"], 0)
                whole += counts[params["of"]]
    return 100.0 * part / whole if whole else None
