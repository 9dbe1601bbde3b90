"""Share of the window's call time spent in the named host spans.

The spans are the program's own ``core/timer.py`` phases of each call
(``train_measures``), each ending in a host sync; the runner copies
them onto the call. Per cent of the summed wall time of the calls.
"""


def read(ctx, params):
    calls = [c for c in ctx.window_calls() if c.phases]
    if not calls:
        return None
    spent = sum(c.phases.get(p, 0.0) for c in calls
                for p in params["phases"])
    return 100.0 * spent / sum(c.seconds for c in calls)
