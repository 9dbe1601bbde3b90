"""Seconds of a host span per unit of work, times ``scale``.

``fit_tree_step_ms``: the ``training`` phase over the trees fitted.
"""


def read(ctx, params):
    calls = [c for c in ctx.window_calls() if c.phases]
    if not calls:
        return None
    spent = sum(c.phases.get(params["phase"], 0.0) for c in calls)
    work = sum(c.work[params["per"]] for c in calls)
    return params.get("scale", 1.0) * spent / work
