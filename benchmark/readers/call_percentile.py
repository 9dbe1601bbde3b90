"""A percentile of the window's call times, in ``scale`` x seconds.

Nearest-rank on the sorted times of ALL calls of the window (the tail
is the tail of every call); ``q`` in percent.
"""

import math


def read(ctx, params):
    times = sorted(c.seconds for c in ctx.window_calls())
    if not times:
        return None
    rank = max(1, math.ceil(params["q"] / 100.0 * len(times)))
    return times[rank - 1] * params.get("scale", 1.0)
