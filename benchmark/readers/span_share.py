"""``phase_share`` of the named spans, and nothing where the program
recorded none of them.

``phase_share`` reads a span the calls never reported as 0 seconds, which
is right for a share that sums spans some fits do not have. A metric
that is one span's share must instead be left out of the line of a
program that has no such span (a parent commit).
"""

from benchmark.lookup import load_module


def read(ctx, params):
    if not any(p in c.phases for c in ctx.window_calls()
               for p in params["phases"]):
        return None
    return load_module("readers", "phase_share").read(ctx, params)
