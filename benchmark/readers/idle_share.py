"""1 - busy share of device 0 over a span of the traced part.

The span runs from the first to the last op on the device. With
``between`` (a regular expression over ``XLA Modules`` names) it runs
from the start of the first to the end of the last matching program:
the tree loop alone, without the ingest before it. Busy is the union of
the device's op intervals inside the span. Per cent.
"""


def read(ctx, params):
    if ctx.trace is None:
        return None
    dev = ctx.trace.device(0)
    span = dev.span()
    if params.get("between"):
        hits = dev.matching(params["between"], line="modules")
        if not hits:
            return None
        span = (min(s for _, s, _ in hits), max(e for _, _, e in hits))
    lo, hi = span
    if hi <= lo:
        return None
    return 100.0 * (1.0 - dev.busy_s(lo, hi) / (hi - lo))
