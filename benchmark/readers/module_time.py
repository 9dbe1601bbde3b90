"""Device time of named programs in the traced part, from device 0's
``XLA Modules`` line.

``match`` is a regular expression over program names. ``per``
``share``: their summed time over that of the programs matching
``of``, per cent. ``per`` a work unit of the traced calls
(``ctx.call(..., unit=n)``): milliseconds of the matching programs per
unit, less ``less`` units a row where the programs do not run for them
(a decode program runs ``new_tokens - 1`` steps a row batch).
"""


def _seconds(dev, pattern):
    return sum(e - s for _, s, e in dev.matching(pattern, line="modules"))


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    dev = ctx.trace.device(0)
    spent = _seconds(dev, params["match"])
    if spent <= 0:
        return None
    if params["per"] == "share":
        whole = _seconds(dev, params["of"])
        return 100.0 * spent / whole if whole > 0 else None
    steps = sum(c.work[params["per"]] / c.work["rows"] - params.get("less", 0)
                for c in ctx.traced_calls)
    return 1000.0 * spent / steps if steps > 0 else None
