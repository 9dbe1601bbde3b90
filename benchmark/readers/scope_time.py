"""Device time of a traced program's scopes, from device 0's ``XLA Ops``
joined to the scopes in the program's own optimized HLO
(``benchmark/scope_time.py``).

``match`` is a regular expression over program names (the ``XLA
Modules`` line). ``scopes`` lists prefixes: ``lm.moe`` covers
``lm.moe`` itself and ``lm.moe.route``; ``exclude`` takes sub-trees out
again (``["lm.moe.experts", "lm.moe.shared"]``); ``"unscoped": true``
adds what no scope claims (with ``"scopes": []``, that alone). ``per``
``share``: the chosen seconds over the matching programs' busy time,
per cent. ``per`` a work unit of the traced calls
(``ctx.call(..., unit=n)``): milliseconds a unit; with ``"a_row": true``
the unit is counted a row (``new_tokens`` of a call over its ``rows``),
less ``less`` units where the program does not run for them (a decode
program runs ``new_tokens - 1`` steps a row batch), as
``readers/module_time.py`` counts them.

A layer file for it:
``{"reader": "scope_time", "params": {"match": "lm_generate", "scopes":
["lm.moe"], "exclude": ["lm.moe.experts", "lm.moe.shared"], "per":
"new_tokens", "a_row": true, "less": 1}}``.

Nothing without a device trace, from a program that hands out no HLO
text (a parent commit), or where no matching program was registered.
"""

from benchmark import scope_time


def covers(prefixes, scope):
    return any(scope == p or scope.startswith(p + ".") for p in prefixes)


def read(ctx, params):
    found = scope_time.by_scope(ctx, params["match"])
    if found is None:
        return None
    wanted, out = params.get("scopes", []), params.get("exclude", [])
    spent = sum(seconds for scope, seconds in found["scopes"].items()
                if covers(wanted, scope) and not covers(out, scope))
    if params.get("unscoped"):
        spent += found["scopes"].get(scope_time.UNSCOPED, 0.0)
    if params["per"] == "share":
        return 100.0 * spent / found["device_s"]
    units = sum(c.work[params["per"]] / (c.work["rows"] if params.get("a_row")
                                         else 1) - params.get("less", 0)
                for c in ctx.traced_calls)
    return 1000.0 * spent / units if units > 0 else None
