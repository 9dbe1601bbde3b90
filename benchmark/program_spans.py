"""The program's own spans, as the benchmark's readers see them.

Every ``fit()``/``transform()`` of the program leaves one record in
``mmlspark_tpu.core.logging_utils.SINK``: the stage's ``uid``,
``className``, ``method``, and, since the program records spans
(``core/timer.py``), ``start_s``/``end_s`` on ``time.perf_counter()``
(the clock of ``run.py``'s ``Call.start``/``.end``) and ``spans``, every
span that closed beneath the call: ``name``, ``start_s``, ``end_s``,
``parent`` (a name) and ``counts``. The benchmark reads the records and
never drains them. A program that records no spans (a parent commit)
gives ``[]`` everywhere here, and the readers then return nothing.
"""

from benchmark.trace_reduce import gaps

UNNAMED = "unnamed"


def records():
    """The SINK's stage records that carry spans, in order of start."""
    from mmlspark_tpu.core.logging_utils import SINK

    found = [r for r in SINK.events
             if isinstance(r.get("spans"), list)
             and r.get("start_s") is not None and r.get("end_s") is not None]
    return sorted(found, key=lambda r: r["start_s"])


def roots_in(call, recs):
    """The outermost records that lie inside one timed call (a stage
    that ran inside another stage's call is read through the outer's
    spans, not twice)."""
    out, edge = [], None
    for r in recs:
        if r["start_s"] < call.start or r["end_s"] > call.end:
            continue
        if edge is None or r["start_s"] >= edge:
            out.append(r)
            edge = r["end_s"]
    return out


def root_name(record):
    return f"{record['className']}.{record['method']}"


def seconds_by_name(record):
    """Summed seconds of the record's spans, by name."""
    out = {}
    for s in record["spans"]:
        out[s["name"]] = out.get(s["name"], 0.0) + s["end_s"] - s["start_s"]
    return out


def calls_with_roots(calls):
    """``[(call, roots)]`` for the calls inside which the program left
    a record with spans."""
    recs = records()
    pairs = [(c, roots_in(c, recs)) for c in calls]
    return [(c, roots) for c, roots in pairs if roots]


def idle_by_span(ops, spans, lo, hi):
    """``{span name: idle seconds}`` for one stretch ``[lo, hi]`` of a
    device's clock: each part of the stretch that no interval of ``ops``
    covers goes to the innermost (shortest) of ``spans``
    (``(name, start, end)``, on the same clock) that covers it, or to
    ``UNNAMED``."""
    out = {}
    for gs, ge in gaps(ops, lo, hi):
        cuts = sorted({gs, ge, *(t for _, s, e in spans for t in (s, e)
                                 if gs < t < ge)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            over = [(e - s, name) for name, s, e in spans if s <= mid <= e]
            name = min(over)[1] if over else UNNAMED
            out[name] = out.get(name, 0.0) + b - a
    return out
