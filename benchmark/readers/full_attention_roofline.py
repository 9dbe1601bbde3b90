"""The full layers' prefill attention of a model of grouped differential
attention on a latent cache (``model_type`` ``Motif``) against its
floor, from device 0's trace of the traced part. Per cent; nothing is
clipped.

The floor is the full layers' prompt (query, key) pairs in the expanded
form, counted as ``gdla_lm_roofline``'s ``mfu`` counts them: each call's
rows at their mean prompt length (``opcount_gdla_lm.attended_pairs``,
no more than the true sum), ``opcount_gdla_lm.pair_flops`` a pair, and
each full layer's cache read once; ``opcount.least_seconds`` turns it
into seconds at the device's peaks. The time is the device seconds
under the scopes ``scopes`` in the programs matching ``match``
(``benchmark/scope_time.py``), whatever instructions stand under them,
so a later kernel or form of the layer is read by the same yardstick.

A layer file for it: ``{"reader": "full_attention_roofline", "params":
{"match": "lm_prefill", "scopes": ["lm.mla.full"], "counter":
"lm_shape"}}``.

Nothing where the program left no ``window_positions`` on its root
(another model), handed out no HLO text, or put no time under the
scopes (a parent commit without them).
"""

from benchmark import opcount, opcount_gdla_lm, scope_time
from benchmark.lookup import load_module


def floor(cfg, calls, peak):
    """``(seconds, bound, flops)`` of the full layers' prompt attention
    over ``calls`` (``gdla_lm_roofline.gather``'s records)."""
    full = opcount_gdla_lm.counts(cfg)[0]
    flops = nbytes = 0.0
    for c in calls:
        rows = max(c["rows"], 1)
        flops += (full * c["rows"] * opcount_gdla_lm.attended_pairs(
            c["prompt_tokens"] / rows, 1) * opcount_gdla_lm.pair_flops(cfg))
        nbytes += (full * c["prompt_tokens"]
                   * opcount_gdla_lm.cache_entry_bytes(cfg))
    return opcount.least_seconds(flops, nbytes, peak) + (flops,)


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    cfg = ctx.counters.get(params["counter"])
    calls = load_module("readers", "gdla_lm_roofline").gather(
        ctx.traced_calls)
    if not cfg or not calls:
        return None
    found = scope_time.by_scope(ctx, params["match"])
    if found is None:
        return None
    covers = load_module("readers", "scope_time").covers
    spent = sum(seconds for scope, seconds in found["scopes"].items()
                if covers(params["scopes"], scope))
    if spent <= 0:
        return None
    least, bound, flops = floor(cfg, calls, opcount.peaks(ctx.device_kind))
    ctx.emit(full=params["scopes"], full_s=spent, floor_s=least,
             bound=bound, flops=flops)
    return 100.0 * least / spent
