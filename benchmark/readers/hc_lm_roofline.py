"""A generation call's share of a peak for a model of latent-attention
layers inside a residual path of several streams (``hc_mult``), from
device 0's trace of the traced part. Per cent; nothing is clipped.

``kind`` ``mfu`` and ``step`` are ``latent_lm_roofline``'s with the
path's operations and bytes counted in (``opcount_hc_lm.model_flops``,
``.prefill``, ``.decode_step``). ``kind`` ``path``: the least time the
chip could take for the path alone, its byte floor
(``opcount_hc_lm.hc_path`` over the root's ``hc_sublayer_tokens``: the
stream read once for norm, projection and read, read and written once
for the write-back), over the device time under the scopes ``scopes``
in the programs matching ``match`` (``benchmark/scope_time.py``). The
time is the scope's, whatever instructions stand under it, so a kernel
that later takes the path's place is read by the same yardstick.

Nothing where the program left no ``hc_sublayer_tokens`` (a parent
commit, another model) or, for ``path``, handed out no HLO text.
"""

from benchmark import opcount, opcount_hc_lm, program_spans, scope_time
from benchmark.lookup import load_module

KEYS = ("expert_pairs", "hc_sublayer_tokens")


def gather(calls):
    """``lm_calls.gather``'s record of each call with the root's
    ``expert_pairs`` and ``hc_sublayer_tokens`` beside it; ``None``
    where no call left the latter."""
    out = []
    for _, roots in program_spans.calls_with_roots(calls):
        for record in roots:
            stack = [s for s in record["spans"] if s["name"] == "lm.stack"]
            counts = record.get("counts") or {}
            if not stack or "hc_sublayer_tokens" not in counts:
                continue
            rows = sum(s["counts"].get("rows", 0) for s in stack)
            out.append(dict(
                {k: counts.get(k, 0) for k in KEYS}, rows=rows,
                decode_steps=counts["new_tokens"] // max(rows, 1) - 1,
                prompt_tokens=sum(s["counts"].get("prompt_tokens", 0)
                                  for s in stack),
                new_tokens=counts["new_tokens"]))
    return out or None


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    cfg = ctx.counters.get(params["counter"])
    calls = gather(ctx.traced_calls)
    dev = ctx.trace.device(0)
    if not cfg or not calls or dev is None:
        return None
    peak = opcount.peaks(ctx.device_kind)
    if params["kind"] == "path":
        found = scope_time.by_scope(ctx, params["match"])
        if found is None:
            return None
        covers = load_module("readers", "scope_time").covers
        spent = sum(seconds for scope, seconds in found["scopes"].items()
                    if covers(params["scopes"], scope))
        if spent <= 0:
            return None
        passages = sum(c["hc_sublayer_tokens"] for c in calls)
        floor, bound = opcount.least_seconds(
            *opcount_hc_lm.hc_path(cfg, passages), peak)
        ctx.emit(path=params["scopes"], path_s=spent, floor_s=floor,
                 bound=bound, sublayer_tokens=passages)
        return 100.0 * floor / spent
    busy = dev.busy_s()
    if busy <= 0:
        return None
    if params["kind"] == "mfu":
        flops = sum(opcount_hc_lm.model_flops(
            cfg, c["prompt_tokens"], c["new_tokens"], c["rows"],
            c["expert_pairs"], c["hc_sublayer_tokens"]) for c in calls)
        return 100.0 * flops / (peak["bf16_flops_per_s"] * busy)
    sparse = opcount_hc_lm.kinds(cfg)[1]
    floor = 0.0
    for c in calls:
        through = c["prompt_tokens"] + c["new_tokens"] - c["rows"]
        pairs_a_token = c["expert_pairs"] / max(through * sparse, 1)
        context = (c["prompt_tokens"] / max(c["rows"], 1)
                   + (c["decode_steps"] + 1) / 2.0)
        floor += opcount.least_seconds(*opcount_hc_lm.prefill(
            cfg, c["prompt_tokens"], c["rows"], pairs_a_token), peak)[0]
        floor += c["decode_steps"] * opcount.least_seconds(
            *opcount_hc_lm.decode_step(cfg, c["rows"], context,
                                       pairs_a_token), peak)[0]
    return 100.0 * floor / busy
