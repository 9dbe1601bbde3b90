"""A generation call's share of a peak for a model of grouped
differential attention on a latent cache, full and window layers
inside a residual path of several streams (``model_type`` ``Motif``),
from device 0's trace of the traced part. Per cent; nothing is
clipped.

``kind`` ``mfu``: the operations the model's mathematics needs for the
rows, tokens, (token, expert) pairs, residual passages and window reads
traced (``opcount_gdla_lm.model_flops``) over the bf16 peak times busy
time. ``kind`` ``step``: the least time the chip could take, the
larger of operations over the peak and bytes over the bandwidth for
each prefill and each decode step (``opcount_gdla_lm.prefill``,
``.decode_step``; a step's context the mean over the call's decode
steps), over busy time. ``kind`` ``window``: the floor of the window
layers' attention (``opcount_gdla_lm.window_attention`` over the root's
``window_positions``, of which a window a decode step a row and window
layer fell in decode: the cell's prompts are a window long or more)
over the device time under the scopes ``scopes`` in the programs
matching ``match`` (``benchmark/scope_time.py``), whatever instructions
stand under them.

Nothing where the program left no ``window_positions`` (a parent
commit, another model) or, for ``window``, handed out no HLO text.
"""

from benchmark import opcount, opcount_gdla_lm, program_spans, scope_time
from benchmark.lookup import load_module

KEYS = ("expert_pairs", "hc_sublayer_tokens", "window_positions")


def gather(calls):
    """``lm_calls.gather``'s record of each call with the root's
    ``KEYS`` beside it; ``None`` where no call left
    ``window_positions``."""
    out = []
    for _, roots in program_spans.calls_with_roots(calls):
        for record in roots:
            stack = [s for s in record["spans"] if s["name"] == "lm.stack"]
            counts = record.get("counts") or {}
            if not stack or "window_positions" not in counts:
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
    if params["kind"] == "window":
        found = scope_time.by_scope(ctx, params["match"])
        if found is None:
            return None
        covers = load_module("readers", "scope_time").covers
        spent = sum(seconds for scope, seconds in found["scopes"].items()
                    if covers(params["scopes"], scope))
        if spent <= 0:
            return None
        positions = sum(c["window_positions"] for c in calls)
        decode = sum(min(opcount_gdla_lm.decode_window_positions(
            cfg, c["rows"], c["decode_steps"]), c["window_positions"])
            for c in calls)
        floor, bound = opcount.least_seconds(
            *opcount_gdla_lm.window_attention(cfg, positions, decode), peak)
        ctx.emit(window=params["scopes"], window_s=spent, floor_s=floor,
                 bound=bound, window_positions=positions,
                 decode_positions=decode)
        return 100.0 * floor / spent
    busy = dev.busy_s()
    if busy <= 0:
        return None
    if params["kind"] == "mfu":
        flops = sum(opcount_gdla_lm.model_flops(
            cfg, c["prompt_tokens"], c["new_tokens"], c["rows"],
            c["expert_pairs"], c["hc_sublayer_tokens"],
            c["window_positions"]) for c in calls)
        return 100.0 * flops / (peak["bf16_flops_per_s"] * busy)
    sparse = opcount_gdla_lm.counts(cfg)[3]
    floor = 0.0
    for c in calls:
        through = c["prompt_tokens"] + c["new_tokens"] - c["rows"]
        pairs_a_token = c["expert_pairs"] / max(through * sparse, 1)
        context = (c["prompt_tokens"] / max(c["rows"], 1)
                   + (c["decode_steps"] + 1) / 2.0)
        floor += opcount.least_seconds(*opcount_gdla_lm.prefill(
            cfg, c["prompt_tokens"], c["rows"], pairs_a_token), peak)[0]
        floor += c["decode_steps"] * opcount.least_seconds(
            *opcount_gdla_lm.decode_step(cfg, c["rows"], context,
                                         pairs_a_token), peak)[0]
    return 100.0 * floor / busy
