"""A generation call's share of a peak for a model with expert layers,
over device 0's busy time in the traced part. Per cent.

``kind`` ``mfu``: the operations the model's mathematics needs for the
rows, tokens and (token, expert) pairs traced
(``opcount_hybrid_lm.model_flops``: real tokens and counted pairs, no
padding) over the bf16 peak times busy time. ``kind`` ``step``: the
least time the chip could take, the larger of operations over the peak
and bytes over the bandwidth for each prefill and for each decode step
(``opcount_hybrid_lm.prefill``, ``.decode_step``; a step's context the
rows' mean prompt length plus half the new tokens), over busy time.
``kind`` ``kernel``: the floor of one launch of the kernel
``opcount_hybrid_lm.<function>`` times its launches (a delta-rule layer
and decode step) over the summed device time of the events whose name
matches ``match``. Nothing is clipped.
"""

from benchmark import opcount, opcount_hybrid_lm
from benchmark.lookup import load_module


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    cfg = ctx.counters.get(params["counter"])
    calls = load_module("readers", "hybrid_lm_calls").gather(
        ctx.traced_calls)
    if not cfg or not calls:
        return None
    peak = opcount.peaks(ctx.device_kind)
    if params["kind"] == "kernel":
        events = ctx.trace.device(0).matching(params["match"])
        spent = sum(e - s for _, s, e in events)
        if spent <= 0:
            return None
        launch = getattr(opcount_hybrid_lm, params["function"])
        layers = opcount_hybrid_lm.kinds(cfg)[0]
        floor = sum(
            layers * c["decode_steps"]
            * opcount.least_seconds(*launch(cfg, c["rows"]), peak)[0]
            for c in calls)
        ctx.emit(kernel=params["match"], events=len(events),
                 kernel_s=spent, floor_s=floor)
        return 100.0 * floor / spent
    busy = ctx.trace.device(0).busy_s()
    if busy <= 0:
        return None
    if params["kind"] == "mfu":
        flops = sum(opcount_hybrid_lm.model_flops(
            cfg, c["prompt_tokens"], c["new_tokens"], c["rows"],
            c["expert_pairs"]) for c in calls)
        return 100.0 * flops / (peak["bf16_flops_per_s"] * busy)
    sparse = opcount_hybrid_lm.kinds(cfg)[3]
    floor = 0.0
    for c in calls:
        through = c["prompt_tokens"] + c["new_tokens"] - c["rows"]
        pairs_a_token = c["expert_pairs"] / max(through * sparse, 1)
        context = (c["prompt_tokens"] + c["new_tokens"] / 2.0) / max(
            c["rows"], 1)
        floor += opcount.least_seconds(*opcount_hybrid_lm.prefill(
            cfg, c["prompt_tokens"], c["rows"], pairs_a_token), peak)[0]
        floor += c["decode_steps"] * opcount.least_seconds(
            *opcount_hybrid_lm.decode_step(cfg, c["rows"], context,
                                           pairs_a_token), peak)[0]
    return 100.0 * floor / busy
