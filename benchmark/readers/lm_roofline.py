"""A generation call's share of a peak, over device 0's busy time in
the traced part. Per cent.

``kind`` ``mfu``: the operations the model's mathematics needs for the
rows and tokens traced (``opcount_lm.model_flops``: real tokens only,
no padding) over the bf16 peak times busy time. ``kind`` ``step``: the
least time the chip could take, the larger of operations over the peak
and bytes over the bandwidth for each prefill and for each decode step
(``opcount_lm.prefill``, ``.decode_step``), over busy time. Nothing is
clipped.
"""

from benchmark import opcount, opcount_lm
from benchmark.lookup import load_module


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    cfg = ctx.counters.get(params["counter"])
    calls = load_module("readers", "lm_calls").gather(ctx.traced_calls)
    busy = ctx.trace.device(0).busy_s()
    if not cfg or not calls or busy <= 0:
        return None
    peak = opcount.peaks(ctx.device_kind)
    if params["kind"] == "mfu":
        flops = sum(opcount_lm.model_flops(
            cfg, c["prompt_tokens"], c["new_tokens"], c["rows"])
            for c in calls)
        return 100.0 * flops / (peak["bf16_flops_per_s"] * busy)
    floor = 0.0
    for c in calls:
        floor += opcount.least_seconds(
            *opcount_lm.prefill(cfg, c["prompt_tokens"], c["rows"]), peak)[0]
        floor += c["decode_steps"] * opcount.least_seconds(
            *opcount_lm.decode_step(cfg, c["rows"]), peak)[0]
    return 100.0 * floor / busy
