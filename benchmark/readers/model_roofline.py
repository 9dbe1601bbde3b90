"""Whole-graph share of the compute peak in the traced part. Per cent.

FLOPs the model's mathematics needs for the rows the traced calls
scored (``opcount.<function>``) over the bf16 peak rate times the device's
busy time (device 0). Padding rows a bucket adds are not counted.
"""

from benchmark import opcount


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    counted = ctx.counters.get(params["counter"])
    if not counted:
        return None
    rows = sum(c.work[params["work"]] for c in ctx.traced_calls)
    flops = getattr(opcount, counted["function"])(rows, **counted["shape"])
    busy = ctx.trace.device(0).busy_s()
    if busy <= 0:
        return None
    peak = opcount.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (peak * busy)
