"""A kernel's share of its roofline in the traced part. Per cent.

The least time the chip could take for the work the traced calls
needed (``opcount.<function>`` from the runner's counters, times the
number of kernel launches the mathematics needs, against
``peaks.json``: the bf16 compute peak and the memory bandwidth) over
the summed device time of the kernel's events (ops on device 0 whose
name matches ``match``). Nothing is clipped: a share above 100 means
the count is too high or the match too narrow.
"""

from benchmark import opcount


def read(ctx, params):
    if ctx.trace is None:
        return None
    events = ctx.trace.device(0).matching(params["match"])
    if not events:
        return None
    kernel_s = sum(e - s for _, s, e in events)
    counted = ctx.counters.get(params["counter"])
    if not counted or kernel_s <= 0:
        return None
    fn = getattr(opcount, counted["function"])
    flops, nbytes = fn(**counted["shape"])
    floor, _ = opcount.least_seconds(flops, nbytes,
                                     opcount.peaks(ctx.device_kind))
    return 100.0 * floor * counted["launches"] / kernel_s
