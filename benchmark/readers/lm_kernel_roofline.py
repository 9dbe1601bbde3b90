"""A retention kernel's share of its roofline in the traced part. Per
cent.

The least time the chip could take for one launch
(``opcount_lm.<function>`` against ``peaks.json``) times the launches
the traced calls needed, over the summed device time of the kernel's
events: ops on device 0 whose name matches ``match`` (the kernel's
``name=``). Launches: ``decode`` is one a layer and decode step,
``prefill`` one a layer and chunk of each call's length rung. Nothing
is clipped.
"""

from benchmark import opcount, opcount_lm
from benchmark.lookup import load_module


def read(ctx, params):
    if ctx.trace is None:
        return None
    events = ctx.trace.device(0).matching(params["match"])
    cfg = ctx.counters.get(params["counter"])
    calls = load_module("readers", "lm_calls").gather(ctx.traced_calls)
    if not events or not cfg or not calls:
        return None
    kernel_s = sum(e - s for _, s, e in events)
    if kernel_s <= 0:
        return None
    peak = opcount.peaks(ctx.device_kind)
    layers = cfg["num_hidden_layers"]
    floor = 0.0
    for c in calls:
        if params["kernel"] == "decode":
            per, _ = opcount.least_seconds(
                *opcount_lm.retention_decode(cfg, c["rows"]), peak)
            launches = layers * c["decode_steps"]
        else:
            chunk = cfg["prefill_chunk"]
            per, _ = opcount.least_seconds(
                *opcount_lm.retention_prefill(cfg, c["rows"], chunk), peak)
            launches = layers * -(-c["length_rung"] // chunk)
        floor += per * launches
    ctx.emit(kernel=params["match"], events=len(events),
             kernel_s=kernel_s, floor_s=floor)
    return 100.0 * floor / kernel_s
