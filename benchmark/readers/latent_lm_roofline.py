"""A generation call's share of a peak for a model of latent-attention
layers over sparse experts, from device 0's trace of the traced part.
Per cent; nothing is clipped.

``kind`` ``mfu``: the operations the model's mathematics needs for the
rows, tokens and (token, expert) pairs traced
(``opcount_latent_lm.model_flops``: real tokens and counted pairs, no
padding) over the bf16 peak times busy time. ``kind`` ``step``: the
least time the chip could take, the larger of operations over the peak
and bytes over the bandwidth for each prefill and for each decode step
(``opcount_latent_lm.prefill``, ``.decode_step``; a step's context the
mean over the call's decode steps, a cached position read once a
step), over busy time. ``kind`` ``kernel``: the floor of the decode
kernel over the cache positions its launches had filled
(``opcount_latent_lm.latent_decode``; the positions from the root's
``cache_positions`` and the steps) over the summed device time of the
events whose name matches ``match``. ``kind`` ``share``: those events'
time over the device time of the programs matching ``of`` (the decode
scan).
"""

from benchmark import opcount, opcount_latent_lm
from benchmark.lookup import load_module


def _prompt_tokens(cfg, call):
    """The prompts' tokens from what the call's cache held at its end:
    every row had filled its prompt and its decode steps, in every
    layer."""
    return (call["cache_positions"] / cfg["num_hidden_layers"]
            - call["rows"] * call["decode_steps"])


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    cfg = ctx.counters.get(params["counter"])
    calls = load_module("readers", "latent_lm_calls").gather(
        ctx.traced_calls)
    dev = ctx.trace.device(0)
    if not cfg or not calls or dev is None:
        return None
    peak = opcount.peaks(ctx.device_kind)
    if params["kind"] in ("kernel", "share"):
        events = dev.matching(params["match"])
        spent = sum(e - s for _, s, e in events)
        if spent <= 0:
            return None
        if params["kind"] == "share":
            whole = sum(e - s for _, s, e in dev.matching(
                params["of"], line="modules"))
            return 100.0 * spent / whole if whole > 0 else None
        positions = sum(
            cfg["num_hidden_layers"] * opcount_latent_lm.decode_positions(
                _prompt_tokens(cfg, c), c["rows"], c["decode_steps"])
            for c in calls)
        floor = opcount.least_seconds(
            *opcount_latent_lm.latent_decode(cfg, positions), peak)[0]
        ctx.emit(kernel=params["match"], events=len(events),
                 kernel_s=spent, floor_s=floor, positions=positions)
        return 100.0 * floor / spent
    busy = dev.busy_s()
    if busy <= 0:
        return None
    if params["kind"] == "mfu":
        flops = sum(opcount_latent_lm.model_flops(
            cfg, c["prompt_tokens"], c["new_tokens"], c["rows"],
            c["expert_pairs"]) for c in calls)
        return 100.0 * flops / (peak["bf16_flops_per_s"] * busy)
    sparse = opcount_latent_lm.kinds(cfg)[1]
    floor = 0.0
    for c in calls:
        through = c["prompt_tokens"] + c["new_tokens"] - c["rows"]
        pairs_a_token = c["expert_pairs"] / max(through * sparse, 1)
        context = (c["prompt_tokens"] / max(c["rows"], 1)
                   + (c["decode_steps"] + 1) / 2.0)
        floor += opcount.least_seconds(*opcount_latent_lm.prefill(
            cfg, c["prompt_tokens"], c["rows"], pairs_a_token), peak)[0]
        floor += c["decode_steps"] * opcount.least_seconds(
            *opcount_latent_lm.decode_step(cfg, c["rows"], context,
                                           pairs_a_token), peak)[0]
    return 100.0 * floor / busy
