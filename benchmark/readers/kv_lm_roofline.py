"""A generation call's share of a peak for a model of grouped-query
attention over a plain key/value cache, full and window layers
(``model_type`` ``mimo_v2``), from device 0's trace of the traced part.
Per cent; nothing is clipped.

``kind`` ``mfu``: the operations the model's mathematics needs for the
rows, tokens, (token, expert) pairs and window reads traced
(``opcount_kv_lm.model_flops``) over the bf16 peak times busy time.
``kind`` ``decode``: the floor of the decode kernel
(``opcount_kv_lm.kv_decode``: the full layers' cached positions its
launches read, from each call's prompt tokens and decode steps, and the
window layers' ring, a window a step and row) over the summed device
time of the events whose name matches ``match``. ``kind`` ``prefill``:
the floor of the full layers' prefill kernel
(``opcount_kv_lm.kv_prefill``: the prompts' (query, key) pairs at their
mean length) over the same for its events.

Nothing where the program left no ``window_positions`` (a parent
commit, another model), where no event matches ``match`` (a kernel
off the path), or without a device trace.
"""

from benchmark import opcount, opcount_kv_lm, program_spans

KEYS = ("expert_pairs", "window_positions")


def gather(calls):
    """Each call's rows, prompt and new tokens and decode steps with the
    root's ``KEYS`` beside them; ``None`` where no call left
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


def kernel_floor(cfg, calls, kind, peak):
    """``(seconds, bound)`` of the ``decode`` or ``prefill`` kernel's
    work over ``calls`` (``gather``'s records)."""
    full, _, _, _ = opcount_kv_lm.counts(cfg)
    if kind == "prefill":
        flops = nbytes = 0.0
        for c in calls:
            f, b = opcount_kv_lm.kv_prefill(cfg, c["prompt_tokens"],
                                            c["rows"])
            flops, nbytes = flops + f, nbytes + b
        return opcount.least_seconds(flops, nbytes, peak)
    full_positions = window_positions = launches = 0.0
    for c in calls:
        steps = c["decode_steps"]
        full_positions += full * opcount_kv_lm.decode_positions(
            c["prompt_tokens"], c["rows"], steps)
        window_positions += min(opcount_kv_lm.decode_window_positions(
            cfg, c["rows"], steps), c["window_positions"])
        launches += cfg["num_hidden_layers"] * c["rows"] * steps
    return opcount.least_seconds(*opcount_kv_lm.kv_decode(
        cfg, full_positions, window_positions, launches), peak)


def read(ctx, params):
    if ctx.trace is None or not ctx.traced_calls:
        return None
    cfg = ctx.counters.get(params["counter"])
    calls = gather(ctx.traced_calls)
    dev = ctx.trace.device(0)
    if not cfg or not calls or dev is None:
        return None
    peak = opcount.peaks(ctx.device_kind)
    if params["kind"] in ("decode", "prefill"):
        events = dev.matching(params["match"])
        spent = sum(e - s for _, s, e in events)
        if spent <= 0:
            return None
        floor, bound = kernel_floor(cfg, calls, params["kind"], peak)
        ctx.emit(kernel=params["match"], events=len(events), kernel_s=spent,
                 floor_s=floor, bound=bound)
        return 100.0 * floor / spent
    busy = dev.busy_s()
    if busy <= 0:
        return None
    flops = sum(opcount_kv_lm.model_flops(
        cfg, c["prompt_tokens"], c["new_tokens"], c["rows"],
        c["expert_pairs"], c["window_positions"]) for c in calls)
    return 100.0 * flops / (peak["bf16_flops_per_s"] * busy)
