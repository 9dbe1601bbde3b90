"""What the generation calls of a model whose whole per-sequence state
is a latent cache did: ``lm_calls.gather``'s record of each call (rows,
prompt tokens, new tokens, decode steps, length rung) with the root's
``expert_pairs``, ``expert_pairs_max``, ``cache_positions`` (filled
positions summed over rows and latent layers when the call ended) and
``cache_capacity`` (the same at capacity) beside it. ``None`` where the
program left no such record (a parent commit without the counts).

As a metric, over the window's calls: ``kind`` ``fill`` (per cent):
``cache_positions`` over ``cache_capacity``, the share of the cache a
call has filled when it ends; ``kind`` ``imbalance`` (a ratio; 1 is an
even spread): the busiest held expert's pairs over the mean a held
expert and layer.
"""

from benchmark import program_spans

KEYS = ("expert_pairs", "expert_pairs_max", "cache_positions",
        "cache_capacity")


def gather(calls):
    out = []
    for _, roots in program_spans.calls_with_roots(calls):
        for record in roots:
            stack = [s for s in record["spans"] if s["name"] == "lm.stack"]
            counts = record.get("counts") or {}
            if not stack or "cache_positions" not in counts:
                continue
            rows = sum(s["counts"].get("rows", 0) for s in stack)
            out.append(dict(
                {k: counts.get(k, 0) for k in KEYS}, rows=rows,
                decode_steps=counts["new_tokens"] // max(rows, 1) - 1,
                prompt_tokens=sum(s["counts"].get("prompt_tokens", 0)
                                  for s in stack),
                new_tokens=counts["new_tokens"],
                length_rung=counts.get("length_rung", 0)))
    return out or None


def read(ctx, params):
    calls = gather(ctx.window_calls())
    if not calls:
        return None
    if params["kind"] == "fill":
        capacity = sum(c["cache_capacity"] for c in calls)
        return (100.0 * sum(c["cache_positions"] for c in calls) / capacity
                if capacity else None)
    from benchmark import opcount_latent_lm

    slots = (opcount_latent_lm.held(ctx.config)
             * opcount_latent_lm.kinds(ctx.config)[1])
    pairs = sum(c["expert_pairs"] for c in calls)
    busiest = sum(c["expert_pairs_max"] for c in calls)
    return busiest * slots / pairs if pairs else None
