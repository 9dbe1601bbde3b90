"""What the generation calls of a model with expert layers did:
``lm_calls.gather``'s record of each call (rows, prompt and padded
tokens, new tokens, decode steps, length rung) with the root's
``expert_pairs``, ``expert_pairs_max``, ``dropped_pairs`` and
``cache_bytes`` beside it. ``None`` where the program left no such
record (a parent commit without the expert layer).

As a metric (``moe_load_imbalance``): the busiest held expert's pairs
over the mean a held expert and layer, over the window's calls. A
ratio; 1 is an even spread.
"""

from benchmark import program_spans

KEYS = ("expert_pairs", "expert_pairs_max", "dropped_pairs", "cache_bytes")


def gather(calls):
    out = []
    for _, roots in program_spans.calls_with_roots(calls):
        for record in roots:
            stack = [s for s in record["spans"] if s["name"] == "lm.stack"]
            counts = record.get("counts") or {}
            if not stack or "expert_pairs" not in counts:
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
    cfg = ctx.config
    if not calls:
        return None
    from benchmark import opcount_hybrid_lm

    slots = (opcount_hybrid_lm.held(cfg)
             * opcount_hybrid_lm.kinds(cfg)[3])
    pairs = sum(c["expert_pairs"] for c in calls)
    busiest = sum(c["expert_pairs_max"] for c in calls)
    return busiest * slots / pairs if pairs else None
