"""What the traced (or the window's) generation calls did, read from
the program's own records: for each call the rows, the real and the
padded prompt tokens (span ``lm.stack``) and the root's ``new_tokens``
and ``length_rung``, and ``decode_steps`` (a call's new tokens a row,
less the first, which the prefill's logits give). ``None`` where the program left no such record (a
parent commit without the stage)."""

from benchmark import program_spans


def gather(calls):
    out = []
    for _, roots in program_spans.calls_with_roots(calls):
        for record in roots:
            stack = [s for s in record["spans"] if s["name"] == "lm.stack"]
            counts = record.get("counts") or {}
            if not stack or "new_tokens" not in counts:
                continue
            rows = sum(s["counts"].get("rows", 0) for s in stack)
            out.append({
                "rows": rows,
                "decode_steps": counts["new_tokens"] // max(rows, 1) - 1,
                "prompt_tokens": sum(s["counts"].get("prompt_tokens", 0)
                                     for s in stack),
                "padded_tokens": sum(s["counts"].get("padded_tokens", 0)
                                     for s in stack),
                "new_tokens": counts["new_tokens"],
                "length_rung": counts.get("length_rung", 0)})
    return out or None


def read(ctx, params):
    """As a metric: the share of the prompt positions processed that
    were padding, over the window's calls. Per cent."""
    calls = gather(ctx.window_calls())
    if not calls:
        return None
    padded = sum(c["padded_tokens"] for c in calls)
    real = sum(c["prompt_tokens"] for c in calls)
    return 100.0 * (padded - real) / padded if padded else None
