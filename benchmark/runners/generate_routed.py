"""``generate`` (the same closed loop, calls, window, traced part and
check, from ``runners/generate.py`` as it is) for a model with sparse
experts, whose comparison with the plain reference needs one thing
more.

A top-k choice of experts can flip on rounding where the k-th and the
(k+1)-th score nearly tie, and the flipped position then differs from
the reference by tens of per cent though nothing is wrong. The
reference (``logits(..., margins=True)``) returns each position's least
routing margin over the expert layers and the experts held, and here
the three maxima of ``generate.check`` are taken over the positions
whose margin is at least ``correct.margin_min``; the root mean square
stays over every position. The share of positions kept is printed and
held to ``correct.margin_kept_floor``; the two largest logit errors of
the positions in each band of margins are printed too (facts for
whoever sets the threshold). No choice of the program's is handed
to the reference. Also checked: the program's own count of pairs that
no tile served (``dropped_pairs``) is 0 over the window's calls.
"""

import numpy as np

from benchmark import program_spans
from benchmark.lookup import load_module


BANDS = (0.0, 0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 2.0, 3.0)   # of margin_min


def margin_errors(margin_min, kept, bands=None):
    """``generate._errors`` with the maxima over the positions whose
    routing margin is at least ``margin_min``; ``kept`` gathers
    ``{precision: [positions kept, positions]}`` and ``bands`` every
    position's largest logit error over the scale, by its margin:
    ``{precision: [[errors of band 0], ...]}``, band ``i`` from
    ``BANDS[i] * margin_min`` up."""

    def errors(reference, subject, prompt, tokens, logprobs, logits, length,
               precision):
        import jax
        import jax.numpy as jnp

        new = len(tokens)
        ids = np.zeros(length, np.int32)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + new] = tokens
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + new)
        want, margin = reference.logits(
            subject["weights"], ids, subject["model_config"], precision,
            positions=at, margins=True)
        want, margin = np.asarray(want), np.asarray(margin)
        keep = margin >= margin_min
        count = kept.setdefault(precision, [0, 0])
        count[0] += int(keep.sum())
        count[1] += new
        scale = float(np.abs(want).max())
        picked = np.asarray(jax.nn.log_softmax(jnp.asarray(want)))[
            np.arange(new), tokens]
        gap = want.max(axis=1) - want[np.arange(new), tokens]

        def largest(per_position):
            return float(per_position[keep].max()) / scale if keep.any() \
                else 0.0

        out = {"logprob": largest(np.abs(picked - logprobs)),
               "argmax_gap": largest(gap)}
        if logits is not None:
            each = np.abs(logits - want).max(axis=1)
            out["logits"] = largest(each)
            out["logits_rms"] = float(
                np.sqrt(np.mean((logits - want) ** 2))) / scale
            if bands is not None:
                band = np.searchsorted(np.asarray(BANDS) * margin_min,
                                       margin, side="right") - 1
                lists = bands.setdefault(precision,
                                         [[] for _ in BANDS])
                for i, err in zip(band, each / scale):
                    lists[i].append(float(err))
        return out, scale

    return errors


def run(ctx):
    generate = load_module("runners", "generate")   # a copy of its own
    spec = ctx.cell["correct"]
    kept, bands = {}, {}
    generate._errors = margin_errors(spec["margin_min"], kept, bands)
    generate.run(ctx)

    for comparison in spec["comparisons"]:
        some, every = kept.get(comparison["precision"], (0, 0))
        share = some / every if every else 0.0
        ctx.emit(check="routing_margin", precision=comparison["precision"],
                 margin_min=spec["margin_min"], positions_kept=some,
                 positions=every, kept_share=share,
                 logits_err_by_margin=[
                     {"margin_from": edge * spec["margin_min"],
                      "positions": len(errs),
                      "largest": sorted(errs)[-2:][::-1]}
                     for edge, errs in zip(BANDS, bands.get(
                         comparison["precision"], []))])
        ctx.check(share >= spec["margin_kept_floor"],
                  f"only {share:.3f} of the compared positions have a "
                  f"routing margin of {spec['margin_min']} or more at "
                  f"{comparison['precision']} precision; the cell asks for "
                  f"{spec['margin_kept_floor']}")
    counts = [record.get("counts") or {}
              for _, roots in program_spans.calls_with_roots(
                  ctx.window_calls()) for record in roots]
    dropped = sum(c.get("dropped_pairs", 0) for c in counts)
    ctx.emit(check="experts", dropped_pairs=dropped,
             expert_pairs=sum(c.get("expert_pairs", 0) for c in counts))
    ctx.check(dropped == 0, f"{dropped} pairs routed to the experts held "
              "here were served by no tile")
    ctx.emit(checked_at_s=ctx.since_start())
