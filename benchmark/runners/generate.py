"""Closed loop, one caller: ``CausalLM.transform()`` over frames of
ragged prompts, back to back; every row generates ``new_tokens`` greedy
tokens.

Frames are drawn in turn from a few pre-built seeded frames. A call
ends when ``transform()`` has returned the completions and their
log-probabilities on the host. The timed call is named
``transform_call`` and carries ``rows``, so the annotations and the
readers the harness has for a transform apply. Between calls the runner
only checks shapes and finiteness.

Cell parameters (``traffic``): ``frame_rows``, ``frames``,
``new_tokens``, the prompt lengths' log-normal (``length_median``,
``length_sigma``, ``length_min``, ``length_max``), ``trace_calls``;
under ``correct`` the number of rows checked against the plain
reference and the comparisons, each a tolerance with its reason, and
``must_fail``: the reference's lower precisions, whose errors are read
(as facts, no verdict) only where ``BENCH_LOWER_PRECISION`` is set.
"""

import os

import numpy as np

from benchmark.lookup import load_module


def _call(ctx, model, column, new_tokens):
    from mmlspark_tpu.core.dataframe import DataFrame

    prompt_tokens = int(sum(len(p) for p in column))
    with ctx.call("transform_call", rows=len(column),
                  prompt_tokens=prompt_tokens,
                  new_tokens=new_tokens * len(column)) as call:
        out = model.transform(DataFrame({"prompt": column}))
        tokens = np.asarray(out.col("completion"))
        logprobs = np.asarray(out.col("logprobs"))
    with ctx.annotate("between_calls"):
        ok = (tokens.shape == logprobs.shape == (len(column), new_tokens)
              and bool(np.isfinite(logprobs).all()))
        if not ok:
            ctx.counters["failed_calls"] = (
                ctx.counters.get("failed_calls", 0) + 1)
    return call, tokens, logprobs


def run(ctx):
    cfg, traffic = ctx.config, ctx.cell["traffic"]
    builder = load_module("builders", cfg["builder"])
    subject = builder.build(ctx)
    ctx.emit(built=cfg["builder"], parameters=subject["parameters"],
             at_s=ctx.since_start())
    frames = builder.make_frames(ctx.seed, traffic["frames"],
                                 traffic["frame_rows"], traffic,
                                 cfg["vocab_size"])
    model, new = subject["model"], traffic["new_tokens"]
    ctx.emit(frames=len(frames), frame_rows=traffic["frame_rows"],
             prompt_lengths=[sorted(len(p) for p in f) for f in frames],
             at_s=ctx.since_start())

    for frame in frames:                    # compiles every shape used
        _call(ctx, model, frame, new)
    ctx.emit(warmed_up_at_s=ctx.since_start())

    ctx.open_window()
    i, timed = 0, None
    while True:
        _, tokens, logprobs = _call(ctx, model, frames[i % len(frames)], new)
        if timed is None:
            timed = (frames[i % len(frames)], tokens, logprobs)
        i += 1
        if not ctx.window_open():
            break
    ctx.close_window()

    if ctx.trace_on:
        with ctx.traced():
            for j in range(traffic["trace_calls"]):
                _call(ctx, model, frames[j % len(frames)], new)
        ctx.counters["lm_shape"] = dict(
            subject["model_config"], prefill_chunk=cfg["prefillChunk"])

    check(ctx, subject, timed, ctx.cell["correct"])


def _errors(reference, subject, prompt, tokens, logprobs, logits, length,
            precision):
    """One row against the reference's forward over ``prompt`` and then
    ``tokens`` (teacher forced), padded to ``length``: the model is
    causal, so what follows the last emitted id moves nothing before it,
    and the reference compiles once a precision. Errors over the row's
    largest reference logit."""
    import jax
    import jax.numpy as jnp

    new = len(tokens)
    ids = np.zeros(length, np.int32)
    ids[:len(prompt)] = prompt
    ids[len(prompt):len(prompt) + new] = tokens
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + new)
    want = np.asarray(reference.logits(
        subject["weights"], ids, subject["model_config"], precision,
        positions=at))
    scale = float(np.abs(want).max())
    picked = np.asarray(jax.nn.log_softmax(jnp.asarray(want)))[
        np.arange(new), tokens]
    gap = want.max(axis=1) - want[np.arange(new), tokens]
    out = {"logprob": float(np.abs(picked - logprobs).max()) / scale,
           "argmax_gap": float(gap.max()) / scale}
    if logits is not None:
        out["logits"] = float(np.abs(logits - want).max()) / scale
        out["logits_rms"] = float(
            np.sqrt(np.mean((logits - want) ** 2))) / scale
    return out, scale


def check(ctx, subject, timed, spec):
    """A few rows of a frame the window timed, against the plain
    reference, once for each of the cell's ``comparisons``. Three
    errors, each over the largest reference logit and each held to the
    comparison's ``max_rel_err`` (the root mean square of (c) to the
    cell's ``rms_rel_err``, against the ``rms_precision`` reference): (a) the log-probabilities the timed
    call returned against the reference's at the emitted ids; (b) how
    far each emitted id's reference logit lies below the reference's
    largest; (c) every logit of one more call for those rows alone (the
    stage's ``logitsCol``; another row rung, another length rung, other
    neighbours) against the reference's over that call's own ids. With
    seeded weights the largest logit changes on rounding, so ids are
    never compared with ids."""
    from mmlspark_tpu.core.dataframe import DataFrame

    ctx.check(ctx.platform == spec["platform"],
              f"platform {ctx.platform!r}, the cell expects "
              f"{spec['platform']!r}")
    ctx.check(ctx.counters.get("failed_calls", 0) == 0,
              f"{ctx.counters.get('failed_calls')} timed calls returned "
              "the wrong shape or a non-finite log-probability")
    frame, tokens, logprobs = timed
    n = spec["reference_rows"]
    model = subject["model"]
    new = tokens.shape[1]
    reference = load_module("reference", ctx.config["reference"])

    model._set(logitsCol="logits")
    out = model.transform(DataFrame({"prompt": frame[:n]}))
    again = {"tokens": np.asarray(out.col("completion")),
             "logprobs": np.asarray(out.col("logprobs")),
             "logits": np.stack(list(out.col("logits")))}
    same = int((again["tokens"] == tokens[:n]).sum())
    longest = max(len(frame[r]) for r in range(n)) + new
    for comparison in spec["comparisons"]:
        precision, limit = comparison["precision"], comparison["max_rel_err"]
        worst, scale = {}, 0.0
        for r in range(n):
            timed_err, row_scale = _errors(
                reference, subject, np.asarray(frame[r]), tokens[r],
                logprobs[r], None, longest, precision)
            again_err, _ = _errors(
                reference, subject, np.asarray(frame[r]),
                again["tokens"][r], again["logprobs"][r],
                again["logits"][r], longest, precision)
            scale = max(scale, row_scale)
            for what, err in list(timed_err.items()) + [
                    ("logits", again_err["logits"]),
                    ("logits_rms", again_err["logits_rms"]),
                    ("logprob_alone", again_err["logprob"])]:
                worst[what] = max(worst.get(what, 0.0), err)
        rms = worst.pop("logits_rms")
        if precision == spec.get("rms_precision"):
            ctx.check(rms <= spec["rms_rel_err"],
                      f"the logits' root-mean-square error against the "
                      f"plain reference at {precision} precision is "
                      f"{rms:.3e} of their scale; the cell allows "
                      f"{spec['rms_rel_err']}")
        for what, err in worst.items():
            ctx.check(err <= limit,
                      f"{what} differs from the plain reference at "
                      f"{precision} precision by {err:.3e} of the logits' "
                      f"scale ({scale:.3e}); the cell allows {limit}")
        ctx.emit(check="generate", reference_rows=n, precision=precision,
                 logit_scale=scale, max_rel_err=limit,
                 ids_equal_alone=same, ids_compared=int(tokens[:n].size),
                 logits_rms_err_over_scale=rms,
                 **{f"{k}_err_over_scale": v for k, v in worst.items()})
    if not os.environ.get("BENCH_LOWER_PRECISION"):
        return
    for precision in spec.get("must_fail", []):
        # readings for whoever sets the limits (BENCH_LOWER_PRECISION=1):
        # the reference in the precisions below the configuration's,
        # which the cell's limits have to refuse (PERF.md has them)
        err, scale = _errors(reference, subject, np.asarray(frame[0]),
                             again["tokens"][0], again["logprobs"][0],
                             again["logits"][0], longest, precision)
        ctx.emit(check="generate_lower_precision", precision=precision,
                 logit_scale=scale,
                 **{f"{k}_err_over_scale": v for k, v in err.items()})
