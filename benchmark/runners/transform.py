"""Closed loop, one caller: ``model.transform()`` over partition-sized
frames, back to back.

Frames are drawn in turn from a few pre-built seeded frames, so no call
sees the frame before it. A call ends when ``transform()`` has returned
its output column on the host. Between calls the runner only checks the
output's shape and finiteness.

Cell parameters (``traffic``): ``frame_rows``, ``frames``,
``trace_calls``; under ``correct`` the reference check's size and its
comparisons, each a tolerance with its reason.
"""

import numpy as np

from benchmark.lookup import load_module


def _call(ctx, model, column, out_width):
    from mmlspark_tpu.core.dataframe import DataFrame

    with ctx.call("transform_call", rows=len(column)) as call:
        out = model.transform(DataFrame({"features": column}))
        logits = np.asarray(out.col("output"))
    with ctx.annotate("between_calls"):
        ok = (logits.shape == (len(column), out_width)
              and bool(np.isfinite(logits).all()))
        if not ok:
            ctx.counters["failed_calls"] = (
                ctx.counters.get("failed_calls", 0) + 1)
    return call, logits


def run(ctx):
    cfg, traffic = ctx.config, ctx.cell["traffic"]
    builder = load_module("builders", cfg["builder"])
    subject = builder.build(ctx)
    ctx.emit(built=cfg["builder"], parameters=subject["parameters"],
             at_s=ctx.since_start())
    frames = builder.make_frames(ctx.seed, traffic["frames"],
                                 traffic["frame_rows"], cfg["image_side"])
    model, classes = subject["model"], cfg["classes"]
    ctx.emit(frames=len(frames), frame_rows=traffic["frame_rows"],
             at_s=ctx.since_start())

    _call(ctx, model, frames[-1], classes)              # compiles

    ctx.open_window()
    i = 0
    while True:
        _call(ctx, model, frames[i % len(frames)], classes)
        i += 1
        if not ctx.window_open():
            break
    ctx.close_window()

    if ctx.trace_on:
        with ctx.traced():
            for j in range(traffic["trace_calls"]):
                _call(ctx, model, frames[j % len(frames)], classes)
        ctx.counters["model_flops"] = {
            "function": cfg["flops_function"],
            "shape": {"image": cfg["image_side"],
                      "stages": subject["stages"],
                      "stem": cfg["stem_width"], "classes": classes}}

    check(ctx, subject, frames, ctx.cell["correct"])


def check(ctx, subject, frames, spec):
    """Logits of a few seeded images against the plain reference, once
    for each entry of the cell's ``comparisons``: the reference's
    precision, the largest error allowed (over the largest reference
    logit) and the reason for it."""
    ctx.check(ctx.platform == spec["platform"],
              f"platform {ctx.platform!r}, the cell expects "
              f"{spec['platform']!r}")
    ctx.check(ctx.counters.get("failed_calls", 0) == 0,
              f"{ctx.counters.get('failed_calls')} timed calls returned "
              "the wrong shape or a non-finite logit")
    n = spec["reference_images"]
    column = frames[0][:n]
    _, got = _call(ctx, subject["model"], column, ctx.config["classes"])
    reference = load_module("reference", ctx.config["reference"])
    for comparison in spec["comparisons"]:
        precision = comparison["precision"]
        want = reference.logits(subject["weights"], np.stack(list(column)),
                                subject["stages"], precision)
        scale = float(np.abs(want).max())
        err = float(np.abs(got - want).max()) / scale
        ctx.check(err <= comparison["max_rel_err"],
                  f"logits differ from the plain reference at {precision} "
                  f"precision by {err:.3e} of their scale ({scale:.3e}); "
                  f"the cell allows {comparison['max_rel_err']}")
        ctx.emit(check="transform", reference_images=n, precision=precision,
                 logit_scale=scale, max_err_over_scale=err,
                 rms_err_over_scale=float(
                     np.sqrt(np.mean((got - want) ** 2))) / scale)
