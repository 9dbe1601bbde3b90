"""Closed job: whole ``Estimator.fit()`` calls, back to back.

A fit starts while the window is open and the last one runs to its
end; at least one. Each call builds a fresh ``DataFrame`` over the
seeded rows and ends when ``fit()`` has returned the model (its trees
are on the host by then: the fit's last act is fetching them). Binning
and ingest are inside the call.

With ``--trace 1`` the window's first fit runs under the profiler, so
the trace and the host spans describe the same call.

The warm-up is one fit at the cell's own tree count: a shorter one
leaves programs whose shapes follow the tree count to compile in the
window. Cell parameters: ``traffic.trees`` a fit, and under ``correct``
the checks' sizes and tolerances with their reasons.
"""

import numpy as np

from benchmark.lookup import load_module


def _fit(ctx, subject, trees):
    from mmlspark_tpu.core.dataframe import DataFrame

    rows = len(subject["x"])
    with ctx.call("fit", mrow_trees=rows * trees / 1e6, trees=trees,
                  rows=rows) as call:
        df = DataFrame({"features": subject["x"], "label": subject["y"]})
        model = subject["make_estimator"](trees).fit(df)
    call.phases = dict(model.get_all_instrumentation())
    return model


def run(ctx):
    trees = ctx.cell["traffic"]["trees"]
    subject = load_module("builders", ctx.config["builder"]).build(ctx)
    ctx.emit(built=ctx.config["builder"], rows=len(subject["x"]),
             held_out=len(subject["x_held"]), at_s=ctx.since_start())

    _fit(ctx, subject, trees)                          # compiles

    ctx.open_window()
    models = []
    while True:
        if ctx.trace_on and not models:
            with ctx.traced():
                models.append(_fit(ctx, subject, trees))
        else:
            models.append(_fit(ctx, subject, trees))
        if not ctx.window_open():
            break
    ctx.close_window()

    if ctx.trace_on:
        # what device 0's kernel needs for the traced fit: its shard's
        # rows, one histogram pass a level a tree (the program may add
        # passes or, by subtraction, save some)
        shards = len(ctx.devices) if ctx.config.get("mesh") else 1
        ctx.counters["hist_levels"] = {
            "function": "hist_level",
            "shape": {"rows": len(subject["x"]) // shards,
                      "features": ctx.config["features"],
                      "bins": ctx.config["params"]["maxBin"]},
            "launches": trees * ctx.config["params"]["maxDepth"]}

    check(ctx, subject, models, ctx.cell["correct"], trees)


def check(ctx, subject, models, spec, trees):
    """``correct`` by values alone; see the cell's file for each
    tolerance and its reason."""
    from mmlspark_tpu.core.dataframe import DataFrame

    reference = load_module("reference", "gbdt")
    model = models[-1]

    # (a) the fit ran where and how the cell says, not a fallback
    stats = model.hist_stats or {}
    for key, want in spec["expect_hist_stats"].items():
        if want == "equal_shards":
            got = stats.get(key) or []
            ok = len(got) == len(ctx.devices) and len(set(got)) == 1
        else:
            ok = stats.get(key) == want
        ctx.check(ok, f"hist_stats[{key!r}] is {stats.get(key)!r}, "
                      f"the cell expects {want!r}")
    ctx.check(ctx.platform == spec["platform"],
              f"platform {ctx.platform!r}, the cell expects "
              f"{spec['platform']!r}")

    # (b) every timed fit returned the cell's number of trees
    counts = [m.booster.num_trees for m in models]
    ctx.check(all(c == trees for c in counts),
              f"timed fits returned {counts} trees, the cell fits {trees}")

    # (c) held-out accuracy against an independent GBDT
    held = DataFrame({"features": subject["x_held"]})
    pred = np.asarray(model.transform(held).col("prediction"))
    accuracy = float((pred == subject["y_held"]).mean())
    ref_accuracy = reference.reference_accuracy(
        subject["x"], subject["y"], subject["x_held"], subject["y_held"],
        ctx.config["params"], trees, spec["reference_sample_rows"],
        ctx.seed)
    ctx.check(accuracy >= ref_accuracy - spec["accuracy_tolerance"],
              f"held-out accuracy {accuracy:.5f} is under the reference's "
              f"{ref_accuracy:.5f} by more than "
              f"{spec['accuracy_tolerance']}")

    # (d) transform()'s margins against a plain traversal of the
    # model's own text
    n = spec["margin_rows"]
    sample = subject["x_held"][:n]
    raw = np.asarray(model.transform(
        DataFrame({"features": sample})).col("rawPrediction"))
    got = raw[:, -1] if raw.ndim == 2 else raw
    want = reference.margins(model.get_model_string(), sample)
    agree = float((np.abs(got - want) <= spec["margin_atol"]).mean())
    ctx.check(agree >= spec["margin_min_agreement"],
              f"margins agree with the plain traversal on {agree:.5f} of "
              f"{n} rows (atol {spec['margin_atol']}), the cell wants "
              f"{spec['margin_min_agreement']}")
    ctx.emit(check="fit", accuracy=accuracy, reference_accuracy=ref_accuracy,
             margin_agreement=agree,
             margin_max_abs_diff=float(np.abs(got - want).max()),
             trees=counts, hist_stats=stats)
