"""Benchmark: GBDT batch scoring — raw vs binned vs the sklearn anchor.

The reference's inference path is the per-row JNI predict UDF
(booster/LightGBMBooster.scala:394,520-557) that SURVEY calls "the
throughput baseline a TPU batch-scoring kernel must beat". This bench
anchors our batch scorer against a MEASURED comparator on the same
machine — sklearn HistGradientBoostingClassifier ``predict`` (the same
histogram-GBDT family the reference wraps) — and A/Bs the binned
formulation (uint8 ``threshold_bin`` compares, VERDICT r4 #4) against
raw float-threshold traversal, with the binning cost reported both
included and excluded.

Model/data shape mirrors bench.py's tracked HIGGS-style config:
100 trees, depth 6 (63 leaves), 28 features; scoring 2M rows.

Prints ONE JSON line:
{"metric", "value" (best ours, Mrow/s), "unit", "backend",
 "variants": {raw, binned, binned_incl_binning, sklearn_anchor},
 "vs_anchor"}.
Run: python tools/bench_scoring.py [n_rows] [--small]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n_score = int(args[0]) if args else 2_000_000
    if "--small" in sys.argv:
        n_score = min(n_score, 100_000)
    from bench import device_stamp
    stamp = device_stamp()

    import jax
    import numpy as np

    from mmlspark_tpu.models.gbdt.trainer import TrainConfig, train
    from mmlspark_tpu.ops.binning import BinMapper

    backend = jax.default_backend()
    trees, depth, f, max_bin = 100, 6, 28, 255
    n_train = 200_000

    rng = np.random.default_rng(0)
    xt = rng.normal(size=(n_train, f)).astype(np.float64)
    yt = (xt[:, 0] + 0.5 * xt[:, 1] * xt[:, 2]
          + 0.2 * rng.normal(size=n_train) > 0).astype(np.float64)
    mapper = BinMapper.fit(xt, max_bin=max_bin)
    cfg = TrainConfig(objective="binary", num_iterations=trees,
                      num_leaves=63, max_depth=depth, min_data_in_leaf=20,
                      max_bin=max_bin)
    res = train(mapper.transform(xt), yt, cfg,
                bin_upper=mapper.bin_upper_values(max_bin))
    booster = res.booster

    x = rng.normal(size=(n_score, f)).astype(np.float32)

    def timed(fn, *a):
        fn(*a)  # warm (compile)
        t0 = time.perf_counter()
        out = fn(*a)
        jax.block_until_ready(out)
        return n_score / (time.perf_counter() - t0) / 1e6

    raw_fn = booster.predict_jit()
    raw_mrows = timed(raw_fn, x)

    from mmlspark_tpu.ops.ingest import binned_ingest_dtype

    binned_fn = booster.predict_binned_jit()
    narrow = binned_ingest_dtype(max_bin)
    xb = mapper.transform(x).astype(narrow)
    binned_mrows = timed(binned_fn, xb)

    # end-to-end binned: re-bin each call (the C++ data plane / numpy
    # searchsorted path) + traversal
    def bin_and_score(xx):
        return binned_fn(mapper.transform(xx).astype(narrow))

    binned_incl = timed(bin_and_score, x)

    # imported-model path: a LightGBM model string carries raw-value
    # thresholds only; derive_binning() recovers per-feature threshold
    # tables from the model's own splits so imports score binned too
    from mmlspark_tpu.models.gbdt.booster import BoosterArrays
    imported = BoosterArrays.load_model_string(booster.save_model_string())
    derived_binning, derived = imported.derive_binning()
    derived_fn = derived.predict_binned_jit()
    xdb = derived_binning.transform(x)
    derived_mrows = timed(derived_fn, xdb)

    # anchor: sklearn HistGradientBoosting predict, same tree count/
    # depth family, measured on this machine (single-core)
    sk_mrows = None
    try:
        from sklearn.ensemble import HistGradientBoostingClassifier
        sk = HistGradientBoostingClassifier(
            max_iter=trees, max_depth=depth, max_leaf_nodes=63,
            max_bins=max_bin, early_stopping=False)
        n_sk_train = min(n_train, 50_000)  # fit is not what's measured
        sk.fit(xt[:n_sk_train], yt[:n_sk_train])
        sk.predict(x[:10_000])  # warm any lazy init
        t0 = time.perf_counter()
        sk.predict(x)
        sk_mrows = n_score / (time.perf_counter() - t0) / 1e6
    except Exception as e:  # anchor failure must not kill our number
        print(f"# sklearn anchor failed: {e!r}", file=sys.stderr)

    best = max(raw_mrows, binned_mrows)
    out = {
        "metric": "gbdt_batch_scoring",
        "value": round(best, 4),
        "unit": "Mrow/s",
        "backend": backend,
        **stamp,
        "n_rows": n_score,
        "trees": trees,
        "variants": {
            "raw": round(raw_mrows, 4),
            "binned": round(binned_mrows, 4),
            "binned_incl_binning": round(binned_incl, 4),
            "imported_derived_binned": round(derived_mrows, 4),
            "sklearn_anchor": None if sk_mrows is None
            else round(sk_mrows, 4),
        },
        "vs_anchor": None if sk_mrows is None
        else round(best / sk_mrows, 4),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
