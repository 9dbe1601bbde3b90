"""Benchmark: lambdarank (MSLR-WEB30K-shaped) training throughput + NDCG.

BASELINE.md's tracked configs name the reference's lambdarank barrier-
mode run (lightgbm/.../params/RankerTrainParams.scala) — the one tracked
config with no bench until now (VERDICT r4 #3). Zero egress, so the
data is an MSLR-shaped synthetic: ~130 docs/query (MSLR averages ~120),
136 features, graded 0-4 relevance generated from a hidden linear
utility + noise, which gives the lambdarank objective real pair
structure to learn.

Prints ONE JSON line:
{"metric", "value" (Mrow-trees/s of fit), "unit", "backend",
 "ndcg@10" (train-set NDCG after fit, sanity floor 0.6)}.
Run: python tools/bench_ranker.py [n_queries] [--small]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_mslr_shaped(n_queries: int, f: int = 136, seed: int = 0,
                     skewed: bool = False):
    """Graded-relevance synthetic with MSLR-like shape: variable group
    sizes (80-180 docs; ``skewed`` draws log-uniform 8..1200 like real
    MSLR's long tail), relevance 0-4 from a hidden utility quantized
    per-query (so every query has a mix of grades)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    if skewed:
        sizes = np.exp(rng.uniform(np.log(8), np.log(1200),
                                   size=n_queries)).astype(np.int64)
    else:
        sizes = rng.integers(80, 181, size=n_queries)
    n = int(sizes.sum())
    x = rng.normal(size=(n, f)).astype(np.float64)
    w_true = rng.normal(size=f) * (rng.random(f) < 0.15)  # sparse signal
    util = x @ w_true + 0.5 * rng.normal(size=n)
    group_ids = np.repeat(np.arange(n_queries), sizes)
    # per-query quantile grading -> labels 0..4
    labels = np.zeros(n)
    start = 0
    for qs in sizes:
        u = util[start:start + qs]
        qt = np.quantile(u, [0.5, 0.75, 0.9, 0.97])
        labels[start:start + qs] = np.searchsorted(qt, u)
        start += qs
    return x, labels, group_ids


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n_queries = int(args[0]) if args else 2000
    trees = 100
    skewed = "--skewed" in sys.argv
    if "--small" in sys.argv:
        n_queries, trees = 100, 10
    from bench import device_stamp
    stamp = device_stamp()

    import jax
    import numpy as np

    from mmlspark_tpu.models.gbdt.metrics import ndcg_at
    from mmlspark_tpu.models.gbdt.trainer import TrainConfig, train
    from mmlspark_tpu.ops.binning import BinMapper

    backend = jax.default_backend()
    x, labels, group_ids = make_mslr_shaped(n_queries, skewed=skewed)
    n = x.shape[0]
    max_bin = 255
    mapper = BinMapper.fit(x, max_bin=max_bin)
    binned = mapper.transform(x)
    bu = mapper.bin_upper_values(max_bin)
    cfg = TrainConfig(objective="lambdarank", num_iterations=trees,
                      num_leaves=63, max_depth=6, min_data_in_leaf=20,
                      max_bin=max_bin, eval_at=10,
                      lambdarank_truncation_level=30)

    # warm run compiles the fused step (steady-state semantics, as
    # bench.py); second run is the measured one
    train(binned, labels, cfg, bin_upper=bu, group_ids=group_ids)
    t0 = time.perf_counter()
    res = train(binned, labels, cfg, bin_upper=bu, group_ids=group_ids)
    dt = time.perf_counter() - t0
    mrow_trees = n * trees / dt / 1e6

    import jax.numpy as jnp
    raw = res.booster.predict_jit()(x)
    ndcg = float(ndcg_at(10)(jnp.asarray(raw), jnp.asarray(labels),
                             group_ids=jnp.asarray(group_ids)))

    print(json.dumps({
        "metric": "lambdarank_fit" + ("_skewed" if skewed else ""),
        "value": round(mrow_trees, 4),
        "unit": "Mrow-trees/s",
        "backend": backend,
        **stamp,
        "n_rows": n,
        "n_queries": n_queries,
        "trees": trees,
        "ndcg@10": round(ndcg, 4),
        "fit_seconds": round(dt, 2),
    }))


if __name__ == "__main__":
    main()
