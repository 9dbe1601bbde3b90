"""Benchmark: VW contextual-bandit training throughput.

BASELINE.json's tracked configs include a VW contextual-bandit run.
Measures end-to-end fit throughput (featurize + IPS-weighted online
updates) at a d=50-feature, 10-action workload.

Prints ONE JSON line. Run: python tools/bench_vw.py [rows]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if args else 200_000
    from bench import device_stamp
    stamp = device_stamp()

    import jax
    import numpy as np

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.vw.bandit import VowpalWabbitContextualBandit

    rng = np.random.default_rng(0)
    d, actions = 50, 10
    x = rng.normal(size=(n, d))
    chosen = rng.integers(1, actions + 1, size=n)
    best = (np.abs(x[:, 0] * 3).astype(int) % actions) + 1
    cost = np.where(chosen == best, 0.0, 1.0)
    prob = np.full(n, 1.0 / actions)
    df = DataFrame({"features": x,
                    "chosenAction": chosen.astype(np.float64),
                    "label": cost, "probability": prob})
    cb = VowpalWabbitContextualBandit(numActions=actions, numPasses=1)
    cb.fit(df)  # warm compile
    t0 = time.perf_counter()
    cb.fit(df)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "vw_bandit_fit",
        "value": round(n / dt, 1),
        "unit": "rows/s",
        "actions": actions,
        "backend": jax.default_backend(),
        **stamp,
    }))


if __name__ == "__main__":
    main()
