"""HIGGS-shaped data and the estimator for the GBDT configurations.

``make_data`` is ``chip_smoke.make_data`` / ``bench.py``'s generator
(28 standard-normal float32 features; the label is a noisy nonlinear
function of the first five), copied here so that a later PR to those
scripts cannot move the yardstick. Departures: the seed is the run's
``--seed`` instead of 0; the normals are drawn as float32 directly; and
the rows are made in blocks of 2**20 on a few threads, each block from
its own child of the seed (``SeedSequence.spawn``), so the data depend
on the seed and the row count only, never on the threads.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 20


def make_data(n, seed, features=28, threads=8):
    x = np.empty((n, features), np.float32)
    y = np.empty(n, np.float64)
    starts = range(0, n, BLOCK)
    children = np.random.SeedSequence(seed).spawn(len(starts))

    def fill(job):
        start, child = job
        rng = np.random.default_rng(child)
        xb = x[start:start + BLOCK]
        rng.standard_normal(size=xb.shape, dtype=np.float32, out=xb)
        logit = (xb[:, 0] * 1.2 - xb[:, 1] + 0.5 * xb[:, 2] * xb[:, 3]
                 + 0.3 * np.sin(xb[:, 4] * 3))
        noise = rng.standard_normal(size=len(xb), dtype=np.float32)
        y[start:start + BLOCK] = logit + noise * 0.5 > 0

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, zip(starts, children)))
    return x, y


def build(ctx):
    """Training rows, held-out rows (never fitted on), and a factory for
    the configuration's estimator at a given number of trees."""
    from mmlspark_tpu.models.gbdt import estimators

    cfg = ctx.config
    rows, held = cfg["rows"], cfg["held_out_rows"]
    x, y = make_data(rows + held, ctx.seed, cfg["features"])
    est_class = getattr(estimators, cfg["estimator"])
    params = dict(cfg["params"])

    def make_estimator(trees):
        est = est_class(**{**params, "numIterations": trees})
        if cfg.get("mesh") == "all_devices":
            from mmlspark_tpu.parallel.mesh import create_mesh
            est = est.set_mesh(create_mesh())
        return est

    return {"x": x[:rows], "y": y[:rows], "x_held": x[rows:],
            "y_held": y[rows:], "make_estimator": make_estimator}
