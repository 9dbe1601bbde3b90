"""Mesh-sharded batch inference (embarrassingly parallel scoring).

The reference broadcasts the model to executors and scores each Spark
partition independently (onnx/ONNXModel.scala:242-251; the per-row
booster UDF, LightGBMClassifier.scala:133). The TPU analog: model
arrays replicate (they are closed-over jit constants), rows shard over
the mesh ``dp`` axis, and XLA runs each device's shard locally — no
collectives in the scoring graph at all.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from mmlspark_tpu.parallel.mesh import DATA_AXIS, axis_size, row_sharded


def pad_rows(x: np.ndarray, multiple: int) -> tuple:
    """Pad the leading dim up to a multiple with zero rows; returns
    (padded, n_valid). Scorers are row-independent, so zero rows are
    output-safe (their outputs are sliced away) and cheaper than
    repeating real data. An empty batch pads up to one full multiple
    so downstream sharding constraints (leading dim divisible by the
    mesh axis) always hold."""
    n = x.shape[0]
    if multiple <= 1:
        return x, n
    padded = max(((n + multiple - 1) // multiple) * multiple, multiple)
    if padded == n:
        return x, n
    fill = np.zeros((padded - n,) + x.shape[1:], dtype=x.dtype)
    return np.concatenate([x, fill]), n


def bucket_ladder(max_batch: int, buckets: Optional[List[int]] = None
                  ) -> List[int]:
    """Pow2 padding ladder ending at ``max_batch`` (ascending).

    Shared by the serving data plane and the shard-rules scoring
    engine so both pad to the same rungs and the jitted scorer
    compiles once per rung. ``buckets`` overrides the ladder (values
    are clamped into [1, max_batch]; max_batch is always included so
    every batch has a rung)."""
    max_batch = max(int(max_batch), 1)
    if buckets:
        ladder = sorted({min(max(int(b), 1), max_batch) for b in buckets}
                        | {max_batch})
        return ladder
    ladder, b = [], 1
    while b < max_batch:
        ladder.append(b)
        b *= 2
    ladder.append(max_batch)
    return ladder


def bucket_for(n: int, ladder: List[int]) -> int:
    """Smallest rung >= n (top rung when n exceeds the ladder)."""
    for b in ladder:
        if n <= b:
            return b
    return ladder[-1]


def length_ladder(max_length: int, floor: int = 128) -> List[int]:
    """Padding ladder over sequence length: powers of two from
    ``floor`` up, ending at the first one that holds ``max_length``."""
    ladder, rung = [], floor
    while rung < max_length:
        ladder.append(rung)
        rung *= 2
    ladder.append(rung)
    return ladder


def length_batches(lengths: np.ndarray, rows: int, ladder: List[int]
                   ) -> List[Tuple[np.ndarray, int]]:
    """Device batches of ragged sequences: rows sorted by length (stable)
    and cut into runs of ``rows``; each run pads to the ladder rung of
    its longest row, so rows of like length share a batch and a short
    row is never padded to the column's longest. Returns ``[(row
    indices, length rung)]``."""
    order = np.argsort(np.asarray(lengths), kind="stable")
    out = []
    for start in range(0, len(order), max(int(rows), 1)):
        index = order[start:start + rows]
        out.append((index, bucket_for(int(lengths[index[-1]]), ladder)))
    return out


def sharded_apply(fn: Callable, x: Any, mesh, axis: str = DATA_AXIS):
    """Run a jitted row-wise function with inputs sharded over ``axis``.

    ``x`` is an array or a dict of arrays sharing the leading (row) dim.
    Rows are padded to the axis size, device_put row-sharded, and the
    outputs sliced back to the true row count on host. The function's
    closed-over model arrays replicate automatically.
    """
    import jax

    size = axis_size(mesh, axis)
    if isinstance(x, dict):
        n = next(iter(x.values())).shape[0]
        fed = {}
        padded = n
        for k, v in x.items():
            pv, _ = pad_rows(np.asarray(v), size)
            padded = pv.shape[0]
            fed[k] = jax.device_put(pv, row_sharded(mesh, pv.ndim, axis))
        out = fn(fed)
    else:
        x = np.asarray(x)
        n = x.shape[0]
        pv, _ = pad_rows(x, size)
        padded = pv.shape[0]
        xd = jax.device_put(pv, row_sharded(mesh, pv.ndim, axis))
        out = fn(xd)

    def unpad(a):
        a = np.asarray(a)
        # only strip rows from outputs that actually carry the batch dim
        # (reductions/scalars pass through untouched)
        return a[:n] if a.ndim >= 1 and a.shape[0] == padded else a

    return jax.tree_util.tree_map(unpad, out)
