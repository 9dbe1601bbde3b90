"""Chunked host->device ingest (VERDICT r2 #9; reference
StreamingPartitionTask.scala:203-277 micro-batch push)."""

import time

import numpy as np
import pytest

from mmlspark_tpu.ops.ingest import binned_ingest_dtype, chunked_device_put


def test_chunked_matches_monolithic(rng):
    import jax.numpy as jnp

    x = rng.integers(0, 255, size=(10_000, 7)).astype(np.int32)
    got = chunked_device_put(x, dtype=np.uint8, chunk_bytes=8_192)
    assert got.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got), x.astype(np.uint8))
    # small arrays fall through to one put
    small = chunked_device_put(x[:8], dtype=np.uint8)
    np.testing.assert_array_equal(np.asarray(small), x[:8].astype(np.uint8))


def test_chunked_sharded_ingest(mesh8, rng):
    from mmlspark_tpu.parallel.mesh import row_sharded

    x = rng.integers(0, 64, size=(4_096, 5)).astype(np.int64)
    got = chunked_device_put(x, row_sharded(mesh8, 2), dtype=np.uint8,
                             chunk_bytes=4_096, row_multiple=8)
    assert len({s.device for s in got.addressable_shards}) == 8
    np.testing.assert_array_equal(np.asarray(got), x.astype(np.uint8))


def test_binned_dtype_selection():
    assert binned_ingest_dtype(255) == np.uint8
    assert binned_ingest_dtype(256) == np.uint8
    assert binned_ingest_dtype(257) == np.uint16
    assert binned_ingest_dtype(65536) == np.uint16
    assert binned_ingest_dtype(65537) == np.int32


def test_uint8_binned_training_parity(rng):
    """The trainer now ingests uint8 bins; results must match an int32
    run bit-for-bit (promotion happens in index math, not data)."""
    from mmlspark_tpu.models.gbdt.trainer import TrainConfig, train
    from mmlspark_tpu.ops.binning import BinMapper

    x = rng.normal(size=(2_000, 6))
    y = (x[:, 0] - x[:, 1] > 0).astype(np.float64)
    mapper = BinMapper.fit(x, max_bin=64)
    binned = mapper.transform(x)
    cfg = TrainConfig(objective="binary", num_iterations=4, num_leaves=8,
                      max_depth=3, max_bin=64)
    r1 = train(binned, y, cfg, bin_upper=mapper.bin_upper_values(64))
    # the binned matrix arrives int32 from BinMapper; train() narrows it
    assert r1.booster.num_trees == 4
    cfg2 = TrainConfig(objective="binary", num_iterations=4, num_leaves=8,
                       max_depth=3, max_bin=300)  # forces int32 path
    r2 = train(np.asarray(binned, np.int32), y, cfg2,
               bin_upper=np.pad(mapper.bin_upper_values(64),
                                ((0, 0), (0, 300 - 64)),
                                constant_values=np.inf))
    p1 = np.asarray(r1.booster.predict_jit()(x))
    p2 = np.asarray(r2.booster.predict_jit()(x))
    np.testing.assert_allclose(p1, p2, atol=1e-6)


def test_overlap_not_slower_than_monolithic(rng):
    """Sanity: chunked ingest of a large array is within 2x of one put
    (and usually faster once host prep is nontrivial)."""
    import jax

    x = rng.integers(0, 255, size=(400_000, 28)).astype(np.int32)

    t0 = time.perf_counter()
    a = jax.device_put(np.ascontiguousarray(x.astype(np.uint8)))
    a.block_until_ready()
    mono = time.perf_counter() - t0

    t0 = time.perf_counter()
    b = chunked_device_put(x, dtype=np.uint8)
    b.block_until_ready()
    chunked = time.perf_counter() - t0
    assert chunked < max(mono * 2.0, 0.5), (chunked, mono)


def _object_column(x):
    col = np.empty(len(x), dtype=object)
    for i in range(len(x)):
        col[i] = x[i]
    return col


def test_concatenate_is_traced_once_for_equal_shapes(rng):
    """The chunks' concatenate is one jitted function a sharding, made
    once (it was a fresh ``jax.jit`` a call: a retrace each)."""
    from mmlspark_tpu.ops.ingest import _concatenate

    _concatenate.cache_clear()
    x = rng.integers(0, 255, size=(1_000, 5)).astype(np.int32)
    y = rng.integers(0, 255, size=(1_000, 5)).astype(np.int32)
    a = chunked_device_put(x, dtype=np.uint8, chunk_bytes=1_024)
    concat = _concatenate(None)
    assert concat._cache_size() == 1
    b = chunked_device_put(y, dtype=np.uint8, chunk_bytes=1_024)
    assert _concatenate(None) is concat and concat._cache_size() == 1
    np.testing.assert_array_equal(np.asarray(a), x.astype(np.uint8))
    np.testing.assert_array_equal(np.asarray(b), y.astype(np.uint8))
    chunked_device_put(x[:900], dtype=np.uint8, chunk_bytes=1_024)
    assert concat._cache_size() == 2     # other shapes: JAX's own cache


@pytest.mark.parametrize("rows,dtype", [(1_000, None), (1_000, np.float32),
                                        (7, None)])
def test_row_source_matches_monolithic(rng, rows, dtype):
    """An object column put as a row source, in chunks (1000 rows) or
    whole (7), is the array ``np.stack`` and one put would have given;
    ``dtype`` casts as ``astype`` does, a chunk at a time."""
    import jax

    from mmlspark_tpu.core.timer import span
    from mmlspark_tpu.ops.ingest import RowSource

    x = rng.normal(size=(rows, 3, 5))
    source = RowSource(_object_column(x), "rows.stack", dtype)
    assert source.shape == x.shape and len(source) == rows
    assert source.dtype == (dtype or np.float64)
    want = np.stack(list(_object_column(x))).astype(source.dtype)
    assert source.nbytes == want.nbytes and source.ndim == want.ndim
    with span("root") as root:
        got = chunked_device_put(source, chunk_bytes=4_096,
                                 span_name="rows.put")
    # (a float64 array is put as float32 unless JAX runs in 64 bits)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jax.device_put(want)))
    chunk_rows = 4_096 // (15 * source.dtype.itemsize)
    chunks = -(-rows // chunk_rows) if rows > chunk_rows else 1
    names = [s.name for s in root.spans]
    assert names == ["rows.stack", "rows.put"] * chunks
    assert {s.counts["chunks"] for s in root.spans
            if s.name == "rows.put"} == {chunks}
    # a window past the last row is rows of zeros (a scorer's padding)
    tail = source.window(rows - 2, 5)
    assert tail.shape == (5, 3, 5)
    np.testing.assert_array_equal(tail.lay_out(0, 5)[:2], want[-2:])
    assert not tail.lay_out(0, 5)[2:].any()
    assert not source.window(rows + 3, 4).lay_out(0, 4).any()
