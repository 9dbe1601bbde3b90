"""Shard-rules layer suite: rule matching, the pad/bucket helpers, and
the engine's bitwise contract — sharded transform output at dp=1/2/8
is byte-identical to the serial path (autocast off), because every
dispatch feeds a constant per-device rung regardless of mesh size.

The ``shard_rules_smoke`` subset runs as a dp=8 virtual-device CI step
(.github/workflows/lint.yml), mirroring quant_smoke/shard_smoke.
"""

import numpy as np
import pytest

from mmlspark_tpu.core.dataframe import DataFrame

smoke = pytest.mark.shard_rules_smoke


@pytest.fixture(scope="module")
def mesh2():
    import jax

    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh
    return create_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])


# --- pad_rows edge cases -------------------------------------------------

def test_pad_rows_zero_rows_pads_full_multiple():
    from mmlspark_tpu.parallel.inference import pad_rows
    x = np.empty((0, 3), np.float32)
    padded, n = pad_rows(x, 8)
    assert n == 0
    assert padded.shape == (8, 3)
    assert (padded == 0).all()


def test_pad_rows_multiple_one_is_identity():
    from mmlspark_tpu.parallel.inference import pad_rows
    x = np.arange(6, dtype=np.float32).reshape(3, 2)
    padded, n = pad_rows(x, 1)
    assert n == 3
    assert padded is x


def test_pad_rows_exact_multiple_is_identity():
    from mmlspark_tpu.parallel.inference import pad_rows
    x = np.arange(8, dtype=np.float32).reshape(4, 2)
    padded, n = pad_rows(x, 4)
    assert n == 4
    assert padded is x


def test_pad_rows_pads_with_zero_rows():
    from mmlspark_tpu.parallel.inference import pad_rows
    x = np.ones((5, 2), np.float32)
    padded, n = pad_rows(x, 4)
    assert n == 5
    assert padded.shape == (8, 2)
    assert (padded[:5] == 1).all() and (padded[5:] == 0).all()


def test_bucket_ladder_and_lookup():
    from mmlspark_tpu.parallel.inference import bucket_for, bucket_ladder
    lad = bucket_ladder(100)
    assert lad == [1, 2, 4, 8, 16, 32, 64, 100]
    assert bucket_for(3, lad) == 4
    assert bucket_for(100, lad) == 100
    assert bucket_for(5000, lad) == 100     # beyond the top: top rung
    # overrides clamp into [1, max] and always include max
    assert bucket_ladder(64, [16, 9999, 0]) == [1, 16, 64]


# --- rule matching -------------------------------------------------------

def test_small_leaves_replicate_before_rules(mesh8):
    from mmlspark_tpu.parallel import shard_rules as sr
    params = {"kernel": np.zeros((8, 8), np.float32),
              "bias": np.zeros((8,), np.float32)}
    specs = sr.match_partition_rules(sr.DL_RULES, params, mesh=mesh8)
    assert specs["kernel"] == () and specs["bias"] == ()


def test_dl_rules_shard_large_kernels_over_mp():
    from mmlspark_tpu.parallel import shard_rules as sr
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh
    mesh = create_mesh(MeshConfig(dp=4, mp=2))
    params = {"dense": {"kernel": np.zeros((512, 512), np.float32),
                        "embedding": np.zeros((512, 512), np.float32)}}
    specs = sr.match_partition_rules(sr.DL_RULES, params, mesh=mesh)
    assert specs["dense"]["kernel"] == (None, sr.MODEL_AXIS)
    assert specs["dense"]["embedding"] == (sr.MODEL_AXIS, None)


def test_rules_skip_specs_that_do_not_fit(mesh8):
    # mesh8 has mp=1... still fits; use a leaf whose dim is not
    # divisible by the axis: dp=8 against a 513-row leaf
    from mmlspark_tpu.parallel import shard_rules as sr
    rules = [(r".*", (sr.DATA_AXIS, None)), (r".*", ())]
    specs = sr.match_partition_rules(
        rules, {"w": np.zeros((513, 257), np.float32)}, mesh=mesh8)
    assert specs["w"] == ()          # falls through to the catch-all


def test_unmatched_leaf_replicates_with_warning(mesh8):
    from mmlspark_tpu.core import logging_utils
    from mmlspark_tpu.parallel import shard_rules as sr
    rules = [(r"^never-matches$", (sr.DATA_AXIS, None))]
    specs = sr.match_partition_rules(
        rules, {"odd_leaf": np.zeros((1024, 128), np.float32)},
        mesh=mesh8, label="warncase")
    assert specs["odd_leaf"] == ()
    # the downgrade warned once, keyed by family label + leaf name
    assert any("warncase" in k and "odd_leaf" in k
               for k in logging_utils._WARNED_ONCE)


def test_resolve_shard_rules_modes(mesh8):
    from mmlspark_tpu.core.env import env_override
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh
    from mmlspark_tpu.parallel.shard_rules import resolve_shard_rules

    assert resolve_shard_rules(None)[0] == "serial"
    mode, reason = resolve_shard_rules(mesh8)
    assert mode == "rules" and "8-device" in reason
    with env_override("MMLSPARK_TPU_SHARD_RULES", "off"):
        mode, reason = resolve_shard_rules(mesh8)
        assert mode == "serial" and "off" in reason
    # a mesh without a dp axis downgrades to replication
    nodp = create_mesh(MeshConfig(dp=8), axis_names=("fp", "mp", "sp"))
    mode, reason = resolve_shard_rules(nodp, label="nodp")
    assert mode == "replicate" and "dp" in reason


def test_autocast_bf16_casts_resident_floats(mesh8):
    import jax.numpy as jnp

    from mmlspark_tpu.core.env import env_override
    from mmlspark_tpu.parallel.shard_rules import ShardedScorer
    w = np.eye(4, dtype=np.float32)
    with env_override("MMLSPARK_TPU_INFER_AUTOCAST", "bf16"):
        scorer = ShardedScorer(lambda p, xb: xb @ p["w"], {"w": w},
                               family="onnx", mesh=mesh8,
                               max_batch=16, label="bf16case")
    assert scorer.autocast == "bf16"
    assert scorer._params["w"].dtype == jnp.bfloat16
    assert scorer.metadata()["infer_autocast"] == "bf16"


# --- bitwise transform parity: dp=1 / dp=2 / dp=8 ------------------------

@smoke
def test_onnx_transform_parity_bitwise(mesh8, mesh2, rng):
    from mmlspark_tpu.onnx.model import ONNXModel
    from tests.onnx.test_onnx import _mlp_model
    proto, _ = _mlp_model(rng)
    x = rng.normal(size=(801, 4)).astype(np.float32)  # uneven rows
    df = DataFrame({"features": x})

    def run(mesh):
        m = ONNXModel(modelPayload=proto, miniBatchSize=64)
        if mesh is not None:
            m.set_mesh(mesh)
        out = np.asarray(list(m.transform(df)["output"]), np.float32)
        return out, m.shard_metadata()

    serial, meta_s = run(None)
    dp2, meta_2 = run(mesh2)
    dp8, meta_8 = run(mesh8)
    assert meta_s["shard_rules"] == "serial"
    assert meta_2["shard_rules"] == "rules" and meta_2["shard_rules_dp"] == 2
    assert meta_8["shard_rules"] == "rules" and meta_8["shard_rules_dp"] == 8
    assert meta_8["infer_autocast"] == "off"   # the parity-pinned arm
    assert np.array_equal(serial, dp2)
    assert np.array_equal(serial, dp8)


@smoke
def test_gbdt_transform_parity_bitwise(mesh8, mesh2, rng):
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier
    n = 801
    x = rng.normal(size=(n, 6))
    y = (x[:, 0] + 0.3 * x[:, 1] > 0).astype(np.float64)
    df = DataFrame({"features": x, "label": y})
    model = LightGBMClassifier(numIterations=3, numLeaves=8,
                               maxBin=32).fit(df)

    def probs(mesh):
        model.set_mesh(mesh)
        return np.asarray(list(model.transform(df)["probability"]),
                          np.float64)

    serial = probs(None)
    dp2 = probs(mesh2)
    dp8 = probs(mesh8)
    assert model.shard_metadata()["shard_rules"] == "rules"
    assert np.array_equal(serial, dp2)
    assert np.array_equal(serial, dp8)


@smoke
def test_vw_transform_parity_bitwise(mesh8, mesh2, rng):
    import jax

    from mmlspark_tpu.models.vw import VowpalWabbitClassifier
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh
    n = 801
    x = rng.normal(size=(n, 8))
    y = (x[:, 0] > 0).astype(np.float64)
    df = DataFrame({"features": x, "label": y})
    model = VowpalWabbitClassifier(numPasses=2, batchSize=32).fit(df)
    mesh1 = create_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])

    def probs(mesh):
        model.set_mesh(mesh)
        return np.asarray(list(model.transform(df)["probability"]),
                          np.float64)

    # mesh-less VW keeps its float64 numpy path; the engine computes
    # in f32, so the cross-arm check is tolerance-based...
    legacy = probs(None)
    # ...and the dp=1/2/8 engine arms are bitwise-identical
    dp1 = probs(mesh1)
    dp2 = probs(mesh2)
    dp8 = probs(mesh8)
    assert model.shard_metadata()["shard_rules"] == "rules"
    np.testing.assert_allclose(legacy, dp8, rtol=1e-5, atol=1e-6)
    assert np.array_equal(dp1, dp2)
    assert np.array_equal(dp1, dp8)


@smoke
def test_dl_transform_parity_bitwise(mesh8, mesh2):
    from mmlspark_tpu.dl import DeepTextClassifier
    texts = np.asarray(["good fine great", "bad poor awful"] * 40,
                       dtype=object)[:79]                 # uneven rows
    labels = np.tile([1.0, 0.0], 40)[:79]
    df = DataFrame({"text": texts, "label": labels})
    model = DeepTextClassifier(batchSize=16, maxEpochs=1,
                               labelCol="label", maxLength=4,
                               embeddingDim=16, numLayers=1,
                               numHeads=2, mesh=mesh2).fit(df)

    def probs(mesh):
        model.set_mesh(mesh)
        return np.asarray(list(model.transform(df)["probability"]),
                          np.float64)

    serial = probs(None)
    dp2 = probs(mesh2)
    dp8 = probs(mesh8)
    assert model.shard_metadata()["shard_rules"] == "rules"
    assert np.array_equal(serial, dp2)
    assert np.array_equal(serial, dp8)


# --- recompile budget ----------------------------------------------------

@smoke
def test_recompile_budget_bounded_by_ladder(mesh8, rng):
    """1k scoring calls with varying row counts compile at most
    ladder-size graphs — graftsan's recompile counter proves the
    bucket padding holds (MMLSPARK_TPU_SAN=1, budget enforced)."""
    from mmlspark_tpu.core import sanitizer
    from mmlspark_tpu.core.env import env_override
    from mmlspark_tpu.parallel.shard_rules import ShardedScorer

    w = rng.normal(size=(4, 3)).astype(np.float32)
    try:
        with env_override("MMLSPARK_TPU_SAN", "1"):
            sanitizer.refresh_from_env()
            sanitizer.reset()
            scorer = ShardedScorer(lambda p, xb: xb @ p["w"], {"w": w},
                                   family="onnx", mesh=mesh8,
                                   max_batch=64, label="budgetcase")
            sanitizer.set_recompile_budget(len(scorer._ladder))
            base = sanitizer.recompile_count()
            for n in rng.integers(1, 500, size=1000):
                out = scorer(np.ones((int(n), 4), np.float32))
                assert out.shape == (int(n), 3)
            assert (sanitizer.recompile_count() - base
                    <= len(scorer._ladder))
    finally:
        sanitizer.refresh_from_env()
        sanitizer.reset()


# --- the chunked feed of a row source (PR 32) ------------------------------

def _object_column(x):
    col = np.empty(len(x), dtype=object)
    for i in range(len(x)):
        col[i] = x[i]
    return col


def _root_spans(fn):
    """``fn()`` under a root span: its result and the spans it left."""
    from mmlspark_tpu.core.timer import span

    with span("root") as root:
        out = fn()
    return out, root.spans


@pytest.fixture
def small_chunks(monkeypatch):
    """``chunked_device_put`` with chunks of 192 bytes (3 rows of 16
    float32, which no rung is a multiple of), lowered through its own
    argument."""
    import functools

    from mmlspark_tpu.ops import ingest
    monkeypatch.setattr(ingest, "chunked_device_put", functools.partial(
        ingest.chunked_device_put, chunk_bytes=192))


def _linear_scorer(rng, mesh=None, max_batch=64):
    from mmlspark_tpu.parallel.shard_rules import ShardedScorer

    w = rng.normal(size=(16, 3)).astype(np.float32)
    return ShardedScorer(lambda p, xb: xb.reshape(len(xb), -1) @ p["w"],
                         {"w": w}, family="onnx", mesh=mesh,
                         max_batch=max_batch, label="feedcase")


@pytest.mark.parametrize("case", [
    "rows_not_a_multiple_of_the_chunk", "short_padded_last_group",
    "mesh_of_4_with_row_multiple", "float64_column_declared_float32"])
def test_chunked_feed_equals_the_whole_put(case, rng, small_chunks):
    import jax

    from mmlspark_tpu.ops.ingest import RowSource
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    rows, mesh, dtype = 30, None, np.float32
    if case == "short_padded_last_group":
        rows = 64 + 64 + 7               # three groups of 64, the last 7
    elif case == "mesh_of_4_with_row_multiple":
        mesh = create_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        rows = 4 * 16 + 4 * 16 + 5       # groups of dp x rung = 64
    elif case == "float64_column_declared_float32":
        dtype = np.float64
    x = rng.normal(size=(rows, 2, 8)).astype(dtype)
    scorer = _linear_scorer(rng, mesh, max_batch=16 if mesh else 64)

    want = scorer(x.astype(np.float32))
    source = RowSource(_object_column(x), "rows.stack").astype(np.float32)
    got, spans = _root_spans(lambda: scorer(source))

    assert got.dtype == want.dtype and np.array_equal(got, want)
    puts = [s for s in spans if s.name == "scorer.put"]
    groups = -(-rows // 64)
    # a group is 64 rows of 64 bytes (30 rows: the 32 rung) in chunks of
    # 3 rows, the last one short; of 4 = dp rows under the mesh
    group_rows = 32 if rows == 30 else 64
    chunks = -(-group_rows // (3 if mesh is None else 4))
    assert len(puts) == groups * chunks
    assert {s.counts["chunks"] for s in puts} == {chunks}
    assert sum(s.counts["bytes"] for s in puts) == groups * group_rows * 64
    assert [s.name for s in spans].count("rows.stack") == len(puts)


def test_chunked_feed_three_frames_in_turn_keep_their_own_rows(
        rng, small_chunks):
    """The staging buffers are written again on every call and, within a
    call, by every third chunk: each call must still return its own
    frame's rows, and the second call on must find the buffers there."""
    from mmlspark_tpu.ops.ingest import RowSource

    scorer = _linear_scorer(rng)
    frames = [rng.normal(size=(50, 16)).astype(np.float32)
              for _ in range(3)]
    want = [scorer(f) for f in frames]
    kept = None
    for turn in range(2):
        for frame, expect in zip(frames, want):
            got = scorer(RowSource(_object_column(frame), "rows.stack"))
            assert np.array_equal(got, expect)
            ring = scorer._staging["__x__"]
            assert all(b is not None and b.shape == (3, 16) for b in ring)
            kept = kept or list(ring)
    # a buffer is replaced only where the runtime took it for the device
    # array (XLA:CPU, if it happens to be aligned): else all are reused
    if all(b.ctypes.data % 64 for b in kept):
        assert all(a is b for a, b in zip(kept, ring))




@pytest.mark.parametrize("aligned", [True, False])
def test_staging_buffer_the_runtime_took_is_not_written_again(rng, aligned):
    """XLA:CPU takes a 64-byte-aligned host buffer as the device array
    itself. A chunk put from such a staging buffer reads it until the
    concatenate has run, so the feed must take a new buffer for the
    chunk that would follow it there; an unaligned one it may reuse."""
    import jax

    from mmlspark_tpu.ops.ingest import (RowSource, _writable,
                                         chunked_device_put)

    def buffer():
        raw = np.zeros(4 * 16 * 4 + 128, np.uint8)
        off = (-raw.ctypes.data) % 64 + (0 if aligned else 4)
        return raw[off:off + 4 * 16 * 4].view(np.float32).reshape(4, 16)

    probe = buffer()
    on_device = jax.device_put(probe)
    on_device.block_until_ready()
    probe[0, 0] = 7.0                    # seen on the device: one memory
    took = float(on_device[0, 0]) == 7.0
    if aligned and not took:
        pytest.skip("this backend copied an aligned host buffer")
    assert took == aligned
    assert _writable(probe, on_device) == (not took)

    x = rng.normal(size=(50, 16)).astype(np.float32)
    staging = [buffer() for _ in range(3)]
    before = list(staging)
    got = chunked_device_put(RowSource(_object_column(x), "rows.stack"),
                             chunk_bytes=256, staging=staging)
    assert np.array_equal(np.asarray(got), x)
    reused = [a is b for a, b in zip(before, staging)]
    assert reused == ([False] * 3 if aligned else [True] * 3)


def test_ragged_column_raises_before_anything_is_put(rng, small_chunks):
    from mmlspark_tpu.onnx.model import ONNXModel
    from mmlspark_tpu.ops.ingest import RowSource
    from tests.onnx.test_onnx import _mlp_model

    col = _object_column(rng.normal(size=(9, 4)).astype(np.float32))
    col[5] = np.zeros(3, np.float32)
    with pytest.raises(ValueError, match="same shape"):
        RowSource(col, "rows.stack")
    with pytest.raises(ValueError, match="need at least one array"):
        RowSource([], "rows.stack")

    proto, _ = _mlp_model(rng)
    model = ONNXModel(modelPayload=proto, miniBatchSize=8)
    with pytest.raises(ValueError, match="same shape") as raised:
        _root_spans(lambda: model.transform(DataFrame({"features": col})))
    with pytest.raises(ValueError, match="same shape"):
        np.stack(list(col))              # what the front end raised before
    assert raised.type is ValueError


def test_scorer_put_counts_its_chunks(rng, small_chunks):
    from mmlspark_tpu.ops.ingest import RowSource

    scorer = _linear_scorer(rng)
    x = rng.normal(size=(40, 16)).astype(np.float32)

    def chunks_of(batch):
        _, spans = _root_spans(lambda: scorer(batch))
        return [s.counts["chunks"] for s in spans if s.name == "scorer.put"]

    # an ndarray, whatever its size, and a row source of one chunk or
    # less: today's single put
    assert chunks_of(x) == [1]
    assert chunks_of(RowSource(_object_column(x[:2]), "rows.stack")) == [1]
    # 40 rows on the 64 rung, 3 rows a chunk
    assert chunks_of(RowSource(_object_column(x), "rows.stack")) == [22] * 22


def test_chunked_feed_callers_on_many_threads_keep_their_own_rows(
        rng, small_chunks):
    """One scorer, more callers than cores, each feeding its own frame
    through the chunked feed again and again: a staging ring is checked
    out for a call, so no caller may ever read rows another one laid."""
    import os
    import sys
    import threading

    from mmlspark_tpu.ops.ingest import RowSource

    scorer = _linear_scorer(rng)
    callers = 2 * (os.cpu_count() or 4)
    frames = [rng.normal(size=(40, 16)).astype(np.float32)
              for _ in range(callers)]
    want = [scorer(f) for f in frames]
    wrong, errors = [], []

    def call(i):
        try:
            for _ in range(5):
                got = scorer(RowSource(_object_column(frames[i]),
                                       "rows.stack"))
                if not np.array_equal(got, want[i]):
                    wrong.append(i)
        except Exception as e:               # surfaced below, not lost
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong


def test_dl_rules_name_the_expert_axis_of_stacked_expert_weights():
    """``(experts, in, out)`` leaves shard their expert axis over mp
    where the mesh has one that divides it, and replicate otherwise."""
    from mmlspark_tpu.parallel import shard_rules as sr
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh
    params = {"ffn": {"experts_gate": np.zeros((16, 64, 32), np.float32),
                      "experts_down": np.zeros((16, 32, 64), np.float32),
                      "shared_gate": np.zeros((64, 32), np.float32),
                      "router": np.zeros((64, 256), np.float32)}}
    mesh = create_mesh(MeshConfig(dp=4, mp=2))
    specs = sr.match_partition_rules(sr.DL_RULES, params, mesh=mesh,
                                     small_numel=0)
    assert specs["ffn"]["experts_gate"] == (sr.MODEL_AXIS, None, None)
    assert specs["ffn"]["experts_down"] == (sr.MODEL_AXIS, None, None)
    assert specs["ffn"]["shared_gate"] == () == specs["ffn"]["router"]
    alone = sr.match_partition_rules(sr.DL_RULES, params, mesh=None,
                                     small_numel=0)
    assert alone["ffn"]["experts_gate"] == ()
