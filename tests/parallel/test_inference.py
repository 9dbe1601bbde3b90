"""Mesh-sharded batch inference == single-device scoring (VERDICT r2
#5; reference: broadcast-model partition scoring,
onnx/ONNXModel.scala:242-251)."""

import numpy as np
import pytest

from mmlspark_tpu.core.dataframe import DataFrame


def test_gbdt_sharded_scoring_matches(mesh8, rng):
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier

    n = 801  # deliberately not a multiple of 8 (padding path)
    x = rng.normal(size=(n, 6))
    y = (x[:, 0] + 0.3 * x[:, 1] > 0).astype(np.float64)
    df = DataFrame({"features": x, "label": y})
    model = LightGBMClassifier(numIterations=5, numLeaves=8,
                               maxBin=32,
                               leafPredictionCol="leaves",
                               featuresShapCol="shap").fit(df)
    single = model.transform(df)
    sharded = model.set_mesh(mesh8).transform(df)
    for col in ("prediction", "probability", "rawPrediction", "leaves",
                "shap"):
        np.testing.assert_allclose(
            np.asarray(list(single[col]), np.float64),
            np.asarray(list(sharded[col]), np.float64),
            rtol=1e-6, atol=1e-6, err_msg=col)


def test_gbdt_mesh_fit_pads_nondivisible_rows(mesh8, rng):
    """Mesh training with N not divisible by the dp axis pads with
    masked rows; the fitted model must match the unsharded fit."""
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier

    n = 1001
    x = rng.normal(size=(n, 5))
    y = (x[:, 0] > 0).astype(np.float64)
    df = DataFrame({"features": x, "label": y})
    kw = dict(numIterations=5, numLeaves=8, maxBin=32)
    sharded = LightGBMClassifier(**kw).set_mesh(mesh8).fit(df)
    plain = LightGBMClassifier(**kw).fit(df)
    ps = np.asarray(list(sharded.transform(df)["probability"]), np.float64)
    pp = np.asarray(list(plain.transform(df)["probability"]), np.float64)
    np.testing.assert_allclose(ps, pp, rtol=1e-4, atol=1e-5)
    # bagging path also honors the mask (device RNG differs from host
    # RNG, so just check it trains and scores finite)
    bagged = LightGBMClassifier(baggingFraction=0.7, baggingFreq=1,
                                **kw).set_mesh(mesh8).fit(df)
    assert np.isfinite(np.asarray(
        list(bagged.transform(df)["probability"]), np.float64)).all()


def test_gbdt_fit_with_mesh_propagates_to_model(mesh8, rng):
    from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor

    x = rng.normal(size=(160, 4))
    y = x[:, 0] * 2.0 + x[:, 1]
    df = DataFrame({"features": x, "label": y})
    model = LightGBMRegressor(numIterations=3, numLeaves=4,
                              maxBin=16).set_mesh(mesh8).fit(df)
    assert model._mesh is mesh8
    out = model.transform(df)
    assert np.isfinite(np.asarray(out["prediction"], np.float64)).all()


def test_deep_model_sharded_logits_match(mesh8, rng):
    from mmlspark_tpu.dl import DeepTextClassifier

    texts = np.asarray(["good fine great", "bad poor awful"] * 40,
                       dtype=object)
    labels = np.tile([1.0, 0.0], 40)
    df = DataFrame({"text": texts, "label": labels})
    model = DeepTextClassifier(batchSize=16, maxEpochs=1, labelCol="label",
                               maxLength=4, embeddingDim=16, numLayers=1,
                               numHeads=2, mesh=mesh8).fit(df)
    assert model._mesh is mesh8  # inherited from the estimator
    sharded = model.transform(df)
    model._mesh = None
    single = model.transform(df)
    np.testing.assert_allclose(
        np.asarray(list(single["probability"]), np.float64),
        np.asarray(list(sharded["probability"]), np.float64),
        rtol=1e-4, atol=1e-5)


def test_onnx_sharded_scoring_matches(mesh8, rng):
    from mmlspark_tpu.onnx.model import ONNXModel
    from tests.onnx.test_onnx import _mlp_model

    proto, _ = _mlp_model(rng)
    x = rng.normal(size=(33, 4)).astype(np.float32)
    df = DataFrame({"features": x})
    single = ONNXModel(modelPayload=proto, miniBatchSize=16).transform(df)
    sharded = ONNXModel(modelPayload=proto,
                        miniBatchSize=16).set_mesh(mesh8).transform(df)
    np.testing.assert_allclose(
        np.asarray(list(single["output"]), np.float64),
        np.asarray(list(sharded["output"]), np.float64),
        rtol=1e-5, atol=1e-6)


def test_length_ladder_and_batches_group_rows_of_like_length():
    from mmlspark_tpu.parallel.inference import (
        length_batches,
        length_ladder,
    )

    assert length_ladder(1024) == [128, 256, 512, 1024]
    assert length_ladder(100) == [128]
    assert length_ladder(1025) == [128, 256, 512, 1024, 2048]
    lengths = np.array([900, 130, 40, 300, 128, 129, 700])
    batches = length_batches(lengths, 3, length_ladder(1024))
    # sorted by length and cut into runs of 3; a run takes the rung of
    # its longest row, so the 40-token row is never padded to 1024
    assert [(list(i), r) for i, r in batches] == [
        ([2, 4, 5], 256), ([1, 3, 6], 1024), ([0], 1024)]
    assert sorted(int(i) for index, _ in batches for i in index) == list(
        range(len(lengths)))


@pytest.mark.parametrize("rows", [1, 3, 8, 64])
def test_a_length_batch_has_its_rows_ascending_by_length(rows):
    """Inside every batch the rows stand in ascending order of length
    (ties in the column's order), so the rows of a device batch that
    have ended are its first ones. A prefill that skips the groups of
    rows with no token left (``HybridLM.hidden_in_groups``) depends on
    this for its gain, not for its result."""
    from mmlspark_tpu.parallel.inference import (
        length_batches,
        length_ladder,
    )

    lengths = np.random.default_rng(rows).integers(1, 1025, 50)
    batches = length_batches(lengths, rows, length_ladder(1024))
    for index, rung in batches:
        assert (np.diff(lengths[index]) >= 0).all()
        assert lengths[index[-1]] <= rung
        ties = np.diff(lengths[index]) == 0
        assert (np.diff(index)[ties] > 0).all()
    assert [len(i) for i, _ in batches] == [rows] * (50 // rows) + (
        [50 % rows] if 50 % rows else [])


def test_scorer_places_params_in_the_dtype_the_model_states(monkeypatch):
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import ShardedScorer

    monkeypatch.delenv("MMLSPARK_TPU_INFER_AUTOCAST", raising=False)
    params = {"kernel": np.ones((4, 3), np.float32),
              "steps": np.arange(3, dtype=np.int32)}
    scorer = ShardedScorer(lambda p, x: x @ p["kernel"].astype(jnp.float32),
                           params, family="dl", max_batch=4,
                           param_dtype=jnp.bfloat16)
    assert scorer._params["kernel"].dtype == jnp.bfloat16
    assert scorer._params["steps"].dtype == jnp.int32      # not a float
    assert scorer.metadata()["infer_autocast"] == "off"
    out = scorer(np.ones((3, 4), np.float32))
    assert out.shape == (3, 3) and np.allclose(out, 4.0)
    with pytest.raises(ValueError, match="max_length"):
        scorer.length_batches([1, 2])


def test_scorer_with_a_length_ladder_counts_a_program_a_pair_of_rungs():
    """``jit=False``: the engine places the params and calls the stage's
    own programs; a dict batch ``{"ids", "lengths"}`` compiles once a
    (row rung, length rung)."""
    from mmlspark_tpu.core import sanitizer
    from mmlspark_tpu.parallel.shard_rules import ShardedScorer

    seen = []

    def apply(params, batch):
        seen.append(tuple(batch["ids"].shape))
        return {"sum": np.asarray(batch["ids"]).sum(axis=1)
                + params["bias"]}

    scorer = ShardedScorer(apply, {"bias": np.float32(1.0)}, family="dl",
                           max_batch=4, max_length=300, jit=False)
    lengths = np.array([5, 200, 7, 130, 9])
    counted = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sanitizer, "count_recompile", counted.append)
        for index, rung in scorer.length_batches(lengths):
            ids = np.ones((len(index), rung), np.int32)
            out = scorer({"ids": ids, "lengths": lengths[index]})
            assert np.allclose(out["sum"], rung + 1.0)
            assert out["sum"].shape == (len(index),)
    # 4 rows at the 256 rung (lengths 5, 7, 9, 130), 1 row at 256
    assert seen == [(4, 256), (1, 256)]
    assert len(counted) == 2 and "(4, 256)" in counted[0]
