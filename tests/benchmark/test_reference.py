"""The plain references against the program, at toy size on the CPU."""

import numpy as np

from benchmark.lookup import load_module


def test_resnet_reference_agrees_with_onnx_transform_on_a_toy():
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.onnx.model import ONNXModel

    builder = load_module("builders", "resnet50_onnx")
    reference = load_module("reference", "resnet50")
    stages = [(1, 8), (1, 16)]              # two bottleneck blocks, 32x32
    weights = builder.make_weights(11, stages, stem=8, classes=10)
    payload = builder.make_proto(weights, stages, image=32)
    column = builder.make_frames(11, 1, 6, 32)[0]
    model = ONNXModel(modelPayload=payload, miniBatchSize=4)
    got = np.asarray(model.transform(
        DataFrame({"features": column})).col("output"))
    want = reference.logits(weights, np.stack(list(column)), stages)
    assert got.shape == want.shape == (6, 10)
    scale = np.abs(want).max()
    # float32 on both sides; only the order of summation may differ
    assert np.abs(got - want).max() / scale < 1e-4
    # the check can fail: one batch-norm mean moved by 0.05
    moved = dict(weights, **{"c3.mean": weights["c3.mean"] + 0.05})
    off = reference.logits(moved, np.stack(list(column)), stages)
    assert np.abs(got - off).max() / scale > 1e-2


def test_gbdt_margins_agree_with_a_plain_traversal_of_the_model_text():
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier

    builder = load_module("builders", "higgs_gbdt")
    reference = load_module("reference", "gbdt")
    x, y = builder.make_data(6000, 5)
    model = LightGBMClassifier(numIterations=4, numLeaves=15, maxDepth=4,
                               maxBin=63, minDataInLeaf=20).fit(
        DataFrame({"features": x[:5000], "label": y[:5000]}))
    raw = np.asarray(model.transform(
        DataFrame({"features": x[5000:]})).col("rawPrediction"))
    want = reference.margins(model.get_model_string(), x[5000:])
    assert np.abs(raw[:, -1] - want).max() < 1e-5
    trees, _ = reference.parse_model(model.get_model_string())
    assert len(trees) == 4
    # and it is a traversal, not an echo: another row gives another margin
    assert np.abs(want - reference.margins(
        model.get_model_string(), x[5000:][::-1])).max() > 1e-3


def test_seeded_data_depend_on_the_seed_and_not_on_the_threads():
    builder = load_module("builders", "higgs_gbdt")
    big = 3_000_000_019                      # more than 32 signed bits hold
    x1, y1 = builder.make_data(builder.BLOCK + 1000, big, threads=1)
    x2, y2 = builder.make_data(builder.BLOCK + 1000, big, threads=4)
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    x3, _ = builder.make_data(2000, big + 1)
    assert not np.array_equal(x1[:2000], x3)
    assert 0.45 < y1.mean() < 0.55
