"""Binned batch scoring (predict_binned_fn) vs raw-feature scoring.

The reference's inference baseline is the per-row JNI UDF re-comparing
float thresholds (booster/LightGBMBooster.scala:394,520-557). When the
caller holds the binned matrix, routing can compare uint8 bin ids
against the stored threshold_bin — results must be IDENTICAL to raw
scoring because threshold_value is exactly the upper edge of
threshold_bin (VERDICT r4 #4).
"""

import numpy as np
import pytest

from mmlspark_tpu.models.gbdt.booster import BoosterArrays
from mmlspark_tpu.models.gbdt.trainer import TrainConfig, train
from mmlspark_tpu.ops.binning import BinMapper


def _fit(rng, n=3000, f=10, max_bin=63, **cfg_kw):
    x = rng.normal(size=(n, f))
    y = (x[:, 0] * x[:, 1] + 0.5 * x[:, 2]
         + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    mapper = BinMapper.fit(x, max_bin=max_bin)
    binned = mapper.transform(x)
    kw = dict(objective="binary", num_iterations=8, num_leaves=31,
              max_depth=5, min_data_in_leaf=5, max_bin=max_bin)
    kw.update(cfg_kw)
    cfg = TrainConfig(**kw)
    res = train(binned, y, cfg, bin_upper=mapper.bin_upper_values(max_bin))
    return res.booster, mapper, x, binned


def test_binned_matches_raw_exactly(rng):
    booster, mapper, x, binned = _fit(rng)
    raw = np.asarray(booster.predict_jit()(x))
    via_bins = np.asarray(booster.predict_binned_jit()(binned))
    np.testing.assert_array_equal(raw, via_bins)


def test_binned_matches_raw_on_unseen_rows(rng):
    """Fresh rows binned by the SAME mapper must score identically:
    within a bin, raw comparison against the bin's upper edge and bin-id
    comparison against threshold_bin pick the same side."""
    booster, mapper, x, _ = _fit(rng)
    x_new = rng.normal(size=(500, x.shape[1]))
    raw = np.asarray(booster.predict_jit()(x_new))
    via_bins = np.asarray(booster.predict_binned_jit()(
        mapper.transform(x_new)))
    np.testing.assert_array_equal(raw, via_bins)


def test_binned_nan_routes_left_like_raw(rng):
    booster, mapper, x, _ = _fit(rng)
    x_nan = x[:200].copy()
    x_nan[::3, 0] = np.nan
    x_nan[::5, 2] = np.nan
    raw = np.asarray(booster.predict_jit()(x_nan))
    via_bins = np.asarray(booster.predict_binned_jit()(
        mapper.transform(x_nan)))
    np.testing.assert_array_equal(raw, via_bins)


def test_multiclass_binned(rng):
    booster, mapper, x, binned = _fit(
        rng, objective="multiclass", num_class=3)
    # rebuild labels appropriate for multiclass via a fresh fit
    x = rng.normal(size=(1500, 6))
    y = np.argmax(x[:, :3] + 0.1 * rng.normal(size=(1500, 3)),
                  axis=1).astype(np.float64)
    mapper = BinMapper.fit(x, max_bin=31)
    binned = mapper.transform(x)
    cfg = TrainConfig(objective="multiclass", num_class=3,
                      num_iterations=4, num_leaves=15, max_depth=4,
                      min_data_in_leaf=5, max_bin=31)
    res = train(binned, y, cfg, bin_upper=mapper.bin_upper_values(31))
    raw = np.asarray(res.booster.predict_jit()(x))
    via_bins = np.asarray(res.booster.predict_binned_jit()(binned))
    assert raw.shape == via_bins.shape == (1500, 3)
    np.testing.assert_array_equal(raw, via_bins)


def test_imported_model_string_refuses_binned(rng):
    booster, mapper, x, binned = _fit(rng)
    reimported = BoosterArrays.load_model_string(booster.save_model_string())
    # raw predictions survive the round trip…
    np.testing.assert_allclose(
        np.asarray(reimported.predict_jit()(x[:100])),
        np.asarray(booster.predict_jit()(x[:100])), rtol=1e-6, atol=1e-6)
    # …but bin thresholds do not exist in the text format
    with pytest.raises(ValueError, match="model string"):
        reimported.predict_binned_fn()


def test_categorical_model_refuses_binned(rng):
    n = 1200
    cat = rng.integers(0, 8, size=n).astype(np.float64)
    x = np.stack([cat, rng.normal(size=n)], axis=1)
    y = (np.isin(cat, [1, 3, 5]).astype(np.float64)
         + 0.05 * rng.normal(size=n) > 0.5).astype(np.float64)
    mapper = BinMapper.fit(x, max_bin=31, categorical_features=[0])
    binned = mapper.transform(x)
    cfg = TrainConfig(objective="binary", num_iterations=3, num_leaves=7,
                      max_depth=3, min_data_in_leaf=5, max_bin=31,
                      categorical_features=(0,))
    res = train(binned, y, cfg, bin_upper=mapper.bin_upper_values(31))
    if res.booster.has_categorical:
        with pytest.raises(NotImplementedError, match="categorical"):
            res.booster.predict_binned_fn()


# -- derived binning for imported model strings ---------------------------

def _import_roundtrip(booster):
    return BoosterArrays.load_model_string(booster.save_model_string())


def test_derived_binning_matches_raw_exactly(rng):
    """An imported model string carries raw thresholds only; deriving a
    binning from its own splits must reproduce predict_fn exactly."""
    booster, mapper, x, _ = _fit(rng)
    imported = _import_roundtrip(booster)
    with pytest.raises(ValueError, match="no binned thresholds"):
        imported.predict_binned_fn()
    binning, derived = imported.derive_binning()
    raw = np.asarray(imported.predict_jit()(x))
    via = np.asarray(derived.predict_binned_jit()(binning.transform(x)))
    np.testing.assert_array_equal(raw, via)
    # unseen rows too (values beyond every threshold, between thresholds)
    x_new = rng.normal(size=(500, x.shape[1])) * 3
    np.testing.assert_array_equal(
        np.asarray(imported.predict_jit()(x_new)),
        np.asarray(derived.predict_binned_jit()(binning.transform(x_new))))


def test_derived_binning_threshold_boundary_rows(rng):
    """Rows sitting EXACTLY on split thresholds route inclusively
    (x <= t goes left) in both paths."""
    booster, mapper, x, _ = _fit(rng)
    imported = _import_roundtrip(booster)
    binning, derived = imported.derive_binning()
    internal = imported.split_feature >= 0
    feats = imported.split_feature[internal]
    thrs = imported.threshold_value[internal]
    x_edge = np.tile(x[:1], (min(64, len(feats)), 1))
    for i in range(x_edge.shape[0]):
        x_edge[i, feats[i]] = thrs[i]
    np.testing.assert_array_equal(
        np.asarray(imported.predict_jit()(x_edge)),
        np.asarray(derived.predict_binned_jit()(
            binning.transform(x_edge))))


def test_derived_binning_nan_policy_uniform(rng):
    """Imported trees carry decision_type; NaN routes per the (uniform)
    per-feature default direction in both paths."""
    booster, mapper, x, _ = _fit(rng)
    imported = _import_roundtrip(booster)
    binning, derived = imported.derive_binning()
    x_nan = x[:200].copy()
    x_nan[::3, 0] = np.nan
    x_nan[::5, 2] = np.nan
    raw = np.asarray(imported.predict_jit()(x_nan))
    via = np.asarray(derived.predict_binned_jit()(
        binning.transform(x_nan)))
    np.testing.assert_array_equal(raw, via)


def test_derived_binning_mixed_nan_directions_refused(rng):
    booster, mapper, x, _ = _fit(rng)
    imported = _import_roundtrip(booster)
    # force mixed NaN default directions on feature 0's nodes
    dt = np.array(imported.decision_type, copy=True) \
        if imported.decision_type is not None \
        else np.zeros_like(imported.split_feature, dtype=np.int8)
    nodes = np.nonzero(imported.split_feature == 0)
    assert len(nodes[0]) >= 2, "fixture needs >= 2 splits on feature 0"
    # missing_type nan (2 << 2 = 8); alternate default-left bit
    for i, (t, m) in enumerate(zip(*nodes)):
        dt[t, m] = np.int8(8 | (2 if i % 2 == 0 else 0))
    import dataclasses
    mixed = dataclasses.replace(imported, decision_type=dt)
    binning, derived = mixed.derive_binning()
    x_nan = x[:50].copy()
    x_nan[::2, 0] = np.nan
    with pytest.raises(ValueError, match="mixes NaN default directions"):
        binning.transform(x_nan)
    # finite rows still fine and exact
    np.testing.assert_array_equal(
        np.asarray(mixed.predict_jit()(x[:100])),
        np.asarray(derived.predict_binned_jit()(
            binning.transform(x[:100]))))


def test_derived_binning_zero_as_missing(rng):
    """All-nodes zero-as-missing with a uniform direction maps exact
    0.0 to the sentinel bin; both paths agree."""
    booster, mapper, x, _ = _fit(rng)
    imported = _import_roundtrip(booster)
    dt = np.zeros_like(imported.split_feature, dtype=np.int8)
    internal = imported.split_feature >= 0
    # missing_type zero (1 << 2 = 4) + default-left (2) on every node
    dt[internal] = np.int8(4 | 2)
    import dataclasses
    zmodel = dataclasses.replace(imported, decision_type=dt)
    binning, derived = zmodel.derive_binning()
    x_z = x[:200].copy()
    x_z[::4, 0] = 0.0
    x_z[::7, 3] = 0.0
    np.testing.assert_array_equal(
        np.asarray(zmodel.predict_jit()(x_z)),
        np.asarray(derived.predict_binned_jit()(binning.transform(x_z))))


def test_derived_binning_dtype_is_narrow(rng):
    booster, mapper, x, _ = _fit(rng)
    imported = _import_roundtrip(booster)
    binning, _ = imported.derive_binning()
    assert binning.transform(x[:10]).dtype == np.uint8


def _with_decision(imported, dt_val):
    import dataclasses
    dt = np.zeros_like(imported.split_feature, dtype=np.int8)
    dt[imported.split_feature >= 0] = np.int8(dt_val)
    return dataclasses.replace(imported, decision_type=dt)


def test_derived_binning_nan_right_policy(rng):
    """All nodes NaN-missing + default-RIGHT: NaN maps past every
    threshold (bin k+1) and both paths agree."""
    booster, mapper, x, _ = _fit(rng)
    # missing_type nan (2 << 2 = 8), default-left bit clear
    model = _with_decision(_import_roundtrip(booster), 8)
    binning, derived = model.derive_binning()
    assert (binning.nan_bin[[len(t) > 0 for t in binning.thresholds]]
            > 0).all()
    x_nan = x[:200].copy()
    x_nan[::3, 0] = np.nan
    x_nan[::5, 2] = np.nan
    np.testing.assert_array_equal(
        np.asarray(model.predict_jit()(x_nan)),
        np.asarray(derived.predict_binned_jit()(
            binning.transform(x_nan))))


@pytest.mark.parametrize("dt_val", [0, 12])
def test_derived_binning_nan_compares_as_zero_policy(rng, dt_val):
    """missing_type none (0) — and the out-of-spec bits value 3 (12)
    which _go_left_fn also treats as compare — converts NaN to 0.0
    before the threshold compare; the derived binning maps NaN to
    bin(0.0)."""
    booster, mapper, x, _ = _fit(rng)
    model = _with_decision(_import_roundtrip(booster), dt_val)
    binning, derived = model.derive_binning()
    x_nan = x[:200].copy()
    x_nan[::3, 0] = np.nan
    x_nan[::4, 1] = np.nan
    x_nan[::5, 2] = np.nan
    np.testing.assert_array_equal(
        np.asarray(model.predict_jit()(x_nan)),
        np.asarray(derived.predict_binned_jit()(
            binning.transform(x_nan))))


# -- model-level auto-binned transform ------------------------------------

def _fit_model(rng, n=2500, f=6, **params):
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] * x[:, 1] + 0.5 * x[:, 2] > 0).astype(np.float64)
    df = DataFrame({"features": x, "label": y})
    m = LightGBMClassifier(numIterations=6, numLeaves=15,
                           **params).fit(df)
    return m, df, x


def test_model_transform_uses_binned_path_identically(rng):
    m, df, x = _fit_model(rng)
    assert m.bin_mapper is not None
    m.set("binnedScoring", True)
    p_binned = np.asarray(m.transform(df)["probability"])
    m.set("binnedScoring", False)
    p_raw = np.asarray(m.transform(df)["probability"])
    np.testing.assert_array_equal(p_binned, p_raw)


def test_model_transform_binned_survives_save_load(rng, tmp_path):
    from mmlspark_tpu.core.pipeline import PipelineStage
    m, df, x = _fit_model(rng)
    p0 = np.asarray(m.transform(df)["probability"])
    m.set("binnedScoring", True)
    m.save(str(tmp_path / "m"))
    loaded = PipelineStage.load(str(tmp_path / "m"))
    assert loaded.bin_mapper is not None
    assert loaded.get("binnedScoring") is True
    np.testing.assert_array_equal(
        p0, np.asarray(loaded.transform(df)["probability"]))


def test_model_transform_nan_rows_identical(rng):
    from mmlspark_tpu.core.dataframe import DataFrame
    m, df, x = _fit_model(rng)
    x_nan = x[:300].copy()
    x_nan[::3, 0] = np.nan
    dfn = DataFrame({"features": x_nan})
    m.set("binnedScoring", True)
    p_binned = np.asarray(m.transform(dfn)["probability"])
    m.set("binnedScoring", False)
    p_raw = np.asarray(m.transform(dfn)["probability"])
    np.testing.assert_array_equal(p_binned, p_raw)


def test_model_transform_categorical_falls_back(rng):
    """Categorical models can't route by bin compare; transform must
    silently use the raw path and still work."""
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier
    n = 2500
    xc = rng.integers(0, 8, size=n).astype(np.float32)
    xn = rng.normal(size=(n, 2)).astype(np.float32)
    x = np.column_stack([xc, xn])
    y = ((xc % 2 == 0) ^ (xn[:, 0] > 0)).astype(np.float64)
    df = DataFrame({"features": x, "label": y})
    m = LightGBMClassifier(numIterations=6, numLeaves=15,
                           categoricalSlotIndexes=[0]).fit(df)
    if not m.booster.has_categorical:
        pytest.skip("fixture produced no categorical splits")
    out = m.transform(df)
    p = np.asarray(out["probability"])
    assert np.isfinite(p).all()


def test_model_transform_zero_as_missing_identical(rng):
    """zeroAsMissing models premap 0.0 -> NaN at fit; the binned
    scoring gate must apply the same premap (review catch: without it
    zeros bin normally and route differently than predict_fn)."""
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier
    n = 2500
    x = rng.normal(size=(n, 5)).astype(np.float32)
    x[rng.random((n, 5)) < 0.15] = 0.0   # plenty of exact zeros
    y = ((x[:, 0] > 0.3) ^ (x[:, 1] < -0.2)).astype(np.float64)
    df = DataFrame({"features": x, "label": y})
    m = LightGBMClassifier(numIterations=8, numLeaves=15,
                           zeroAsMissing=True).fit(df)
    assert m.booster.zero_premap_mode == "all_left"
    m.set("binnedScoring", True)
    p_binned = np.asarray(m.transform(df)["probability"])
    m.set("binnedScoring", False)
    p_raw = np.asarray(m.transform(df)["probability"])
    np.testing.assert_array_equal(p_binned, p_raw)


def test_zero_premap_mode_mixed_is_unsupported(rng):
    import dataclasses
    booster, mapper, x, _ = _fit(rng)
    imported = _import_roundtrip(booster)
    dt = np.zeros_like(imported.split_feature, dtype=np.int8)
    internal = imported.split_feature >= 0
    dt[internal] = np.int8(4 | 2)          # zero-missing, left
    t, mlist = np.nonzero(internal)
    dt[t[0], mlist[0]] = np.int8(4)        # one node: zero-missing, right
    mixed = dataclasses.replace(imported, decision_type=dt)
    assert mixed.zero_premap_mode == "unsupported"


def test_derived_binning_uint16_tier(rng):
    """A model with >255 distinct thresholds on one feature pushes the
    derived binning into the uint16 dtype tier; scoring stays exact."""
    import dataclasses
    booster, mapper, x, _ = _fit(rng)
    imported = _import_roundtrip(booster)
    # widen feature 0's threshold table artificially: give every
    # feature-0 node a distinct threshold and synthesize extras by
    # cloning trees with shifted thresholds
    tv = np.array(imported.threshold_value, copy=True)
    sf = imported.split_feature
    reps = []
    for shift in np.linspace(-3, 3, 40):
        t2 = np.array(tv, copy=True)
        t2[sf == 0] += shift
        reps.append(dataclasses.replace(imported, threshold_value=t2))
    big = dataclasses.replace(
        imported,
        split_feature=np.concatenate([r.split_feature for r in reps]),
        threshold_bin=np.concatenate([r.threshold_bin for r in reps]),
        threshold_value=np.concatenate([r.threshold_value for r in reps]),
        node_value=np.concatenate([r.node_value for r in reps]),
        count=np.concatenate([r.count for r in reps]),
        tree_weights=np.concatenate([r.tree_weights for r in reps]),
        decision_type=(None if imported.decision_type is None else
                       np.concatenate([imported.decision_type] * len(reps))))
    binning, derived = big.derive_binning()
    if binning.num_bins <= 256:
        pytest.skip("fixture did not exceed 256 thresholds")
    xb = binning.transform(x[:500])
    assert xb.dtype == np.uint16
    np.testing.assert_array_equal(
        np.asarray(big.predict_jit()(x[:500])),
        np.asarray(derived.predict_binned_jit()(xb)))
