"""Leaf-wise (max-gain priority queue) tree growth
(MMLSPARK_TPU_GROW_POLICY=leafwise; arXiv:1706.08359 §2).

Determinism is the load-bearing property: the heap is keyed
(-gain, slot) and split-argmax ties break on the first maximum, so a
repeated fit must be BIT-identical — under every histogram
formulation, since split decisions happen on float64 host math over
f32 histogram sums that each formulation must reproduce.
"""

import numpy as np
import pytest

from mmlspark_tpu.core.env import env_override
from mmlspark_tpu.models.gbdt import trainer as trainer_mod
from mmlspark_tpu.models.gbdt.trainer import TrainConfig, train
from mmlspark_tpu.ops.binning import BinMapper

_BOOSTER_ARRAYS = ("split_feature", "threshold_bin", "node_value",
                   "count", "decision_type")


def _fit_case(n=6000, f=7, seed=17):
    """Gain-skewed data: a strong interaction on one side of the root
    split, so leaf-wise growth genuinely diverges from depth-wise."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    left = x[:, 0] < 0
    signal = np.where(left, x[:, 1] * x[:, 2] + x[:, 3],
                      0.2 * x[:, 4])
    y = (signal + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    return BinMapper.fit(x, max_bin=64).transform(x), y


def _cfg(**kw):
    base = dict(objective="binary", num_iterations=8, num_leaves=10,
                max_depth=8, min_data_in_leaf=20, seed=4)
    base.update(kw)
    return TrainConfig(**base)


def _booster_equal(b1, b2):
    for fld in _BOOSTER_ARRAYS:
        a1, a2 = getattr(b1, fld, None), getattr(b2, fld, None)
        if a1 is None or a2 is None:
            continue
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2),
                                      err_msg=fld)


@pytest.mark.parametrize("native_lib,expect", [
    (None, None), (True, "native"), (False, "per_feature")])
def test_repeated_fits_bit_identical(native_lib, expect, monkeypatch):
    """Same data + seed + policy -> bit-identical booster, for the
    histogram the host resolves by itself, the native callback (numpy
    fallback where the library is missing) and the XLA formulation."""
    binned, y = _fit_case()
    if native_lib is not None:
        monkeypatch.setattr(trainer_mod, "native_histogram_available",
                            lambda: native_lib)
    with env_override("MMLSPARK_TPU_GROW_POLICY", "leafwise"):
        r1 = train(binned, y, _cfg())
        r2 = train(binned, y, _cfg())
    assert r1.hist_stats["grow_policy"] == "leafwise"
    if expect is not None:
        assert trainer_mod.resolve_histogram_formulation(64) == expect
    _booster_equal(r1.booster, r2.booster)


def test_num_leaves_cap_and_actual_divergence_from_depthwise():
    # seed 23's draw is skewed enough that a 10-leaf budget spent
    # greedily picks different splits than level-order growth
    binned, y = _fit_case(seed=23)
    cfg = _cfg(num_leaves=10, max_depth=8)
    with env_override("MMLSPARK_TPU_GROW_POLICY", "leafwise"):
        r_leaf = train(binned, y, cfg)
    with env_override("MMLSPARK_TPU_GROW_POLICY", None):
        r_depth = train(binned, y, cfg)
    leaves = r_leaf.booster.num_leaves_per_tree
    assert (leaves <= 10).all()
    assert leaves.max() == 10  # rich signal: the budget is actually used
    assert r_depth.hist_stats["grow_policy"] == "depthwise"
    # the policies must pick genuinely different trees on this data
    assert not np.array_equal(r_leaf.booster.split_feature,
                              r_depth.booster.split_feature)


def test_leafwise_quality_reasonable():
    """Leaf-wise spends the same leaf budget where the gain is; on
    gain-skewed data it must at least match depth-wise training loss
    within a small margin (usually beating it)."""
    binned, y = _fit_case(seed=23)
    cfg = _cfg(num_iterations=12)
    with env_override("MMLSPARK_TPU_GROW_POLICY", "leafwise"):
        r_leaf = train(binned, y, cfg)
    with env_override("MMLSPARK_TPU_GROW_POLICY", None):
        r_depth = train(binned, y, cfg)

    def logloss(r):
        p = np.clip(np.asarray(r.booster.predict_binned_fn()(binned)),
                    1e-7, 1 - 1e-7)
        return float(-(y * np.log(p) + (1 - y) * np.log(1 - p)).mean())

    assert logloss(r_leaf) <= logloss(r_depth) * 1.02


def test_unsupported_config_downgrades_with_warning(monkeypatch):
    binned, y = _fit_case(n=2000, f=5)
    cfg = _cfg(num_iterations=3,
               monotone_constraints=(1, 0, 0, 0, 0))
    monkeypatch.setattr(trainer_mod, "_WARNED_LEAFWISE_DOWNGRADE", False)
    with env_override("MMLSPARK_TPU_GROW_POLICY", "leafwise"):
        with pytest.warns(UserWarning, match="monotone_constraints"):
            r = train(binned, y, cfg)
    assert r.hist_stats["grow_policy"] == "depthwise"
    # warn-once: the second downgraded fit is silent
    with env_override("MMLSPARK_TPU_GROW_POLICY", "leafwise"):
        r2 = train(binned, y, cfg)
    assert r2.hist_stats["grow_policy"] == "depthwise"
    _booster_equal(r.booster, r2.booster)


def test_bad_grow_policy_value_warns_once(monkeypatch):
    from mmlspark_tpu.models.gbdt.trainer import resolve_grow_policy

    monkeypatch.setattr(trainer_mod, "_WARNED_BAD_GROW", False)
    with env_override("MMLSPARK_TPU_GROW_POLICY", "lossguide"):
        with pytest.warns(UserWarning, match="GROW_POLICY"):
            assert resolve_grow_policy() == "depthwise"
        assert resolve_grow_policy() == "depthwise"


def test_leafwise_ignores_quant_and_efb():
    """Leaf-wise histograms on the host loop's own matrix: quant/EFB
    requests must be recorded as inactive, and the fit must still be
    deterministic."""
    binned, y = _fit_case(n=3000, f=5)
    with env_override("MMLSPARK_TPU_GROW_POLICY", "leafwise"), \
            env_override("MMLSPARK_TPU_HIST_QUANT", "q16"), \
            env_override("MMLSPARK_TPU_EFB", "on"):
        r = train(binned, y, _cfg(num_iterations=4))
    assert r.hist_stats["grow_policy"] == "leafwise"
    assert r.hist_stats["hist_quant"] == "off"
    assert r.hist_stats["efb_bundles"] == 0
