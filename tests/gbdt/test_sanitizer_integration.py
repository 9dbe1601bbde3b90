"""graftsan end-to-end on the GBDT training path.

The closed loop the ISSUE demands: the fault harness injects NaNs into
the native histogram callback (``gbdt.level_hist:corrupt``); with
``MMLSPARK_TPU_SAN=1`` the fit must abort with a diagnostic naming that
jit boundary, and with the sanitizer off the same corruption completes
silently (a NaN gain becomes ``-inf`` and just disables splits — the
exact silent-failure mode the guard exists for). Plus the divergence
detector against the real shard_map builders on the 8-device mesh.
"""

import numpy as np
import pytest

from mmlspark_tpu.core import faults
from mmlspark_tpu.core import sanitizer as san
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.models.gbdt import trainer as trainer_mod
from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor
from mmlspark_tpu.models.gbdt.trainer import TrainConfig, train
from mmlspark_tpu.ops.binning import BinMapper
from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean_state():
    faults.reset()
    san.disable()
    san.reset()
    yield
    faults.reset()
    san.disable()
    san.reset()


def _df(n=400, f=3, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    y = 2.0 * x[:, 0] + rng.normal(size=n) * 0.1
    return DataFrame({"features": x, "label": y})


def _nan_corrupt(h):
    h = np.array(h, copy=True)
    h.flat[0] = np.nan
    return h


_KW = dict(numIterations=3, numLeaves=4, maxBin=16)


def _native_hist(monkeypatch):
    """These fits need the histogram callback boundary; where the
    library is missing the bindings fall back to numpy behind it."""
    monkeypatch.setattr(trainer_mod, "native_histogram_available",
                        lambda: True)


def test_injected_hist_nan_caught_at_named_boundary(monkeypatch):
    """SAN=1 + armed NaN corruption on the histogram callback must
    abort the fit with a diagnostic naming the jit boundary. jax wraps
    callback exceptions, so match on the message, not the type."""
    _native_hist(monkeypatch)
    san.enable()
    with faults.injected("gbdt.level_hist", "corrupt", count=None,
                         corrupt=_nan_corrupt):
        with pytest.raises(Exception) as ei:
            LightGBMRegressor(**_KW).fit(_df())
    msg = str(ei.value)
    assert "graftsan" in msg, msg
    assert "gbdt.level_hist" in msg, msg
    assert "NaN" in msg, msg


def test_injected_hist_nan_is_silent_with_sanitizer_off(monkeypatch):
    """The control arm: without the sanitizer the NaN histogram is
    absorbed (NaN gain -> -inf -> no split) and the fit completes —
    the silent failure mode the guard closes."""
    _native_hist(monkeypatch)
    assert not san.enabled()
    with faults.injected("gbdt.level_hist", "corrupt", count=None,
                         corrupt=_nan_corrupt):
        model = LightGBMRegressor(**_KW).fit(_df())
    assert model is not None


def test_clean_fit_has_no_false_positives(monkeypatch):
    """SAN=1 over an uncorrupted native-histogram fit: every boundary
    guard (entry, callback, metrics sync, exit) sees finite data."""
    _native_hist(monkeypatch)
    san.enable()
    model = LightGBMRegressor(**_KW).fit(_df())
    pred = np.asarray(model.transform(_df())["prediction"])
    assert np.isfinite(pred).all()


def _trace_voting(mesh, recorder, top_k, seed=0):
    """Fit the voting-parallel learner with ``recorder`` active,
    clearing the trainer's compile caches first so the shard_map body
    is re-traced (record_collective fires at trace time)."""
    trainer_mod._CHUNK_CACHE.clear()
    trainer_mod._BUILDER_CACHE.clear()
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(512, 8))
    y = (1.5 * x[:, 0] - x[:, 1] > 0).astype(np.float64)
    mapper = BinMapper.fit(x, max_bin=32)
    cfg = TrainConfig(objective="binary", num_iterations=2,
                      num_leaves=7, max_depth=3, min_data_in_leaf=5,
                      max_bin=32, tree_learner="voting", top_k=top_k)
    with san.use_recorder(recorder):
        train(mapper.transform(x), y, cfg,
              bin_upper=mapper.bin_upper_values(32), mesh=mesh)
    return recorder


@pytest.fixture(scope="module")
def dp_mesh():
    return create_mesh(MeshConfig(dp=8))


def test_divergence_detector_flags_rank_divergent_protocol(dp_mesh):
    """Two simulated ranks compile the voting builder with different
    top_k: the candidate-histogram psum shapes differ, so the recorded
    collective protocols diverge and the cross-check must name rank 1.
    This is GL006's runtime counterpart on a real 8-device program."""
    san.enable()
    rank0 = _trace_voting(dp_mesh, san.CollectiveRecorder(), top_k=8)
    rank1 = _trace_voting(dp_mesh, san.CollectiveRecorder(), top_k=2)
    assert len(rank0) > 0 and len(rank1) > 0
    with pytest.raises(san.CollectiveDivergence) as ei:
        san.crosscheck_hashes([rank0.sequence_hash(),
                               rank1.sequence_hash()])
    assert "rank 1" in str(ei.value)


def test_divergence_detector_clean_on_identical_ranks(dp_mesh):
    """No false positive: ranks tracing the SAME program record the
    same (op, axis, shape, dtype) sequence, hashes agree."""
    san.enable()
    rank0 = _trace_voting(dp_mesh, san.CollectiveRecorder(), top_k=8)
    rank1 = _trace_voting(dp_mesh, san.CollectiveRecorder(), top_k=8)
    assert len(rank0) == len(rank1) > 0
    assert rank0.events == rank1.events
    san.crosscheck_hashes([rank0.sequence_hash(),
                           rank1.sequence_hash()])


def test_recompiles_are_counted_through_trainer_caches(dp_mesh):
    san.enable()
    before = san.recompile_count()
    _trace_voting(dp_mesh, san.CollectiveRecorder(), top_k=4, seed=1)
    assert san.recompile_count() > before
