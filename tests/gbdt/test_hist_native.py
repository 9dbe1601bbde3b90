"""Native C++ level-histogram kernel vs the XLA formulations, and the
one dispatch policy (``resolve_histogram_formulation``).

The native kernel (native/data_plane.cpp mmls_level_hist_*) is the CPU
default, so most of the suite exercises it implicitly; these tests pin
it EXPLICITLY against both XLA formulations — with and without the
compiled library (numpy fallback), across empty nodes, subtraction
on/off, and per-shard inside both explicit shard_map tree learners.
"""

import numpy as np
import pytest

import mmlspark_tpu.native.bindings as bindings_mod
from mmlspark_tpu.models.gbdt import trainer as trainer_mod
from mmlspark_tpu.models.gbdt.trainer import (
    TrainConfig,
    _level_histogram,
    resolve_histogram_formulation,
    resolve_subtract,
    train,
)
from mmlspark_tpu.ops.binning import BinMapper


def _case(n, f, b, width, seed=0, integer_stats=False, bin_dtype=np.uint8):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.int64)
                         .astype(bin_dtype))
    if integer_stats:
        grad = jnp.asarray(rng.integers(-8, 9, size=n).astype(np.float32))
        hess = jnp.asarray(rng.integers(1, 9, size=n).astype(np.float32))
    else:
        grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
        hess = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    live = jnp.asarray((rng.random(n) < 0.9).astype(np.float32))
    local = jnp.asarray(rng.integers(0, width, size=n, dtype=np.int64)
                        .astype(np.int32))
    return binned, grad, hess, live, local


def _fit_data(n=1500, f=6, max_bin=64, seed=11):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    y = (x[:, 0] * x[:, 1] + 0.3 * x[:, 2]
         + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    mapper = BinMapper.fit(x, max_bin=max_bin)
    return x, y, mapper.transform(x), mapper.bin_upper_values(max_bin)


# the two XLA formulations agree exactly with each other (pinned by
# test_hist_pallas.py::test_separate_agrees_with_per_feature), so the
# shape matrix runs mostly against per_feature and two cases take
# separate — same coverage, fewer jit compiles
@pytest.mark.parametrize("n,f,b,width,bin_dtype,xla", [
    (2000, 7, 32, 4, np.uint8, "per_feature"),    # generic
    (2000, 7, 32, 4, np.uint8, "separate"),
    (1500, 6, 300, 2, np.int32, "separate"),      # more than 256 bins
    (999, 3, 255, 8, np.int32, "per_feature"),    # int32, full bin range
    (100, 5, 16, 16, np.uint8, "per_feature"),    # empty nodes
    (4096, 2, 64, 1, np.uint8, "per_feature"),    # root level
    (3000, 4, 63, 32, np.uint8, "per_feature"),   # wide level, many nodes
])
def test_native_matches_xla_formulations(n, f, b, width, bin_dtype, xla):
    case = _case(n, f, b, width, bin_dtype=bin_dtype)
    got = np.asarray(_level_histogram(*case, width, f, b,
                                      formulation="native"))
    ref = np.asarray(_level_histogram(*case, width, f, b,
                                      formulation=xla))
    assert got.shape == ref.shape == (width, f, b, 3)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)
    # counts are integers: exact
    np.testing.assert_array_equal(got[..., 2], ref[..., 2])


def test_bitwise_exact_on_integer_stats():
    """Integer-valued grad/hess make every f32 add exact, so summation
    order cannot matter: native must be bit-for-bit against XLA."""
    case = _case(3000, 4, 63, 8, integer_stats=True)
    got = np.asarray(_level_histogram(*case, 8, 4, 63,
                                      formulation="native"))
    ref = np.asarray(_level_histogram(*case, 8, 4, 63,
                                      formulation="separate"))
    np.testing.assert_array_equal(got, ref)


def test_numpy_fallback_parity(monkeypatch):
    """Without the compiled library the formulation must still work
    (bincount fallback) and agree with the C++ kernel — the acceptance
    path for compiler-less environments."""
    case = _case(2500, 5, 31, 8, seed=3)
    native = np.asarray(_level_histogram(*case, 8, 5, 31,
                                         formulation="native"))
    monkeypatch.setattr(bindings_mod, "ensure_built", lambda: False)
    fallback = np.asarray(_level_histogram(*case, 8, 5, 31,
                                           formulation="native"))
    np.testing.assert_allclose(fallback, native, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(fallback[..., 2], native[..., 2])


@pytest.mark.parametrize("formulation",
                         ["native", "per_feature", "separate"])
def test_empty_input_returns_zero_histogram(formulation):
    """A zero-row level must return a zero histogram of the level's
    shape on every formulation (ADVICE r5: chunk math once divided by
    the row count)."""
    case = _case(0, 4, 16, 2)
    out = np.asarray(_level_histogram(*case, 2, 4, 16,
                                      formulation=formulation))
    assert out.shape == (2, 4, 16, 3)
    assert not out.any()


# every row of the policy, from what the code can observe: backend,
# whether the native library loaded, shard_map, whether GSPMD would
# have to partition the call (allow_*), and the bin count
@pytest.mark.parametrize(
    "backend,native_lib,in_shard_map,bins,allow,expect", [
        ("tpu", False, False, 255, True, "pallas"),
        ("tpu", False, True, 255, True, "pallas"),
        ("tpu", False, False, 1023, True, "per_feature"),
        ("tpu", False, True, 1023, True, "separate"),
        ("tpu", False, False, 255, False, "per_feature"),
        ("tpu", True, False, 255, False, "per_feature"),
        ("cpu", True, False, 255, True, "native"),
        ("cpu", True, True, 255, True, "native"),
        ("cpu", True, False, 1023, True, "native"),
        ("cpu", True, False, 255, False, "per_feature"),
        ("cpu", False, False, 255, True, "per_feature"),
        ("cpu", False, True, 255, True, "separate"),
        ("cpu", False, True, 1023, True, "separate"),
    ])
def test_resolution_policy_table(backend, native_lib, in_shard_map, bins,
                                 allow, expect, monkeypatch):
    import jax

    for name in ("MMLSPARK_TPU_PALLAS_HIST", "MMLSPARK_TPU_NATIVE_HIST"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(trainer_mod, "native_histogram_available",
                        lambda: native_lib)
    assert resolve_histogram_formulation(
        bins, in_shard_map=in_shard_map, allow_pallas=allow,
        allow_native=allow) == expect


def test_default_resolution_policy(monkeypatch):
    """Best-available on the CPU backend: native when the library
    loads; MMLSPARK_TPU_NATIVE_HIST=0 falls back to the XLA defaults;
    subtraction defaults track the native resolution."""
    if not trainer_mod.native_histogram_available():
        pytest.skip("native library not built in this environment")
    assert resolve_histogram_formulation(255) == "native"
    assert resolve_histogram_formulation(255, in_shard_map=True) == "native"
    assert resolve_subtract("serial", 255) is True
    assert resolve_subtract("voting", 255) is False
    monkeypatch.setenv("MMLSPARK_TPU_NATIVE_HIST", "0")
    assert resolve_histogram_formulation(255) == "per_feature"
    assert resolve_histogram_formulation(255, in_shard_map=True) == "separate"
    assert resolve_subtract("serial", 255) is False
    # the explicit env override still forces subtraction on XLA
    monkeypatch.setenv("MMLSPARK_TPU_HIST_SUB", "1")
    assert resolve_subtract("serial", 255) is True


def test_trainer_routes_native_by_default(monkeypatch):
    """A plain serial fit on the CPU backend must run the C++ kernel
    (ensure_built smoke: a silent numpy/XLA fallback here would undo
    the tentpole), and produce the same model as the XLA formulation."""
    if not trainer_mod.native_histogram_available():
        pytest.skip("native library not built in this environment")
    x, y, binned, bu = _fit_data()
    cfg = TrainConfig(objective="binary", num_iterations=4, num_leaves=15,
                      max_depth=4, min_data_in_leaf=5, max_bin=64)
    calls = {"n": 0}
    orig = bindings_mod.level_histogram

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(bindings_mod, "level_histogram", counting)
    res_native = train(binned, y, cfg, bin_upper=bu)
    assert calls["n"] > 0, "default CPU fit did not use the native kernel"
    monkeypatch.setenv("MMLSPARK_TPU_NATIVE_HIST", "0")
    res_xla = train(binned, y, cfg, bin_upper=bu)
    p0 = np.asarray(res_native.booster.predict_jit()(x))
    p1 = np.asarray(res_xla.booster.predict_jit()(x))
    np.testing.assert_allclose(p0, p1, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("sub", ["0", "1"])
def test_native_subtraction_parity(sub, monkeypatch):
    """The masked smaller-child pass (native subtract) against the full
    pass, with bagging exercising fractional live masks' 0/1 branches;
    both against the XLA reference."""
    x, y, binned, bu = _fit_data(n=3000)
    # deep-ish trees + bagging exercise dead branches and live masks
    cfg = TrainConfig(objective="binary", num_iterations=6, num_leaves=31,
                      max_depth=5, min_data_in_leaf=10, max_bin=64,
                      bagging_fraction=0.8, bagging_freq=1)
    monkeypatch.setenv("MMLSPARK_TPU_NATIVE_HIST", "0")
    base = train(binned, y, cfg, bin_upper=bu)
    monkeypatch.delenv("MMLSPARK_TPU_NATIVE_HIST")
    monkeypatch.setenv("MMLSPARK_TPU_HIST_SUB", sub)
    got = train(binned, y, cfg, bin_upper=bu)
    p0 = np.asarray(base.booster.predict_jit()(x))
    p1 = np.asarray(got.booster.predict_jit()(x))
    np.testing.assert_allclose(p0, p1, rtol=1e-3, atol=1e-3)
    # well-separated root splits must agree exactly
    assert (base.booster.split_feature[:, 0]
            == got.booster.split_feature[:, 0]).all()


@pytest.mark.parametrize("tree_learner,mesh_cfg", [
    ("voting", dict(dp=8)),
    ("feature", dict(dp=1, fp=8)),
])
def test_native_under_shard_map_modes(monkeypatch, tree_learner, mesh_cfg):
    """The distributed tree learners run the native kernel PER-SHARD
    inside their explicit shard_maps (local rows only; the psum on the
    returned histogram is unchanged) and reproduce the XLA path."""
    if not trainer_mod.native_histogram_available():
        pytest.skip("native library not built in this environment")
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(**mesh_cfg))
    x, y, binned, bu = _fit_data(n=512, f=8, max_bin=32, seed=5)
    cfg = TrainConfig(objective="binary", num_iterations=4, num_leaves=15,
                      max_depth=4, min_data_in_leaf=5, max_bin=32,
                      tree_learner=tree_learner, top_k=8)
    monkeypatch.setenv("MMLSPARK_TPU_NATIVE_HIST", "0")
    base = train(binned, y, cfg, bin_upper=bu, mesh=mesh)
    monkeypatch.delenv("MMLSPARK_TPU_NATIVE_HIST")

    calls = {"n": 0}
    orig = bindings_mod.level_histogram

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(bindings_mod, "level_histogram", counting)
    swapped = train(binned, y, cfg, bin_upper=bu, mesh=mesh)
    assert calls["n"] > 0, "native kernel not selected per-shard"
    # per-shard float sum order differs from the XLA scatter's, so
    # compare predictions to float tolerance, not trees bit-for-bit
    p0 = np.asarray(base.booster.predict_jit()(x))
    p1 = np.asarray(swapped.booster.predict_jit()(x))
    np.testing.assert_allclose(p0, p1, rtol=1e-4, atol=1e-4)
