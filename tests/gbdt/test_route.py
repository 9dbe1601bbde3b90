"""A level's routing (``trainer.route_level``): the select form against
the gather it replaced on an accelerator.

Routing is integer logic, so the two forms must give every row the same
node, and a fit routed by either must come out array for array the
same. The CPU backend resolves ``gather`` (``route_form``), as levels wider
than ``ROUTE_SELECT_MAX_WIDTH`` do everywhere, so the select form is
pinned here by calling it directly, and whole fits run
it with ``route_form`` patched: the serial builder (numeric,
categorical, and partitioned by GSPMD) and both ``shard_map`` builders
on four virtual devices, with the varying-axes checker on.
"""

import numpy as np
import pytest

from mmlspark_tpu.models.gbdt import trainer as T
from mmlspark_tpu.ops.binning import BinMapper
from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

N = 8 * 128 + 37                    # no multiple of a lane tile
B = 255
LEVELS = [(dtype, f, width, cat)
          for dtype in (np.uint8, np.int32)
          for f in (1, 28, 136)
          for width in (1, 2, 32, 64)
          for cat in (False, True)]


def _level(dtype, f, width, cat, seed):
    """One level's operands: rows spread over the nodes, a fifth of them
    settled above this level already, a third of the nodes not split."""
    rng = np.random.default_rng(seed)
    local = rng.integers(0, width, size=N).astype(np.int32)
    ops = {
        "binned": rng.integers(0, B, size=(N, f)).astype(dtype),
        "node": (width - 1 + local).astype(np.int32),
        "done": rng.random(N) < 0.2,
        "local": local,
        "do_split": rng.random(width) < 0.67,
        "best_feat": rng.integers(0, f, size=width).astype(np.int32),
        "best_bin": rng.integers(0, B - 1, size=width).astype(np.int32),
    }
    if width > 1:
        ops["do_split"][1] = False
    if cat:
        # a categorical node's left set is no prefix of the bins
        ops["left_mask"] = rng.random((width, B)) < 0.5
    return ops


def _route_numpy(binned, node, done, local, do_split, best_feat, best_bin,
                 left_mask=None):
    nbin = binned[np.arange(len(local)), best_feat[local]]
    go_left = (nbin <= best_bin[local] if left_mask is None
               else left_mask[local, nbin])
    stays = done | ~do_split[local]
    child = np.where(go_left, 2 * node + 1, 2 * node + 2)
    return np.where(stays, node, child), stays


@pytest.mark.parametrize(
    "dtype,f,width,cat", LEVELS,
    ids=[f"{np.dtype(d).name}-f{f}-w{w}-{'cat' if c else 'num'}"
         for d, f, w, c in LEVELS])
def test_select_routes_every_row_as_the_gather_does(dtype, f, width, cat):
    import jax
    import jax.numpy as jnp

    ops = _level(dtype, f, width, cat, seed=f * 1000 + width)
    want_node, want_done = _route_numpy(**ops)
    assert (want_node != ops["node"]).any() or not ops["do_split"].any()
    for form in T.ROUTE_FORMS:
        node, done = jax.jit(
            lambda kw, form=form: T.route_level(**kw, form=form))(
                {k: jnp.asarray(v) for k, v in ops.items()})
        assert node.dtype == jnp.int32 and done.dtype == jnp.bool_
        np.testing.assert_array_equal(np.asarray(node), want_node, form)
        np.testing.assert_array_equal(np.asarray(done), want_done, form)


def test_select_lowers_for_the_tpu_with_no_gather():
    """What the form is for: the numeric level's program, lowered for
    the TPU, holds no gather at all (the nodes' entries and columns are
    dynamic slices inside one loop), where the other form holds
    several."""
    import jax
    import jax.numpy as jnp

    ops = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
           for k, v in _level(np.uint8, 28, 32, False, seed=0).items()}
    text = {form: jax.jit(
        lambda kw, form=form: T.route_level(**kw, form=form)).trace(
            ops).lower(lowering_platforms=("tpu",)).as_text()
            for form in T.ROUTE_FORMS}
    assert "gather" not in text["select"]
    assert text["select"].count("stablehlo.while") == 1
    assert "stablehlo.dynamic_slice" in text["select"]
    assert "stablehlo.gather" in text["gather"]


# ---------------------------------------------------------------------------
# whole fits: routed by select, the trees the gather's fit grows
# ---------------------------------------------------------------------------

DEPTH = 5
FITS = {
    # name -> (TrainConfig fields, devices, environment, tree mode)
    "numeric": ({}, 0, {}, "serial"),
    "categorical": ({"categorical_features": (1, 5),
                     "min_data_per_group": 10}, 0, {}, "serial"),
    # NATIVE_HIST=0: the shard_map builders then run an XLA formulation
    # with the varying-axes checker on (parallel_modes._check_vma)
    "dp4_data_parallel": ({"tree_learner": "data"}, 4,
                          {"MMLSPARK_TPU_HIST_SHARD": "on",
                           "MMLSPARK_TPU_NATIVE_HIST": "0"}, "data_sharded"),
    "dp4_voting": ({"tree_learner": "voting", "top_k": 8}, 4,
                   {"MMLSPARK_TPU_NATIVE_HIST": "0"}, "voting"),
    # the serial builder over row-sharded operands: GSPMD partitions it
    "dp4_gspmd": ({"tree_learner": "serial"}, 4,
                  {"MMLSPARK_TPU_HIST_SHARD": "off"}, "serial"),
}


def _fit(fields, devices):
    import jax

    rng = np.random.default_rng(5)
    x = rng.normal(size=(1500, 8))
    x[:, 1] = rng.integers(0, 9, size=len(x))
    x[:, 5] = rng.integers(0, 4, size=len(x))
    y = (1.5 * x[:, 0] - x[:, 2] + np.isin(x[:, 1], (2, 5, 7))
         + rng.normal(size=len(x)) * 0.3 > 0.5).astype(np.float64)
    mapper = BinMapper.fit(
        x, max_bin=32,
        categorical_features=fields.get("categorical_features", ()))
    cfg = T.TrainConfig(**{
        **dict(objective="binary", num_iterations=3, num_leaves=2 ** DEPTH,
               max_depth=DEPTH, min_data_in_leaf=5, max_bin=32), **fields})
    mesh = (create_mesh(MeshConfig(dp=devices),
                        devices=jax.devices()[:devices]) if devices else None)
    return T.train(mapper.transform(x), y, cfg,
                   bin_upper=mapper.bin_upper_values(32), mesh=mesh)


def _drop_programs():
    """The compiled builders are cached by configuration, not by the
    routing's form (a process has one backend)."""
    for cache in (T._BUILDER_CACHE, T._CHUNK_CACHE):
        cache.clear()


@pytest.fixture
def fresh_programs():
    _drop_programs()
    yield
    _drop_programs()


@pytest.mark.parametrize("name", list(FITS))
def test_fit_routed_by_select_grows_the_gathers_trees(
        name, monkeypatch, fresh_programs):
    fields, devices, env, tree_mode = FITS[name]
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    by_gather = _fit(fields, devices)
    assert by_gather.hist_stats["tree_mode"] == tree_mode
    assert by_gather.hist_stats["route"] == {"select": 0, "gather": DEPTH}

    _drop_programs()
    monkeypatch.setattr(T, "route_form", lambda width: "select")
    by_select = _fit(fields, devices)
    assert by_select.hist_stats["route"] == {"select": DEPTH, "gather": 0}

    a, b = by_gather.booster, by_select.booster
    assert (a.split_feature >= 0).sum() >= 3 * 8        # the trees grew
    if fields.get("categorical_features"):
        assert (a.decision_type & 1).any()
    for field in ("split_feature", "threshold_bin", "node_value", "count",
                  "decision_type", "cat_bitset", "threshold_value"):
        got, want = getattr(b, field), getattr(a, field)
        if want is None:
            assert got is None, field
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          field)


def test_route_form_follows_the_backend_and_the_width(monkeypatch):
    """``gather`` on the CPU backend at every width; elsewhere ``select``
    up to ``ROUTE_SELECT_MAX_WIDTH`` nodes, and the counter sums a
    tree's levels by it. The feature-parallel builder keeps its own
    routing (a gathered bin and a vote across the column shards)."""
    import jax

    widths = [2 ** d for d in range(12)]
    assert T.route_by_form(widths) == {"select": 0, "gather": 12}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = sum(w > T.ROUTE_SELECT_MAX_WIDTH for w in widths)
    assert 0 < wide < 12
    assert T.route_by_form(widths) == {"select": 12 - wide, "gather": wide}
    assert T.route_form(T.ROUTE_SELECT_MAX_WIDTH) == "select"
    assert T.route_form(2 * T.ROUTE_SELECT_MAX_WIDTH) == "gather"
    assert T.route_by_form(widths, "feature") == {"select": 0, "gather": 12}
