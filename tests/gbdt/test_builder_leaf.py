"""The builder's ``node`` against the walk it replaced.

Every builder ``_get_builder`` can return hands back, beside the tree,
the slot each of its rows settled in. The fused step updates the
training rows' raw scores from it (``nv[node]``) where it used to walk
the finished tree again (``_make_predict_tree``). Pinned here, for the
serial builder and the three shard_map builders on virtual CPU devices:
``node`` is the slot the walk reaches for every row, and ``raw`` after
a step is ``raw + walk`` bitwise.
"""

from dataclasses import replace

import numpy as np
import pytest

from mmlspark_tpu.models.gbdt import trainer as T
from mmlspark_tpu.ops.binning import BinMapper
from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

N, F, B, DEPTH = 768, 8, 32, 4
LAST_LEVEL = 2 ** DEPTH - 1          # first slot of the deepest level
BASE = dict(objective="binary", num_leaves=2 ** DEPTH, max_depth=DEPTH,
            max_bin=B, min_data_in_leaf=5)

# case -> TrainConfig fields; what else a case needs is keyed off its name
CASES = {
    "numeric": {},
    "categorical": {"categorical_features": (1, 5), "min_data_per_group": 10},
    "monotone": {"monotone_constraints": (1, -1, 0, 0, 0, 0, 0, 0)},
    "bag_mask": {"bagging_fraction": 0.5, "bagging_freq": 1},
    "goss": {"boosting_type": "goss", "top_rate": 0.2, "other_rate": 0.2},
    "leaf_budget": {"num_leaves": 11},
    "min_data": {"min_data_in_leaf": 60},
    "multiclass": {"objective": "multiclass", "num_class": 3},
    "efb": {},
    "padded_rows": {},
}
SHARDED = ("numeric", "bag_mask", "goss", "leaf_budget", "min_data",
           "multiclass")
MATRIX = ([("serial", c) for c in CASES if c != "padded_rows"]
          + [("serial_gspmd", "numeric")]
          + [(m, c) for m in ("voting", "data_sharded", "feature")
             for c in SHARDED]
          + [("data_sharded", "padded_rows")])


@pytest.fixture(scope="module")
def meshes():
    import jax
    four = jax.devices()[:4]
    dp4 = create_mesh(MeshConfig(dp=4), devices=four)
    return {"serial": None, "serial_gspmd": dp4, "voting": dp4,
            "data_sharded": dp4,
            "feature": create_mesh(MeshConfig(dp=1, fp=4), devices=four)}


def _binned(case, rng):
    if case == "efb":
        # three mutually exclusive sparse columns (one bundle), the
        # rest dense
        x = rng.integers(0, B, size=(N, F))
        x[:, :3] = 0
        owner = rng.integers(0, 3, size=N)
        for j in range(3):
            rows = (owner == j) & (rng.random(N) < 0.6)
            x[rows, j] = rng.integers(1, 6, size=int(rows.sum()))
        return x.astype(np.uint8)
    x = rng.normal(size=(N, F))
    if case == "categorical":
        x[:, 1] = rng.integers(0, 9, size=N)
        x[:, 5] = rng.integers(0, 4, size=N)
    return BinMapper.fit(x, max_bin=B).transform(x).astype(np.uint8)


def _walk(sf, bgl, nv, binned):
    return T._get_predict_tree(DEPTH)(sf, bgl, nv, binned)


@pytest.mark.parametrize("mode,case", MATRIX,
                         ids=[f"{m}-{c}" for m, c in MATRIX])
def test_node_is_the_walks_slot_and_raw_is_raw_plus_walk(
        mode, case, meshes, monkeypatch):
    import jax
    import jax.numpy as jnp

    if case == "efb":
        # the XLA formulations take the bundled matrix as an operand;
        # the native one reads it from a host registry by token
        monkeypatch.setenv("MMLSPARK_TPU_NATIVE_HIST", "0")
    rng = np.random.default_rng(len(mode) * 100 + len(case))
    mesh = meshes[mode]
    tree_mode = "serial" if mode == "serial_gspmd" else mode
    cfg = T._loop_only_normalized(T.TrainConfig(**{
        **BASE, **CASES[case],
        "tree_learner": mode if mode in ("voting", "feature") else "serial",
        "top_k": F}))
    k = cfg.num_class if cfg.objective == "multiclass" else 1
    binned = _binned(case, rng)
    efb_plan, extra = None, {}
    if case == "efb":
        from mmlspark_tpu.ops import efb
        efb_plan = efb.plan_bundles(binned, B, mode="on")
        assert efb_plan is not None and efb_plan.bundles
        extra["binned_hist"] = jnp.asarray(efb.apply_plan(binned, efb_plan))
    row_valid = np.ones(N, np.float32)
    if case == "padded_rows":
        # what train() appends so that dp divides N: copies of the last
        # row at weight 0, masked out of sampling and histograms
        binned[-3:] = binned[-4]
        row_valid[-3:] = 0.0
    binned_d = jnp.asarray(binned)

    # ---- the builder alone: node against the walk's slot ------------
    builder = T._get_builder(F, B, cfg, tree_mode, mesh, efb_plan=efb_plan)
    valid = row_valid.copy()
    if case in ("bag_mask", "goss"):
        valid *= rng.random(N) < 0.5
    slots = jnp.arange(2 ** (DEPTH + 1) - 1, dtype=jnp.float32)
    for _ in range(k):
        grad = rng.normal(size=N).astype(np.float32)
        if case == "categorical":
            grad += 2.0 * np.isin(binned[:, 1], (2, 5, 7))
        hess = rng.uniform(0.2, 1.0, size=N).astype(np.float32)
        sf, tb, nv, cnt, dt, bgl, node = builder(
            binned_d, jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(valid), jnp.ones(F, jnp.float32),
            jnp.int32(cfg.num_leaves), **extra)
        node = np.asarray(node)
        assert node.shape == (N,) and node.dtype == np.int32
        # the walk returns nv[slot]; with nv = 0, 1, 2, ... that is the slot
        np.testing.assert_array_equal(
            node, np.asarray(_walk(sf, bgl, slots, binned_d)).astype(np.int32))
        sf = np.asarray(sf)
        assert (sf[node] < 0).all()               # every row sits in a leaf
        assert (sf >= 0).sum() >= 3               # and the tree did grow
        if case in ("bag_mask", "goss"):
            # rows the tree never saw are routed like any other
            assert len(set(node[valid == 0])) > 1
        if case == "leaf_budget":
            assert (sf >= 0).sum() + 1 == 11               # budget spent
            last_parents = np.arange(2 ** (DEPTH - 1) - 1, LAST_LEVEL)
            # the budget ran out inside the deepest level of splits
            assert 0 < (sf[last_parents] >= 0).sum() < len(last_parents)
        if case == "min_data":
            # a branch stopped early: rows done above the deepest level
            assert (node < 2 ** (DEPTH - 1) - 1).any()
        if case == "categorical":
            assert (np.asarray(dt) == 1).any()

    # ---- one fused step: raw' against raw + walk, bitwise -----------
    step = T._get_step_fn(F, B, cfg, k, 0, tree_mode, mesh,
                          efb_plan=efb_plan)
    labels = rng.integers(0, max(k, 2), size=N).astype(np.float32)
    raw0 = rng.normal(size=(N,) if k == 1 else (N, k)).astype(np.float32)
    data = {
        "binned": binned_d, "labels": jnp.asarray(labels * row_valid),
        "weights": jnp.asarray(row_valid), "groups": None,
        "group_layout": None, "row_valid": jnp.asarray(row_valid),
        "base": jnp.float32(0.0), "key": jax.random.key(7),
        # one class: a rate whose product rounds. Three: XLA:CPU places
        # the column updates of this reference and of the step in
        # different fusions and contracts the shrink into the add in
        # one and not the other, so a power of two, whose product is
        # exact either way (whole multiclass fits are bitwise the
        # parent's at any rate: PERF.md, PR 28)
        "lr": jnp.float32(0.1 if k == 1 else 0.125), "valids": (), **extra}
    (raw1, _), ys = step(data, (jnp.asarray(raw0), ()), jnp.int32(0))
    # the same trees unshrunk: the learning rate is traced data and no
    # split depends on it
    _, ys_raw = step({**data, "lr": jnp.float32(1.0)},
                     (jnp.asarray(raw0), ()), jnp.int32(0))
    walk = T._make_predict_tree(DEPTH)

    @jax.jit
    def walked(raw, lr, sfs, bgls, nvs):
        # the step as it was before the builder returned ``node``:
        # shrink, walk, add, in one program (XLA may contract the shrink
        # into the add, so the order of the three is part of the bits)
        for cls in range(k):
            pred = walk(sfs[cls], bgls[cls], nvs[cls] * lr, binned_d)
            raw = raw + pred if k == 1 else raw.at[:, cls].add(pred)
        return raw

    sfs, tbs = ys[0], ys[1]
    np.testing.assert_array_equal(np.asarray(sfs), np.asarray(ys_raw[0]))
    assert np.asarray(sfs).max() >= 0
    if cfg.categorical_features:
        bgls = ys[6]
    else:
        bgls = ((jnp.arange(B)[None, None, :] <= tbs[:, :, None])
                & (sfs >= 0)[:, :, None])
    want = walked(jnp.asarray(raw0), data["lr"], sfs, bgls, ys_raw[2])
    np.testing.assert_array_equal(np.asarray(raw1).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


FITS = {
    "gbdt": ({}, None, {}, "builder_leaf"),
    "goss": ({"boosting_type": "goss"}, None, {}, "builder_leaf"),
    "rf": ({"boosting_type": "rf", "bagging_fraction": 0.7,
            "bagging_freq": 1}, None, {}, "builder_leaf"),
    "valid_set": ({}, None, {}, "builder_leaf"),
    "serial_gspmd": ({}, "serial_gspmd",
                     {"MMLSPARK_TPU_HIST_SHARD": "off"}, "builder_leaf"),
    "voting": ({"tree_learner": "voting", "top_k": F}, "voting", {},
               "builder_leaf"),
    "data_sharded": ({}, "data_sharded", {}, "builder_leaf"),
    "feature": ({"tree_learner": "feature"}, "feature", {},
                "builder_leaf"),
    "dart": ({"boosting_type": "dart"}, None, {}, "tree_walk"),
    "custom_objective": ({}, None, {}, "tree_walk"),
    "leafwise": ({}, None, {"MMLSPARK_TPU_GROW_POLICY": "leafwise"},
                 "tree_walk"),
    "ooc": ({"objective": "regression"}, None,
            {"MMLSPARK_TPU_OOC": "on", "MMLSPARK_TPU_HIST_QUANT": "q16",
             "MMLSPARK_TPU_EFB": "off",
             "MMLSPARK_TPU_OOC_CHUNK_ROWS": "1024"}, "tree_walk"),
}


@pytest.mark.parametrize("name", list(FITS))
def test_hist_stats_says_how_the_raw_scores_were_updated(
        name, meshes, monkeypatch):
    fields, mesh_key, env, want = FITS[name]
    for var, value in env.items():
        monkeypatch.setenv(var, value)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(N, F))
    y = (x[:, 0] - x[:, 1] + 0.3 * rng.normal(size=N) > 0).astype(np.float64)
    mapper = BinMapper.fit(x, max_bin=B)
    cfg = replace(T.TrainConfig(**BASE), num_iterations=2, **fields)
    kw = {}
    if name == "valid_set":
        kw["valid_sets"] = [(mapper.transform(x[:100]), y[:100], None)]
    if name == "custom_objective":
        kw["custom_objective"] = lambda raw, labels, weights: (
            raw - labels, np.ones_like(np.asarray(raw)))
    res = T.train(mapper.transform(x), y, cfg,
                  bin_upper=mapper.bin_upper_values(B),
                  mesh=meshes[mesh_key] if mesh_key else None, **kw)
    st = res.hist_stats
    assert st["raw_update"] == want
    if mesh_key:
        assert st["tree_mode"] == ("serial" if mesh_key == "serial_gspmd"
                                   else mesh_key)
    assert st["ooc"] is (name == "ooc")
    assert st["grow_policy"] == ("leafwise" if name == "leafwise"
                                 else "depthwise")
