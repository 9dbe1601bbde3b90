"""Mosaic (TPU) lowering of both Pallas kernels — no chip required.

VERDICT r4 weak #2: neither kernel had ever been THROUGH the Mosaic
pipeline (interpret mode bypasses it), so first TPU contact risked
unsupported-primitive / layout failures. ``jax.jit(...).trace().lower``
with a TPU lowering platform runs the full Pallas->Mosaic lowering on
any host and embeds the serialized Mosaic module in a
``tpu_custom_call`` — only XLA:TPU's final compile and execution remain
hardware-gated (``chip_smoke.py`` covers those for the histogram kernel).

``test_lowering_check_is_not_vacuous`` proves this catches real
problems: a kernel using an unimplemented primitive must be rejected.
"""

import functools

import numpy as np
import pytest


def _lower_tpu(fn, *args) -> str:
    import jax

    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _kernel_calls(fn, *args):
    """The ``pallas_call`` equations of ``fn``."""
    import jax

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from calls(sub)

    return list(calls(jax.make_jaxpr(fn)(*args).jaxpr))


def _kernel_dots(fn, *args):
    """The ``dot_general`` equations inside ``fn``'s ``pallas_call``."""
    import jax

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return [eqn for call in _kernel_calls(fn, *args)
            for eqn in walk(call.params["jaxpr"])]


def _hist_cases():
    """(path, width, features). At the cell's 28 features: widths 1, 8
    and 32 in place and one width on each side of the bound between the
    two paths, wherever a measurement puts it. At 64, 136 and 200
    features (an MSLR-wide matrix and past it) the paths cross at
    narrower levels, and at 600 the level's accumulator, which grows
    with the feature count, no longer fits the kernel's share of VMEM
    at 32 nodes."""
    from mmlspark_tpu.models.gbdt.hist_pallas import IN_PLACE_MAX_WIDTH

    in_place = sorted({1, 8, 32, IN_PLACE_MAX_WIDTH})
    return ([("in_place", w, 28) for w in in_place]
            + [("sorted", 2 * IN_PLACE_MAX_WIDTH, 28),
               ("in_place", 64, 64), ("sorted", 128, 64),
               ("in_place", 32, 136), ("sorted", 64, 136),
               ("in_place", 32, 200), ("sorted", 128, 200),
               ("in_place", 16, 600), ("sorted", 32, 600)])


def _hist_level(width, f, n=4100, b=255):
    """One level at bench-like dims (255 bins, N no multiple of the
    block): the traced function and its arguments' shapes."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt import hist_pallas

    fn = functools.partial(hist_pallas._pallas_level_histogram, width=width,
                           f=f, b=b, block_rows=512, interpret=False)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    return fn, (jax.ShapeDtypeStruct((n, f), jnp.uint8), vec, vec, vec,
                jax.ShapeDtypeStruct((n,), jnp.int32))


@pytest.mark.parametrize("path,width,f", _hist_cases())
def test_hist_kernel_lowers_to_mosaic(path, width, f):
    """Both paths of the level histogram go through Mosaic with their
    bf16 operands (the stats' three parts against the one-hot, one
    default-precision product); the in-place one asks XLA for no sort
    and no gather (it has no feed beyond the stats' element-wise
    writes), and asks Mosaic for no more VMEM than its budget, its
    accumulator counted twice."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt import hist_pallas

    assert hist_pallas.level_feed(width, f) == path
    fn, args = _hist_level(width, f)
    txt = _lower_tpu(fn, *args)
    assert "tpu_custom_call" in txt  # the serialized Mosaic module
    # the kernel's products: one a feature, bf16 x bf16 -> f32, no
    # precision asked for (a float32 product at HIGHEST is six passes)
    dots = _kernel_dots(fn, *args)
    assert len(dots) == f
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
        assert eqn.outvars[0].aval.dtype == jnp.float32
        assert eqn.params["precision"] is None
    has_feed = "stablehlo.sort" in txt and "stablehlo.gather" in txt
    assert has_feed == (path == "sorted")
    if path == "in_place":
        assert "sort" not in txt and "gather" not in txt
        assert "scatter" not in txt
        (call,) = _kernel_calls(fn, *args)
        asked = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        acc = call.outvars[0].aval
        assert acc.shape[0] == f and acc.shape[2:] == (8, 256)
        assert (2 * acc.size * acc.dtype.itemsize < asked
                <= hist_pallas.IN_PLACE_VMEM_BUDGET)


@pytest.fixture(scope="module")
def one_v5e():
    """A described (not attached) v5e chip to compile for; built inside
    the fixture so that only the worker that runs this file loads the
    TPU's library."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("width,f", [(128, 28), (64, 84)])
def test_widest_in_place_levels_compile_for_v5e(one_v5e, width, f):
    """The TPU's own compiler takes the in-place kernel at the widest
    levels the rule admits past 32 nodes: 128 at 28 features and 64 at
    84 (45 and 54 MiB of VMEM asked). A compile that passes is not a
    chip run; the chip's are beside ``IN_PLACE_MAX_WIDTH``."""
    import jax

    from mmlspark_tpu.models.gbdt import hist_pallas

    assert hist_pallas.level_feed(width, f) == "in_place"
    assert hist_pallas.level_feed(2 * width, f) == "sorted"
    fn, args = _hist_level(width, f, n=100_000)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e)
            for a in args]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_kernel_lowers_to_mosaic():
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.flash import flash_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(
        rng.normal(size=(2, 1024, 4, 64)).astype(np.float32))
        for _ in range(3))
    txt = _lower_tpu(
        lambda a, b, c: flash_attention(a, b, c, causal=True,
                                        interpret=False), q, k, v)
    assert "tpu_custom_call" in txt


def test_voting_builder_with_pallas_lowers_to_mosaic(monkeypatch):
    """The round-5 distributed path end to end, exactly as it runs on
    TPU: shard_map over dp with check_vma ON, the pallas kernel
    selected per-shard (FORCE_COMPILE skips the off-TPU interpret
    fallback), lowered through Mosaic."""
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_HIST", "1")
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_FORCE_COMPILE", "1")

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.parallel_modes import (
        _check_vma,
        make_build_tree_voting,
    )
    from mmlspark_tpu.models.gbdt.trainer import (
        TrainConfig,
        _loop_only_normalized,
    )
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    # the on-TPU configuration keeps the checker ON
    assert _check_vma(64) is True
    mesh = create_mesh(MeshConfig(dp=8))
    cfg = _loop_only_normalized(TrainConfig(
        objective="binary", num_leaves=15, max_depth=4, max_bin=64,
        top_k=8))
    fn = make_build_tree_voting(8, 64, cfg, mesh)
    n, f = 1024, 8
    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.integers(0, 64, size=(n, f)).astype(np.uint8)),
            jnp.asarray(rng.normal(size=n).astype(np.float32)),
            jnp.asarray(rng.uniform(0.1, 1, size=n).astype(np.float32)),
            jnp.ones(n, jnp.float32),
            jnp.ones(f, jnp.float32),
            jnp.int32(15))
    txt = _lower_tpu(fn, *args)
    assert "tpu_custom_call" in txt
    assert "shard_map" in txt or "all_reduce" in txt or "psum" in txt


@pytest.mark.parametrize("subtract", [False, True])
def test_serial_builder_lowers_for_tpu(monkeypatch, subtract):
    """The core tree builder (XLA formulation, with and without the
    histogram-subtraction trick) lowers for TPU — no Mosaic involved,
    but sized-nonzero compaction and scatter shapes must pass the TPU
    lowering rules."""
    # the lowering host's backend is cpu, whose default is the native
    # host callback — not a program a TPU run ever selects
    monkeypatch.setenv("MMLSPARK_TPU_NATIVE_HIST", "0")
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.trainer import (
        TrainConfig,
        _loop_only_normalized,
        make_build_tree,
    )

    cfg = _loop_only_normalized(TrainConfig(
        objective="binary", num_leaves=31, max_depth=5, max_bin=255))
    fn = make_build_tree(28, 255, cfg, subtract=subtract)
    n, f = 4096, 28
    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.integers(0, 255, size=(n, f)).astype(np.uint8)),
            jnp.asarray(rng.normal(size=n).astype(np.float32)),
            jnp.asarray(rng.uniform(0.1, 1, size=n).astype(np.float32)),
            jnp.ones(n, jnp.float32),
            jnp.ones(f, jnp.float32),
            jnp.int32(31))
    txt = _lower_tpu(fn, *args)
    assert "stablehlo" in txt or len(txt) > 1000


def test_scoring_paths_lower_for_tpu():
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.booster import BoosterArrays

    rng = np.random.default_rng(0)
    trees, depth, num_f = 100, 6, 28
    slots = 2 ** (depth + 1) - 1
    internal = 2 ** depth - 1
    sf = np.full((trees, slots), -1, dtype=np.int32)
    sf[:, :internal] = rng.integers(0, num_f, size=(trees, internal))
    tv = np.full((trees, slots), np.inf)
    tv[:, :internal] = rng.normal(size=(trees, internal))
    booster = BoosterArrays(
        split_feature=sf,
        threshold_bin=rng.integers(0, 255, size=(trees, slots)).astype(
            np.int32),
        threshold_value=tv,
        node_value=rng.normal(size=(trees, slots)).astype(np.float32),
        count=np.ones((trees, slots), np.float32),
        tree_weights=np.ones(trees, np.float32),
        max_depth=depth, num_features=num_f, num_class=1,
        objective="binary", init_score=0.0)
    x = jnp.asarray(rng.normal(size=(2048, num_f)).astype(np.float32))
    xb = jnp.asarray(rng.integers(0, 255, size=(2048, num_f)).astype(
        np.uint8))
    assert len(_lower_tpu(booster.predict_fn(), x)) > 1000
    assert len(_lower_tpu(booster.predict_binned_fn(), xb)) > 1000


def test_long_context_attention_lowers_for_tpu():
    """Ring + Ulysses attention over an sp mesh, and blockwise: the
    long-context plane's ppermute/all_to_all collectives must pass TPU
    lowering."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.attention import (
        blockwise_attention,
        ring_attention,
        ulysses_attention,
    )
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    sp_mesh = create_mesh(MeshConfig(dp=1, sp=8))
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(
        rng.normal(size=(1, 1024, 8, 64)).astype(np.float32))
        for _ in range(3))
    for fn in (lambda a, b, c: ring_attention(a, b, c, sp_mesh,
                                              causal=True),
               lambda a, b, c: ulysses_attention(a, b, c, sp_mesh,
                                                 causal=True),
               lambda a, b, c: blockwise_attention(a, b, c, causal=True)):
        assert len(_lower_tpu(fn, q, k, v)) > 1000


def test_vw_sharded_pass_lowers_for_tpu():
    """The VW sharded online pass (shard_map + pmean/pmax sync) with
    the full adaptive+normalized+invariant update family."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.core.jax_compat import pcast_varying, shard_map
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.models.vw.learners import make_sgd_train
    from mmlspark_tpu.parallel.mesh import DATA_AXIS, create_mesh

    mesh = create_mesh()
    nw = 1 << 12
    run = make_sgd_train(nw, "logistic", 0.5, 0.5, 1.0, True, 0.0, 0.0,
                         normalized=True, invariant=True)

    def sharded(w, g2, s, n_acc, bias, t, bi, bv, by, bw):
        w, g2, s, n_acc, bias, t = pcast_varying(
            (w, g2, s, n_acc, bias, t), (DATA_AXIS,))
        w, g2, s, n_acc, bias, t, _ = run(w, g2, s, n_acc, bias, t,
                                          bi, bv, by, bw)
        return (jax.lax.pmean(w, DATA_AXIS),
                jax.lax.pmean(g2, DATA_AXIS),
                jax.lax.pmax(s, DATA_AXIS))

    bspec = P(DATA_AXIS)
    fn = shard_map(sharded, mesh=mesh,
                   in_specs=(P(), P(), P(), P(), P(), P(), bspec, bspec,
                             bspec, bspec),
                   out_specs=(P(), P(), P()))
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    nb, bsz, wdt = 16, 8, 10
    args = (jnp.zeros(nw, jnp.float32), jnp.zeros(nw, jnp.float32),
            jnp.zeros(nw, jnp.float32), jnp.zeros(()), jnp.zeros(()),
            jnp.zeros(()),
            jnp.asarray(rng.integers(0, nw, size=(nb, bsz, wdt))
                        .astype(np.int32)),
            jnp.asarray(rng.normal(size=(nb, bsz, wdt)).astype(np.float32)),
            jnp.asarray((rng.random((nb, bsz)) > 0.5).astype(np.float32)),
            jnp.ones((nb, bsz), np.float32))
    assert len(_lower_tpu(fn, *args)) > 1000


@pytest.mark.parametrize("flags,max_bin", [
    ({}, 255),
    ({"MMLSPARK_TPU_PALLAS_HIST": "1",
      "MMLSPARK_TPU_PALLAS_FORCE_COMPILE": "1"}, 255),
    ({"MMLSPARK_TPU_HIST_SUB": "1"}, 255),
    # more than 256 bins: the kernel is enabled as on the chip and the
    # policy must still leave it for per_feature
    ({"MMLSPARK_TPU_PALLAS_HIST": "1",
      "MMLSPARK_TPU_PALLAS_FORCE_COMPILE": "1"}, 1023),
])
def test_full_fused_step_lowers_for_tpu(monkeypatch, flags, max_bin):
    """The ENTIRE fused boosting step (gradients -> tree build -> raw
    update -> metrics) at the fit cell's configuration, in every kernel
    configuration a chip run can select — the exact per-iteration
    program ``train()`` dispatches."""
    for kk, vv in flags.items():
        monkeypatch.setenv(kk, vv)
    from mmlspark_tpu.models.gbdt.trainer import (
        TrainConfig,
        aot_lower_step,
    )

    cfg = TrainConfig(objective="binary", num_leaves=63, max_depth=6,
                      max_bin=max_bin, min_data_in_leaf=20)
    txt = aot_lower_step(cfg, n=8192, num_f=28, platform="tpu")
    assert len(txt) > 1000
    # the Mosaic histogram kernel, exactly where the policy selects it
    assert ("tpu_custom_call" in txt) == (
        "MMLSPARK_TPU_PALLAS_HIST" in flags and max_bin <= 256)


def test_resnet50_scoring_lowers_for_tpu():
    """The ONNX->XLA ResNet-50 (the transform cell's graph, at the
    published stages) lowers for TPU — the converter's conv/BN/pool
    emission must pass TPU rules."""
    import jax.numpy as jnp

    from benchmark.lookup import load_module
    from mmlspark_tpu.onnx import convert_model

    builder = load_module("builders", "resnet50_onnx")
    stages = [(3, 64), (4, 128), (6, 256), (3, 512)]
    payload = builder.make_proto(builder.make_weights(0, stages), stages,
                                 image=224)
    rng = np.random.default_rng(0)
    run = convert_model(payload).convert()
    x = jnp.asarray(rng.normal(size=(4, 3, 224, 224)).astype(np.float32))
    graph_in = "x"
    txt = _lower_tpu(lambda xx: run({graph_in: xx}), x)
    assert len(txt) > 1000


def test_deeptext_train_step_lowers_for_tpu():
    """One BERT-shaped text fine-tune step (fwd+bwd+optax update)."""
    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.dl.backbones import TextTransformer

    module = TextTransformer(num_classes=2, vocab_size=2048, dim=128,
                             heads=4, layers=2, max_len=64)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 2048, size=(8, 64)).astype(np.int32))
    y = jnp.asarray(rng.integers(0, 2, size=8).astype(np.int32))
    params = module.init(jax.random.key(0), ids)
    tx = optax.adamw(1e-4)
    opt_state = tx.init(params)

    def step(params, opt_state, ids, y):
        def loss_fn(p):
            logits = module.apply(p, ids)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    txt = _lower_tpu(step, params, opt_state, ids, y)
    assert len(txt) > 1000


@pytest.mark.parametrize("objective,boosting,kw", [
    ("lambdarank", "gbdt", dict(rows_per_group=128)),
    ("multiclass", "gbdt", {}),
    ("binary", "goss", {}),   # nanquantile (sort) must pass TPU rules
    ("binary", "rf", {}),
])
def test_other_tracked_configs_lower_for_tpu(objective, boosting, kw):
    from mmlspark_tpu.models.gbdt.trainer import (
        TrainConfig,
        aot_lower_step,
    )

    cfg_kw = dict(objective=objective, num_leaves=31, max_depth=5,
                  max_bin=255, boosting_type=boosting)
    if objective == "multiclass":
        cfg_kw["num_class"] = 3
    if boosting == "goss":
        cfg_kw.update(top_rate=0.2, other_rate=0.1)
    if boosting == "rf":
        cfg_kw.update(bagging_fraction=0.8, bagging_freq=1)
    txt = aot_lower_step(TrainConfig(**cfg_kw), n=4096, num_f=28, **kw)
    assert len(txt) > 1000


def test_ulysses_never_materializes_dense_scores():
    """Ulysses' inner attention must stream KV blocks: the lowered
    program at a long sequence may not contain an (n, n) score tensor
    (which would be quadratic memory — the thing sequence parallelism
    exists to avoid)."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.attention import ulysses_attention
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    sp_mesh = create_mesh(MeshConfig(dp=1, sp=8))
    n = 8192
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(
        rng.normal(size=(1, n, 8, 16)).astype(np.float32))
        for _ in range(3))
    txt = _lower_tpu(
        lambda a, b, c: ulysses_attention(a, b, c, sp_mesh, causal=True),
        q, k, v)
    assert f"{n}x{n}" not in txt and f"{n},{n}" not in txt, \
        "dense (n, n) scores materialized in the lowered program"


def test_attention_awkward_lengths():
    """Non-power-of-two / non-block-divisible sequence lengths must
    work through every attention path (the old dense Ulysses inner
    accepted any length; the streaming one must too)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.attention import (
        blockwise_attention,
        dense_attention,
        ulysses_attention,
    )
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    rng = np.random.default_rng(0)
    for n in (704, 1021):  # 704 = 2^6*11; 1021 prime
        q, k, v = (jnp.asarray(
            rng.normal(size=(1, n, 8, 16)).astype(np.float32))
            for _ in range(3))
        want = dense_attention(q, k, v, causal=True)
        got = blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
    sp_mesh = create_mesh(MeshConfig(dp=1, sp=8))
    n = 704  # divisible by sp=8, not by 512
    q, k, v = (jnp.asarray(
        rng.normal(size=(1, n, 8, 16)).astype(np.float32))
        for _ in range(3))
    want = dense_attention(q, k, v, causal=True)
    got = jax.jit(lambda a, b, c: ulysses_attention(
        a, b, c, sp_mesh, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_ring_streams_rotated_chunks():
    """Ring attention's per-rotation attend must stream the rotated KV
    chunk in sub-blocks: at n=8192 over sp=8 the chunk is 1024, so a
    non-streamed attend would materialize (1024, 1024) score tiles."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.attention import ring_attention
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    sp_mesh = create_mesh(MeshConfig(dp=1, sp=8))
    n = 8192
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(
        rng.normal(size=(1, n, 8, 16)).astype(np.float32))
        for _ in range(3))
    txt = _lower_tpu(
        lambda a, b, c: ring_attention(a, b, c, sp_mesh, causal=True),
        q, k, v)
    assert "1024x1024" not in txt and f"{n}x{n}" not in txt, \
        "chunk-squared score tile materialized in ring attention"


def test_gspmd_dp_falls_back_to_xla_histogram(monkeypatch):
    """GSPMD cannot auto-partition Mosaic kernels ('Please wrap the
    call in a shard_map'): the serial builder under a mesh must bypass
    the Pallas kernel even when the flag is on, or dp training with
    MMLSPARK_TPU_PALLAS_HIST=1 would CRASH at TPU compile. Lowering
    over row-sharded inputs must succeed WITHOUT a tpu_custom_call."""
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_HIST", "1")
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_FORCE_COMPILE", "1")

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mmlspark_tpu.models.gbdt.trainer import (
        TrainConfig,
        _get_builder,
        _loop_only_normalized,
    )
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(dp=8))
    cfg = _loop_only_normalized(TrainConfig(
        objective="binary", num_leaves=15, max_depth=4, max_bin=64))
    fn = _get_builder(8, 64, cfg, "serial", mesh)
    n, f = 1024, 8
    rng = np.random.default_rng(0)
    row = NamedSharding(mesh, P("dp"))
    row2 = NamedSharding(mesh, P("dp", None))
    args = (jax.device_put(
                rng.integers(0, 64, size=(n, f)).astype(np.uint8), row2),
            jax.device_put(rng.normal(size=n).astype(np.float32), row),
            jax.device_put(
                rng.uniform(0.1, 1, size=n).astype(np.float32), row),
            jax.device_put(np.ones(n, np.float32), row),
            jnp.ones(f, jnp.float32),
            jnp.int32(15))
    txt = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in txt  # XLA formulation selected
    assert len(txt) > 1000


def test_lowering_check_is_not_vacuous():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def bad_kernel(x_ref, o_ref):
        # sort is unimplemented in the Pallas TPU lowering
        o_ref[...] = jnp.sort(x_ref[...], axis=0)[:8]

    def bad(x):
        return pl.pallas_call(
            bad_kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))(x)

    with pytest.raises(Exception, match="[Uu]nimplemented|[Nn]ot.*implement"):
        _lower_tpu(bad, jnp.zeros((256, 128), jnp.float32))


def test_voting_builder_with_separate_lowers_for_tpu(monkeypatch):
    """The separate formulation inside the voting shard_map builder (the
    chip's multi-chip path above 256 bins, where the policy leaves the
    enabled Pallas kernel) passes TPU lowering with check_vma on."""
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_HIST", "1")
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_FORCE_COMPILE", "1")
    monkeypatch.setenv("MMLSPARK_TPU_NATIVE_HIST", "0")

    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.parallel_modes import (
        make_build_tree_voting,
    )
    from mmlspark_tpu.models.gbdt.trainer import (
        TrainConfig,
        _loop_only_normalized,
    )
    from mmlspark_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(dp=8))
    cfg = _loop_only_normalized(TrainConfig(
        objective="binary", num_leaves=15, max_depth=4, max_bin=1023,
        top_k=8))
    fn = make_build_tree_voting(8, 1023, cfg, mesh)
    n, f = 1024, 8
    rng = np.random.default_rng(0)
    args = (jnp.asarray(
                rng.integers(0, 1023, size=(n, f)).astype(np.int32)),
            jnp.asarray(rng.normal(size=n).astype(np.float32)),
            jnp.asarray(rng.uniform(0.1, 1, size=n).astype(np.float32)),
            jnp.ones(n, jnp.float32),
            jnp.ones(f, jnp.float32),
            jnp.int32(15))
    txt = _lower_tpu(fn, *args)
    assert len(txt) > 1000 and "tpu_custom_call" not in txt


def test_retention_kernels_lower_to_mosaic_at_the_published_widths():
    """Both power-retention kernels at Brumby's head shapes (8 key-value
    heads of 128, 5 query heads each, a 128-token chunk): the dynamic
    lane roll, the transposed products at HIGHEST and the in-place state
    go through the Mosaic pipeline."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel import retention as R

    b, kv, heads, d, c = 2, 8, 40, 128, 128
    f32 = jnp.float32
    state = {k: jax.ShapeDtypeStruct(shape, f32)
             for k, shape in R.state_shapes(b, kv, d).items()}

    def step(q, k, v, log_g, state):
        return R.retention_step(q, k, v, log_g, state, scale=d ** -0.5,
                                pallas=True)

    txt = _lower_tpu(step, jax.ShapeDtypeStruct((b, heads, d), f32),
                     jax.ShapeDtypeStruct((b, kv, d), f32),
                     jax.ShapeDtypeStruct((b, kv, d), f32),
                     jax.ShapeDtypeStruct((b, kv), f32), state)
    assert "tpu_custom_call" in txt and "retention_decode" in txt

    def prefill(q, k, v, log_g, lengths, state):
        return R.retention_prefill(q, k, v, log_g, lengths, state,
                                   scale=d ** -0.5, chunk=c, pallas=True)

    txt = _lower_tpu(prefill, jax.ShapeDtypeStruct((b, 2 * c, heads, d), f32),
                     jax.ShapeDtypeStruct((b, 2 * c, kv, d), f32),
                     jax.ShapeDtypeStruct((b, 2 * c, kv, d), f32),
                     jax.ShapeDtypeStruct((b, 2 * c, kv), f32),
                     jax.ShapeDtypeStruct((b,), jnp.int32), state)
    assert "tpu_custom_call" in txt and "retention_prefill" in txt


def test_gdn_decode_kernel_lowers_to_mosaic():
    """The delta rule's one-token step at the published head sizes (64
    value heads of 128 x 128 over 32 key heads) goes through Mosaic."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel import delta_rule

    b, kh, heads, d = 2, 32, 64, 128
    f32 = jnp.float32
    args = (jnp.zeros((b, kh, d), f32), jnp.zeros((b, kh, d), f32),
            jnp.zeros((b, heads, d), f32), jnp.zeros((b, heads), f32),
            jnp.zeros((b, heads), f32), jnp.zeros((b, heads, d, d), f32))
    txt = _lower_tpu(functools.partial(delta_rule.delta_step, pallas=True),
                     *args)
    assert "tpu_custom_call" in txt and "gdn_decode" in txt


@pytest.mark.parametrize("rows,capacity", [(512, 1280), (128, 768)],
                         ids=["kimi_k2_6.reason", "gigachat.generate"])
def test_latent_decode_kernel_lowers_to_mosaic(rows, capacity):
    """The absorbed decode over the latent cache at the published widths
    (64 heads, latent 512, rotated key 64, bfloat16) and the two cells'
    batches and capacities: the scalar-prefetched positions in the block
    index, the transposed products and the 64-wide key block go through
    Mosaic."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel import latent

    bf = jnp.bfloat16

    def decode(q_n, q_r, c, r, w_uk, w_uv, pos):
        return latent.latent_decode(q_n, q_r, {"c": c, "r": r}, w_uk, w_uv,
                                    pos, scale=0.1, dtype=bf, pallas=True)

    txt = _lower_tpu(
        decode, jax.ShapeDtypeStruct((rows, 64, 128), jnp.float32),
        jax.ShapeDtypeStruct((rows, 64, 64), jnp.float32),
        jax.ShapeDtypeStruct((rows, capacity, 512), bf),
        jax.ShapeDtypeStruct((rows, capacity, 64), bf),
        jax.ShapeDtypeStruct((512, 64, 128), bf),
        jax.ShapeDtypeStruct((512, 64, 128), bf),
        jax.ShapeDtypeStruct((rows,), jnp.int32))
    assert "tpu_custom_call" in txt and "latent_decode" in txt
