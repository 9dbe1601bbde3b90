"""Out-of-core GBDT training: chunked boosting over a spill directory.

The in-core trainer holds the (N, F) binned matrix, raw-score carry and
per-round grad/hess resident for the whole fit. This module streams the
same boosting loop over fixed-size row chunks read from an
:class:`~mmlspark_tpu.ops.ingest.SpillReader` directory, so peak working
memory is bounded by the chunk size rather than N — the LightGBM
``two_round`` / external-memory analog for 100M+-row fits.

Exactness contract (pinned by tests/gbdt/test_ooc.py): the streamed fit
builds **bitwise-identical trees** to the in-core path on data both can
hold, given the same bin edges and MMLSPARK_TPU_HIST_QUANT != off. The
three pillars:

  - histograms are quantized (arXiv:2011.02022): per-round grad/hess
    become integers under a shared pow2 scale, and integer bin totals
    are accumulated across chunks in float64 — exact below 2**53, so a
    chunk-merged histogram is bitwise the full-pass one. The per-chunk
    accumulation mirrors ``native/bindings.level_histogram_quant``'s
    reference expression per feature, and the in-core native kernel is
    pinned bit-identical to that reference;
  - split finding / sibling derivation run the *same jitted expression
    graphs* as the compiled builder (``trainer._find_numeric_splits``,
    ``trainer._derive_sibling_hist``, ``trainer._leaf_objective_impl``)
    — a shared subgraph is the cheapest bitwise-parity guarantee;
  - row routing, leaf prediction and the raw-score carry update are
    exact integer/float ops replayed per chunk in numpy (gather + f32
    add round identically on host and XLA:CPU).

Per-iteration passes over the chunk stream (each wrapped in the
double-buffered :class:`~mmlspark_tpu.parallel.prefetch.BatchPrefetcher`
so disk reads overlap compute):

  1. grad/hess amax (quantization scales need the global max first);
  2. level 0: recompute grad/hess from the carry, quantize, persist the
     int16/int8 quanta, accumulate the root histogram;
  3. levels 1..D-1: replay the previous level's routing, persist the
     updated node ids, accumulate the (optionally subtraction-gated)
     level histogram;
  4. carry: route the final level, add the shrunken leaf values to the
     per-chunk raw-score carry.

Resumability composes at the estimator layer: crash-safe segment
checkpoints re-enter ``trainer.train`` per segment with a fresh
``init_raw``, and the out-of-core dispatch engages per segment — no
extra state to checkpoint here.

Unsupported configs (sampling, validation sets, multiclass, categorical
/ monotone splits, ...) raise here and are screened in
``trainer._ooc_supported`` before auto-dispatch.
"""

from __future__ import annotations

import os
import shutil
import tempfile
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from mmlspark_tpu.core import sanitizer
from mmlspark_tpu.core.faults import fault_point
from mmlspark_tpu.core.logging_utils import warn_once
from mmlspark_tpu.models.gbdt import objectives as obj_mod
from mmlspark_tpu.models.gbdt import trainer as trainer_mod
from mmlspark_tpu.models.gbdt.trainer import TrainConfig, TrainResult
from mmlspark_tpu.ops.ingest import (ChunkStore, SpillCorrupt, SpillReader,
                                     SpillWriter, binned_ingest_dtype)
from mmlspark_tpu.parallel import resilience
from mmlspark_tpu.parallel.prefetch import BatchPrefetcher

__all__ = ["train_from_binned", "train_ooc"]


# -- jit caches (keyed on static config; jax.jit caches by function
# identity, so closures must be reused across segments/iterations) ---------

_GH_CACHE: Dict[Any, Tuple[Callable, Callable, Callable]] = {}
_LEVEL_CACHE: Dict[Any, Callable] = {}


def _gh_fns(objective: str, okw: Dict[str, Any], quant: str):
    """(gh_amax, gh_quant, scales) jits for one objective config.

    ``gh_amax``/``gh_quant`` recompute grad/hess from the raw-score
    carry with the exact expressions the fused in-core step traces
    (multiplying by the all-ones valid mask is bitwise free, so it is
    omitted); ``scales`` is the shared pow2 quantization scale pair.
    """
    key = (objective, tuple(sorted(okw.items())), quant)
    fns = _GH_CACHE.get(key)
    if fns is not None:
        return fns
    import jax
    import jax.numpy as jnp

    objective_fn = obj_mod.get_objective(objective)
    qdt = jnp.int8 if quant == "q8" else jnp.int16
    qmax = 120.0 if quant == "q8" else 32000.0

    def _gh(raw, y, w):
        g, h = objective_fn(raw, y, w, **okw)
        return g.astype(jnp.float32), h.astype(jnp.float32)

    def gh_amax(raw, y, w):
        g, h = _gh(raw, y, w)
        return jnp.max(jnp.abs(g)), jnp.max(jnp.abs(h))

    def gh_quant(raw, y, w, gscale, hscale):
        g, h = _gh(raw, y, w)
        return (jnp.rint(g * gscale).astype(qdt),
                jnp.rint(h * hscale).astype(qdt))

    def scales(gmax, hmax):
        return (trainer_mod._pow2_scale(gmax, qmax)
                + trainer_mod._pow2_scale(hmax, qmax))

    fns = (jax.jit(gh_amax), jax.jit(gh_quant), jax.jit(scales))
    _GH_CACHE[key] = fns
    return fns


def _level_step(width: int, b: int, f: int, derive: bool, root: bool,
                lam1, lam2, min_child, min_hess, min_gain, path_smooth,
                max_delta_step):
    """Jitted per-level split step over a host-assembled histogram.

    Runs the module-level helpers the compiled builder's numeric fast
    path runs (derive -> root stats -> ``_find_numeric_splits``), so
    the streamed and in-core trees agree bitwise. Returns the numeric
    split tuple + the (possibly derived) histogram (next level's
    subtraction parent) + root (value, count) when ``root``.
    """
    key = (width, b, f, derive, root, lam1, lam2, min_child, min_hess,
           min_gain, path_smooth, max_delta_step)
    fn = _LEVEL_CACHE.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    def _body(hist, remaining, parent_value):
        if root:
            # quantized-plane root stats from the level-0 histogram
            # (mirrors the builder: any one feature's bins partition
            # the live rows), recorded before split finding so path
            # smoothing sees the root value
            tot0 = jnp.sum(hist[0, 0], axis=0)
            rv0, _ = trainer_mod._leaf_objective_impl(tot0[0], tot0[1],
                                                      lam1, lam2)
            if max_delta_step > 0:
                rv0 = jnp.clip(rv0, -max_delta_step, max_delta_step)
            parent_value = jnp.reshape(rv0, (1,))
            root_out = (rv0, tot0[2])
        else:
            root_out = (jnp.float32(0.0), jnp.float32(0.0))
        feat_mask = jnp.ones(f, jnp.float32)
        res = trainer_mod._find_numeric_splits(
            hist, feat_mask, remaining, parent_value, b=b, lam1=lam1,
            lam2=lam2, min_child=min_child, min_hess=min_hess,
            min_gain=min_gain, path_smooth=path_smooth,
            max_delta_step=max_delta_step)
        return res + (hist,) + root_out

    if derive:
        def step(hist_small, prev_hist, prev_split, prev_ss, remaining,
                 parent_value):
            hist = trainer_mod._derive_sibling_hist(
                hist_small, prev_hist, prev_split, prev_ss)
            return _body(hist, remaining, parent_value)
    else:
        def step(hist, remaining, parent_value):
            return _body(hist, remaining, parent_value)
    fn = jax.jit(step)
    _LEVEL_CACHE[key] = fn
    return fn


_CARRY_CACHE: Dict[int, Callable] = {}


def _carry_step(depth: int):
    """Jitted raw-score carry update for one chunk: shrink -> leaf
    gather -> add. A chunk is streamed from the spill and no builder
    kept a slot for its rows, so it walks the finished tree
    (``predict_tree``) to the slot the fused in-core step is handed by
    its builder as ``node``; from there the two trace the same
    expression (``nv * lr``, then one gather of it by slot, then
    ``raw + pred``), so XLA makes the same fusion/rounding decisions
    and the sums are bitwise equal — the walk only decides which slot,
    an integer. A host numpy mul-then-add is NOT bitwise equivalent on
    backends that fuse the multiply into the gather consumer."""
    fn = _CARRY_CACHE.get(depth)
    if fn is not None:
        return fn
    import jax

    predict_tree = trainer_mod._make_predict_tree(depth)

    def step(carry, binned, sf, bgl, nv, lr):
        nv = nv * lr
        pred = predict_tree(sf, bgl, nv, binned)
        return carry + pred

    fn = jax.jit(step)
    _CARRY_CACHE[depth] = fn
    return fn


# -- host-side chunk kernels ------------------------------------------------


def _accumulate_hist(acc: np.ndarray, binned: np.ndarray,
                     local: np.ndarray, gate: np.ndarray,
                     gq: np.ndarray, hq: np.ndarray, b: int) -> None:
    """Fold one chunk into the float64 quanta accumulator.

    Mirrors ``native/bindings.level_histogram_quant``'s reference
    expression per feature (the layout the in-core kernel is pinned
    against): integer-valued float64 bincounts are exact below 2**53,
    so the cross-chunk sum is bitwise the full-pass sum.
    """
    width_b = acc.shape[2]
    g64 = np.where(gate, gq, 0).astype(np.float64)
    h64 = np.where(gate, hq, 0).astype(np.float64)
    c64 = gate.astype(np.float64)
    base = local.astype(np.int64) * b
    for j in range(binned.shape[1]):
        idx = base + binned[:, j]
        acc[j, 0] += np.bincount(idx, weights=g64, minlength=width_b)
        acc[j, 1] += np.bincount(idx, weights=h64, minlength=width_b)
        acc[j, 2] += np.bincount(idx, weights=c64, minlength=width_b)


def _dequantize(acc: np.ndarray, width: int, b: int,
                gscale_inv: float, hscale_inv: float) -> np.ndarray:
    """(F, 3, width*B) float64 quanta -> (width, F, B, 3) f32 histogram,
    dequantized once with the kernel reference's exact expression."""
    f = acc.shape[0]
    hist = np.empty((width, f, b, 3), np.float32)
    scales = (np.float64(gscale_inv), np.float64(hscale_inv),
              np.float64(1.0))
    for j in range(f):
        for c, s in enumerate(scales):
            hist[:, j, :, c] = (acc[j, c].reshape(width, b)
                                * s).astype(np.float32)
    return hist


def _route_level(node: np.ndarray, binned: np.ndarray, d: int,
                 rt: Dict[str, np.ndarray]) -> np.ndarray:
    """Advance one chunk's node ids through level ``d``'s recorded
    splits (exact integer/bool replay of the builder's routing)."""
    level_start = 2 ** d - 1
    width = 2 ** d
    local = np.clip(node - level_start, 0, width - 1)
    live = node >= level_start          # rows settled earlier stay put
    nfeat = rt["best_feat"][local]
    nbin = binned[np.arange(binned.shape[0]), nfeat]
    nsplit = rt["do_split"][local]
    go_left = rt["left_mask"][local, nbin]
    child = np.where(go_left, 2 * node + 1, 2 * node + 2)
    return np.where(live & nsplit, child, node).astype(np.int32)


def _hist_gate(node: np.ndarray, d: int, subtract: bool,
               prev_ss: Optional[np.ndarray]
               ) -> Tuple[np.ndarray, np.ndarray]:
    """(local slot ids, contribution gate) for level ``d``'s histogram.

    With subtraction on, only each split's smaller child is
    histogrammed (the builder's masked-smaller-child pass); the sibling
    is derived on device in ``_derive_sibling_hist``.
    """
    level_start = 2 ** d - 1
    width = 2 ** d
    local = np.clip(node - level_start, 0, width - 1)
    gate = node >= level_start
    if subtract and d > 0:
        gate = gate & ((local % 2).astype(np.int32)
                       == prev_ss[local // 2])
    return local, gate


def _chunk_getter(obj, offsets: List[int], rows: List[int],
                  dtype=None) -> Optional[Callable[[int], np.ndarray]]:
    """Per-chunk accessor over an in-memory array or a per-chunk store
    (anything with ``.get(i)``, e.g. :class:`ChunkStore`); None stays
    None so callers can substitute defaults."""
    if obj is None:
        return None
    if hasattr(obj, "get"):
        if dtype is None:
            return lambda i: np.asarray(obj.get(i))
        return lambda i: np.asarray(obj.get(i), dtype=dtype)
    arr = np.asarray(obj) if dtype is None else np.asarray(obj, dtype=dtype)

    def get(i: int) -> np.ndarray:
        return arr[offsets[i]:offsets[i] + rows[i]]
    return get


# -- public entry points ----------------------------------------------------


def train_from_binned(binned: np.ndarray, labels: np.ndarray,
                      cfg: TrainConfig,
                      weights: Optional[np.ndarray] = None,
                      bin_upper: Optional[np.ndarray] = None,
                      init_model=None,
                      init_raw: Optional[np.ndarray] = None,
                      callbacks=None, measures=None,
                      iteration_offset: int = 0) -> TrainResult:
    """Stream an already-materialized binned matrix through the
    out-of-core loop: spill it to a temp directory in
    MMLSPARK_TPU_OOC_CHUNK_ROWS chunks and run :func:`train_ooc`.

    This is ``trainer.train``'s auto-dispatch target — the caller's
    matrix stays on host, but device residency and every intermediate
    (carry, grad/hess, histograms) are bounded by the chunk size. For
    fits whose rows never fit in host memory at all, write the spill
    directly with :class:`~mmlspark_tpu.ops.ingest.SpillWriter` and
    call :func:`train_ooc`.
    """
    from mmlspark_tpu.core.timer import InstrumentationMeasures

    measures = measures if measures is not None else InstrumentationMeasures()
    chunk_rows = trainer_mod.resolve_ooc_chunk_rows()
    n = binned.shape[0]
    tmp = tempfile.mkdtemp(prefix="mmlspark-ooc-")
    try:
        with measures.phase("dataPreparation"):
            writer = SpillWriter(os.path.join(tmp, "binned"),
                                 dtype=binned_ingest_dtype(cfg.max_bin))
            for s in range(0, n, chunk_rows):
                writer.append(np.asarray(binned[s:s + chunk_rows]))
            spill = writer.finalize()
        # the caller's matrix outlives the spill: a chunk that fails
        # its checksum mid-fit is re-derived from it bitwise
        return train_ooc(spill, labels, cfg, weights=weights,
                         bin_upper=bin_upper, init_model=init_model,
                         init_raw=init_raw, callbacks=callbacks,
                         measures=measures,
                         iteration_offset=iteration_offset,
                         work_dir=os.path.join(tmp, "state"),
                         source=lambda i: np.asarray(
                             binned[i * chunk_rows:(i + 1) * chunk_rows]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def train_ooc(spill: SpillReader, labels, cfg: TrainConfig, *,
              weights=None, bin_upper: Optional[np.ndarray] = None,
              init_model=None, init_raw=None, callbacks=None,
              measures=None, iteration_offset: int = 0,
              work_dir: Optional[str] = None,
              source: Optional[Callable[[int], np.ndarray]] = None
              ) -> TrainResult:
    """Chunked boosting over a sealed spill directory (see module doc).

    ``labels`` / ``weights`` / ``init_raw`` are either full (N,) arrays
    or per-chunk stores (``.get(i)`` with the spill's chunking — e.g. a
    :class:`ChunkStore` populated while writing the spill), so a truly
    larger-than-memory fit never materializes any full-N array.
    ``work_dir`` holds the per-chunk carry / quanta / node-id state
    (defaults to a temp directory removed on exit).

    ``source``, when given, maps a chunk index back to its binned rows
    (the iterator that fed the :class:`SpillWriter`): a spill chunk
    failing its crc32 is then re-derived and rewritten bitwise —
    binning is deterministic on fixed sketch edges — instead of
    raising; without it the attributed
    :class:`~mmlspark_tpu.ops.ingest.SpillCorrupt` propagates, naming
    the chunk.
    """
    import jax

    from mmlspark_tpu.core.timer import InstrumentationMeasures

    measures = measures if measures is not None else InstrumentationMeasures()

    n = spill.total_rows
    f = spill.n_features
    b = cfg.max_bin
    k = cfg.num_class if cfg.objective in ("multiclass", "softmax",
                                           "multiclassova") else 1
    reason = trainer_mod._ooc_supported(
        cfg, None, k=k, has_valid=False, has_custom=False,
        has_groups=False, total_bins=b)
    if reason is not None:
        raise ValueError(
            f"out-of-core training cannot stream this fit: {reason}")

    quant = trainer_mod.resolve_hist_quant(warn=False)
    if quant == "off":
        # the f32 histogram sum is not associative across row chunks;
        # the quantized plane's integer accumulation is. Promote rather
        # than silently producing chunk-count-dependent trees.
        quant = "q16"
        warn_once(
            "gbdt.ooc.quant",
            "out-of-core training quantizes histograms (q16): exact "
            "chunk merges need integer accumulation — set "
            "MMLSPARK_TPU_HIST_QUANT to pick the plane explicitly")
    subtract = trainer_mod.resolve_subtract("serial", b, None)
    chunk_rows = max(spill.chunk_rows) if spill.chunk_rows else 0

    depth = cfg.effective_depth
    num_slots = 2 ** (depth + 1) - 1
    nl = cfg.num_leaves if cfg.num_leaves > 0 else 2 ** depth
    lr = np.float32(cfg.learning_rate)
    okw = trainer_mod._objective_kwargs(cfg)
    gh_amax, gh_quant, scales_fn = _gh_fns(cfg.objective, okw, quant)
    qdt = np.int8 if quant == "q8" else np.int16

    offsets, rows = spill.offsets, spill.chunk_rows
    nc = spill.num_chunks
    get_labels = _chunk_getter(labels, offsets, rows, dtype=np.float32)
    if get_labels is None:
        raise ValueError("train_ooc needs labels (array or chunk store)")
    get_weights = _chunk_getter(weights, offsets, rows, dtype=np.float32)
    get_init_raw = _chunk_getter(init_raw, offsets, rows, dtype=np.float32)

    # base score: mirrors trainer.train's resolution exactly
    if init_model is not None:
        base_score = init_model.init_score
        if get_init_raw is None:
            raise ValueError("warm start needs init_raw (the init "
                             "model's raw scores on the training rows)")
    elif get_init_raw is not None:
        base_score = 0.0
    elif cfg.boost_from_average and cfg.objective != "lambdarank":
        if isinstance(labels, np.ndarray) or not hasattr(labels, "get"):
            base_score = obj_mod.init_score(cfg.objective, labels, weights)
        elif cfg.objective in ("regression_l1", "l1", "mae", "quantile"):
            raise ValueError(
                f"objective {cfg.objective!r} boosts from the label "
                "median, which needs full labels: pass labels as an "
                "array, or init_raw / boost_from_average=False")
        else:
            # streaming weighted mean; the objective transforms of
            # obj_mod.init_score depend on labels only through it
            tot = wtot = 0.0
            for i in range(nc):
                y = np.asarray(get_labels(i), dtype=np.float64)
                w = (np.ones_like(y) if get_weights is None
                     else np.asarray(get_weights(i), dtype=np.float64))
                tot += float(np.sum(y * w))
                wtot += float(np.sum(w))
            mean = tot / max(wtot, 1e-300)
            base_score = obj_mod.init_score(cfg.objective,
                                            np.asarray([mean]),
                                            np.asarray([1.0]))
        base_score = float(base_score)
    else:
        base_score = 0.0

    own_work = work_dir is None
    if own_work:
        work_dir = tempfile.mkdtemp(prefix="mmlspark-ooc-state-")
    carry_st = ChunkStore(work_dir, "carry")
    gq_st = ChunkStore(work_dir, "gq")
    hq_st = ChunkStore(work_dir, "hq")
    node_st = ChunkStore(work_dir, "node")

    with measures.phase("dataPreparation"):
        for i in range(nc):
            if get_init_raw is not None:
                carry_st.put(i, np.asarray(get_init_raw(i),
                                           np.float32).reshape(rows[i]))
            else:
                carry_st.put(i, np.full(rows[i], base_score, np.float32))

    def read_binned(i):
        """Spill read with detect-and-repair: a chunk failing its
        checksum is re-derived from ``source`` (bitwise — runs on the
        prefetcher's producer thread, so repair cost overlaps compute
        like any other read)."""
        try:
            return spill.read(i)
        except SpillCorrupt as e:
            if source is None:
                raise
            warn_once(
                "gbdt.ooc.spill_repair",
                "spill chunk %s failed verification (%s); re-deriving "
                "it from the source chunk iterator — repairs are "
                "bitwise, the fit continues", i, e)
            spill.repair(i, source(i))
            return spill.read(i)

    def sweep(*loaders):
        """Prefetched (i, *chunk arrays) stream over the spill order."""
        def gen():
            for i in range(nc):
                yield (i,) + tuple(ld(i) for ld in loaders)
        return BatchPrefetcher(gen(), label="ooc-chunks")

    def ones_chunk(i):
        return np.ones(rows[i], np.float32)

    get_w = get_weights if get_weights is not None else ones_chunk
    lam1, lam2 = cfg.lambda_l1, cfg.lambda_l2

    trees_sf: List[np.ndarray] = []
    trees_tb: List[np.ndarray] = []
    trees_nv: List[np.ndarray] = []
    trees_cnt: List[np.ndarray] = []

    def _boost_loop():
        trainer_mod._clear_callback_failure()
        with resilience.fit_watchdog("gbdt.train_ooc"):
            for t in range(cfg.num_iterations):
                it = t + iteration_offset
                resilience.step_start(it)
                trainer_mod._check_callback_failure()
                fault_point("gbdt.train_step")
                with measures.phase("training"):
                    _boost_one_tree(t)
                if callbacks:
                    record = {"iteration": t}
                    for cb in callbacks:
                        cb(t, record)
                resilience.step_end()
        # a swallowed host-callback failure on the final tree must
        # abort here, before the ensemble is returned or checkpointed
        trainer_mod._check_callback_failure()

    def _boost_one_tree(t):
        # -- pass 1: global grad/hess amax -> pow2 scales -------------
        gmax = hmax = np.float32(0.0)
        with sweep(carry_st.get, get_labels, get_w) as pf:
            for i, carry, y, w in pf:
                gm, hm = jax.device_get(gh_amax(carry, y, w))
                gmax = np.maximum(gmax, gm)
                hmax = np.maximum(hmax, hm)
        gscale, gscale_inv, hscale, hscale_inv = scales_fn(gmax, hmax)
        ginv = float(jax.device_get(gscale_inv))
        hinv = float(jax.device_get(hscale_inv))

        sf_t = np.full(num_slots, -1, np.int32)
        tb_t = np.zeros(num_slots, np.int32)
        nv_t = np.zeros(num_slots, np.float32)
        cnt_t = np.zeros(num_slots, np.float32)
        route: List[Dict[str, np.ndarray]] = []
        rem = int(nl) - 1
        prev_hist_dev = None

        def zeros_node(i):
            return np.zeros(rows[i], np.int32)

        for d in range(depth):
            level_start = 2 ** d - 1
            width = 2 ** d
            slots = level_start + np.arange(width)
            derive = subtract and d > 0
            acc = np.zeros((f, 3, width * b), np.float64)
            prev_ss = route[d - 1]["small_side"] if d else None

            # -- chunk pass: route level d-1, histogram level d -------
            if d == 0:
                with sweep(read_binned, carry_st.get, get_labels,
                           get_w) as pf:
                    for i, bn, carry, y, w in pf:
                        gq, hq = jax.device_get(gh_quant(
                            carry, y, w, gscale, hscale))
                        gq_st.put(i, gq)
                        hq_st.put(i, hq)
                        local = np.zeros(rows[i], np.int64)
                        gate = np.ones(rows[i], bool)
                        _accumulate_hist(acc, bn, local, gate, gq, hq, b)
            else:
                node_ld = node_st.get if d > 1 else zeros_node
                with sweep(read_binned, node_ld, gq_st.get,
                           hq_st.get) as pf:
                    for i, bn, node, gq, hq in pf:
                        node = _route_level(node, bn, d - 1, route[d - 1])
                        node_st.put(i, node)
                        local, gate = _hist_gate(node, d, subtract,
                                                 prev_ss)
                        _accumulate_hist(acc, bn, local, gate, gq, hq, b)

            hist = _dequantize(acc, width, b, ginv, hinv)
            sanitizer.check_finite("gbdt.ooc.level_hist", hist)

            # -- split step: shared jitted expression graphs ----------
            step = _level_step(
                width, b, f, derive, d == 0, lam1, lam2,
                float(cfg.min_data_in_leaf),
                cfg.min_sum_hessian_in_leaf, cfg.min_gain_to_split,
                cfg.path_smooth, cfg.max_delta_step)
            parent = nv_t[slots]
            if derive:
                outs = step(hist, prev_hist_dev,
                            route[d - 1]["do_split"], prev_ss,
                            np.int32(rem), parent)
            else:
                outs = step(hist, np.int32(rem), parent)
            hist_dev = outs[10]
            (do_split, best_feat, best_bin, left_mask, lval, rval,
             lstats, rstats, rem_out, small_side, rv0, cnt0) = \
                jax.device_get(outs[:10] + outs[11:])
            prev_hist_dev = hist_dev
            rem = int(rem_out)

            # -- record (the builder's slot layout) -------------------
            if d == 0:
                nv_t[0] = rv0
                cnt_t[0] = cnt0
            sf_t[slots] = np.where(do_split, best_feat, -1)
            tb_t[slots] = np.where(do_split, best_bin, 0)
            nv_t[2 * slots + 1] = np.where(do_split, lval, 0.0)
            nv_t[2 * slots + 2] = np.where(do_split, rval, 0.0)
            cnt_t[2 * slots + 1] = np.where(do_split, lstats[:, 2], 0.0)
            cnt_t[2 * slots + 2] = np.where(do_split, rstats[:, 2], 0.0)
            route.append({"do_split": do_split, "best_feat": best_feat,
                          "left_mask": left_mask,
                          "small_side": small_side})

        # -- carry pass: shrink -> leaf gather -> add, via the shared
        # jitted expression (host mul-then-add rounds differently when
        # XLA fuses the shrink into the gather consumer) --------------
        carry_fn = _carry_step(depth)
        bgl_t = np.zeros((num_slots, b), bool)
        for dd in range(depth):
            ls, w_ = 2 ** dd - 1, 2 ** dd
            bgl_t[ls:ls + w_] = (route[dd]["left_mask"]
                                 & route[dd]["do_split"][:, None])
        with sweep(read_binned, carry_st.get) as pf:
            for i, bn, carry in pf:
                carry_st.put(i, np.asarray(jax.device_get(
                    carry_fn(carry, bn, sf_t, bgl_t, nv_t, lr))))
        nv_shrunk = nv_t * lr
        sanitizer.check_finite("gbdt.ooc.carry", nv_shrunk)

        trees_sf.append(sf_t)
        trees_tb.append(tb_t)
        trees_nv.append(nv_shrunk)
        trees_cnt.append(cnt_t)

    sanitizer.check_finite("gbdt.ooc.entry", np.float32(base_score))
    try:
        _boost_loop()
    finally:
        if own_work:
            shutil.rmtree(work_dir, ignore_errors=True)

    with measures.phase("assembly"):
        booster = trainer_mod._assemble_booster(
            (trees_sf, trees_tb, trees_nv, trees_cnt, [], []),
            [1.0] * len(trees_sf), cfg, k, f, b, depth, num_slots,
            bin_upper, base_score, -1, init_model)
    stores = (carry_st, gq_st, hq_st, node_st)
    hist_stats: Dict[str, object] = {
        "grow_policy": "depthwise", "hist_quant": quant,
        # the chunked loop only ever runs the native integer kernel
        # (trainer._ooc_supported screens everything else out)
        "hist_formulation": "native", "tree_mode": "serial",
        "pallas_interpret": None, "hist_feed": None, "hist_product": None,
        # levels are routed on the host, in NumPy (_route_level)
        "route": None,
        # chunks are streamed and keep no slot from the level loop, so
        # the carry walks each finished tree (_carry_step)
        "raw_update": "tree_walk",
        "hist_shard": "off", "grad_shard": "off",
        "efb_bundles": 0, "efb_bundled_features": 0,
        "ooc": True, "ooc_reason": None, "chunk_rows": chunk_rows,
        "n_chunks": nc, "hist_subtract": subtract,
        "spill_verify": spill.verify_mode,
        "spill_verify_s": round(
            spill.verify_s + sum(st.verify_s for st in stores), 6),
        "spill_verify_chunks": int(
            spill.verify_chunks + sum(st.verify_chunks
                                      for st in stores)),
        "spill_repairs": int(spill.repairs)}
    return TrainResult(booster=booster, evals=[], best_iteration=-1,
                       hist_stats=hist_stats)
