"""Histogram-GBDT training engine — the flagship compute path.

This is the TPU-native replacement for everything the reference drives
through LightGBM C++: histogram building, split finding, tree growth and
the distributed histogram reduction
(SURVEY.md §2.7 row 1; lightgbm/.../TrainUtils.scala:98-135 iteration
loop, StreamingPartitionTask.scala data push, NetworkManager ring
allreduce). Design:

  - rows live sharded over the mesh ``dp`` axis; bin boundaries and tree
    state are replicated (the "reference dataset" broadcast analog);
  - per-level histograms are built with one `segment_sum` scatter over
    all rows — when inputs are row-sharded, XLA GSPMD turns the segment
    reduction into per-device partials + an ICI all-reduce, which *is*
    LightGBM's ``data_parallel`` histogram allreduce with no rendezvous;
  - trees grow level-wise over a fixed ``max_depth`` (static shapes for
    XLA), with a traced ``num_leaves`` budget that gates splits by
    within-level gain rank — the budgeted analog of LightGBM's leaf-wise
    growth;
  - the per-iteration loop stays in Python, matching the reference's
    driver-side loop shape while keeping all math on device: gbdt, goss
    and rf dispatch one fused jitted step a tree (``_make_step_fn``:
    gradients, tree build, raw-score updates, metrics); DART, custom
    objectives and leaf-wise growth run the pieces eagerly
    (``_train_loop``);
  - every tree builder hands back, beside the tree, the slot each of its
    rows settled in (``node``), so the fused step updates the training
    rows' raw scores with one gather, ``node_value[node]``. Rows no
    builder routed (validation sets, out-of-core chunks, the eager
    loop) walk the finished tree (``_make_predict_tree``). A fit's
    ``hist_stats["raw_update"]`` says which: ``builder_leaf`` or
    ``tree_walk``;
  - a level sends its rows to their children through one function,
    ``route_level``, in one of two forms that give every row the same
    node: compare-and-select over the level's nodes where a per-row
    index is a serial gather (the TPU, up to 512 nodes), the gather on
    the CPU. A fit's
    ``hist_stats["route"]`` counts a tree's levels by form
    (``{"select": 6, "gather": 0}`` at depth 6 on the chip).

GOSS / bagging / feature-fraction / DART semantics follow
params/LightGBMParams.scala; voting/feature/data-parallel builders live
in ``mmlspark_tpu.models.gbdt.parallel_modes``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from mmlspark_tpu.core.env import (env_flag, env_int, env_override,
                                   env_raw, env_str)
from mmlspark_tpu.core import sanitizer, scopes
from mmlspark_tpu.core.faults import fault_point
from mmlspark_tpu.parallel import resilience
from mmlspark_tpu.models.gbdt import metrics as metrics_mod
from mmlspark_tpu.models.gbdt import objectives as obj_mod
from mmlspark_tpu.models.gbdt.booster import BoosterArrays


@dataclass(frozen=True)
class TrainConfig:
    """Static training configuration (hashable: becomes jit static arg).

    Field names mirror the reference's param surface
    (lightgbm/.../params/LightGBMParams.scala:1) in snake_case.
    """

    objective: str = "regression"
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_depth: int = 5            # full-tree layout depth (2^d leaves max)
    max_bin: int = 255
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    feature_fraction: float = 1.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    boosting_type: str = "gbdt"   # gbdt | rf | dart | goss
    top_rate: float = 0.2         # goss
    other_rate: float = 0.1       # goss
    drop_rate: float = 0.1        # dart
    skip_drop: float = 0.5        # dart
    num_class: int = 1
    sigmoid: float = 1.0
    alpha: float = 0.9            # huber / quantile
    tweedie_variance_power: float = 1.5
    poisson_max_delta_step: float = 0.7
    fair_c: float = 1.0
    early_stopping_round: int = 0
    metric: Optional[str] = None
    eval_at: Any = 5              # NDCG@k position(s): int or list of ints
    # distributed tree learner (LightGBMParams.scala:25-29):
    # serial | data | voting | feature — "data" is the default sharded
    # path (XLA-derived histogram all-reduce); voting/feature use the
    # explicit shard_map builders in parallel_modes.py
    tree_learner: str = "serial"
    top_k: int = 20               # voting_parallel local vote size
    seed: int = 0
    deterministic: bool = True
    boost_from_average: bool = True
    # categorical split handling (params/LightGBMParams.scala categorical
    # group; core/schema/Categoricals.scala): features listed here split
    # by set membership over category bins, not ordered thresholds
    categorical_features: Any = ()
    cat_smooth: float = 10.0      # added to hessian in the sort ratio
    cat_l2: float = 10.0          # extra L2 when evaluating cat splits
    max_cat_threshold: int = 32   # max categories on the scanned side
    max_cat_to_onehot: int = 4    # <=: one-vs-rest instead of sorted scan
    # monotone constraints (LightGBM monotone_constraints, "basic"
    # method): per-feature -1/0/+1; +1 forces predictions non-decreasing
    # in the feature. Direction-violating splits are rejected and child
    # subtrees are clamped to the split midpoint bound.
    monotone_constraints: Any = ()
    # LightGBM path_smooth: child outputs shrink toward the parent's by
    # n/(n+path_smooth); applied at value recording (split selection
    # still uses unsmoothed scores)
    path_smooth: float = 0.0
    # LightGBM max_delta_step: clamp |leaf output| (0 = off)
    max_delta_step: float = 0.0
    # LightGBM pos/neg_bagging_fraction: per-class bagging rates for
    # binary labels (both default 1.0 = plain bagging_fraction)
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    # LightGBM extra_trees: evaluate ONE random threshold per
    # (node, feature) instead of scanning every bin
    extra_trees: bool = False
    # DART extras (BaseTrainParams.scala DartModeParams): cap on trees
    # dropped per iteration (<=0 = unlimited), uniform vs
    # weight-proportional drop selection, and a dedicated drop RNG
    # stream (None = derived from seed)
    max_drop: int = 50
    uniform_drop: bool = False
    drop_seed: Optional[int] = None
    # seed family (LightGBM derives per-purpose streams; defaults match
    # its conventions: bagging 3, feature_fraction 2, extra 6)
    bagging_seed: int = 3
    feature_fraction_seed: int = 2
    extra_seed: int = 6
    # lambdarank (RankerTrainParams maxPosition / labelGain)
    lambdarank_truncation_level: int = 30
    label_gain: Any = ()
    # LightGBM zero_as_missing: zeros are binned as missing (the
    # estimator maps 0.0 -> NaN pre-binning) and trained nodes stamp
    # zero-missing decision bits so raw scoring routes zeros the same
    zero_as_missing: bool = False
    # LightGBM feature_fraction_bynode: re-sample the feature subset at
    # every tree node instead of once per tree
    feature_fraction_by_node: float = 1.0
    # early-stopping improvement tolerance (TrainUtils.scala:143-169:
    # an eval counts as improved iff cur-best > tol for higher-better
    # metrics, cur-best < tol for lower-better)
    improvement_tolerance: float = 0.0
    # LightGBM min_data_per_group: categories below this count are
    # excluded from the sorted categorical scan (one-hot mode keeps
    # its per-bin min_data_in_leaf guard)
    min_data_per_group: int = 100
    # LightGBM min_data_in_bin: consumed by BinMapper at fit time (the
    # trainer itself sees only binned codes); lives here so
    # passThroughArgs can reach it
    min_data_in_bin: int = 3

    def __post_init__(self):
        # eval_at may arrive as a list; the config is used as a cache key
        # for compiled functions, so every field must be hashable.
        # Sequence fields also accept a bare scalar ('label_gain=1' via
        # passThroughArgs, or direct construction — ADVICE r4): wrap it
        # in a 1-tuple here so tuple(cfg.label_gain) consumers never see
        # an opaque TypeError. eval_at stays scalar-or-tuple (a scalar
        # is a documented value for it).
        if isinstance(self.eval_at, list):
            object.__setattr__(self, "eval_at", tuple(self.eval_at))
        if isinstance(self.label_gain, (int, float)):
            object.__setattr__(self, "label_gain",
                               (float(self.label_gain),))
        elif isinstance(self.label_gain, (list, np.ndarray)):
            object.__setattr__(self, "label_gain",
                               tuple(float(g) for g in self.label_gain))
        if isinstance(self.categorical_features, (int, np.integer)):
            object.__setattr__(self, "categorical_features",
                               (int(self.categorical_features),))
        elif isinstance(self.categorical_features, (list, np.ndarray)):
            object.__setattr__(self, "categorical_features",
                               tuple(int(i) for i in self.categorical_features))
        if isinstance(self.monotone_constraints, (int, np.integer)):
            object.__setattr__(self, "monotone_constraints",
                               (int(self.monotone_constraints),))
        elif isinstance(self.monotone_constraints, (list, np.ndarray)):
            object.__setattr__(self, "monotone_constraints",
                               tuple(int(i) for i in self.monotone_constraints))

    @property
    def effective_depth(self) -> int:
        # enough depth for num_leaves leaves, capped by max_depth if set
        need = max(1, math.ceil(math.log2(max(self.num_leaves, 2))))
        if self.max_depth and self.max_depth > 0:
            return min(need, self.max_depth) if self.num_leaves > 0 else self.max_depth
        return need


def _objective_kwargs(cfg: TrainConfig) -> Dict[str, Any]:
    name = cfg.objective
    if name == "binary":
        return {"sigmoid": cfg.sigmoid}
    if name in ("multiclass", "softmax", "multiclassova"):
        return {"num_class": cfg.num_class}
    if name == "huber":
        return {"alpha": cfg.alpha}
    if name == "quantile":
        return {"alpha": cfg.alpha}
    if name == "fair":
        return {"fair_c": cfg.fair_c}
    if name == "tweedie":
        return {"tweedie_variance_power": cfg.tweedie_variance_power}
    if name == "poisson":
        return {"max_delta_step": cfg.poisson_max_delta_step}
    if name == "lambdarank":
        kw: Dict[str, Any] = {
            "sigmoid": cfg.sigmoid,
            "truncation_level": cfg.lambdarank_truncation_level}
        if cfg.label_gain:
            kw["label_gain"] = tuple(cfg.label_gain)
        return kw
    return {}


# ---------------------------------------------------------------------------
# Tree building (device side)
# ---------------------------------------------------------------------------

def native_histogram_available() -> bool:
    """Is the C++ level-histogram kernel loadable (builds lazily)?"""
    from mmlspark_tpu.native import bindings
    return bindings.is_available()


def _native_hist_default_enabled() -> bool:
    """Native kernel as the DEFAULT formulation: CPU backend only (on
    TPU the data never visits the host; under GSPMD the callback is not
    partitionable — callers gate that via ``allow_native``) and only
    when the compiled library actually loaded (the numpy fallback is
    for correctness tests, not a default). MMLSPARK_TPU_NATIVE_HIST=0
    is the kill switch back to the XLA formulations."""
    if not env_flag("MMLSPARK_TPU_NATIVE_HIST", default=True):
        return False
    import jax
    return jax.default_backend() == "cpu" and native_histogram_available()


def resolve_histogram_formulation(b: int, in_shard_map: bool = False,
                                  allow_pallas: bool = True,
                                  allow_native: bool = True) -> str:
    """The one histogram-kernel policy, shared by the trainer dispatch
    and the shard_map builders. The first rule that holds decides:

      1. ``pallas`` where the kernel is enabled (by default on the TPU
         backend), the caller allows it (single-program or per-shard:
         GSPMD cannot partition a custom call) and the bins fit its
         256-lane tile;
      2. ``native``, the cache-blocked C++ kernel behind a host
         callback, on the CPU backend when the library loaded and the
         caller allows it (GSPMD cannot partition a callback either;
         on the TPU the rows never visit the host);
      3. ``per_feature`` outside shard_map: a fori_loop of per-feature
         segment_sums never materializes the (N*F, 3) broadcast;
      4. ``separate`` inside shard_map, where that loop's carry would
         need manual varying-axes casts: three scalar segment_sums over
         one shared index vector, no carry.
    """
    from mmlspark_tpu.models.gbdt.hist_pallas import (
        pallas_histogram_enabled,
    )

    if pallas_histogram_enabled() and allow_pallas and b <= 256:
        return "pallas"
    if allow_native and _native_hist_default_enabled():
        return "native"
    return "separate" if in_shard_map else "per_feature"


def _single_program(mesh) -> bool:
    """No mesh, or a mesh of ONE device: there is nothing for GSPMD to
    partition, so the Pallas kernel and the native callback (neither of
    which it can partition) stay selectable. ``.set_mesh(create_mesh())``
    on a single chip must not silently leave the TPU default kernel."""
    return mesh is None or mesh.devices.size == 1


def resolve_fit_formulation(total_bins: int, mode: str, mesh) -> str:
    """The histogram formulation a fit in tree ``mode`` over ``mesh``
    runs: the serial builder resolves single-program (GSPMD partitions
    it when a multi-device mesh shards the rows, which rules out Pallas
    and native), the explicit shard_map builders resolve per-shard.
    Shared by the builder cache, the subtraction policy and the fit's
    ``hist_stats`` so the recorded name is the kernel that ran."""
    if mode != "serial":
        return resolve_histogram_formulation(total_bins, in_shard_map=True)
    single = _single_program(mesh)
    return resolve_histogram_formulation(
        total_bins, in_shard_map=False, allow_pallas=single,
        allow_native=single)


_WARNED_BAD_QUANT = False
_WARNED_QUANT_SHARD = False

_VALID_QUANT = ("off", "q16", "q8")


def resolve_hist_quant(in_shard_map: bool = False,
                       warn: bool = True) -> str:
    """Gradient/hessian histogram-quantization policy
    (MMLSPARK_TPU_HIST_QUANT, default off): per-round grad/hess
    quantized to int16 (q16) or int8 (q8) with a shared power-of-two
    scale, accumulated in int32 with periodic rescale into wide
    accumulators, dequantized only at split-gain evaluation
    (arXiv:2011.02022's quantized training scheme). Follows core.env's
    bad-value contract: a mistyped value warns once and runs
    unquantized rather than mislabeling a measurement.
    Single-program only — the shard_map builders keep f32
    histograms (the native quant kernel is a host callback and the
    chunked-scan XLA mirror's carry is not shard_map-safe), downgrading
    with a warning so A/B labels stay honest."""
    global _WARNED_BAD_QUANT, _WARNED_QUANT_SHARD
    raw = (env_str("MMLSPARK_TPU_HIST_QUANT", "") or "").strip().lower()
    if not raw:
        return "off"
    if raw not in _VALID_QUANT:
        if warn and not _WARNED_BAD_QUANT:
            _WARNED_BAD_QUANT = True
            import warnings
            warnings.warn(
                f"MMLSPARK_TPU_HIST_QUANT={raw!r} is not one of "
                "off|q16|q8; histograms run unquantized", stacklevel=2)
        return "off"
    if raw != "off" and in_shard_map:
        if warn and not _WARNED_QUANT_SHARD:
            _WARNED_QUANT_SHARD = True
            import warnings
            warnings.warn(
                "MMLSPARK_TPU_HIST_QUANT is single-program only; "
                "sharded (data/voting/feature-parallel) fits build f32 "
                "histograms — label A/B measurements accordingly",
                stacklevel=2)
        return "off"
    return raw


_WARNED_BAD_GROW = False
_WARNED_LEAFWISE_DOWNGRADE = False

_VALID_GROW = ("depthwise", "leafwise")


def resolve_grow_policy(warn: bool = True) -> str:
    """Tree growth policy (MMLSPARK_TPU_GROW_POLICY, default
    depthwise): ``leafwise`` grows each tree by a max-gain priority
    queue capped by ``num_leaves`` (LightGBM's native policy;
    arXiv:1706.08359 §2) over the same level-histogram kernels with
    sibling subtraction; ``depthwise`` is the compiled full-level
    builder with the within-level leaf budget. Bad values warn once
    and run depthwise (core.env contract)."""
    global _WARNED_BAD_GROW
    raw = (env_str("MMLSPARK_TPU_GROW_POLICY", "") or "").strip().lower()
    if not raw:
        return "depthwise"
    if raw not in _VALID_GROW:
        if warn and not _WARNED_BAD_GROW:
            _WARNED_BAD_GROW = True
            import warnings
            warnings.warn(
                f"MMLSPARK_TPU_GROW_POLICY={raw!r} is not one of "
                "depthwise|leafwise; growing depthwise", stacklevel=2)
        return "depthwise"
    return raw


def _leafwise_supported(cfg: "TrainConfig", mesh) -> Optional[str]:
    """None when leaf-wise growth can honor this config, else the
    human-readable reason for the depthwise fallback."""
    if mesh is not None:
        return "a device mesh is attached (leafwise is single-program)"
    if cfg.tree_learner in ("voting", "feature"):
        return f"tree_learner={cfg.tree_learner!r}"
    if cfg.categorical_features:
        return "categorical_features"
    if any(cfg.monotone_constraints or ()):
        return "monotone_constraints"
    if cfg.extra_trees:
        return "extra_trees"
    if cfg.feature_fraction_by_node < 1.0:
        return "feature_fraction_by_node"
    return None


_WARNED_BAD_OOC = False
_WARNED_OOC_DOWNGRADE = False

_VALID_OOC = ("auto", "off", "on")


def resolve_ooc(warn: bool = True) -> str:
    """Out-of-core training policy (MMLSPARK_TPU_OOC, default auto):
    ``auto`` streams a supported fit through the chunked spill plane
    once the row count reaches MMLSPARK_TPU_OOC_ROWS; ``on`` forces it
    (downgrading with one warning when the fit shape is unsupported);
    ``off`` disables. Bad values warn once and run auto (core.env
    contract)."""
    global _WARNED_BAD_OOC
    raw = (env_str("MMLSPARK_TPU_OOC", "") or "").strip().lower()
    if not raw:
        return "auto"
    if raw not in _VALID_OOC:
        if warn and not _WARNED_BAD_OOC:
            _WARNED_BAD_OOC = True
            import warnings
            warnings.warn(
                f"MMLSPARK_TPU_OOC={raw!r} is not one of auto|off|on; "
                "using auto", stacklevel=2)
        return "auto"
    return raw


def resolve_ooc_chunk_rows() -> int:
    return env_int("MMLSPARK_TPU_OOC_CHUNK_ROWS", 262_144, minimum=1024)


def _ooc_supported(cfg: "TrainConfig", mesh, k: int, has_valid: bool,
                   has_custom: bool, has_groups: bool,
                   total_bins: int) -> Optional[str]:
    """None when the chunked out-of-core loop can reproduce this fit
    exactly, else the human-readable reason for staying in-core.

    The supported surface is the serial depthwise numeric plane whose
    histograms merge exactly across row chunks: the native kernel's
    integer-quantized accumulation is row-partition invariant, so a
    chunk-merged histogram is bitwise the in-core one. Anything that
    samples rows/features per iteration, needs resident full-N state
    (validation scoring, lambdarank groups), or runs a different
    builder stays in-core."""
    if mesh is not None:
        return "a device mesh is attached (out-of-core is single-program)"
    if resolve_grow_policy(warn=False) == "leafwise":
        return "leafwise growth"
    if cfg.tree_learner in ("voting", "feature"):
        return f"tree_learner={cfg.tree_learner!r}"
    if cfg.boosting_type != "gbdt":
        return f"boosting_type={cfg.boosting_type!r}"
    if has_custom:
        return "a custom objective"
    if k > 1:
        return "multiclass objectives"
    if cfg.objective == "lambdarank" or has_groups:
        return "lambdarank / grouped fits"
    if has_valid or cfg.early_stopping_round > 0:
        return "validation sets / early stopping"
    if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0:
        return "bagging"
    if cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0:
        return "pos/neg bagging"
    if cfg.feature_fraction < 1.0 or cfg.feature_fraction_by_node < 1.0:
        return "feature sampling"
    if cfg.extra_trees:
        return "extra_trees"
    if cfg.categorical_features:
        return "categorical_features"
    if any(cfg.monotone_constraints or ()):
        return "monotone_constraints"
    if resolve_histogram_formulation(total_bins) != "native":
        return ("the native histogram kernel is unavailable (chunk-exact "
                "merges need its integer accumulation)")
    return None


_WARNED_BAD_SHARD = False
_WARNED_SHARD_DOWNGRADE_DP = False

_VALID_SHARD = ("auto", "off", "on")


def resolve_hist_shard(warn: bool = True) -> str:
    """Raw MMLSPARK_TPU_HIST_SHARD policy value (auto|off|on, default
    auto). ``auto`` turns the sharded reduction on exactly when the fit
    is data-parallel over dp>1 and :func:`_hist_shard_supported` allows
    the config; ``on`` forces it, downgrading with one warning when the
    config cannot honor it; ``off`` keeps the legacy full-psum GSPMD
    path. Bad values warn once and run auto (core.env contract)."""
    global _WARNED_BAD_SHARD
    raw = (env_str("MMLSPARK_TPU_HIST_SHARD", "") or "").strip().lower()
    if not raw:
        return "auto"
    if raw not in _VALID_SHARD:
        if warn and not _WARNED_BAD_SHARD:
            _WARNED_BAD_SHARD = True
            import warnings
            warnings.warn(
                f"MMLSPARK_TPU_HIST_SHARD={raw!r} is not one of "
                "auto|off|on; using auto", stacklevel=2)
        return "auto"
    return raw


def _hist_shard_supported(cfg: "TrainConfig", mesh) -> Optional[str]:
    """None when the reduce-scatter data-parallel builder can honor
    this config bitwise-identically to the full-psum path, else the
    human-readable reason for staying on the GSPMD path."""
    if mesh is None:
        return "no device mesh is attached"
    if cfg.tree_learner in ("voting", "feature"):
        return f"tree_learner={cfg.tree_learner!r}"
    from mmlspark_tpu.parallel.mesh import axis_size
    if axis_size(mesh, "dp") < 2:
        return "dp axis size is 1"
    if cfg.categorical_features:
        return "categorical_features"
    if any(cfg.monotone_constraints or ()):
        return "monotone_constraints"
    if cfg.extra_trees:
        return "extra_trees"
    if cfg.feature_fraction_by_node < 1.0:
        return "feature_fraction_by_node"
    return None


def resolve_hist_shard_mode(cfg: "TrainConfig", mesh,
                            warn: bool = True
                            ) -> Tuple[str, Optional[str]]:
    """(resolved mode, downgrade reason): ``("on", None)`` routes the
    fit through the explicit reduce-scatter shard_map builder,
    ``("off", reason-or-None)`` keeps the full-psum path. A forced
    ``on`` that the config cannot honor warns once (honest A/B
    labeling, as the leafwise/quant downgrades); ``auto`` downgrades
    silently — off is simply its resolution for unsupported fits."""
    global _WARNED_SHARD_DOWNGRADE_DP
    raw = resolve_hist_shard(warn=warn)
    if raw == "off":
        return "off", None
    reason = _hist_shard_supported(cfg, mesh)
    if reason is None:
        return "on", None
    if raw == "on":
        if warn and not _WARNED_SHARD_DOWNGRADE_DP:
            _WARNED_SHARD_DOWNGRADE_DP = True
            import warnings
            warnings.warn(
                "MMLSPARK_TPU_HIST_SHARD=on cannot shard the histogram "
                f"reduction for this fit ({reason}); running the "
                "full-psum path — label A/B measurements accordingly",
                stacklevel=2)
    return "off", reason


# An exception raised inside a native-histogram host callback surfaces
# from XLA late and unattributed (or, on some runtimes, not at all), so
# a failing kernel would otherwise show up as an anonymous crash. The
# latch records the first failure and the boosting loops re-raise it —
# attributed, with the original exception chained — at the next
# per-iteration host sync (and once more after the loop, so a failure
# on the final iteration cannot be checkpointed into a poisoned
# segment).
_CALLBACK_FAILURE: List[BaseException] = []


class CallbackFailed(RuntimeError):
    """A native-histogram host callback raised mid-execution; the fit
    aborts at the next host sync with the original error chained."""


def _latch_callback_failure(e: BaseException) -> None:
    if not _CALLBACK_FAILURE:
        _CALLBACK_FAILURE.append(e)


def _clear_callback_failure() -> None:
    _CALLBACK_FAILURE.clear()


def _check_callback_failure() -> None:
    if _CALLBACK_FAILURE:
        e = _CALLBACK_FAILURE[0]
        _CALLBACK_FAILURE.clear()
        raise CallbackFailed(
            "[native.callback] native histogram host callback failed "
            f"mid-fit ({type(e).__name__}: {e}); aborting before a "
            "tree built from it can be committed") from e


def _native_level_histogram(binned, grad, hess, live, local, width, f, b):
    """The C++ cache-blocked level-histogram kernel
    (native/data_plane.cpp mmls_level_hist_*) as a host callback: the
    CPU-backend twin of the Pallas kernel's VMEM restructuring. Inside
    jit on the CPU backend the buffers are already host-resident, so
    the callback costs one (width, F, B, 3) result copy. Falls back to
    a numpy bincount implementation when the library isn't built
    (bindings.level_histogram), so the formulation stays selectable in
    compiler-less environments."""
    import jax
    import jax.numpy as jnp

    def _cb(bn, g, h, lv, lo, _w=width, _b=b):
        try:
            fault_point("native.callback")
            from mmlspark_tpu.native import bindings
            with resilience.boundary("host_callback",
                                     "native.level_histogram"):
                return bindings.level_histogram(
                    np.asarray(bn), np.asarray(g), np.asarray(h),
                    np.asarray(lv), np.asarray(lo), _w, _b)
        except BaseException as e:
            # latch AND re-raise: however the runtime surfaces the
            # callback error, the fit ends attributed
            _latch_callback_failure(e)
            raise

    # under shard_map the per-shard result varies over whatever mesh
    # axes the inputs vary over; declare the union (mirrors
    # hist_pallas's out_shape)
    from mmlspark_tpu.core.jax_compat import (operand_vma,
                                              shape_dtype_struct)
    out_type = shape_dtype_struct(
        (width, f, b, 3), jnp.float32,
        vma=operand_vma(binned, grad, hess, live, local))
    return jax.pure_callback(_cb, out_type, binned, grad, hess, live,
                             local.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Host-binned registry: the binned matrix is host-resident numpy for the
# whole fit, so the native-histogram callback can read it directly
# instead of receiving it as a traced operand. At bench shape the
# operand marshal (2M x 28 uint8 per level) dominated callback cost;
# the registered-matrix path passes a scalar int32 token instead. The
# token is a TRACED operand (not a jit constant): successive fits reuse
# one compiled step with different tokens, so the compile caches (and
# the sanitizer's recompile budget) see one program, not one per fit.
# ---------------------------------------------------------------------------

_HOST_BINNED_REG: Dict[int, np.ndarray] = {}
_HOST_BINNED_NEXT = [1]


def _register_host_binned(arr: np.ndarray) -> int:
    """Register a host binned matrix for callback-side lookup; returns
    the token to pass as the builder's ``hist_token``. The caller owns
    the lifetime: release after every dispatched step has completed
    (``train`` releases after the final ``block_until_ready``)."""
    tok = _HOST_BINNED_NEXT[0]
    _HOST_BINNED_NEXT[0] += 1
    _HOST_BINNED_REG[tok] = arr
    return tok


def _release_host_binned(tok: int) -> None:
    _HOST_BINNED_REG.pop(tok, None)


def _host_binned_lookup(tok: int) -> np.ndarray:
    try:
        return _HOST_BINNED_REG[tok]
    except KeyError:
        raise RuntimeError(
            f"host-binned token {tok} is not registered — a histogram "
            "callback ran after its train() call released the training "
            "matrix (or a compiled step was invoked outside train)"
        ) from None


def _native_level_histogram_v2(binned, grad, hess, live, local, width,
                               f, b, gscale_inv=None, hscale_inv=None,
                               token=None, quant="off"):
    """Native level histogram with the two extras the flagship CPU
    path needs: ``token`` looks the binned matrix up host-side from
    ``_HOST_BINNED_REG`` instead of marshalling it through the callback
    per level (``binned`` is ignored when set), and ``quant``
    ("off" | "q16" | "q8") dispatches to the int32-accumulating kernels
    (mmls_level_hist_q16/_q8), taking int grad/hess, a uint8 live gate
    and the two f32 dequant scales. Output contract matches
    ``_native_level_histogram``: (width, f, b, 3) f32."""
    import jax
    import jax.numpy as jnp

    ops = [token if token is not None else binned,
           grad, hess, live, local.astype(jnp.int32)]
    if quant != "off":
        ops += [gscale_inv, hscale_inv]

    def _cb(*args, _w=width, _b=b, _q=quant, _tok=token is not None):
        try:
            fault_point("native.callback")
            from mmlspark_tpu.native import bindings
            with resilience.boundary("host_callback",
                                     "native.level_histogram"):
                host = [np.asarray(a) for a in args]
                bn = (_host_binned_lookup(int(host[0])) if _tok
                      else host[0])
                if _q == "off":
                    return bindings.level_histogram(bn, *host[1:5],
                                                    _w, _b)
                return bindings.level_histogram_quant(
                    bn, *host[1:5], _w, _b, float(host[5]),
                    float(host[6]))
        except BaseException as e:
            _latch_callback_failure(e)
            raise

    from mmlspark_tpu.core.jax_compat import (operand_vma,
                                              shape_dtype_struct)
    out_type = shape_dtype_struct((width, f, b, 3), jnp.float32,
                                  vma=operand_vma(*ops))
    return jax.pure_callback(_cb, out_type, *ops)


def _pow2_scale(amax, qmax):
    """Power-of-two quantization scale pair (scale, scale_inv) mapping
    |x| <= amax into [-qmax, qmax]. Restricting to powers of two makes
    ``int_value * scale_inv`` an exponent shift — exact in f32 — so
    every backend dequantizing the same int32 totals produces identical
    floats, and the native kernel's int64-exact merge stays bit-stable
    across worker counts."""
    import jax.numpy as jnp
    amax = jnp.maximum(amax.astype(jnp.float32), jnp.float32(1e-30))
    e = jnp.clip(jnp.floor(jnp.log2(jnp.float32(qmax) / amax)),
                 -126.0, 126.0)
    return jnp.exp2(e).astype(jnp.float32), \
        jnp.exp2(-e).astype(jnp.float32)


def _level_histogram_quant(binned, grad_q, hess_q, live, local, width,
                           f, b, gscale_inv, hscale_inv,
                           formulation: str, token=None):
    """Quantized-gradient level histogram: (N,) int16/int8 grad/hess ->
    (width, F, B, 3) f32 dequantized sums. ``live`` keeps the f32 0/1
    row-mask contract of ``_level_histogram`` (converted to the uint8
    gate the native kernel takes). Three formulations mirror the f32
    dispatch:

      - native: mmls_level_hist_q16/_q8 (int32 SIMD tiles, periodic
        flush into per-worker int64 accumulators, single f32 rounding
        at merge — bit-identical to an int64 reference for any worker
        count);
      - pallas: exact dequantize (int * pow2 scale) feeding the
        existing Mosaic kernel — int histogramming inside VMEM is a
        measured-on-TPU follow-up, the mirror exists for parity;
      - XLA (per_feature and separate alike, one implementation):
        lax.scan over flush-sized row chunks, int32 segment_sum
        per chunk folded into an f32 accumulator — the periodic-rescale
        idiom (graftlint GL007 enforces the int32 widening).
    """
    import jax
    import jax.numpy as jnp

    if formulation == "native":
        return _native_level_histogram_v2(
            binned, grad_q, hess_q, live.astype(jnp.uint8), local,
            width, f, b, gscale_inv=gscale_inv, hscale_inv=hscale_inv,
            token=token,
            quant="q8" if grad_q.dtype == jnp.int8 else "q16")

    if formulation == "pallas":
        from mmlspark_tpu.models.gbdt.hist_pallas import (
            pallas_level_histogram_quant,
        )
        return pallas_level_histogram_quant(
            binned, grad_q, hess_q, live, local, width, f, b,
            gscale_inv, hscale_inv)

    # XLA mirror, one implementation for the segment_sum formulations:
    # int32 products are safe within a chunk (q16: 2^16 rows * 32001 <
    # 2^31; q8: 2^24 rows * 121 < 2^31), and each chunk's exact int32
    # partial is rescaled into the f32 accumulator before the next
    # chunk can overflow.
    n = binned.shape[0]
    if n == 0:
        return jnp.zeros((width, f, b, 3), jnp.float32)
    flush = (1 << 24) if grad_q.dtype == jnp.int8 else (1 << 16)
    chunk = min(n, flush)
    pad = (-n) % chunk
    gate = (live > 0).astype(jnp.int32)
    g32 = grad_q.astype(jnp.int32) * gate
    h32 = hess_q.astype(jnp.int32) * gate
    bc = jnp.pad(binned, ((0, pad), (0, 0))) if pad else binned
    lc = jnp.pad(local, (0, pad)) if pad else local
    gc = jnp.pad(g32, (0, pad)) if pad else g32
    hc = jnp.pad(h32, (0, pad)) if pad else h32
    # padded rows carry a zero gate, so they add nothing to bin 0
    cc = jnp.pad(gate, (0, pad)) if pad else gate

    def chunk_body(acc, xs):
        cb, cl, cg, ch, cn = xs
        base = (cl[:, None] * f + jnp.arange(f, dtype=jnp.int32)[None, :]) * b
        idx = (base + cb.astype(jnp.int32)).reshape(-1)
        data = jnp.stack([
            jnp.broadcast_to(cg[:, None], (chunk, f)).reshape(-1),
            jnp.broadcast_to(ch[:, None], (chunk, f)).reshape(-1),
            jnp.broadcast_to(cn[:, None], (chunk, f)).reshape(-1),
        ], axis=-1)
        part = jax.ops.segment_sum(data, idx,
                                   num_segments=width * f * b)
        return acc + part.astype(jnp.float32), None

    xs = (bc.reshape(-1, chunk, f), lc.reshape(-1, chunk),
          gc.reshape(-1, chunk), hc.reshape(-1, chunk),
          cc.reshape(-1, chunk))
    acc, _ = jax.lax.scan(
        chunk_body, jnp.zeros((width * f * b, 3), jnp.float32), xs)
    scales = jnp.stack([gscale_inv, hscale_inv, jnp.float32(1.0)])
    return (acc * scales[None, :]).reshape(width, f, b, 3)


def _level_histogram(binned, grad, hess, live, local, width, f, b,
                     in_shard_map: bool = False,
                     allow_pallas: bool = True,
                     allow_native: bool = True,
                     formulation: Optional[str] = None):
    """Per-level histogram: (N, F) bins + per-row stats ->
    (width, F, B, 3) grad/hess/count sums.

    ``formulation`` pins a pre-resolved choice (the serial builder
    resolves once per build so its subtraction strategy and histogram
    backend agree); otherwise ``resolve_histogram_formulation`` picks
    the kernel for this backend and caller.

    What the chip has said of the XLA formulations: per_feature took
    0.385 s a level at 2M x 28 x 32 nodes on the v5e against the Pallas
    kernel's 0.037 s (chip_smoke.py's kernel phase, PERF.md, PR 38;
    0.384 against 0.142 s in PR 22); separate is unmeasured there, and
    the A/B that would rank the two is still owed (ROADMAP S1, D1). The
    Pallas kernel itself is 30 to 80 times from its roofline and bound
    by its own VPU and MXU work, not by bandwidth (hist_pallas.py's
    cost note), so none of these figures says what the chip allows.
    """
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.hist_pallas import (
        pallas_level_histogram,
    )

    choice = formulation or resolve_histogram_formulation(
        b, in_shard_map=in_shard_map, allow_pallas=allow_pallas,
        allow_native=allow_native)

    if choice == "pallas":
        # Safe per-shard under shard_map too: the kernel only ever sees
        # this program's local rows, and the cross-device psum happens
        # on the returned histogram exactly as for the XLA formulations
        # (tests/gbdt/test_hist_pallas.py::test_pallas_under_shard_map_modes)
        return pallas_level_histogram(binned, grad, hess, live, local,
                                      width, f, b)

    if choice == "native":
        # same per-shard story as pallas: the callback sees only this
        # program's local rows and the psum happens on the result
        return _native_level_histogram(binned, grad, hess, live, local,
                                       width, f, b)

    if choice == "per_feature":
        data = jnp.stack([grad * live, hess * live, live], axis=-1)

        def body(fi, acc):
            idx = local * b + binned[:, fi].astype(jnp.int32)
            h = jax.ops.segment_sum(data, idx, num_segments=width * b)
            return acc.at[:, fi].set(h.reshape(width, b, 3))

        return jax.lax.fori_loop(
            0, f, body, jnp.zeros((width, f, b, 3), jnp.float32))

    if choice != "separate":
        raise ValueError(
            f"unknown histogram formulation {choice!r}: expected one of "
            "pallas|native|per_feature|separate")

    # Three separate scalar segment_sums sharing the index vector
    # (flat index = (local * F + f) * B + bin): shard_map-safe (no loop
    # carry), which per_feature is not.
    n = binned.shape[0]
    base = (local[:, None] * f + jnp.arange(f, dtype=jnp.int32)[None, :]) * b
    idx = (base + binned).reshape(-1)
    outs = []
    for chan in (grad * live, hess * live, live):
        flat = jnp.broadcast_to(chan[:, None], (n, f)).reshape(-1)
        outs.append(jax.ops.segment_sum(
            flat, idx, num_segments=width * f * b))
    return jnp.stack(outs, axis=-1).reshape(width, f, b, 3)


def _leaf_objective_impl(g, h, lam1, lam2, extra_l2=0.0):
    """L1-regularized leaf value and its score contribution.

    Module-level so the out-of-core loop (models/gbdt/ooc.py) evaluates
    the exact same expression graph as the compiled builder — a shared
    subgraph is the cheapest bitwise-parity guarantee."""
    import jax.numpy as jnp

    g_adj = jnp.sign(g) * jnp.maximum(jnp.abs(g) - lam1, 0.0)
    denom = h + lam2 + extra_l2 + 1e-30
    value = -g_adj / denom
    score = g_adj * g_adj / denom
    return value, score


def _derive_sibling_hist(hist_small, prev_hist, prev_split, prev_ss):
    """Histogram-subtraction sibling derivation for one level.

    ``hist_small`` (width, F, B, 3) holds real histograms only on each
    split's smaller child; the larger sibling is parent - smaller, and
    slots under non-split parents are zeroed. Shared between the
    compiled builder and the out-of-core loop (bitwise-equal trees need
    identical derive arithmetic, not just identical inputs)."""
    import jax.numpy as jnp

    width = hist_small.shape[0]
    kids = jnp.arange(width, dtype=jnp.int32)
    par_idx = kids // 2
    is_small = (kids % 2) == prev_ss[par_idx]
    sib = hist_small[kids ^ 1]
    parent_h = prev_hist[par_idx]
    hist = jnp.where(
        is_small[:, None, None, None], hist_small,
        jnp.where(prev_split[par_idx][:, None, None, None],
                  parent_h - sib, 0.0))
    # float cancellation can leave tiny negative counts / hessians on
    # the derived side; clamp for the guards
    hist = hist.at[..., 1].max(0.0)
    hist = hist.at[..., 2].max(0.0)
    return hist


ROUTE_FORMS = ("select", "gather")
# Widest level the select form routes on an accelerator. One level at
# 20M x 28 on a TPU v5e, seconds a call (tools/route_level_ab.py; my
# chip runs, PR 34), select | gather:
#   width   1: 0.0024 | 0.345      width 128: 0.153 | 0.690
#   width   8: 0.0110 | 0.267      width 256: 0.305 | 0.711
#   width  32: 0.0396 | 0.255      width 512: 0.609 | 0.711
# The select's time follows the nodes (1.2 ms each: a column's slice
# and compare, two ORs of pred[N]); the gather's does not grow past
# 0.71 s. By that arithmetic they cross near 600 nodes; 1024 was not
# measured.
ROUTE_SELECT_MAX_WIDTH = 512


def route_form(width: int) -> str:
    """The form the routing of a level of ``width`` nodes takes
    (``route_level``), read from the backend as
    ``resolve_histogram_formulation`` reads it and from the static
    width; nothing a user sets.

    On the TPU a per-row index lowers to a serial gather, 0.26-0.71 s a
    level for one byte a row at 20M x 28, while what the index chooses
    among is few, the level's nodes: ``select``, up to
    ``ROUTE_SELECT_MAX_WIDTH``; 0.076 s a tree of depth 6 against 3.16
    (the traced fit; PERF.md §6, PR 34). The CPU backend gathers in
    a few cycles a row and selecting is ``width`` strided passes over
    the row-major matrix (3.6 times the gather at width 32, 2M rows):
    ``gather``."""
    import jax

    if jax.default_backend() == "cpu" or width > ROUTE_SELECT_MAX_WIDTH:
        return "gather"
    return "select"


def route_by_form(widths, tree_mode: str = "serial") -> dict:
    """How many of a tree's levels, of these widths, route in each form:
    what ``hist_stats["route"]`` records. The feature-parallel builder
    holds a slice of the columns a device and keeps its own routing, a
    gathered bin and a vote."""
    forms = ["gather" if tree_mode == "feature" else route_form(w)
             for w in widths]
    return {name: forms.count(name) for name in ROUTE_FORMS}


def route_level(binned, node, done, local, do_split, best_feat, best_bin,
                left_mask=None, form=None):
    """Send each live row of one level to its child, or settle it in a
    leaf: ``(node, done)`` after the level. The one routing of the
    serial builder and both ``shard_map`` builders.

    ``local`` (N,) is each row's node within the level (every entry in
    ``[0, width)``), ``do_split``, ``best_feat`` and ``best_bin``
    (width,) the level's splits. A row goes left where the bin of its
    node's split feature is at most the node's ``best_bin``; with
    categorical splits ``left_mask`` (width, B) bool says instead which
    bins of each node go left.

    ``form`` is ``route_form``'s choice unless a test pins one; both
    give every row the same node. ``select`` reads no per-row index: a
    loop over the level's nodes takes node ``w``'s column of ``binned``
    (one ``dynamic_slice``: the device keeps the matrix column-major)
    and keeps its verdict for the rows with ``local == w``. The loop's
    carry starts from node 0's verdict, so inside ``shard_map`` it
    varies over the axes its inputs vary over with no cast. Only the
    categorical table is still looked up a row, by the bin so chosen."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("gbdt.route"):
        if form is None:
            form = route_form(do_split.shape[0])
        if form == "gather":
            nbin = jnp.take_along_axis(
                binned, best_feat[local][:, None], 1)[:, 0]
            picked = nbin <= best_bin[local] if left_mask is None else nbin
            nsplit = do_split[local]
        else:
            def of_node(w):
                here = local == w
                col = jax.lax.dynamic_slice_in_dim(
                    binned, best_feat[w], 1, axis=1)[:, 0]
                # numeric: whether the row goes left; else its bin
                pick = (here & (col <= best_bin[w]) if left_mask is None
                        else jnp.where(here, col, 0))
                return pick, here & do_split[w]

            # every row has exactly one node, so OR-ing the nodes'
            # verdicts is choosing among them
            picked, nsplit = jax.lax.fori_loop(
                1, do_split.shape[0],
                lambda w, acc: tuple(a | v for a, v in zip(acc, of_node(w))),
                of_node(0))
        go_left = picked if left_mask is None else left_mask[local, picked]
        child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
        return jnp.where(done | ~nsplit, node, child), done | ~nsplit


def _find_numeric_splits(hist, feat_mask, remaining, parent_value, *, b,
                         lam1, lam2, min_child, min_hess, min_gain,
                         path_smooth, max_delta_step):
    """Numeric-only split finding for one level: ordered cumulative scan,
    leaf-budget ranking, and child values, from the (width, F, B, 3)
    level histogram. ``parent_value`` is the per-slot current node value
    (path smoothing shrinks children toward it).

    Returns (do_split, best_feat, best_bin, left_mask, lval, rval,
    left_stats, right_stats, remaining, smaller_side). This is the
    whole split pipeline for fits with no categorical / monotone /
    extra-trees / per-node-sampling features — the depthwise builder's
    fast path and the out-of-core loop both call it, so the two paths
    build bitwise-identical trees from bitwise-identical histograms.
    """
    import jax.numpy as jnp

    width = hist.shape[0]
    cum = jnp.cumsum(hist, axis=2)              # left stats per bin
    tot = cum[:, :, -1:, :]
    gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
    gt, ht, ct = tot[..., 0], tot[..., 1], tot[..., 2]
    gr, hr, cr = gt - gl, ht - hl, ct - cl
    _, score_l = _leaf_objective_impl(gl, hl, lam1, lam2)
    _, score_r = _leaf_objective_impl(gr, hr, lam1, lam2)
    _, score_p = _leaf_objective_impl(gt, ht, lam1, lam2)
    gain = 0.5 * (score_l + score_r - score_p)
    ok = ((cl >= min_child) & (cr >= min_child)
          & (hl >= min_hess) & (hr >= min_hess)
          & (gain > min_gain))
    node_fmask = feat_mask[None, :] > 0
    ok &= node_fmask[:, :, None]
    # last bin can't split (right side empty by construction)
    ok &= jnp.arange(b, dtype=jnp.int32)[None, None, :] < b - 1
    gain = jnp.where(ok, gain, -jnp.inf)

    flat_gain = gain.reshape(width, -1)
    best_fb = jnp.argmax(flat_gain, axis=1)
    best_gain = jnp.take_along_axis(flat_gain, best_fb[:, None], 1)[:, 0]
    best_feat = (best_fb // b).astype(jnp.int32)
    best_bin = (best_fb % b).astype(jnp.int32)

    # leaf budget: within-level gain ranking
    can_split = jnp.isfinite(best_gain)
    order = jnp.argsort(-jnp.where(can_split, best_gain, -jnp.inf))
    rank = jnp.zeros(width, dtype=jnp.int32).at[order].set(
        jnp.arange(width, dtype=jnp.int32))
    do_split = can_split & (rank < remaining)
    remaining = remaining - jnp.sum(do_split.astype(jnp.int32))

    left_mask = jnp.arange(b, dtype=jnp.int32)[None, :] <= best_bin[:, None]
    hist_best = hist[jnp.arange(width, dtype=jnp.int32), best_feat]      # (width, B, 3)
    left_stats = jnp.sum(hist_best * left_mask[..., None], axis=1)
    tot_best = jnp.sum(hist_best, axis=1)
    right_stats = tot_best - left_stats
    lval, _ = _leaf_objective_impl(left_stats[:, 0], left_stats[:, 1],
                                   lam1, lam2)
    rval, _ = _leaf_objective_impl(right_stats[:, 0], right_stats[:, 1],
                                   lam1, lam2)
    if path_smooth > 0:
        # shrink child outputs toward the parent's by n/(n+ps)
        wl = left_stats[:, 2] / (left_stats[:, 2] + path_smooth)
        wr = right_stats[:, 2] / (right_stats[:, 2] + path_smooth)
        lval = lval * wl + parent_value * (1.0 - wl)
        rval = rval * wr + parent_value * (1.0 - wr)
    if max_delta_step > 0:
        lval = jnp.clip(lval, -max_delta_step, max_delta_step)
        rval = jnp.clip(rval, -max_delta_step, max_delta_step)
    smaller_side = jnp.where(
        left_stats[:, 2] <= right_stats[:, 2], 0, 1).astype(jnp.int32)
    return (do_split, best_feat, best_bin, left_mask, lval, rval,
            left_stats, right_stats, remaining, smaller_side)


def make_build_tree(num_features: int, total_bins: int, cfg: TrainConfig,
                    subtract: bool = False, allow_pallas: bool = True,
                    allow_native: bool = True, efb_plan=None):
    """Compile-once tree builder: (binned, grad, hess, valid, feat_mask,
    remaining_leaves) -> (split_feature, threshold_bin, node_value, count,
    decision_type, bin_go_left, node).

    All shapes static: N rows, F features, B bins, depth D. Returns the
    full-layout arrays described in booster.py; ``bin_go_left`` is a
    (num_slots, B) bool mask — for every internal slot, which bin ids
    route left. Numerical splits fill it with ``bin <= threshold``;
    categorical splits with the chosen category subset, so binned
    prediction is a single gather regardless of split type. A level's
    row routing is ``route_level``: numeric fits compare the row's bin
    with its node's threshold, and only categorical fits look the bin
    up in the level's masks.
    ``node`` is int32 (N,): the slot every row of ``binned`` settled in
    after the last level's routing, bagged-out and padded rows included
    — the slot ``_make_predict_tree``'s walk of the finished tree
    reaches, so ``node_value[node]`` is the tree's prediction for the
    rows it was built on. Every builder ``_get_builder`` returns gives
    the same seven.

    ``subtract=True`` enables LightGBM's histogram-subtraction trick
    (feature_histogram.hpp Subtract): below the root, only the SMALLER
    child of each split is histogrammed and the sibling is derived as
    parent - smaller. Histogram row-work per tree drops from N*D to
    ~N*(1 + (D-1)/2). With the native CPU kernel the smaller child is
    selected by MASKING its sibling's rows out of ``live`` — the kernel
    skips masked rows before touching their bin row, so masking is the
    compaction; the XLA formulations instead compact rows to a static
    N/2 buffer via sized nonzero (a scatter over masked-to-zero rows
    would still cost full-N work there). Single-program only: the
    compaction gather is data-dependent, so sharded (GSPMD) builders
    keep the full pass.

    Categorical features (``cfg.categorical_features``) follow LightGBM's
    algorithm (core/schema/Categoricals.scala; LightGBM's
    FindBestThresholdCategorical): bins sorted by grad/(hess+cat_smooth),
    prefix scan with ``lambda_l2 + cat_l2`` regularization and the
    ``max_cat_threshold`` side cap; nodes with few used categories
    (<= max_cat_to_onehot) use one-vs-rest splits instead. The missing
    bin (0) is never placed in a categorical left set — missing routes
    right, matching LightGBM's unseen-category rule.
    """
    import jax
    import jax.numpy as jnp

    depth = cfg.effective_depth
    num_slots = 2 ** (depth + 1) - 1
    lam1, lam2 = cfg.lambda_l1, cfg.lambda_l2
    min_child = float(cfg.min_data_in_leaf)
    min_hess = cfg.min_sum_hessian_in_leaf
    min_gain = cfg.min_gain_to_split
    cat_feats = tuple(cfg.categorical_features or ())
    is_cat_np = np.zeros(num_features, dtype=bool)
    if cat_feats:
        is_cat_np[list(cat_feats)] = True
    has_cat = bool(is_cat_np.any())
    # one resolution per builder: the subtraction strategy (masking vs
    # compaction) and every level's histogram call must agree on the
    # kernel; the compiled-builder cache is keyed on the same env state
    hist_formulation = resolve_histogram_formulation(
        total_bins, in_shard_map=False, allow_pallas=allow_pallas,
        allow_native=allow_native)
    masked_subtract = subtract and hist_formulation == "native"
    # quantization and EFB are serial single-program paths; the GSPMD /
    # shard_map builders keep f32 full-feature histograms (allow_native
    # is the single-program proxy the native default shares)
    hist_quant = resolve_hist_quant(warn=False) if allow_native else "off"
    use_efb = efb_plan is not None
    f_hist = efb_plan.n_cols if use_efb else num_features
    if use_efb:
        # static unbundling index maps (ops/efb.py): bundled-histogram
        # slots scatter back to (original feature, original bin), then
        # every bundled member's default bin is reconstructed as the
        # node total minus its present bins (each live row contributes
        # exactly once per bundled column)
        ub_sc_col, ub_sc_bin, ub_sc_feat, ub_sc_obin = \
            efb_plan.scatter_arrays()
        ub_md_feat, ub_md_bin = efb_plan.member_default_arrays()
        ub_pt_col, ub_pt_feat = efb_plan.passthrough_arrays()
    mono_np = np.zeros(num_features, dtype=np.float32)
    if cfg.monotone_constraints:
        if len(cfg.monotone_constraints) > num_features:
            raise ValueError(
                f"monotone_constraints has {len(cfg.monotone_constraints)} "
                f"entries but there are only {num_features} features")
        mono_np[:len(cfg.monotone_constraints)] = cfg.monotone_constraints
    has_mono = bool(mono_np.any())
    # numeric-only fast path: split math delegates to the module-level
    # _find_numeric_splits shared with the out-of-core loop, so both
    # build bitwise-identical trees from identical histograms
    simple_numeric = (not has_cat and not has_mono and not cfg.extra_trees
                      and cfg.feature_fraction_by_node >= 1.0)

    def leaf_objective(g, h, extra_l2=0.0):
        # L1-regularized leaf value and its score contribution
        return _leaf_objective_impl(g, h, lam1, lam2, extra_l2)

    def build_tree(binned, grad, hess, valid, feat_mask, remaining_leaves,
                   key=None, hist_token=None, binned_hist=None):
        """binned (N,F) int32; grad/hess (N,) f32; valid (N,) f32 row mask
        (bagging/GOSS already folded into grad/hess scaling + this mask);
        feat_mask (F,) f32; remaining_leaves traced int; key seeds the
        extra_trees random thresholds (required when extra_trees).

        ``hist_token``: scalar int32 token of a host-registered binned
        matrix (native formulation only) — histogram callbacks read the
        registered matrix instead of marshalling ``binned`` per level.
        ``binned_hist``: the EFB-bundled matrix for non-native
        formulations (when a plan is active and no token is given).
        Both default to None so direct callers keep the old signature;
        routing and split recording always use the original ``binned``."""
        if (cfg.extra_trees or cfg.feature_fraction_by_node < 1.0) \
                and key is None:
            raise ValueError("extra_trees / feature_fraction_by_node "
                             "need an rng key")
        if use_efb and binned_hist is None and hist_token is None:
            raise ValueError("an EFB-planned builder needs binned_hist "
                             "(XLA formulations) or hist_token (native)")
        n = binned.shape[0]
        f = num_features
        b = total_bins
        # matrix histogram calls index: the bundled one under EFB (the
        # token path never reads it — callbacks hold the bundled host
        # matrix — so the original stands in as a placeholder operand)
        hist_mat = binned_hist if (use_efb and binned_hist is not None) \
            else binned
        if hist_quant != "off":
            # per-round shared pow2 scale; invalid rows quantize to 0
            # (valid is folded in) so the kernels' live gate and the
            # quantized values agree
            qdt = jnp.int8 if hist_quant == "q8" else jnp.int16
            qmax = 120.0 if hist_quant == "q8" else 32000.0
            gscale, gscale_inv = _pow2_scale(
                jnp.max(jnp.abs(grad) * valid), qmax)
            hscale, hscale_inv = _pow2_scale(
                jnp.max(jnp.abs(hess) * valid), qmax)
            grad_h = jnp.rint(grad * valid * gscale).astype(qdt)
            hess_h = jnp.rint(hess * valid * hscale).astype(qdt)
        else:
            grad_h, hess_h = grad, hess
            gscale_inv = hscale_inv = None

        def _unbundle_hist(hb, width):
            # (width, f_hist, B, 3) bundled -> (width, F, B, 3) original
            hist = jnp.zeros((width, f, b, 3), hb.dtype)
            if len(ub_pt_col):
                hist = hist.at[:, ub_pt_feat].set(hb[:, ub_pt_col])
            if len(ub_sc_col):
                hist = hist.at[:, ub_sc_feat, ub_sc_obin].set(
                    hb[:, ub_sc_col, ub_sc_bin])
            if len(ub_md_feat):
                # node totals from any one bundled column (every live
                # row lands in exactly one of its bins); a member's
                # default-bin stats are total minus its present bins —
                # exact for counts, f32-rounding for grad/hess
                total = hb[:, 0].sum(axis=1)             # (width, 3)
                present = hist[:, ub_md_feat].sum(axis=2)
                hist = hist.at[:, ub_md_feat, ub_md_bin].set(
                    total[:, None, :] - present)
            return hist

        def _hist(bn_h, g_, h_, lv, lo, width):
            if hist_quant != "off":
                hist = _level_histogram_quant(
                    bn_h, g_, h_, lv, lo, width, f_hist, b,
                    gscale_inv, hscale_inv,
                    formulation=hist_formulation,
                    token=(hist_token
                           if hist_formulation == "native" else None))
            elif hist_token is not None and hist_formulation == "native":
                hist = _native_level_histogram_v2(
                    bn_h, g_, h_, lv, lo, width, f_hist, b,
                    token=hist_token)
            else:
                hist = _level_histogram(
                    bn_h, g_, h_, lv, lo, width, f_hist, b,
                    allow_pallas=allow_pallas,
                    allow_native=allow_native,
                    formulation=hist_formulation)
            return _unbundle_hist(hist, width) if use_efb else hist

        if subtract:
            prev_hist = prev_split = prev_ss = None
            if not masked_subtract:
                # +1 dummy slot: sized-nonzero fill target for the
                # smaller-child compaction gather (over the histogram
                # matrix and the possibly-quantized stats)
                n_half = n // 2 + 1
                binned_pad = jnp.concatenate(
                    [hist_mat, jnp.zeros((1, f_hist), hist_mat.dtype)])
                grad_pad = jnp.concatenate(
                    [grad_h, jnp.zeros(1, grad_h.dtype)])
                hess_pad = jnp.concatenate(
                    [hess_h, jnp.zeros(1, hess_h.dtype)])

        node = jnp.zeros(n, dtype=jnp.int32)       # slot in full layout
        done = jnp.zeros(n, dtype=jnp.bool_)        # settled in a leaf
        split_feature = jnp.full(num_slots, -1, dtype=jnp.int32)
        threshold_bin = jnp.zeros(num_slots, dtype=jnp.int32)
        node_value = jnp.zeros(num_slots, dtype=jnp.float32)
        node_count = jnp.zeros(num_slots, dtype=jnp.float32)
        decision_type = jnp.zeros(num_slots, dtype=jnp.int8)
        bin_go_left = jnp.zeros((num_slots, b), dtype=jnp.bool_)
        is_cat_f = jnp.asarray(is_cat_np)
        mono_f = jnp.asarray(mono_np)
        # per-slot output bounds (monotone "basic" method): children of
        # a constrained split may not cross the split midpoint
        node_lower = jnp.full(num_slots, -jnp.inf, dtype=jnp.float32)
        node_upper = jnp.full(num_slots, jnp.inf, dtype=jnp.float32)
        # root stats: exact-plane fits reduce grad/hess directly; the
        # quantized plane instead derives them from the level-0
        # histogram totals (below, inside the loop) — bin sums of the
        # exact integer accumulation — so a chunk-merged out-of-core
        # histogram reproduces the root bitwise too
        if hist_quant == "off":
            with jax.named_scope("gbdt.leaf"):
                root_g, root_h, root_c = (jnp.sum(grad * valid),
                                          jnp.sum(hess * valid),
                                          jnp.sum(valid))
                rv, _ = leaf_objective(root_g, root_h)
                if cfg.max_delta_step > 0:
                    rv = jnp.clip(rv, -cfg.max_delta_step, cfg.max_delta_step)
                node_value = node_value.at[0].set(rv)
                node_count = node_count.at[0].set(root_c)

        remaining = remaining_leaves - 1  # root is one leaf

        for d in range(depth):
            level_start = 2 ** d - 1
            width = 2 ** d
            local = jnp.clip(node - level_start, 0, width - 1)
            live = (~done).astype(grad.dtype) * valid

            # --- histogram --------------------------------------------
            with jax.named_scope("gbdt.hist"):
                if subtract and d > 0:
                    # smaller child only; sibling by subtraction.
                    # INVARIANT (ADVICE r4): ``live`` must stay BINARY.
                    # prev_ss picks the smaller child by the cover stat
                    # (left_stats[:,2] = sum of live), which bounds its ROW
                    # count by n//2+1 only because every live row weighs
                    # exactly 1 (GOSS folds amplification into grad/hess,
                    # bagging masks are 0/1). A fractional row mask would
                    # let the weighted-smaller side hold more than n_half
                    # rows and the sized nonzero below would silently drop
                    # rows, corrupting histograms.
                    par_row = local // 2
                    side = (local % 2).astype(jnp.int32)
                    sel = (live > 0) & (side == prev_ss[par_row])
                    if masked_subtract:
                        # native kernel: masked rows are skipped before
                        # their bin row is read, so zeroing ``live`` on the
                        # larger sibling IS the compaction — no gather
                        hist_small = _hist(
                            hist_mat, grad_h, hess_h,
                            live * sel.astype(live.dtype), local, width)
                    else:
                        idx = jnp.nonzero(sel, size=n_half, fill_value=n)[0]
                        live_pad = jnp.concatenate(
                            [live, jnp.zeros(1, live.dtype)])
                        local_pad = jnp.concatenate(
                            [local, jnp.zeros(1, local.dtype)])
                        hist_small = _hist(
                            binned_pad[idx], grad_pad[idx], hess_pad[idx],
                            live_pad[idx], local_pad[idx], width)
                    hist = _derive_sibling_hist(hist_small, prev_hist,
                                                prev_split, prev_ss)
                else:
                    hist = _hist(hist_mat, grad_h, hess_h, live, local,
                                 width)
                if subtract:
                    prev_hist = hist
            if hist_quant != "off" and d == 0:
                with jax.named_scope("gbdt.leaf"):
                    # quantized-plane root stats from the level-0 histogram
                    # (any one feature's bins partition the live rows);
                    # recorded before split finding so path smoothing sees
                    # the root value at this level
                    tot0 = jnp.sum(hist[0, 0], axis=0)
                    rv0, _ = leaf_objective(tot0[0], tot0[1])
                    if cfg.max_delta_step > 0:
                        rv0 = jnp.clip(rv0, -cfg.max_delta_step,
                                       cfg.max_delta_step)
                    node_value = node_value.at[0].set(rv0)
                    node_count = node_count.at[0].set(tot0[2])

            slots = level_start + jnp.arange(width, dtype=jnp.int32)
            if simple_numeric:
                with jax.named_scope("gbdt.split"):
                    (do_split, best_feat, best_bin, left_mask, lval, rval,
                     left_stats, right_stats, remaining, small_side) = \
                        _find_numeric_splits(
                            hist, feat_mask, remaining, node_value[slots],
                            b=b, lam1=lam1, lam2=lam2, min_child=min_child,
                            min_hess=min_hess, min_gain=min_gain,
                            path_smooth=cfg.path_smooth,
                            max_delta_step=cfg.max_delta_step)
                with jax.named_scope("gbdt.leaf"):
                    split_feature = split_feature.at[slots].set(
                        jnp.where(do_split, best_feat, -1))
                    threshold_bin = threshold_bin.at[slots].set(
                        jnp.where(do_split, best_bin, 0))
                    num_bits = 6 if cfg.zero_as_missing else 10
                    decision_type = decision_type.at[slots].set(
                        jnp.where(do_split, num_bits, 0).astype(jnp.int8))
                    bin_go_left = bin_go_left.at[slots].set(
                        left_mask & do_split[:, None])
                    lslots, rslots = 2 * slots + 1, 2 * slots + 2
                    node_value = node_value.at[lslots].set(
                        jnp.where(do_split, lval, 0.0))
                    node_value = node_value.at[rslots].set(
                        jnp.where(do_split, rval, 0.0))
                    node_count = node_count.at[lslots].set(
                        jnp.where(do_split, left_stats[:, 2], 0.0))
                    node_count = node_count.at[rslots].set(
                        jnp.where(do_split, right_stats[:, 2], 0.0))
                if subtract:
                    prev_split = do_split
                    prev_ss = small_side
                node, done = route_level(binned, node, done, local,
                                         do_split, best_feat, best_bin)
                continue

            # --- numerical split finding: ordered cumulative scan -------
            with jax.named_scope("gbdt.split"):
                cum = jnp.cumsum(hist, axis=2)              # left stats per bin
                tot = cum[:, :, -1:, :]
                gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
                gt, ht, ct = tot[..., 0], tot[..., 1], tot[..., 2]
                gr, hr, cr = gt - gl, ht - hl, ct - cl
                val_l, score_l = leaf_objective(gl, hl)
                val_r, score_r = leaf_objective(gr, hr)
                _, score_p = leaf_objective(gt, ht)
                gain = 0.5 * (score_l + score_r - score_p)
                ok = ((cl >= min_child) & (cr >= min_child)
                      & (hl >= min_hess) & (hr >= min_hess)
                      & (gain > min_gain))
                # per-tree feature mask, optionally re-sampled per node
                # (LightGBM feature_fraction_bynode)
                node_fmask = feat_mask[None, :] > 0         # (1|width, F)
                if cfg.feature_fraction_by_node < 1.0:
                    # sample per node from the TREE's feature subset (as
                    # LightGBM feature_fraction_bynode composes with
                    # feature_fraction), never leaving a node featureless
                    avail = jnp.sum(feat_mask > 0)
                    keep_n = jnp.maximum(1, jnp.round(
                        avail * cfg.feature_fraction_by_node)).astype(jnp.int32)
                    kn = jax.random.fold_in(jax.random.fold_in(key, 101), d)
                    draw = jax.random.uniform(kn, (width, num_features))
                    draw = jnp.where(feat_mask[None, :] > 0, draw, -1.0)
                    sortd = jnp.sort(draw, axis=1)[:, ::-1]  # descending
                    kth = jnp.take_along_axis(
                        sortd, jnp.broadcast_to(keep_n - 1, (width,))[:, None],
                        axis=1)
                    node_fmask = node_fmask & (draw >= kth)
                ok &= node_fmask[:, :, None]
                # last bin can't split (right side empty by construction)
                ok &= jnp.arange(b, dtype=jnp.int32)[None, None, :] < b - 1
                if has_mono:
                    # reject splits whose child values violate the feature's
                    # monotone direction (LightGBM "basic" rejection)
                    ok &= mono_f[None, :, None] * (val_r - val_l) >= 0
                if cfg.extra_trees:
                    # one random candidate threshold per (node, feature)
                    kd = jax.random.fold_in(key, d)
                    rand_bin = jax.random.randint(kd, (width, f), 0, b - 1)
                    ok &= jnp.arange(b, dtype=jnp.int32)[None, None, :] == rand_bin[..., None]
                gain = jnp.where(ok, gain, -jnp.inf)

                if has_cat:
                    # --- categorical split finding ----------------------
                    g_b, h_b, c_b = hist[..., 0], hist[..., 1], hist[..., 2]
                    not_missing = jnp.arange(b, dtype=jnp.int32)[None, None, :] > 0
                    used = (c_b > 0) & not_missing
                    # LightGBM min_data_per_group: the sorted scan only
                    # considers categories with enough rows (filtered ones
                    # route right); one-hot mode keeps the plain used set
                    used_sorted = used & (
                        c_b >= float(max(cfg.min_data_per_group, 1)))
                    ratio = jnp.where(used_sorted,
                                      g_b / (h_b + cfg.cat_smooth), jnp.inf)
                    sort_idx = jnp.argsort(ratio, axis=2)   # unused sort last
                    shist = jnp.take_along_axis(
                        hist, sort_idx[..., None], axis=2)
                    scum = jnp.cumsum(shist, axis=2)
                    num_used = jnp.sum(used, axis=2)        # (width, F)
                    num_sorted = jnp.sum(used_sorted, axis=2)
                    gl_c, hl_c, cl_c = scum[..., 0], scum[..., 1], scum[..., 2]
                    gr_c, hr_c = gt - gl_c, ht - hl_c
                    cr_c = ct - cl_c
                    _, cscore_l = leaf_objective(gl_c, hl_c, cfg.cat_l2)
                    _, cscore_r = leaf_objective(gr_c, hr_c, cfg.cat_l2)
                    _, cscore_p = leaf_objective(gt, ht, cfg.cat_l2)
                    cgain = 0.5 * (cscore_l + cscore_r - cscore_p)
                    pos1 = jnp.arange(1, b + 1, dtype=jnp.int32)[None, None, :]  # left-set size
                    side = jnp.minimum(pos1, num_sorted[..., None] - pos1)
                    cok = ((pos1 < num_sorted[..., None])
                           & (side <= cfg.max_cat_threshold)
                           & (cl_c >= min_child) & (cr_c >= min_child)
                           & (hl_c >= min_hess) & (hr_c >= min_hess)
                           & (cgain > min_gain))
                    cgain = jnp.where(cok, cgain, -jnp.inf)
                    # one-vs-rest for low-cardinality nodes (indexed by the
                    # actual bin id, not a sort position)
                    gr_o, hr_o, cr_o = gt - g_b, ht - h_b, ct - c_b
                    _, oscore_l = leaf_objective(g_b, h_b, cfg.cat_l2)
                    _, oscore_r = leaf_objective(gr_o, hr_o, cfg.cat_l2)
                    ogain = 0.5 * (oscore_l + oscore_r - cscore_p)
                    ook = (used & (c_b >= min_child) & (cr_o >= min_child)
                           & (h_b >= min_hess) & (hr_o >= min_hess)
                           & (ogain > min_gain) & (num_used[..., None] > 1))
                    ogain = jnp.where(ook, ogain, -jnp.inf)
                    onehot = (num_used <= cfg.max_cat_to_onehot)[..., None]
                    cat_gain = jnp.where(onehot, ogain, cgain)
                    cat_gain = jnp.where(node_fmask[:, :, None],
                                         cat_gain, -jnp.inf)
                    gain = jnp.where(is_cat_f[None, :, None], cat_gain, gain)

                flat_gain = gain.reshape(width, f * b)
                best_fb = jnp.argmax(flat_gain, axis=1)
                best_gain = jnp.take_along_axis(flat_gain, best_fb[:, None], 1)[:, 0]
                best_feat = (best_fb // b).astype(jnp.int32)
                best_bin = (best_fb % b).astype(jnp.int32)

                # --- leaf budget: within-level gain ranking ------------------
                can_split = jnp.isfinite(best_gain)
                order = jnp.argsort(-jnp.where(can_split, best_gain, -jnp.inf))
                rank = jnp.zeros(width, dtype=jnp.int32).at[order].set(
                    jnp.arange(width, dtype=jnp.int32))
                do_split = can_split & (rank < remaining)
                remaining = remaining + 0 if width == 0 else (
                    remaining - jnp.sum(do_split.astype(jnp.int32)))

                # --- per-node left-bin mask for the chosen split -------------
                sel = jnp.arange(width, dtype=jnp.int32)
                mask_num = jnp.arange(b, dtype=jnp.int32)[None, :] <= best_bin[:, None]
                if has_cat:
                    chosen_cat = is_cat_f[best_feat] & do_split
                    s_idx = sort_idx[sel, best_feat]        # (width, B)
                    # rank of bin id in sorted order = inverse permutation
                    bin_rank = jnp.argsort(s_idx, axis=1)
                    used_sel = used_sorted[sel, best_feat]
                    onehot_sel = num_used[sel, best_feat] <= cfg.max_cat_to_onehot
                    mask_prefix = (bin_rank <= best_bin[:, None]) & used_sel
                    mask_onehot = jnp.arange(b, dtype=jnp.int32)[None, :] == best_bin[:, None]
                    mask_cat = jnp.where(onehot_sel[:, None], mask_onehot,
                                         mask_prefix)
                    left_mask = jnp.where(chosen_cat[:, None], mask_cat, mask_num)
                else:
                    chosen_cat = jnp.zeros(width, dtype=jnp.bool_)
                    left_mask = mask_num

            # --- record splits & child stats -----------------------------
            with jax.named_scope("gbdt.leaf"):
                split_feature = split_feature.at[slots].set(
                    jnp.where(do_split, best_feat, -1))
                threshold_bin = threshold_bin.at[slots].set(
                    jnp.where(do_split, best_bin, 0))
                # numerical splits carry default-left + NaN-missing bits
                # (2 | 8 = 10): training routes the missing bin left, and
                # loaded models reproduce that routing from the bits
                num_bits = 6 if cfg.zero_as_missing else 10
                decision_type = decision_type.at[slots].set(
                    jnp.where(do_split,
                              jnp.where(chosen_cat, 1, num_bits),
                              0).astype(jnp.int8))
                bin_go_left = bin_go_left.at[slots].set(
                    left_mask & do_split[:, None])

                hist_best = hist[sel, best_feat]            # (width, B, 3)
                left_stats = jnp.sum(hist_best * left_mask[..., None], axis=1)
                tot_best = jnp.sum(hist_best, axis=1)
                right_stats = tot_best - left_stats
                lx2 = jnp.where(chosen_cat, cfg.cat_l2, 0.0)
                lval, _ = leaf_objective(left_stats[:, 0], left_stats[:, 1], lx2)
                rval, _ = leaf_objective(right_stats[:, 0], right_stats[:, 1], lx2)
                lslots, rslots = 2 * slots + 1, 2 * slots + 2
                if cfg.path_smooth > 0:
                    # shrink child outputs toward the parent's by n/(n+ps)
                    pv = node_value[slots]
                    wl = left_stats[:, 2] / (left_stats[:, 2] + cfg.path_smooth)
                    wr = right_stats[:, 2] / (right_stats[:, 2] + cfg.path_smooth)
                    lval = lval * wl + pv * (1.0 - wl)
                    rval = rval * wr + pv * (1.0 - wr)
                if cfg.max_delta_step > 0:
                    lval = jnp.clip(lval, -cfg.max_delta_step,
                                    cfg.max_delta_step)
                    rval = jnp.clip(rval, -cfg.max_delta_step,
                                    cfg.max_delta_step)
                if has_mono:
                    # clamp child outputs into the parent's bounds, then
                    # tighten the children's bounds at the split midpoint
                    # when this split's feature is constrained
                    p_lo, p_hi = node_lower[slots], node_upper[slots]
                    lval = jnp.clip(lval, p_lo, p_hi)
                    rval = jnp.clip(rval, p_lo, p_hi)
                    c_mono = mono_f[best_feat] * (~chosen_cat)
                    mid = (lval + rval) / 2.0
                    l_hi = jnp.where(c_mono > 0, jnp.minimum(p_hi, mid), p_hi)
                    r_lo = jnp.where(c_mono > 0, jnp.maximum(p_lo, mid), p_lo)
                    l_lo = jnp.where(c_mono < 0, jnp.maximum(p_lo, mid), p_lo)
                    r_hi = jnp.where(c_mono < 0, jnp.minimum(p_hi, mid), p_hi)
                    node_lower = node_lower.at[lslots].set(
                        jnp.where(do_split, l_lo, p_lo))
                    node_upper = node_upper.at[lslots].set(
                        jnp.where(do_split, l_hi, p_hi))
                    node_lower = node_lower.at[rslots].set(
                        jnp.where(do_split, r_lo, p_lo))
                    node_upper = node_upper.at[rslots].set(
                        jnp.where(do_split, r_hi, p_hi))
                node_value = node_value.at[lslots].set(
                    jnp.where(do_split, lval, 0.0))
                node_value = node_value.at[rslots].set(
                    jnp.where(do_split, rval, 0.0))
                node_count = node_count.at[lslots].set(
                    jnp.where(do_split, left_stats[:, 2], 0.0))
                node_count = node_count.at[rslots].set(
                    jnp.where(do_split, right_stats[:, 2], 0.0))

            if subtract:
                prev_split = do_split
                prev_ss = jnp.where(
                    left_stats[:, 2] <= right_stats[:, 2], 0, 1
                ).astype(jnp.int32)

            # a numeric left_mask is ``bin <= best_bin`` by construction;
            # only categorical membership needs the table
            node, done = route_level(binned, node, done, local, do_split,
                                     best_feat, best_bin,
                                     left_mask if has_cat else None)

        return (split_feature, threshold_bin, node_value, node_count,
                decision_type, bin_go_left, node)

    return build_tree


# ---------------------------------------------------------------------------
# Compiled-function caches (cross-call reuse)
# ---------------------------------------------------------------------------
#
# ``train`` used to build fresh closures (and therefore fresh jit caches)
# on every call, so every ``fit`` recompiled the tree builder; and the
# boosting loop dispatched ~30 eager ops + a blocking ``float()`` metric
# sync per iteration. On a remote-attached TPU each sync is a full
# round trip, which dominated wall clock (the histogram math itself is
# sub-millisecond). The redesign below:
#
#   - caches compiled builders/fused-steps at module level, keyed by the
#     (hashable) TrainConfig + shapes-independent statics;
#   - fuses each boosting iteration into ONE jitted step dispatched
#     asynchronously (no host syncs inside the loop), with per-iteration
#     metrics computed on device and synced in blocks;
#   - keeps a Python-loop fallback only for DART, whose dropped-tree
#     bookkeeping is dynamic across iterations.

_CACHE_LIMIT = 64  # crude eviction bound: sweeps over many configs


def _cache_put(cache, key, factory):
    if key not in cache:
        if len(cache) >= _CACHE_LIMIT:
            cache.clear()  # drop all compiled fns; next calls recompile
        sanitizer.count_recompile(repr(key))
        cache[key] = factory()
    return cache[key]


_CHUNK_CACHE: Dict[Any, Callable] = {}
_BUILDER_CACHE: Dict[Any, Callable] = {}
_PREDICT_CACHE: Dict[int, Callable] = {}


def _make_predict_tree(depth: int) -> Callable:
    """(sf, bin_go_left, nv, binned) -> (N,) leaf values. Routing is one
    gather into the per-slot left-bin mask, uniform across numerical and
    categorical splits. For rows no builder in the same program routed:
    validation sets, streamed chunks, the Python loop. The rows a tree
    was built on have their slot in the builder's ``node``."""
    import jax
    import jax.numpy as jnp

    def predict_tree_binned(sf, bgl, nv, bd):
        with jax.named_scope("gbdt.predict"):
            nodev = jnp.zeros(bd.shape[0], dtype=jnp.int32)
            for _ in range(depth):
                feat = sf[nodev]
                is_leaf = feat < 0
                fb = jnp.take_along_axis(
                    bd, jnp.maximum(feat, 0)[:, None], 1)[:, 0]
                child = jnp.where(bgl[nodev, fb], 2 * nodev + 1,
                                  2 * nodev + 2)
                nodev = jnp.where(is_leaf, nodev, child)
            return nv[nodev]

    return predict_tree_binned


def _get_predict_tree(depth: int) -> Callable:
    import jax
    return _cache_put(_PREDICT_CACHE, depth,
                      lambda: jax.jit(_make_predict_tree(depth)))


def _loop_only_normalized(cfg: TrainConfig) -> TrainConfig:
    """Zero out fields the compiled step/builder never reads (they only
    steer the host loop, or are passed in as traced data), so sweeps
    over them reuse one compiled executable."""
    return replace(cfg, num_iterations=0, early_stopping_round=0, seed=0,
                   learning_rate=0.1)


def _resolve_mode(cfg: TrainConfig, mesh) -> str:
    """Distributed tree-learner mode: explicit shard_map builders exist
    for voting/feature (selected by ``tree_learner``) and for the
    data-parallel reduce-scatter path (``data_sharded``, selected by
    MMLSPARK_TPU_HIST_SHARD when the config supports it); everything
    else is the serial builder (which GSPMD data-parallelizes when
    inputs are row-sharded, with a full-histogram allreduce)."""
    if cfg.tree_learner in ("voting", "feature") and mesh is not None:
        return cfg.tree_learner
    if mesh is not None and resolve_hist_shard_mode(
            cfg, mesh, warn=False)[0] == "on":
        return "data_sharded"
    return "serial"


def _with_bin_mask(fn, total_bins):
    """Adapt a numerical-only builder, (split_feature, threshold_bin,
    node_value, count, node), to ``make_build_tree``'s seven: synthesize
    decision_type=0 and the ordered ``bin <= threshold`` left mask from
    the recorded thresholds; ``node`` passes through."""
    import jax.numpy as jnp

    def wrapped(*args):
        sf, tb, nv, cnt, node = fn(*args)
        bins = jnp.arange(total_bins, dtype=jnp.int32)
        bgl = (bins[None, :] <= tb[:, None]) & (sf >= 0)[:, None]
        return sf, tb, nv, cnt, jnp.zeros(sf.shape[0], jnp.int8), bgl, node

    return wrapped


def _get_builder(num_f: int, total_bins: int, cfg: TrainConfig, mode: str,
                 mesh, efb_plan=None) -> Callable:
    import jax

    cfg = _loop_only_normalized(cfg)

    def build():
        if mode == "voting":
            from mmlspark_tpu.models.gbdt.parallel_modes import (
                make_build_tree_voting)
            fn = _with_bin_mask(
                make_build_tree_voting(num_f, total_bins, cfg, mesh),
                total_bins)
        elif mode == "feature":
            from mmlspark_tpu.models.gbdt.parallel_modes import (
                make_build_tree_feature_parallel)
            fn = _with_bin_mask(
                make_build_tree_feature_parallel(num_f, total_bins, cfg,
                                                 mesh),
                total_bins)
        elif mode == "data_sharded":
            from mmlspark_tpu.models.gbdt.parallel_modes import (
                make_build_tree_data_parallel)
            fn = _with_bin_mask(
                make_build_tree_data_parallel(num_f, total_bins, cfg,
                                              mesh),
                total_bins)
        else:
            # serial builder under a multi-device mesh = GSPMD
            # auto-partitioning, which can partition neither Mosaic
            # kernels ("Please wrap the call in a shard_map") nor host
            # callbacks — the Pallas and native histograms are only
            # selectable single-program here; the distributed modes
            # above run them per-shard inside their explicit shard_maps
            single = _single_program(mesh)
            fn = make_build_tree(num_f, total_bins, cfg,
                                 subtract=subtract,
                                 allow_pallas=single,
                                 allow_native=single,
                                 efb_plan=efb_plan)
        return jax.jit(fn)

    if mode in ("voting", "feature") and cfg.categorical_features:
        raise NotImplementedError(
            "categorical splits are implemented for the serial/data "
            "tree learners; voting/feature parallel modes treat all "
            "features as numerical — drop categorical_features or use "
            "tree_learner='data'")
    if mode in ("voting", "feature") and any(cfg.monotone_constraints or ()):
        raise NotImplementedError(
            "monotone constraints are implemented for the serial/data "
            "tree learners; voting/feature parallel modes would silently "
            "violate them — use tree_learner='data'")
    if mode in ("voting", "feature") and cfg.extra_trees:
        raise NotImplementedError(
            "extra_trees is implemented for the serial/data tree "
            "learners — use tree_learner='data'")
    from mmlspark_tpu.models.gbdt.hist_pallas import (
        pallas_histogram_enabled,
    )
    subtract = resolve_subtract(mode, total_bins, mesh)
    # the histogram backend is chosen at trace time, so it must key the
    # compiled-builder cache or flipping env flags is silently ignored;
    # an EFB plan bakes static index maps into the trace, so its
    # fingerprint keys the cache the same way
    return _cache_put(
        _BUILDER_CACHE,
        (num_f, total_bins, cfg, mode, mesh, pallas_histogram_enabled(),
         subtract, _hist_env_key(),
         efb_plan.cache_key if efb_plan is not None else None),
        build)


def resolve_subtract(mode: str, total_bins: int, mesh=None) -> bool:
    """Histogram-subtraction default policy (LightGBM's sibling trick),
    shared by the builder cache and the out-of-core loop.

    MMLSPARK_TPU_HIST_SUB=1/0 forces it on/off. Unset, subtraction is
    ON exactly when the serial single-program builder's histogram
    resolves to the native CPU kernel, whose masked smaller-child pass
    skips rows instead of compacting them (parity pinned by
    tests/gbdt/test_hist_native.py; 2.0x fit throughput at bench shape
    vs the full pass). It stays OFF elsewhere: the XLA compaction
    gather measured slower than the full pass on XLA:CPU (1.287 vs
    1.548 Mrow-trees/s, round 4; PERF.md keeps the CPU history), and
    with the pallas kernel it is unmeasured on the chip — measure
    before defaulting there. Sharded modes never subtract (the
    compaction is data-dependent)."""
    if mode != "serial":
        return False
    raw = env_str("MMLSPARK_TPU_HIST_SUB", "").strip()
    if raw:
        return env_flag("MMLSPARK_TPU_HIST_SUB")
    return resolve_fit_formulation(total_bins, mode, mesh) == "native"


def _hist_env_key() -> tuple:
    """Trace-time histogram-policy state; every compiled-step/builder
    cache key must include it or flipping the env vars (or the native
    library's availability) between fits in one process is silently
    ignored and a cached step built under the other policy runs."""
    return (env_str("MMLSPARK_TPU_HIST_SUB", "").strip(),
            env_str("MMLSPARK_TPU_NATIVE_HIST", "").strip(),
            env_str("MMLSPARK_TPU_HIST_QUANT", "").strip(),
            env_str("MMLSPARK_TPU_HIST_SHARD", "").strip(),
            native_histogram_available())


def _resolve_metrics(cfg: TrainConfig):
    """(metric_name, [(label, fn)], higher_better, metric_kwargs)."""
    metric_name = cfg.metric or metrics_mod.default_metric(cfg.objective)
    if metric_name == "ndcg":
        positions = cfg.eval_at if isinstance(cfg.eval_at, (list, tuple)) \
            else [cfg.eval_at]
        lg = tuple(cfg.label_gain or ()) or None
        metric_list = [(f"ndcg@{p}",
                        metrics_mod.ndcg_at(int(p), label_gain=lg))
                       for p in positions]
        higher_better = True
    else:
        metric_fn, higher_better = metrics_mod.METRICS[metric_name]
        metric_list = [(metric_name, metric_fn)]
    # evaluate with the same objective params we train with
    # (TrainUtils.scala evals via the booster's own config): quantile's
    # pinball alpha must match cfg.alpha, not the metric default
    metric_kwargs = {"alpha": cfg.alpha} if metric_name == "quantile" else {}
    return metric_name, metric_list, higher_better, metric_kwargs


# ---------------------------------------------------------------------------
# Fused scan path (gbdt / goss / rf)
# ---------------------------------------------------------------------------

def _make_step_fn(num_f: int, total_bins: int, cfg: TrainConfig, k: int,
                  n_valid: int, mode: str, mesh, efb_plan=None):
    """One jitted function running ONE fused boosting iteration on device:
    gradients → tree build → raw/valid-raw updates → metric vector.

    The training rows' update is ``raw + nv[node]``: the builder routed
    exactly these rows through exactly this tree, and ``node`` is where
    each one settled. Validation rows were routed by no builder, so they
    walk the finished tree (``_make_predict_tree``). Both read
    ``nv * shrink`` through one gather and add it, so either gives the
    same sums bitwise.

    ``step(data, carry, it)`` takes the global iteration number as a
    traced scalar (so bagging refresh schedules and RNG folding don't
    recompile per iteration). Carry: (raw, valid raws, bag mask). The
    host loop dispatches steps asynchronously and never syncs inside the
    loop except for (block-wise) early-stopping checks.

    A ``lax.scan`` over iterations would be the obvious alternative, but
    the TPU backend compiles scan-of-scatter bodies pathologically
    slowly (minutes for a 20-iteration scan at depth 6); a single-step
    jit compiles in seconds and async dispatch hides the per-step
    launch cost.

    The stages carry ``jax.named_scope`` names, so that every device op
    of the compiled program says in its ``op_name`` which stage it came
    from: ``gbdt.sample``, ``gbdt.grad``, then inside the builder a
    level ``gbdt.hist`` (with the Pallas feed as ``gbdt.hist.feed``),
    ``gbdt.split``, ``gbdt.leaf``, ``gbdt.route`` (``route_level``: on an
    accelerator a loop over the level's nodes that selects each row's
    verdict with no per-row index, on the CPU a gather), and
    ``gbdt.predict`` (the raw updates: the leaf gather and, for
    validation rows, the walk), ``gbdt.metric``. Where scopes nest, the
    innermost names the op.
    They are metadata: the program and its compile-cache key are as
    without them. The jitted function stays named ``step``.
    """
    import jax
    import jax.numpy as jnp

    depth = cfg.effective_depth
    build_tree = _get_builder(num_f, total_bins, cfg, mode, mesh,
                              efb_plan=efb_plan)
    predict_tree = _make_predict_tree(depth)
    objective_fn = obj_mod.get_objective(cfg.objective)
    obj_kwargs = _objective_kwargs(cfg)
    metric_name, metric_list, _, metric_kwargs = _resolve_metrics(cfg)
    is_rf = cfg.boosting_type == "rf"
    is_goss = cfg.boosting_type == "goss"
    nl = cfg.num_leaves if cfg.num_leaves > 0 else 2 ** depth
    frac = cfg.bagging_fraction
    freq = cfg.bagging_freq
    pos_neg = (cfg.pos_bagging_fraction < 1.0
               or cfg.neg_bagging_fraction < 1.0)
    bag_active = (freq > 0 and (frac < 1.0 or pos_neg)) or is_rf
    rf_frac = frac if frac < 1.0 else 0.632

    def step(data, carry, it):
        binned, labels = data["binned"], data["labels"]
        weights, groups = data["weights"], data["groups"]
        base = data["base"]
        # seed key and learning rate ride in as traced data so sweeps
        # over them don't recompile the step
        base_key = data["key"]
        shrink = 1.0 if is_rf else data["lr"]
        n = labels.shape[0]
        rv = data["row_valid"]
        raw, vraws = carry
        # ----- sampling masks (device RNG, deterministic by seed) ----
        with jax.named_scope("gbdt.sample"):
            if bag_active:
                # key by the last refresh iteration rather than carrying the
                # mask: iterations within a bagging period draw the same
                # mask, and a resumed segment (iteration_offset) reproduces
                # it exactly
                if freq > 0:
                    ref_it = it - (it % freq)
                else:
                    ref_it = 0  # rf with no freq: one fixed bag
                kbag = jax.random.fold_in(jax.random.fold_in(
                    jax.random.fold_in(base_key, 1), cfg.bagging_seed),
                    ref_it)
                draw = jax.random.uniform(kbag, (n,))
                if pos_neg and not is_rf:
                    # per-class rates (LightGBM pos/neg_bagging_fraction)
                    thr_vec = jnp.where(labels > 0,
                                        cfg.pos_bagging_fraction,
                                        cfg.neg_bagging_fraction)
                    sample_mask = (draw < thr_vec).astype(jnp.float32) * rv
                else:
                    use_frac = rf_frac if is_rf else frac
                    sample_mask = (draw < use_frac).astype(jnp.float32) * rv
            else:
                sample_mask = rv
            if cfg.feature_fraction < 1.0:
                keep = max(1, int(round(num_f * cfg.feature_fraction)))
                kf = jax.random.fold_in(jax.random.fold_in(
                    jax.random.fold_in(base_key, 2),
                    cfg.feature_fraction_seed), it)
                perm = jax.random.permutation(kf, num_f)
                feat_mask = jnp.zeros(num_f, jnp.float32).at[perm[:keep]].set(1.0)
            else:
                feat_mask = jnp.ones(num_f, jnp.float32)

        # ----- gradients --------------------------------------------
        with jax.named_scope("gbdt.grad"):
            score_in = raw if not is_rf else jnp.full_like(raw, base)
            okw = dict(obj_kwargs)
            if cfg.objective == "lambdarank":
                okw["group_ids"] = groups
                if data.get("group_layout") is not None:
                    okw["group_layout"] = data["group_layout"]
            g, h = objective_fn(score_in, labels, weights, **okw)
            if mode == "data_sharded" and mesh is not None:
                # pin the per-round grad/hess recompute to the dp slice
                # owning the rows — the sharded histogram builder consumes
                # them shard-local, so nothing may force a gather here
                from mmlspark_tpu.parallel.mesh import row_sharded
                g = jax.lax.with_sharding_constraint(
                    g, row_sharded(mesh, g.ndim))
                h = jax.lax.with_sharding_constraint(
                    h, row_sharded(mesh, h.ndim))

            if is_goss:
                absg = jnp.abs(g) if k == 1 else jnp.sum(jnp.abs(g), axis=1)
                # padded rows are excluded from the gradient quantile
                thr = jnp.nanquantile(jnp.where(rv > 0, absg, jnp.nan),
                                      1.0 - cfg.top_rate)
                big = absg >= thr
                kg = jax.random.fold_in(jax.random.fold_in(base_key, 3), it)
                small_keep = jax.random.uniform(kg, absg.shape) < (
                    cfg.other_rate / max(1.0 - cfg.top_rate, 1e-12))
                amplify = (1.0 - cfg.top_rate) / max(cfg.other_rate, 1e-12)
                mult = jnp.where(big, 1.0, jnp.where(small_keep, amplify, 0.0))
                sample_mask = sample_mask * (mult > 0)
                gm = mult if k == 1 else mult[:, None]
                g, h = g * gm, h * gm

        # ----- one tree per class, raw updates ----------------------
        sfs, tbs, nvs, cnts, dts, bgls = [], [], [], [], [], []
        new_vraws = list(vraws)
        tkw = {}
        if data.get("hist_token") is not None:
            tkw["hist_token"] = data["hist_token"]
        if data.get("binned_hist") is not None:
            tkw["binned_hist"] = data["binned_hist"]
        for cls in range(k):
            gc = g if k == 1 else g[:, cls]
            hc = h if k == 1 else h[:, cls]
            if cfg.extra_trees or cfg.feature_fraction_by_node < 1.0:
                tkw["key"] = jax.random.fold_in(jax.random.fold_in(
                    jax.random.fold_in(base_key, 4 + cls),
                    cfg.extra_seed), it)
            sf, tb, nv, cnt, dt, bgl, node = build_tree(
                binned, gc.astype(jnp.float32), hc.astype(jnp.float32),
                sample_mask.astype(jnp.float32), feat_mask,
                jnp.int32(nl), **tkw)
            with jax.named_scope("gbdt.leaf"):
                nv = nv * shrink
            sfs.append(sf); tbs.append(tb); nvs.append(nv); cnts.append(cnt)
            dts.append(dt); bgls.append(bgl)
            with jax.named_scope("gbdt.predict"):  # the raw updates too
                pred = nv[node]
                raw = raw + pred if k == 1 else raw.at[:, cls].add(pred)
                for vi in range(n_valid):
                    vpred = predict_tree(sf, bgl, nv,
                                         data["valids"][vi]["binned"])
                    new_vraws[vi] = (
                        new_vraws[vi] + vpred if k == 1
                        else new_vraws[vi].at[:, cls].add(vpred))

        # ----- per-iteration metrics (on device) --------------------
        with jax.named_scope("gbdt.metric"):
            mvals = []
            for m_label, m_fn in metric_list:
                mkw = dict(metric_kwargs)
                if metric_name == "ndcg" and groups is not None:
                    mkw["group_ids"] = groups
                mvals.append(m_fn(raw, labels, weights, **mkw))
                for vi in range(n_valid):
                    vs = data["valids"][vi]
                    vkw = dict(metric_kwargs)
                    if metric_name == "ndcg":
                        vkw["group_ids"] = vs["groups"]
                    mvals.append(m_fn(new_vraws[vi], vs["labels"],
                                      vs["weights"], **vkw))

        ys = (jnp.stack(sfs), jnp.stack(tbs), jnp.stack(nvs),
              jnp.stack(cnts), jnp.stack(mvals).astype(jnp.float32))
        if cfg.categorical_features:
            # only categorical trees need the per-slot masks on host;
            # numerical ones are fully derivable from threshold_bin, so
            # don't retain (num_slots, B) bools per iteration for them
            ys = ys + (jnp.stack(dts), jnp.stack(bgls))
        return (raw, tuple(new_vraws)), ys


    return jax.jit(step)


def _get_step_fn(num_f, total_bins, cfg, k, n_valid, mode, mesh,
                 efb_plan=None):
    from mmlspark_tpu.models.gbdt.hist_pallas import (
        pallas_histogram_enabled,
    )

    cfg = _loop_only_normalized(cfg)
    key = (num_f, total_bins, cfg, k, n_valid, mode, mesh,
           pallas_histogram_enabled(), env_flag("MMLSPARK_TPU_HIST_SUB"),
           _hist_env_key(),
           efb_plan.cache_key if efb_plan is not None else None)
    return _cache_put(_CHUNK_CACHE, key,
                      lambda: _make_step_fn(num_f, total_bins, cfg, k,
                                            n_valid, mode, mesh,
                                            efb_plan=efb_plan))


def aot_lower_step(cfg: TrainConfig, n: int, num_f: int,
                   platform: str = "tpu",
                   rows_per_group: int = 0,
                   debug_info: bool = False,
                   valid_rows: int = 0) -> str:
    """AOT-lower ONE fused boosting step for ``platform`` and return
    its StableHLO text — the exact program ``train()`` dispatches per
    iteration, checkable on any host. Used by
    tests/parallel/test_mosaic_lowering.py to gate TPU-day risk, and
    handy on TPU day itself to inspect what XLA is given.

    ``rows_per_group``: > 0 builds lambdarank group structure (uniform
    query sizes) with the bucketed pairwise layout. ``debug_info``
    keeps the locations, which carry the ``gbdt.*`` scope of every op.
    ``valid_rows``: > 0 gives the step one validation set of that many
    rows, as ``train(valid_sets=...)`` would (not with lambdarank)."""
    import jax
    import jax.numpy as jnp

    cfg = _loop_only_normalized(cfg)
    k = cfg.num_class if cfg.objective in ("multiclass", "softmax",
                                           "multiclassova") else 1
    # the artifact must represent the TPU-day program: the lowering
    # host's default backend is cpu, which would otherwise bake the
    # host-callback native histogram into a "tpu" lowering that the
    # real TPU run (backend == tpu) never selects
    with env_override("MMLSPARK_TPU_NATIVE_HIST", "0"):
        return _aot_lower_step_inner(cfg, n, num_f, k, platform,
                                     rows_per_group, debug_info,
                                     valid_rows)


def _aot_lower_step_inner(cfg: TrainConfig, n: int, num_f: int, k: int,
                          platform: str, rows_per_group: int,
                          debug_info: bool, valid_rows: int) -> str:
    import jax
    import jax.numpy as jnp

    n_valid = 1 if valid_rows > 0 else 0
    step_fn = _get_step_fn(num_f, cfg.max_bin, cfg, k, n_valid, "serial",
                           None)
    rng = np.random.default_rng(0)
    ones = jnp.ones(n, jnp.float32)
    if cfg.objective == "lambdarank":
        if rows_per_group <= 0:
            raise ValueError("lambdarank lowering needs rows_per_group")
        from mmlspark_tpu.models.gbdt.objectives import make_group_layout
        gids = np.repeat(np.arange(n // rows_per_group + 1),
                         rows_per_group)[:n]
        groups = jnp.asarray(gids)
        group_layout = tuple((jnp.asarray(r), jnp.asarray(m))
                             for r, m in make_group_layout(gids))
        labels = jnp.asarray(rng.integers(0, 5, size=n).astype(np.float32))
    else:
        groups, group_layout = None, None
        labels = jnp.asarray(
            rng.integers(0, max(k, 2), size=n).astype(np.float32))
    data = {
        "binned": jnp.asarray(
            rng.integers(0, cfg.max_bin, size=(n, num_f)).astype(
                np.uint8 if cfg.max_bin <= 256 else np.int32)),
        "labels": labels,
        "weights": ones,
        "groups": groups,
        "group_layout": group_layout,
        "row_valid": ones,
        "base": jnp.float32(0.0),
        "key": jax.random.key(0),
        "lr": jnp.float32(0.1),
        # a validation set as train() stages one: int32 bins
        "valids": tuple({
            "binned": jnp.asarray(rng.integers(
                0, cfg.max_bin, size=(valid_rows, num_f)).astype(np.int32)),
            "labels": jnp.zeros(valid_rows, jnp.float32),
            "weights": jnp.ones(valid_rows, jnp.float32),
            "groups": None} for _ in range(n_valid)),
    }
    raw_shape = (n,) if k == 1 else (n, k)
    carry = (jnp.zeros(raw_shape, jnp.float32),
             tuple(jnp.zeros((valid_rows,) + raw_shape[1:], jnp.float32)
                   for _ in range(n_valid)))
    # step_fn is already jitted by _make_step_fn
    return step_fn.trace(data, carry, jnp.int32(0)).lower(
        lowering_platforms=(platform,)).as_text(debug_info=debug_info)


# ---------------------------------------------------------------------------
# Boosting driver
# ---------------------------------------------------------------------------

@dataclass
class TrainResult:
    booster: BoosterArrays
    evals: List[Dict[str, float]] = field(default_factory=list)
    best_iteration: int = -1
    # histogram-path provenance for this fit (the benchmark's
    # expect_hist_stats reads it, so a throughput swing is attributable
    # without rerunning): formulation, grow policy, quant mode, EFB
    # bundle counts
    hist_stats: Dict[str, object] = field(default_factory=dict)


def _rows_per_device(arr) -> List[int]:
    """Leading-axis length of each addressable shard of a device array."""
    return [int(sh.data.shape[0]) for sh in arr.addressable_shards]


def warm_start_scores(init_model: Optional[BoosterArrays],
                      x: np.ndarray,
                      offset: Optional[np.ndarray] = None
                      ) -> Optional[np.ndarray]:
    """Raw-space warm-start margins for continuing a fit on fresh data.

    A continued booster needs the previous ensemble's margin as
    ``train(init_raw=)``; computing it on the **raw** features (not bin
    ids) keeps the warm start valid even when the new data is binned
    differently — which is exactly the streaming-refresh case, where
    each refit re-fits its BinMapper on the fresh window. ``offset``
    is the optional per-row initScoreCol contribution. Returns ``None``
    when there is nothing to warm-start from (both args None)."""
    s = None if init_model is None else np.asarray(
        init_model.predict_jit()(x))
    if offset is not None:
        s = offset if s is None else s + offset
    return s


def train(binned: np.ndarray, labels: np.ndarray, cfg: TrainConfig,
          weights: Optional[np.ndarray] = None,
          group_ids: Optional[np.ndarray] = None,
          bin_upper: Optional[np.ndarray] = None,
          valid_sets: Optional[List[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]]] = None,
          init_model: Optional[BoosterArrays] = None,
          init_raw: Optional[np.ndarray] = None,
          valid_init_raws: Optional[List[np.ndarray]] = None,
          custom_objective: Optional[Callable] = None,
          mesh=None,
          callbacks: Optional[List[Callable[[int, Dict[str, float]], None]]] = None,
          measures=None, iteration_offset: int = 0) -> TrainResult:
    """Boosting loop. ``binned``: (N,F) int32 bin ids; ``bin_upper``:
    (F,B) raw-value bin upper edges (threshold materialization).

    ``valid_sets``: list of (binned_valid, labels_valid, weights_valid);
    early stopping follows TrainUtils.scala:143-169 semantics — stop when
    the first metric hasn't improved for ``early_stopping_round`` rounds,
    return the best iteration.

    ``mesh``: if given, rows are device_put sharded over the ``dp`` axis
    and XLA inserts the histogram all-reduce (data_parallel mode).

    gbdt/goss/rf run as one fused jitted step per iteration, dispatched
    asynchronously with no host syncs in the loop (iterations
    chunked only for early stopping); DART falls back to a per-iteration
    host loop because its dropped-tree set is dynamic.
    """
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core.timer import InstrumentationMeasures
    from mmlspark_tpu.parallel.mesh import replicated, row_sharded

    measures = measures if measures is not None else InstrumentationMeasures()

    n, num_f = binned.shape
    total_bins = cfg.max_bin
    k = cfg.num_class if cfg.objective in ("multiclass", "softmax",
                                           "multiclassova") else 1
    depth = cfg.effective_depth
    num_slots = 2 ** (depth + 1) - 1

    if cfg.objective == "lambdarank" and group_ids is None:
        raise ValueError("lambdarank requires group_ids")
    if (cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0) \
            and cfg.objective != "binary":
        raise ValueError(
            "pos/neg_bagging_fraction applies to the binary objective "
            "only (LightGBM semantics); got objective="
            f"{cfg.objective!r}")

    # ---- out-of-core dispatch: supported big fits stream from a spill
    # directory instead of residing on device (models/gbdt/ooc.py) ------
    ooc_mode = resolve_ooc(warn=True)
    if ooc_mode == "off":
        ooc_reason: Optional[str] = "MMLSPARK_TPU_OOC=off"
    else:
        ooc_reason = _ooc_supported(
            cfg, mesh, k=k, has_valid=bool(valid_sets),
            has_custom=custom_objective is not None,
            has_groups=group_ids is not None, total_bins=total_bins)
        want_ooc = (ooc_mode == "on"
                    or n >= env_int("MMLSPARK_TPU_OOC_ROWS", 4_000_000,
                                    minimum=1))
        if want_ooc and ooc_reason is None:
            from mmlspark_tpu.core.serialize import DiskFull
            from mmlspark_tpu.models.gbdt import ooc as ooc_mod
            try:
                return ooc_mod.train_from_binned(
                    binned, labels, cfg, weights=weights,
                    bin_upper=bin_upper,
                    init_model=init_model, init_raw=init_raw,
                    callbacks=callbacks, measures=measures,
                    iteration_offset=iteration_offset)
            except DiskFull as e:
                # the spill disk filled up, but this entry point was
                # handed the full binned matrix — the rows fit in
                # memory, so degrade to the in-core path instead of
                # killing the fit (truly larger-than-memory fits enter
                # via train_ooc directly and keep the hard error)
                from mmlspark_tpu.core.logging_utils import warn_once
                warn_once(
                    "gbdt.ooc.disk_full",
                    "out-of-core spill hit a full disk (%s); the rows "
                    "already fit in memory, so this fit continues "
                    "IN-CORE — free spill space to restore chunked "
                    "training", e)
                ooc_reason = "io.disk_full: spill write failed"
        elif want_ooc and ooc_mode == "on":
            global _WARNED_OOC_DOWNGRADE
            if not _WARNED_OOC_DOWNGRADE:
                _WARNED_OOC_DOWNGRADE = True
                import warnings
                warnings.warn(
                    f"MMLSPARK_TPU_OOC=on cannot stream this fit "
                    f"({ooc_reason}); training in-core — label A/B "
                    "measurements accordingly", stacklevel=2)
        elif ooc_reason is None:
            ooc_reason = (f"auto: {n} rows below the "
                          "MMLSPARK_TPU_OOC_ROWS threshold")

    with measures.phase("dataPreparation"):
        if init_model is not None:
            # continued training (modelString warm start): keep the old
            # model's base, fit residuals on top of its predictions
            base_score = init_model.init_score
            if init_raw is None:
                raise ValueError("warm start needs init_raw (the init "
                                 "model's raw scores on the training rows)")
        elif init_raw is not None:
            # standalone per-row init scores (LightGBM init_score):
            # boost_from_average is auto-disabled and the offset is NOT
            # recorded in the model (predict excludes it, as LightGBM)
            base_score = 0.0
        else:
            base_score = (obj_mod.init_score(cfg.objective, labels, weights)
                          if cfg.boost_from_average and cfg.objective != "lambdarank"
                          else 0.0)
        feature_mode = cfg.tree_learner == "feature" and mesh is not None
        row_valid = None
        if mesh is not None and not feature_mode:
            # row sharding needs N divisible by the dp axis: pad with
            # zero-weight rows masked out of sampling/histograms via
            # ``row_valid`` (the device analog of the reference's
            # empty-partition tolerance, BasePartitionTask.scala:134-137)
            from mmlspark_tpu.parallel.mesh import axis_size
            dp_size = axis_size(mesh, "dp")
            rem = n % dp_size
            if rem:
                pad_n = dp_size - rem
                binned = np.concatenate(
                    [binned, np.repeat(binned[-1:], pad_n, axis=0)])
                labels = np.concatenate(
                    [np.asarray(labels, np.float64), np.zeros(pad_n)])
                weights = np.concatenate(
                    [np.asarray(weights, np.float64) if weights is not None
                     else np.ones(n), np.zeros(pad_n)])
                if group_ids is not None:
                    # padded rows get their OWN group: in lambdarank a
                    # pad row sharing a real group would form valid
                    # pairs (and rank positions) with real rows even at
                    # weight 0
                    group_ids = np.concatenate(
                        [group_ids,
                         np.full(pad_n, np.max(group_ids) + 1,
                                 dtype=np.asarray(group_ids).dtype)])
                if init_raw is not None:
                    init_raw = np.concatenate(
                        [np.asarray(init_raw, np.float32).reshape(
                            (n,) if k == 1 else (n, k)),
                         np.zeros((pad_n,) if k == 1 else (pad_n, k),
                                  np.float32)])
                row_valid = np.concatenate(
                    [np.ones(n, np.float32), np.zeros(pad_n, np.float32)])
                n = n + pad_n
        # binned rows stream to device in async chunks at the narrowest
        # bin dtype (the StreamingPartitionTask micro-batch push analog);
        # uint8 widens for free in downstream gathers/index math
        from mmlspark_tpu.ops.ingest import (binned_ingest_dtype,
                                             chunked_device_put)
        ing_dtype = binned_ingest_dtype(total_bins)
        if feature_mode:
            # feature_parallel: rows replicated, features sharded on fp
            from jax.sharding import NamedSharding, PartitionSpec as P

            from mmlspark_tpu.parallel.mesh import FEATURE_AXIS
            dev_put = lambda a, nd=1: jax.device_put(a, replicated(mesh))  # noqa: E731
            binned_d = chunked_device_put(
                binned, NamedSharding(mesh, P(None, FEATURE_AXIS)),
                dtype=ing_dtype)
        else:
            dev_put = (lambda a, nd=1: jax.device_put(
                a, row_sharded(mesh, nd)) if mesh is not None
                else jnp.asarray(a))
            from mmlspark_tpu.parallel.mesh import axis_size as _axis_size
            binned_d = chunked_device_put(
                binned, row_sharded(mesh, 2) if mesh is not None else None,
                dtype=ing_dtype,
                row_multiple=_axis_size(mesh, "dp") if mesh is not None
                else 1)
        labels_d = dev_put(np.asarray(labels, dtype=np.float32))
        weights_d = None if weights is None else dev_put(
            np.asarray(weights, dtype=np.float32))
        row_valid_d = None if row_valid is None else dev_put(row_valid)

        # ---- histogram-construction acceleration (serial
        # single-program fits only) --------------------------------------
        # grow policy: leaf-wise routes through the eager host loop
        # (its frontier is dynamically shaped); unsupported configs
        # fall back to depthwise with one warning so results stay
        # honest rather than silently ignoring constraints
        grow_policy = resolve_grow_policy()
        if grow_policy == "leafwise":
            reason = _leafwise_supported(cfg, mesh)
            if reason is not None:
                global _WARNED_LEAFWISE_DOWNGRADE
                if not _WARNED_LEAFWISE_DOWNGRADE:
                    _WARNED_LEAFWISE_DOWNGRADE = True
                    import warnings
                    warnings.warn(
                        "MMLSPARK_TPU_GROW_POLICY=leafwise does not "
                        f"support {reason}; growing depthwise — label "
                        "A/B measurements accordingly", stacklevel=2)
                grow_policy = "depthwise"
        # EFB plan + host-binned token: the compiled builders take the
        # bundled matrix (or a host-registry token) as call-time data,
        # so everything here is per-fit state released in the finally
        # below. Leaf-wise histograms on the host loop's own matrix and
        # skips both.
        efb_plan = None
        hist_token_d = None
        binned_hist_d = None
        host_tokens: List[int] = []
        # resolved shard mode is recorded for EVERY fit (serial fits
        # trivially "off") so a multi-device A/B is attributable from
        # hist_stats alone; forced-on downgrades warn once inside
        # resolve_hist_shard_mode
        shard_mode, shard_reason = resolve_hist_shard_mode(cfg, mesh,
                                                           warn=True)
        tree_mode = _resolve_mode(cfg, mesh)
        hist_formulation = resolve_fit_formulation(total_bins, tree_mode,
                                                   mesh)
        from mmlspark_tpu.models.gbdt.hist_pallas import (
            HIST_PRODUCT, feed_by_path, resolve_pallas_interpret)
        # the eager loop: DART's dropped-tree set and a custom
        # objective's host code fit no fixed-shape step, and the
        # leaf-wise frontier is grown on the host
        eager_loop = (cfg.boosting_type == "dart"
                      or custom_objective is not None
                      or grow_policy == "leafwise")

        def hist_feed(f_call):
            # a feature-parallel shard holds its share of the columns;
            # leaf-wise growth asks for one node at a time
            if hist_formulation != "pallas":
                return None
            if feature_mode:
                from mmlspark_tpu.parallel.mesh import FEATURE_AXIS
                f_call //= mesh.shape[FEATURE_AXIS]
            return feed_by_path(
                [1] if grow_policy == "leafwise"
                else [2 ** d for d in range(cfg.effective_depth)], f_call)

        hist_stats: Dict[str, object] = {
            "grow_policy": grow_policy, "hist_quant": "off",
            # how the training rows' raw scores take each new tree: from
            # the slot the builder left each row in (the fused step), or
            # by a second walk of the finished tree (the eager loop)
            "raw_update": "tree_walk" if eager_loop else "builder_leaf",
            # the kernel and tree learner THIS fit resolved (not a
            # caller-side re-resolution, which disagrees whenever a mesh
            # is attached), and whether the Pallas kernel was compiled
            # by Mosaic or run through the interpreter
            "hist_formulation": hist_formulation,
            "tree_mode": tree_mode,
            "pallas_interpret": (resolve_pallas_interpret()
                                 if hist_formulation == "pallas"
                                 else None),
            # the levels of a tree by the path the Pallas kernel takes
            # at their width and the feature count of one call
            # (hist_pallas.level_feed): in place over the rows as they
            # lie, or through the sort by node
            "hist_feed": hist_feed(num_f),
            # the Pallas kernel's product of stats and one-hot: one bf16
            # pass over float32 stats in three bf16 parts
            "hist_product": (HIST_PRODUCT if hist_formulation == "pallas"
                             else None),
            # a tree's levels by the form their routing takes
            # (route_form): compare-and-select over the level's nodes,
            # or a gather a row. Leaf-wise growth routes on the host
            "route": (None if grow_policy == "leafwise"
                      else route_by_form(
                          [2 ** d for d in range(cfg.effective_depth)],
                          tree_mode)),
            # rows of the binned matrix resident on each device: N/dp
            # apiece when the ingest sharded them, nothing staged whole
            "binned_rows_per_device": _rows_per_device(binned_d),
            "hist_shard": shard_mode,
            # raw-score carry (and therefore the per-round grad/hess
            # recompute) placement: row-sharded over dp in data-parallel
            # fits, replicated/serial otherwise
            "grad_shard": ("dp" if (mesh is not None and not feature_mode)
                           else "off"),
            "efb_bundles": 0, "efb_bundled_features": 0,
            "ooc": False, "ooc_reason": ooc_reason}
        if mesh is not None and shard_reason is not None:
            hist_stats["hist_shard_reason"] = shard_reason
        if mesh is not None and resolve_hist_quant(warn=False) != "off":
            # the quantized accumulation is single-program only; sharded
            # fits (GSPMD full-psum AND the explicit builders) keep f32
            # histograms — warn once and record the honest resolution
            # instead of the old silent serial-only downgrade
            resolve_hist_quant(in_shard_map=True, warn=True)
        if (mesh is None and tree_mode == "serial"
                and grow_policy == "depthwise"):
            if not cfg.categorical_features:
                # categorical splits index per-feature bin HISTOGRAM
                # positions during the sorted scan; bundling those
                # columns would change category identity — skip
                from mmlspark_tpu.ops import efb as efb_mod
                efb_plan = efb_mod.plan_bundles(
                    np.asarray(binned), total_bins,
                    mode=efb_mod.resolve_efb())
            hist_host = None
            if efb_plan is not None:
                from mmlspark_tpu.ops import efb as efb_mod
                hist_host = efb_mod.apply_plan(np.asarray(binned),
                                               efb_plan)
            if hist_formulation == "native":
                mat = (hist_host if hist_host is not None
                       else np.asarray(binned))
                tok = _register_host_binned(
                    np.ascontiguousarray(mat, dtype=ing_dtype))
                host_tokens.append(tok)
                hist_token_d = jnp.asarray(tok, jnp.int32)
            elif hist_host is not None:
                binned_hist_d = chunked_device_put(hist_host, None,
                                                   dtype=ing_dtype)
            hist_stats["hist_quant"] = resolve_hist_quant(warn=True)
            if efb_plan is not None:
                # the kernel runs over the bundled columns
                hist_stats["hist_feed"] = hist_feed(efb_plan.n_cols)
                hist_stats["efb_bundles"] = len(efb_plan.bundles)
                hist_stats["efb_bundled_features"] = (
                    efb_plan.n_bundled_features)
    group_ids_dev = None if group_ids is None else jnp.asarray(group_ids)
    if cfg.objective == "lambdarank" and group_ids is not None:
        # host-computed padded (G, S) bucket layout, built ONCE from the
        # host array: the lambdarank pairwise work runs per group,
        # never as an (N, N) matrix
        from mmlspark_tpu.models.gbdt.objectives import make_group_layout
        group_layout = tuple(
            (jnp.asarray(r), jnp.asarray(m))
            for r, m in make_group_layout(np.asarray(group_ids)))
    else:
        group_layout = None

    # raw scores, (N,) or (N,K) — placed like the rows they score: in
    # data-parallel fits the carry is sharded over dp so each round's
    # grad/hess recompute stays on the replica owning the rows and
    # feeds the sharded histogram builder without a gather
    raw_shape = (n,) if k == 1 else (n, k)
    if init_raw is not None:
        # warm start (modelString continuation, LightGBMBase.scala:48-51,
        # where init_raw includes the old model's base score) or
        # standalone init scores (initScoreCol)
        raw = dev_put(np.asarray(init_raw, dtype=np.float32).reshape(
            raw_shape), len(raw_shape))
    else:
        raw = dev_put(np.full(raw_shape, base_score, dtype=np.float32),
                      len(raw_shape))
    hist_stats["raw_rows_per_device"] = _rows_per_device(raw)

    valid_states = []
    for vi, vset in enumerate(valid_sets or []):
        vb, vy, vw = vset[:3]
        vgroup = vset[3] if len(vset) > 3 else None
        if valid_init_raws is not None:
            vraw = jnp.asarray(np.asarray(
                valid_init_raws[vi], dtype=np.float32).reshape(
                    (vb.shape[0],) if k == 1 else (vb.shape[0], k)))
        else:
            vraw = jnp.full((vb.shape[0],) if k == 1 else (vb.shape[0], k),
                            base_score, dtype=jnp.float32)
        valid_states.append({
            "binned": jnp.asarray(vb, dtype=jnp.int32),
            "labels": jnp.asarray(vy, dtype=jnp.float32),
            "weights": None if vw is None else jnp.asarray(vw, dtype=np.float32),
            "raw": vraw,
            "group_ids": None if vgroup is None else jnp.asarray(vgroup),
        })

    metric_name, metric_list, higher_better, metric_kwargs = \
        _resolve_metrics(cfg)
    if metric_name == "ndcg":
        for vi, vs in enumerate(valid_states):
            if vs["group_ids"] is None:
                raise ValueError(
                    f"valid set {vi}: ndcg eval requires its own "
                    f"group ids (pass 4-tuples in valid_sets)")

    try:
        with resilience.fit_watchdog("gbdt.train"):
            if eager_loop:
                trees, tree_weights, evals, best_iter = _train_loop(
                    cfg, k, num_f, total_bins, depth, binned_d, labels_d,
                    weights_d, group_ids_dev, raw, valid_states,
                    custom_objective, mesh, metric_name, metric_list,
                    higher_better, metric_kwargs, base_score, callbacks,
                    measures, n, row_valid, iteration_offset,
                    group_layout=group_layout, hist_token=hist_token_d,
                    binned_hist=binned_hist_d, efb_plan=efb_plan,
                    leafwise=grow_policy == "leafwise")
            else:
                trees, tree_weights, evals, best_iter = _train_scan(
                    cfg, k, num_f, total_bins, binned_d, labels_d, weights_d,
                    group_ids_dev, raw, valid_states, mesh,
                    metric_list, higher_better, base_score, callbacks,
                    measures, row_valid_d, iteration_offset,
                    group_layout=group_layout, hist_token=hist_token_d,
                    binned_hist=binned_hist_d, efb_plan=efb_plan)
    finally:
        # the loops drain every dispatched step before returning
        # (block_until_ready / eager device_get) — except when a step
        # raised (fault injection, preemption): a histogram callback
        # still in flight then must not outlive its token, or it fails
        # with a spurious "token not registered" when the runtime
        # blocks on outstanding effects at interpreter exit
        if host_tokens:
            try:
                jax.effects_barrier()
            except Exception:
                pass  # a poisoned step must not mask the real error
        for tok in host_tokens:
            _release_host_binned(tok)
    with measures.phase("assembly"):
        booster = _assemble_booster(trees, tree_weights, cfg, k, num_f,
                                    total_bins, depth, num_slots, bin_upper,
                                    base_score, best_iter, init_model)
    return TrainResult(booster=booster, evals=evals,
                       best_iteration=best_iter, hist_stats=hist_stats)


def _assemble_booster(trees, tree_weights, cfg, k, num_f, total_bins, depth,
                      num_slots, bin_upper, base_score, best_iter,
                      init_model):
    """Pack per-tree host arrays into a BoosterArrays (shared by the
    in-core loops and the out-of-core trainer): rf weight normalization,
    early-stop truncation, raw-value thresholds from bin_upper,
    categorical bitsets, and warm-start concat."""
    trees_sf, trees_tb, trees_nv, trees_cnt, trees_dt, trees_bgl = trees

    num_trees = len(trees_sf)
    weights_arr = np.asarray(tree_weights, dtype=np.float32)
    if cfg.boosting_type == "rf" and num_trees:
        weights_arr = weights_arr / (num_trees / max(k, 1))
    if (cfg.early_stopping_round > 0 and best_iter >= 0
            and best_iter + 1 < (num_trees // max(k, 1))):
        keep = (best_iter + 1) * k
        trees_sf, trees_tb = trees_sf[:keep], trees_tb[:keep]
        trees_nv, trees_cnt = trees_nv[:keep], trees_cnt[:keep]
        trees_dt, trees_bgl = trees_dt[:keep], trees_bgl[:keep]
        weights_arr = weights_arr[:keep]

    if bin_upper is None:
        bin_upper = np.full((num_f, total_bins), np.inf)
    sf_all = np.stack(trees_sf) if trees_sf else np.full((0, num_slots), -1, np.int32)
    tb_all = np.stack(trees_tb) if trees_tb else np.zeros((0, num_slots), np.int32)
    dt_all = (np.stack(trees_dt).astype(np.int8) if trees_dt
              else np.zeros(sf_all.shape, np.int8))
    thr_val = np.where(
        sf_all >= 0,
        bin_upper[np.maximum(sf_all, 0), tb_all],
        np.inf)
    cat_bitset = None
    if cfg.categorical_features and trees_bgl:
        # bin-subset masks -> packed bitsets over raw category VALUES
        # (bin_upper holds the category id at each categorical bin), the
        # layout LightGBM model strings use (cat_threshold words)
        thr_val = np.where(dt_all == 1, np.nan, thr_val)
        bgl_all = np.stack(trees_bgl)
        node_vals = []  # (t, m, left-set category values)
        for t, m in np.argwhere(dt_all == 1):
            vals = bin_upper[sf_all[t, m], 1:][bgl_all[t, m, 1:]]
            vals = vals[np.isfinite(vals)]
            if vals.size and ((vals < 0).any()
                              or (vals != np.floor(vals)).any()):
                raise ValueError(
                    "categorical feature values must be non-negative "
                    "integers (index them first, e.g. ValueIndexer)")
            node_vals.append((t, m, vals.astype(np.int64)))
        max_val = max((int(v.max()) for _, _, v in node_vals if v.size),
                      default=0)
        if max_val >= 1 << 20:
            raise ValueError(
                f"categorical value {max_val} too large for bitset "
                f"representation; re-index categories to a dense range")
        words = max_val // 32 + 1
        cat_bitset = np.zeros((sf_all.shape[0], num_slots, words), np.uint32)
        for t, m, vals in node_vals:
            for v in vals:
                cat_bitset[t, m, v // 32] |= np.uint32(1) << np.uint32(v % 32)
    booster = BoosterArrays(
        split_feature=sf_all,
        threshold_bin=tb_all,
        threshold_value=thr_val,
        node_value=np.stack(trees_nv) if trees_nv else np.zeros((0, num_slots), np.float32),
        count=np.stack(trees_cnt) if trees_cnt else np.zeros((0, num_slots), np.float32),
        tree_weights=weights_arr,
        max_depth=depth,
        num_features=num_f,
        num_class=k,
        objective=cfg.objective,
        init_score=base_score,
        decision_type=(
            dt_all if cat_bitset is not None
            # numeric-only trees don't retain per-tree decision bits,
            # but zero-as-missing scoring needs the zero-missing stamp
            # (6 = default-left | missing_type zero) on internal nodes
            else np.where(sf_all >= 0, 6, 0).astype(np.int8)
            if cfg.zero_as_missing else None),
        cat_bitset=cat_bitset,
    )
    if init_model is not None:
        booster = BoosterArrays.concat(init_model, booster)
    return booster


def _train_scan(cfg, k, num_f, total_bins, binned_d, labels_d, weights_d,
                group_ids_dev, raw, valid_states, mesh,
                metric_list, higher_better, base_score, callbacks, measures,
                row_valid_d=None, iteration_offset=0, group_layout=None,
                hist_token=None, binned_hist=None, efb_plan=None):
    """Fused device loop: one async dispatch per iteration, zero host
    syncs inside the loop. Early stopping syncs the (tiny) metric matrix
    in blocks of ``early_stopping_round`` and truncates post hoc — trees
    don't depend on metrics, so this reproduces the per-iteration stop
    rule exactly, overshooting by at most one block of compute."""
    import jax
    import jax.numpy as jnp

    # graftsan: fresh collective/recompile log per run (keeps ranks'
    # cumulative sequence hashes comparable) BEFORE the compile caches
    # run, so their misses are counted against this run's budget
    sanitizer.reset()

    n_valid = len(valid_states)
    mode = _resolve_mode(cfg, mesh)
    step_fn = _get_step_fn(num_f, total_bins, cfg, k, n_valid, mode, mesh,
                           efb_plan=efb_plan)
    ones = jnp.ones(labels_d.shape[0], jnp.float32)
    data = {
        "binned": binned_d,
        "hist_token": hist_token,
        "binned_hist": binned_hist,
        "labels": labels_d,
        "weights": weights_d if weights_d is not None else ones,
        "groups": group_ids_dev,
        "group_layout": group_layout,
        "row_valid": row_valid_d if row_valid_d is not None else ones,
        "base": jnp.float32(base_score),
        "key": jax.random.key(cfg.seed),
        "lr": jnp.float32(cfg.learning_rate),
        "valids": tuple({
            "binned": vs["binned"],
            "labels": vs["labels"],
            "weights": (vs["weights"] if vs["weights"] is not None
                        else jnp.ones(vs["labels"].shape[0], jnp.float32)),
            "groups": vs["group_ids"],
        } for vs in valid_states),
    }
    carry = (raw, tuple(vs["raw"] for vs in valid_states))

    # entry guard: a NaN entering here would otherwise surface 100
    # iterations later as a mysteriously constant model; the dtype
    # contract pins the input widths so a config-flipped default
    # cannot silently retrain at a different precision
    sanitizer.check_finite("gbdt.train_scan.entry", data)
    sanitizer.check_dtype_contract("gbdt.train_scan.entry", data)

    # metric record layout must match the step body's stacking order
    labels_order = []
    for m_label, _ in metric_list:
        labels_order.append(f"train_{m_label}")
        for vi in range(n_valid):
            labels_order.append(f"valid{vi}_{m_label}")

    esr = cfg.early_stopping_round
    has_es = esr > 0 and n_valid > 0
    total = cfg.num_iterations
    block = max(esr, 8) if has_es else total

    outs: List[Any] = []          # device-resident per-iteration tuples
    met_host: List[np.ndarray] = []   # synced metric rows (host)
    stop_after = total            # iterations to keep (1-based)
    best_val = -np.inf if higher_better else np.inf
    best_iter, rounds_no_improve = -1, 0

    def sync_metrics_through(upto):
        """Pull metric rows [len(met_host), upto) to host in one get."""
        if upto > len(met_host):
            # host boundary of the cross-replica metric reduction: the
            # device_get below is where an allreduce failure would
            # surface, so the injection point lives here — and a hang
            # here is what the watchdog classifies as collective-stall
            fault_point("allreduce")
            prev_b = resilience.mark_boundary(
                "collective",
                lambda: f"gbdt metric sync through iter {upto}")
            try:
                fault_point("mesh.collective_hang")
                stacked = jnp.stack([outs[i][4] for i in
                                     range(len(met_host), upto)])
                rows = np.asarray(jax.device_get(stacked))
            finally:
                resilience.restore_boundary(prev_b)
            met_host.extend(rows)
            # first host sync after the reduced metrics land: guard
            # them and cross-check the collective-sequence hash here
            sanitizer.check_finite("gbdt.metrics_sync", rows)
            sanitizer.step_boundary("gbdt.metrics_sync")

    vidx = (labels_order.index(f"valid0_{metric_list[0][0]}")
            if has_es else -1)
    es_fed = 0  # iterations already fed to the stop rule

    def feed_stop_rule(upto):
        """Apply the per-iteration stop rule to synced rows [es_fed, upto);
        returns True once stopping triggers (stop_after set)."""
        nonlocal es_fed, best_val, best_iter, rounds_no_improve, stop_after
        while es_fed < upto:
            j = es_fed
            es_fed += 1
            cur = float(met_host[j][vidx])
            # TrainUtils.scala:143-169: improvement must clear the
            # tolerance (higher-better), or stay within it (lower-better)
            tol = cfg.improvement_tolerance
            improved = (cur - best_val > tol if higher_better
                        else cur - best_val < tol)
            if improved:
                best_val, best_iter, rounds_no_improve = cur, j, 0
            else:
                rounds_no_improve += 1
                if rounds_no_improve >= esr:
                    stop_after = j + 1
                    return True
        return False

    it = 0
    _clear_callback_failure()
    # what the loop dispatches, for whoever asks core.scopes.hlo_texts()
    scopes.register(step_fn, data, carry, iteration_offset)
    while it < total:
        # per-iteration injection point (host side, outside the jitted
        # step): arming a raise here is the deterministic stand-in for
        # a preempted worker mid-fit — the kill-and-resume parity test
        # interrupts exactly here and resumes from the last checkpoint
        resilience.step_start(it + iteration_offset)
        _check_callback_failure()
        fault_point("gbdt.train_step")
        fault_point("train.participant_loss")
        with measures.phase("training"):
            carry, ys = step_fn(data, carry, it + iteration_offset)
            outs.append(ys)
            it += 1
        if callbacks:
            # live per-iteration contract: callbacks force a sync each
            # iteration (opt-in cost; without callbacks the loop is
            # fully asynchronous)
            with measures.phase("training"):
                jax.block_until_ready(carry)  # attribute compute honestly
            with measures.phase("validation"):
                sync_metrics_through(it)
            record = {"iteration": it - 1}
            for mi, name in enumerate(labels_order):
                record[name] = float(met_host[it - 1][mi])
            for cb in callbacks:
                cb(it - 1, record)
        if has_es:
            # metrics already on host when callbacks ran: check every
            # iteration (no phantom work past the stop point); otherwise
            # sync in blocks and replay the rule over the new rows
            if callbacks:
                if feed_stop_rule(it):
                    break
            elif it % block == 0 or it == total:
                with measures.phase("training"):
                    jax.block_until_ready(carry)  # attribute compute honestly
                with measures.phase("validation"):
                    sync_metrics_through(it)
                if feed_stop_rule(it):
                    break
        resilience.step_end()
    _check_callback_failure()

    kept = outs[:stop_after]
    trees_sf: List[np.ndarray] = []
    trees_tb: List[np.ndarray] = []
    trees_nv: List[np.ndarray] = []
    trees_cnt: List[np.ndarray] = []
    trees_dt: List[np.ndarray] = []
    trees_bgl: List[np.ndarray] = []
    evals: List[Dict[str, float]] = []
    if not kept:  # num_iterations == 0: empty booster, no evals
        return ((trees_sf, trees_tb, trees_nv, trees_cnt, trees_dt,
                 trees_bgl), [], evals, best_iter)
    has_cat = len(kept[0]) > 5
    # the fused loop dispatches steps asynchronously, so nearly all
    # device compute lands in this drain — the watchdog times it as one
    # span (the MIN_S floor must cover it; see PARAMS.md)
    resilience.step_start("drain")
    with measures.phase("training"):
        jax.block_until_ready(carry)  # drain async dispatches
    # async dispatch: the last steps' callbacks only ran during the
    # drain, so a latched callback failure is first visible here
    _check_callback_failure()
    # jit-boundary exit guard: raw scores after the last fused step
    sanitizer.check_finite("gbdt.train_scan.exit", carry)
    sanitizer.check_dtype_contract("gbdt.train_scan.exit", carry)
    with measures.phase("validation"):
        sync_metrics_through(stop_after)
    with measures.phase("treeFetch", trees=len(kept) * k):
        # single batched transfer of all kept trees
        sf_h, tb_h, nv_h, cnt_h = jax.device_get((
            jnp.stack([o[0] for o in kept]),
            jnp.stack([o[1] for o in kept]),
            jnp.stack([o[2] for o in kept]),
            jnp.stack([o[3] for o in kept])))
        if has_cat:
            dt_h, bgl_h = jax.device_get((
                jnp.stack([o[5] for o in kept]),
                jnp.stack([o[6] for o in kept])))
    resilience.step_end()

    with measures.phase("assembly"):
        for j in range(stop_after):
            for cls in range(k):
                trees_sf.append(sf_h[j, cls])
                trees_tb.append(tb_h[j, cls])
                trees_nv.append(nv_h[j, cls])
                trees_cnt.append(cnt_h[j, cls])
                if has_cat:
                    trees_dt.append(dt_h[j, cls])
                    trees_bgl.append(bgl_h[j, cls])
            record: Dict[str, float] = {"iteration": j}
            for mi, name in enumerate(labels_order):
                record[name] = float(met_host[j][mi])
            evals.append(record)
    return ((trees_sf, trees_tb, trees_nv, trees_cnt, trees_dt, trees_bgl),
            [1.0] * len(trees_sf), evals, best_iter)


def _train_loop(cfg, k, num_f, total_bins, depth, binned_d, labels_d,
                weights_d, group_ids_dev, raw, valid_states,
                custom_objective, mesh, metric_name, metric_list,
                higher_better, metric_kwargs, base_score, callbacks,
                measures, n, row_valid=None, iteration_offset=0,
                group_layout=None, hist_token=None, binned_hist=None,
                efb_plan=None, leafwise=False):
    """Per-iteration eager host loop. Used for (a) DART, whose
    dropped-tree set is a dynamically sized subset of all prior trees
    that doesn't fit a fixed-shape compiled step, and (b) custom
    objectives, which the eager path calls with concrete arrays so
    host-side (numpy) objectives keep working. Compiled pieces are
    cached across calls."""
    import jax
    import jax.numpy as jnp

    sanitizer.reset()
    sanitizer.check_finite(
        "gbdt.train_loop.entry",
        (labels_d, weights_d, raw, row_valid))

    is_dart = cfg.boosting_type == "dart"
    is_rf = cfg.boosting_type == "rf"
    is_goss = cfg.boosting_type == "goss"

    mode = _resolve_mode(cfg, mesh)
    if leafwise:
        from mmlspark_tpu.models.gbdt.leafwise import make_build_tree_leafwise
        build_tree = make_build_tree_leafwise(num_f, total_bins, cfg)
    else:
        build_tree = _get_builder(num_f, total_bins, cfg, mode, mesh,
                                  efb_plan=efb_plan)
    predict_tree_binned = _get_predict_tree(depth)
    objective_fn = custom_objective or obj_mod.get_objective(cfg.objective)
    obj_kwargs = _objective_kwargs(cfg)
    if cfg.objective == "lambdarank":
        obj_kwargs = {
            "group_ids": group_ids_dev, "sigmoid": cfg.sigmoid,
            "truncation_level": cfg.lambdarank_truncation_level,
            "group_layout": group_layout}
        if cfg.label_gain:
            obj_kwargs["label_gain"] = tuple(cfg.label_gain)
    if custom_objective is not None:
        # the documented fobj contract is (preds, labels, weights) ->
        # (grad, hess): the named objective's kwargs must not leak in
        # (group-aware custom objectives close over their group ids)
        obj_kwargs = {}

    # offset keys the host/device RNG streams so a resumed segment
    # continues rather than replays (exact on the fused path; the eager
    # loop's host RNG re-seeds per segment)
    bag_rng = np.random.default_rng(
        cfg.seed * 1000003 + cfg.bagging_seed + iteration_offset)
    ff_rng = np.random.default_rng(
        cfg.seed * 1000003 + cfg.feature_fraction_seed + iteration_offset)
    # DART drop decisions ride a dedicated stream (LightGBM drop_seed)
    # so changing drop params never perturbs bagging/feature sampling
    drop_rng = np.random.default_rng(
        (cfg.seed + 4 if cfg.drop_seed is None else cfg.drop_seed)
        + iteration_offset)
    trees_sf, trees_tb, trees_nv, trees_cnt = [], [], [], []
    trees_dt, trees_bgl = [], []
    tree_weights: List[float] = []
    dart_tree_preds: List[Any] = []

    evals: List[Dict[str, float]] = []
    best_val = -np.inf if higher_better else np.inf
    best_iter = -1
    rounds_no_improve = 0

    rv_host = (np.ones(n, dtype=np.float32) if row_valid is None
               else np.asarray(row_valid, dtype=np.float32))
    pos_neg = (cfg.pos_bagging_fraction < 1.0
               or cfg.neg_bagging_fraction < 1.0)
    labels_host = np.asarray(labels_d) if pos_neg else None
    bag_mask = rv_host.copy()
    _clear_callback_failure()
    for it in range(cfg.num_iterations):
        # same per-iteration injection point as the fused path
        resilience.step_start(it + iteration_offset)
        _check_callback_failure()
        fault_point("gbdt.train_step")
        fault_point("train.participant_loss")
        # ----- sampling masks (host RNG, deterministic by seed) ----------
        if (cfg.bagging_freq > 0
                and (cfg.bagging_fraction < 1.0 or pos_neg)
                and it % cfg.bagging_freq == 0) or (is_rf and it == 0):
            if pos_neg and not is_rf:
                thr_vec = np.where(labels_host > 0,
                                   cfg.pos_bagging_fraction,
                                   cfg.neg_bagging_fraction)
                bag_mask = (bag_rng.random(n) < thr_vec).astype(np.float32) * rv_host
            else:
                frac = cfg.bagging_fraction if cfg.bagging_fraction < 1.0 else 0.632
                bag_mask = (bag_rng.random(n) < frac).astype(np.float32) * rv_host
        feat_mask = np.ones(num_f, dtype=np.float32)
        if cfg.feature_fraction < 1.0:
            keep = max(1, int(round(num_f * cfg.feature_fraction)))
            chosen = ff_rng.choice(num_f, size=keep, replace=False)
            feat_mask = np.zeros(num_f, dtype=np.float32)
            feat_mask[chosen] = 1.0

        # ----- dart: drop trees for this iteration's gradients -----------
        raw_for_grad = raw
        dropped: List[int] = []
        if is_dart and trees_sf and drop_rng.random() >= cfg.skip_drop:
            if cfg.uniform_drop:
                probs = np.full(len(trees_sf), cfg.drop_rate)
            else:
                # LightGBM dart.hpp: drop probability proportional to
                # tree weight, normalized to mean drop_rate
                wts = np.asarray(tree_weights, dtype=np.float64)
                mean_w = max(float(wts.mean()), 1e-12)
                probs = np.clip(cfg.drop_rate * wts / mean_w, 0.0, 1.0)
            drops = drop_rng.random(len(trees_sf)) < probs
            dropped = list(np.nonzero(drops)[0])
            if cfg.max_drop > 0 and len(dropped) > cfg.max_drop:
                dropped = sorted(drop_rng.choice(
                    dropped, size=cfg.max_drop, replace=False))
            for i in dropped:  # tree i belongs to class i % k
                contrib = dart_tree_preds[i] * tree_weights[i]
                if k == 1:
                    raw_for_grad = raw_for_grad - contrib
                else:
                    raw_for_grad = raw_for_grad.at[:, i % k].add(-contrib)

        # ----- gradients --------------------------------------------------
        with measures.phase("training"):
            score_in = raw_for_grad if not is_rf else jnp.full_like(
                raw, base_score)
            g, h = objective_fn(score_in, labels_d, weights_d,
                                **obj_kwargs)

        sample_mask = jnp.asarray(bag_mask)
        if is_goss:
            g = jnp.asarray(g)
            h = jnp.asarray(h)
            absg = jnp.abs(g) if k == 1 else jnp.sum(jnp.abs(g), axis=1)
            thr = jnp.nanquantile(
                jnp.where(jnp.asarray(rv_host) > 0, absg, jnp.nan),
                1.0 - cfg.top_rate)
            big = absg >= thr
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(cfg.seed), 3),
                it + iteration_offset)
            small_keep = jax.random.uniform(key, absg.shape) < (
                cfg.other_rate / max(1.0 - cfg.top_rate, 1e-12))
            amplify = (1.0 - cfg.top_rate) / max(cfg.other_rate, 1e-12)
            mult = jnp.where(big, 1.0, jnp.where(small_keep, amplify, 0.0))
            sample_mask = sample_mask * (mult > 0)
            gm = mult if k == 1 else mult[:, None]
            g, h = g * gm, h * gm

        # ----- one tree per class ----------------------------------------
        it_trees = []
        for cls in range(k):
            gc = g if k == 1 else g[:, cls]
            hc = h if k == 1 else h[:, cls]
            with measures.phase("training"):
                kw = {}
                if cfg.extra_trees or cfg.feature_fraction_by_node < 1.0:
                    kw["key"] = jax.random.fold_in(jax.random.fold_in(
                        jax.random.fold_in(jax.random.key(cfg.seed),
                                           4 + cls), cfg.extra_seed),
                        it + iteration_offset)
                if not leafwise:
                    if hist_token is not None:
                        kw["hist_token"] = hist_token
                    if binned_hist is not None:
                        kw["binned_hist"] = binned_hist
                # the leaf-wise builder routes on the host and returns
                # no ``node``; this loop walks every tree either way
                sf, tb, nv, cnt, dt, bgl = build_tree(
                    binned_d, jnp.asarray(gc, jnp.float32),
                    jnp.asarray(hc, jnp.float32),
                    sample_mask.astype(jnp.float32),
                    jnp.asarray(feat_mask),
                    jnp.int32(cfg.num_leaves if cfg.num_leaves > 0 else 2 ** depth),
                    **kw)[:6]
            nv = nv * (1.0 if is_rf else cfg.learning_rate)
            trees_sf.append(np.asarray(sf))
            trees_tb.append(np.asarray(tb))
            trees_nv.append(np.asarray(nv))
            trees_cnt.append(np.asarray(cnt))
            if cfg.categorical_features:
                # numerical-only masks are derivable from threshold_bin;
                # don't pull (num_slots, B) bools to host per tree
                trees_dt.append(np.asarray(dt))
                trees_bgl.append(np.asarray(bgl))
            it_trees.append((sf, bgl, nv))

        # ----- dart weight updates / raw score update ---------------------
        if dropped:
            norm = len(dropped) / (len(dropped) + 1.0)
            # scale dropped trees toward the new ensemble (per class)
            for i in dropped:
                old_w = tree_weights[i]
                tree_weights[i] = old_w * norm
                delta = dart_tree_preds[i] * (tree_weights[i] - old_w)
                if k == 1:
                    raw = raw + delta
                else:
                    raw = raw.at[:, i % k].add(delta)
            w_new = 1.0 / (len(dropped) + 1.0)
        else:
            w_new = 1.0

        for cls, (sf, bgl, nv) in enumerate(it_trees):
            with measures.phase("training"):
                pred = predict_tree_binned(sf, bgl, nv, binned_d)
            tree_weights.append(w_new)
            if is_dart:
                dart_tree_preds.append(pred)
            upd = pred * w_new
            if k == 1:
                raw = raw + upd
            else:
                raw = raw.at[:, cls].add(upd)
            for vs in valid_states:
                vpred = predict_tree_binned(sf, bgl, nv, vs["binned"]) * w_new
                vs["raw"] = (vs["raw"] + vpred if k == 1
                             else vs["raw"].at[:, cls].add(vpred))

        # ----- eval + early stopping -------------------------------------
        with measures.phase("validation"):
            # host boundary of the per-iteration metric sync (the
            # float() casts block on cross-replica reductions)
            prev_b = resilience.mark_boundary(
                "collective", lambda: f"gbdt eager metric eval iter {it}")
            fault_point("mesh.collective_hang")
            record: Dict[str, float] = {"iteration": it}
            for m_label, m_fn in metric_list:
                mkw = dict(metric_kwargs)
                if metric_name == "ndcg" and group_ids_dev is not None:
                    mkw["group_ids"] = group_ids_dev
                record[f"train_{m_label}"] = float(
                    m_fn(raw, labels_d, weights_d, **mkw))
                for vi, vs in enumerate(valid_states):
                    vkw = dict(metric_kwargs)
                    if metric_name == "ndcg":
                        vkw["group_ids"] = vs["group_ids"]
                    record[f"valid{vi}_{m_label}"] = float(
                        m_fn(vs["raw"], vs["labels"], vs["weights"], **vkw))
            evals.append(record)
            resilience.restore_boundary(prev_b)
        for cb in (callbacks or []):
            cb(it, record)

        if cfg.early_stopping_round > 0 and valid_states:
            cur = record[f"valid0_{metric_list[0][0]}"]
            # TrainUtils.scala:143-169: improvement must clear the
            # tolerance (higher-better), or stay within it (lower-better)
            tol = cfg.improvement_tolerance
            improved = (cur - best_val > tol if higher_better
                        else cur - best_val < tol)
            if improved:
                best_val, best_iter, rounds_no_improve = cur, it, 0
            else:
                rounds_no_improve += 1
                if rounds_no_improve >= cfg.early_stopping_round:
                    break
        resilience.step_end()
    # a failure on the final iteration must not be checkpointed away
    _check_callback_failure()

    return ((trees_sf, trees_tb, trees_nv, trees_cnt, trees_dt, trees_bgl),
            tree_weights, evals, best_iter)
