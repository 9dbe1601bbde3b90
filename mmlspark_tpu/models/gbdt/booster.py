"""Tree-ensemble representation and batch inference.

Replaces the reference's JNI booster wrapper
(lightgbm/.../booster/LightGBMBooster.scala:212-) and its per-row
predict UDF with thread-local native buffers (BoosterHandler:56-150,
predictForMat/CSRSingleRow :520-557). Here the ensemble is a structure of
dense arrays — every tree stored in a fixed full-binary layout (node i's
children are 2i+1/2i+2) — and prediction is a jit/vmap batch traversal:
``depth`` gather steps over the whole batch, no per-row dispatch.

Layout choice: XLA wants static shapes; a full binary tree of depth D has
2^(D+1)-1 slots, so trees of any actual shape pack into the same arrays
and the traversal loop unrolls exactly D times. Sparse/degenerate trees
waste slots, not time.

Also carries model-text import/export in LightGBM's native model-string
format (the reference checkpoints via model strings:
LightGBMBooster.saveNativeModel, booster/LightGBMBooster.scala:458;
warm start via modelString, LightGBMBase.scala:48-51).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class BoosterArrays:
    """SoA ensemble. All (T, M) with M = 2^(D+1)-1 full-tree slots.

    ``split_feature < 0`` marks a leaf slot; ``node_value`` holds the
    (already shrunk) output value for leaves and the would-be output for
    internal nodes (used by Saabas-style contributions).
    """

    split_feature: np.ndarray      # (T, M) int32, -1 for leaf
    threshold_bin: np.ndarray      # (T, M) int32  (bins <= t go left)
    threshold_value: np.ndarray    # (T, M) float64 raw-value upper edge
    node_value: np.ndarray         # (T, M) float32
    count: np.ndarray              # (T, M) float32 train rows per node
    tree_weights: np.ndarray       # (T,) float32
    max_depth: int
    num_features: int
    num_class: int = 1             # trees are interleaved per class
    objective: str = "regression"
    init_score: float = 0.0
    feature_names: Optional[List[str]] = None
    # categorical splits: decision_type bit 0 set marks a node that
    # routes by set membership; cat_bitset (T, M, W) uint32 packs the
    # left-set over raw category values (LightGBM cat_threshold layout)
    decision_type: Optional[np.ndarray] = None   # (T, M) int8
    cat_bitset: Optional[np.ndarray] = None      # (T, M, W) uint32

    @property
    def num_trees(self) -> int:
        return self.split_feature.shape[0]

    @property
    def has_categorical(self) -> bool:
        return (self.decision_type is not None and self.cat_bitset is not None
                and bool((self.decision_type & 1).any()))

    def _jitted(self, name: str, maker):
        """Per-instance cache of jitted scorers — transform is called in
        loops (per minibatch / per partition analog) and must not pay XLA
        recompilation every call."""
        cache = self.__dict__.setdefault("_fn_cache", {})
        if name not in cache:
            import jax
            cache[name] = jax.jit(maker())
        return cache[name]

    def clear_jit_cache(self) -> None:
        """Drop the per-instance jitted-scorer cache (the serving
        warm/cold LRU eviction hook): compiled executables release, and
        scorers rebuild lazily on next use. The memoized eligibility
        verdicts (``supports_binned`` / ``zero_premap_mode``) stay —
        they describe the immutable arrays, not compiled artifacts."""
        self.__dict__.pop("_fn_cache", None)

    def predict_jit(self):
        return self._jitted("predict", self.predict_fn)

    def leaf_index_jit(self):
        return self._jitted("leaves", self.leaf_index_fn)

    def contrib_jit(self):
        return self._jitted("contrib", self.contrib_fn)

    def contrib_saabas_jit(self):
        return self._jitted("contrib_saabas", self.contrib_saabas_fn)

    @property
    def num_nodes(self) -> int:
        return self.split_feature.shape[1]

    @property
    def num_leaves_per_tree(self) -> np.ndarray:
        """(T,) actual leaves per tree. In the full heap layout every
        split turns one leaf into two, so leaves = splits + 1 — policy-
        agnostic: depth-wise trees report their within-level budget
        usage, leaf-wise trees (MMLSPARK_TPU_GROW_POLICY=leafwise)
        their best-first allocation against the ``num_leaves`` cap."""
        return np.asarray((self.split_feature >= 0).sum(axis=1) + 1)

    @property
    def supports_binned(self) -> bool:
        """Single source of truth for binned-scoring eligibility
        (``predict_binned_fn``'s raise-paths and the model-level
        ``binnedScoring`` gate both use it): numerical-only routing and
        valid bin thresholds. Cached — the (T, M) scan is constant per
        booster and transform runs in serving loops. The memoized
        verdict (like ``zero_premap_mode``'s) assumes the arrays are
        immutable after construction: derive modified boosters with
        ``dataclasses.replace``, never by mutating in place."""
        cached = self.__dict__.get("_supports_binned")
        if cached is None:
            cached = (not self.has_categorical
                      and not bool((self.threshold_bin[
                          self.split_feature >= 0] < 0).any()))
            self.__dict__["_supports_binned"] = cached
        return cached

    @property
    def zero_premap_mode(self) -> str:
        """How exact-0.0 inputs must be handled before binned scoring:

        - ``"none"``: no zero-as-missing nodes — bin raw values as-is.
        - ``"all_left"``: every internal node routes missing (0.0/NaN)
          left (the stamp trained zero_as_missing boosters carry,
          trainer decision bits 6) — map 0.0 -> NaN before
          ``BinMapper.transform`` so zeros enter bin 0, exactly as fit
          did.
        - ``"unsupported"``: mixed per-node zero semantics a single
          per-feature bin id cannot express — use ``predict_fn``.

        Memoized under the same immutable-after-construction assumption
        as ``supports_binned``: derive modified boosters with
        ``dataclasses.replace``, never by mutating arrays in place.
        """
        cached = self.__dict__.get("_zero_premap_mode")
        if cached is None:
            if self.decision_type is None:
                cached = "none"
            else:
                internal = self.split_feature >= 0
                dt = self.decision_type[internal]
                num_dt = dt[(dt & 1) == 0]   # numerical internal nodes
                mt1 = ((num_dt >> 2) & 3) == 1
                if not bool(mt1.any()):
                    cached = "none"
                elif bool((mt1 & ((num_dt & 2) != 0)).all()):
                    cached = "all_left"
                else:
                    cached = "unsupported"
            self.__dict__["_zero_premap_mode"] = cached
        return cached

    def _go_left_fn(self):
        """Shared per-step routing: (tree_idx, node, fx) -> bool (N,).

        Numerical nodes follow LightGBM's decision_type bits: bit 1 is
        default-left (where missing values go), bits 2-3 the missing
        type (0 = none: NaN converts to 0.0 and compares; 1 = zeros and
        NaN are missing; 2 = NaN is missing). Boosters trained without
        categorical features carry no decision_type (NaN routes left,
        matching training where the missing bin satisfies
        bin <= threshold); cat-bearing trained boosters stamp numerical
        splits with 10 (default-left, NaN missing), and imported model
        strings honor
        whatever bits they carry. Categorical nodes (bit 0): the value is
        truncated toward zero (LightGBM's static_cast<int>) and goes
        left iff its bit is set in the node's value bitset; NaN /
        negative / unseen values go right (LightGBM's unseen-category
        rule)."""
        import jax.numpy as jnp

        tv = jnp.asarray(self.threshold_value)
        dt_np = self.decision_type

        if dt_np is None:
            def go_left(tree_idx, node, fx):
                return jnp.isnan(fx) | (fx <= tv[tree_idx][node])
            return go_left

        dt = jnp.asarray(dt_np)
        has_cat = self.has_categorical
        if has_cat:
            bs = jnp.asarray(self.cat_bitset)
            w = int(self.cat_bitset.shape[2])

        def go_left(tree_idx, node, fx):
            d = dt[tree_idx][node]
            default_left = (d & 2) != 0
            mt = (d >> 2) & 3
            # missing_type none (0): NaN converts to 0.0 and compares;
            # zero (1): 0.0 and NaN are missing; nan (2): NaN is missing
            fx0 = jnp.where(jnp.isnan(fx), 0.0, fx)
            missing = jnp.where(mt == 2, jnp.isnan(fx),
                                (mt == 1) & (fx0 == 0.0))
            num_left = jnp.where(missing, default_left,
                                 fx0 <= tv[tree_idx][node])
            if not has_cat:
                return num_left
            is_cat = (d & 1) == 1
            # LightGBM's CategoricalDecision truncates toward zero
            # (static_cast<int>), so 3.7 routes as category 3; values
            # truncating below 0 (and NaN) go right.
            safe = jnp.where(jnp.isnan(fx), -1.0, fx)
            ti = jnp.trunc(safe)
            valid = (ti >= 0) & (ti < w * 32)
            vi = jnp.clip(ti, 0, w * 32 - 1).astype(jnp.int32)
            word = bs[tree_idx][node, vi >> 5]
            member = ((word >> (vi & 31).astype(jnp.uint32)) & 1) == 1
            return jnp.where(is_cat, valid & member, num_left)

        return go_left

    # -- device-side batch prediction ---------------------------------------
    def predict_fn(self):
        """Returns jittable fn: raw features (N, F) -> raw scores.

        Output shape (N,) for num_class==1 else (N, K). NaN routes left,
        matching training where the missing bin (0) satisfies bin <= t.
        """
        import jax
        import jax.numpy as jnp

        sf = jnp.asarray(self.split_feature)
        nv = jnp.asarray(self.node_value)
        tw = jnp.asarray(self.tree_weights)
        depth, k = self.max_depth, self.num_class
        route = self._go_left_fn()

        def one_tree(carry, tree_idx):
            acc, x = carry
            node = jnp.zeros(x.shape[0], dtype=jnp.int32)
            for _ in range(depth):
                feat = sf[tree_idx][node]
                is_leaf = feat < 0
                fx = jnp.take_along_axis(
                    x, jnp.maximum(feat, 0)[:, None], axis=1)[:, 0]
                go_left = route(tree_idx, node, fx)
                child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
                node = jnp.where(is_leaf, node, child)
            val = nv[tree_idx][node] * tw[tree_idx]
            cls = tree_idx % k
            acc = acc.at[:, cls].add(val)
            return (acc, x), None

        def predict(x):
            x = jnp.asarray(x)
            acc = jnp.full((x.shape[0], k), self.init_score, dtype=jnp.float32)
            (acc, _), _ = jax.lax.scan(
                one_tree, (acc, x), jnp.arange(self.num_trees, dtype=jnp.int32))
            return acc[:, 0] if k == 1 else acc

        return predict

    def predict_binned_jit(self, autocast: str = "off"):
        if autocast == "off":
            return self._jitted("predict_binned", self.predict_binned_fn)
        return self._jitted(f"predict_binned.{autocast}",
                            lambda: self.predict_binned_fn(autocast))

    def predict_binned_fn(self, autocast: str = "off"):
        """Returns jittable fn: BINNED features (N, F) small-int bin ids
        (the ``BinMapper.transform`` output the model was trained on) ->
        raw scores, identical to ``predict_fn`` on the raw features.

        The reference's inference path re-compares float thresholds per
        node (the per-row JNI UDF, booster/LightGBMBooster.scala:394,
        520-557). When the caller already holds the binned matrix —
        scoring the training frame, eval loops, or a pipeline that bins
        once upfront — routing can compare the stored ``threshold_bin``
        against small-int bin ids instead: no NaN/missing-type decode
        (the missing bin is 0, which satisfies ``bin <= t`` = route
        left, exactly as training) and the same gather count at far
        fewer bytes. Pass the matrix at the narrowest dtype
        (``ops.ingest.binned_ingest_dtype``: uint8 for <=256 bins) —
        gathers run in the input dtype, so uint8 moves 4x fewer bytes
        than the int32 ``BinMapper.transform`` default (measured ~2x
        end-to-end on CPU). Numerical splits
        only: categorical models route by raw-value bitsets, so they
        take ``predict_fn``.

        ``autocast="bf16"`` places the leaf-value table at bfloat16
        through the ``shard_rules.placement_cast`` seam (halving the
        hot gather's bytes); the per-tree contribution promotes back to
        float32 against the f32 tree weights, so accumulation stays at
        full width (GL015's contract) and only the stored leaf values
        are rounded — error is bounded by bf16's 2^-8 relative step per
        leaf, summed over the trees. ``"off"`` (the default) is
        bitwise-identical to the pre-autocast path: same closure, no
        cast, same jit cache key.
        """
        import jax
        import jax.numpy as jnp

        if autocast not in ("off", "bf16"):
            raise ValueError(
                f"predict_binned_fn: autocast={autocast!r} not in "
                f"('off', 'bf16')")
        if not self.supports_binned:
            if self.has_categorical:
                raise NotImplementedError(
                    "binned scoring routes by threshold_bin; categorical "
                    "splits route by raw-value bitset — use predict_fn")
            raise ValueError(
                "this booster has no binned thresholds (imported from a "
                "LightGBM model string, which carries raw-value "
                "thresholds only) — use predict_fn on raw features, or "
                "derive_binning() to recover a binning from the model's "
                "own splits and score binned")
        sf = jnp.asarray(self.split_feature)
        tb = jnp.asarray(self.threshold_bin)
        nv = jnp.asarray(self.node_value)
        tw = jnp.asarray(self.tree_weights)
        if autocast == "bf16":
            from mmlspark_tpu.parallel.shard_rules import placement_cast
            nv = placement_cast(nv, jnp.bfloat16)
        depth, k = self.max_depth, self.num_class

        def one_tree(carry, tree_idx):
            acc, bd = carry
            node = jnp.zeros(bd.shape[0], dtype=jnp.int32)
            for _ in range(depth):
                feat = sf[tree_idx][node]
                is_leaf = feat < 0
                fb = jnp.take_along_axis(
                    bd, jnp.maximum(feat, 0)[:, None], axis=1)[:, 0]
                # widen only the gathered column for the compare — the
                # (N, F) matrix stays in the caller's dtype so a uint8
                # input gathers 4x fewer bytes than int32 (measured
                # ~2x total on CPU at 2M x 28)
                go_left = fb.astype(jnp.int32) <= tb[tree_idx][node]
                child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
                node = jnp.where(is_leaf, node, child)
            val = nv[tree_idx][node] * tw[tree_idx]
            cls = tree_idx % k
            acc = acc.at[:, cls].add(val)
            return (acc, bd), None

        def predict_binned(binned):
            bd = jnp.asarray(binned)
            acc = jnp.full((bd.shape[0], k), self.init_score,
                           dtype=jnp.float32)
            (acc, _), _ = jax.lax.scan(
                one_tree, (acc, bd), jnp.arange(self.num_trees, dtype=jnp.int32))
            return acc[:, 0] if k == 1 else acc

        return predict_binned

    def derive_binning(self) -> "tuple[DerivedBinning, BoosterArrays]":
        """Recover a binning from the model's own split thresholds so an
        IMPORTED model string (raw-value thresholds only, threshold_bin
        stamped -1) can use the fast ``predict_binned_fn`` path.

        The per-feature sorted unique thresholds T define bins
        ``bin(x) = 1 + #{T_i < x}`` (bin 0 reserved as the always-left
        missing sentinel, mirroring trained models); a node splitting at
        T[j] gets ``threshold_bin = 1 + j``, so the binned compare
        ``bin(x) <= 1 + j  <=>  x <= T[j]`` reproduces raw routing
        exactly. Returns ``(binning, booster)`` where ``booster`` is a
        copy with ``threshold_bin`` filled.

        Missing-value semantics follow ``_go_left_fn``: NaN (and, for
        zero-as-missing nodes, exact 0.0) route per-node by
        decision_type. A single bin id can only express a PER-FEATURE
        policy, so ``DerivedBinning.transform`` maps NaN/0.0 when every
        node on that feature agrees (always-left -> bin 0, always-right
        -> past every threshold, NaN-compares-as-0.0 -> bin(0.0)) and
        raises when the model mixes directions for a feature whose
        column actually contains such values. Categorical models route
        by raw-value bitsets and are refused (same as
        ``predict_binned_fn``).
        """
        if self.has_categorical:
            raise NotImplementedError(
                "binned scoring routes by threshold_bin; categorical "
                "splits route by raw-value bitset — use predict_fn")
        import dataclasses

        thresholds: List[np.ndarray] = []
        nodes_per_feature: List[List[tuple]] = [
            [] for _ in range(self.num_features)]
        internal = self.split_feature >= 0
        for t, m in zip(*np.nonzero(internal)):
            d = int(self.decision_type[t, m]) \
                if self.decision_type is not None else None
            nodes_per_feature[int(self.split_feature[t, m])].append(
                (float(self.threshold_value[t, m]), d))
        nan_bin = np.zeros(self.num_features, dtype=np.int64)
        zero_bin = np.full(self.num_features, -1, dtype=np.int64)
        for f in range(self.num_features):
            tf = np.unique(np.asarray(
                [thr for thr, _ in nodes_per_feature[f]], dtype=np.float64))
            thresholds.append(tf)
            k = len(tf)
            # NaN policy: where does a NaN in this column have to land?
            pol = set()
            for _, d in nodes_per_feature[f]:
                if d is None:
                    pol.add("left")     # trained no-cat: NaN routes left
                else:
                    mt = (d >> 2) & 3
                    dl = (d & 2) != 0
                    # _go_left_fn: only mt==2 treats NaN as missing;
                    # mt==0 and the out-of-spec mt==3 compare NaN as
                    # 0.0, and mt==1 treats NaN (and 0.0) as missing
                    pol.add("zero" if mt in (0, 3)
                            else ("left" if dl else "right"))
            if not pol or pol == {"left"}:
                nan_bin[f] = 0
            elif pol == {"right"}:
                nan_bin[f] = k + 1
            elif pol == {"zero"}:
                nan_bin[f] = 1 + int(np.searchsorted(tf, 0.0, side="left"))
            else:
                nan_bin[f] = -1     # mixed: unsupported if NaN appears
            # zero-as-missing policy (decision_type missing_type == 1):
            # exact 0.0 routes by default direction at those nodes
            zpol = set()
            for _, d in nodes_per_feature[f]:
                if d is not None and ((d >> 2) & 3) == 1:
                    zpol.add("left" if (d & 2) != 0 else "right")
                else:
                    zpol.add("compare")
            if zpol and zpol != {"compare"}:
                if zpol == {"left"}:
                    zero_bin[f] = 0
                elif zpol == {"right"}:
                    zero_bin[f] = k + 1
                else:
                    zero_bin[f] = -2    # mixed: unsupported if 0.0 appears
        max_bin_id = max((len(t) + 1 for t in thresholds), default=1)
        binning = DerivedBinning(thresholds=thresholds, nan_bin=nan_bin,
                                 zero_bin=zero_bin,
                                 num_bins=max_bin_id + 1)
        tb = np.array(self.threshold_bin, copy=True)
        for t, m in zip(*np.nonzero(internal)):
            f = int(self.split_feature[t, m])
            tb[t, m] = 1 + int(np.searchsorted(
                thresholds[f], float(self.threshold_value[t, m]),
                side="left"))
        booster = dataclasses.replace(self, threshold_bin=tb)
        return binning, booster

    def leaf_index_fn(self):
        """(N, F) -> (N, T) final node slot per tree (predLeaf analog,
        LightGBMModelMethods.scala:13)."""
        import jax
        import jax.numpy as jnp

        sf = jnp.asarray(self.split_feature)
        depth = self.max_depth
        route = self._go_left_fn()

        def leaves(x):
            x = jnp.asarray(x)

            def one_tree(x_c, tree_idx):
                node = jnp.zeros(x_c.shape[0], dtype=jnp.int32)
                for _ in range(depth):
                    feat = sf[tree_idx][node]
                    is_leaf = feat < 0
                    fx = jnp.take_along_axis(
                        x_c, jnp.maximum(feat, 0)[:, None], axis=1)[:, 0]
                    go_left = route(tree_idx, node, fx)
                    child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
                    node = jnp.where(is_leaf, node, child)
                return x_c, node

            _, out = jax.lax.scan(one_tree, x, jnp.arange(self.num_trees, dtype=jnp.int32))
            return out.T  # (N, T)

        return leaves

    def _ancestor_tables(self):
        """Static per-slot root->slot path tables for the full-binary
        layout: (anc_node, anc_child, anc_valid, is_left) each (M, D).
        Slot s's path entry j is the split at ``anc_node[s, j]`` whose
        on-path child is ``anc_child[s, j]``; unused entries padded."""
        m, d = self.num_nodes, self.max_depth
        anc_node = np.zeros((m, d), np.int32)
        anc_child = np.zeros((m, d), np.int32)
        anc_valid = np.zeros((m, d), bool)
        for s in range(m):
            chain = []
            cur = s
            while cur > 0:
                par = (cur - 1) // 2
                chain.append((par, cur))
                cur = par
            chain.reverse()
            for j, (par, ch) in enumerate(chain):
                anc_node[s, j] = par
                anc_child[s, j] = ch
                anc_valid[s, j] = True
        is_left = anc_child == 2 * anc_node + 1
        return anc_node, anc_child, anc_valid, is_left

    def contrib_fn(self):
        """Exact path-dependent TreeSHAP contributions (N, F+1), last
        column = expected value (parity: LightGBM ``predict_contrib``
        surfaced by the reference as featuresShap,
        LightGBMBooster.scala:418).

        Leaf-wise formulation (the GPUTreeShap decomposition of
        Lundberg's EXTEND/UNWIND): for every reachable leaf, the
        root->leaf path contributes
        ``v_leaf * (o_i - z_i) * PSI_i`` to each unique path feature i,
        where o is the row's routing indicator, z the train-cover ratio,
        and PSI_i the permutation-weighted sum over subsets of the other
        path entries — the coefficients of ``prod_{j != i} (z_j + o_j t)``
        dotted with ``l!(D-1-l)!/D!``. Each leave-one-out polynomial is
        built directly by positive multiply-adds (deconvolving the full
        product by entry i is O(D) cheaper but catastrophically cancels
        in f32 once covers get small). Duplicate path features merge
        multiplicatively; padded entries are (z=1, o=1), which is
        exactly neutral under the factorial weights, so every path can
        be treated as length D. Multi-class models return per-class
        blocks ``(N, K*(F+1))`` — tree t contributes to class
        ``t % K`` — matching LightGBM predict_contrib's layout.
        """
        import jax
        import jax.numpy as jnp

        sf = jnp.asarray(self.split_feature)
        nv = jnp.asarray(self.node_value)
        ct = jnp.asarray(self.count)
        tw = jnp.asarray(self.tree_weights)
        depth, num_f = self.max_depth, self.num_features
        # NOTE: the merge loop below reuses ``k`` as an index, so the
        # class count gets an unshadowable name
        n_cls = max(self.num_class, 1)
        m = self.num_nodes
        route = self._go_left_fn()
        anc_node, anc_child, anc_valid, is_left = self._ancestor_tables()
        anc_valid_j = jnp.asarray(anc_valid)
        # permutation weights l!(D-1-l)!/D! for the fixed path length D
        import math as _math
        wgt = np.array([
            _math.factorial(lv) * _math.factorial(depth - 1 - lv)
            / _math.factorial(depth) for lv in range(depth)], np.float32)

        def contribs(x):
            x = jnp.asarray(x)
            n = x.shape[0]
            all_nodes = jnp.arange(m, dtype=jnp.int32)

            def one_tree(acc, tree_idx):
                sf_t = sf[tree_idx]
                ct_t = ct[tree_idx]
                v_t = nv[tree_idx] * tw[tree_idx]
                # row routing decision at every slot at once
                fx = jnp.take(x, jnp.maximum(sf_t, 0), axis=1)   # (N, M)
                gl = route(tree_idx, all_nodes, fx)               # (N, M)

                # path entries: feature, zero/one fractions, (M, D)
                u = [jnp.where(anc_valid_j[:, j],
                               sf_t[anc_node[:, j]], -1)
                     for j in range(depth)]
                z = [jnp.where(
                        anc_valid_j[:, j],
                        ct_t[anc_child[:, j]]
                        / jnp.maximum(ct_t[anc_node[:, j]], 1.0),
                        1.0) for j in range(depth)]
                o = [jnp.where(
                        anc_valid_j[None, :, j],
                        jnp.where(is_left[None, :, j],
                                  gl[:, anc_node[:, j]],
                                  ~gl[:, anc_node[:, j]]),
                        True).astype(jnp.float32) for j in range(depth)]

                # merge duplicate features within each path (first
                # occurrence absorbs later ones; absorbed -> neutral)
                merged = [jnp.zeros((m,), bool) for _ in range(depth)]
                for j in range(1, depth):
                    taken = jnp.zeros((m,), bool)
                    for k in range(j):
                        hit = ((u[k] == u[j]) & (u[j] >= 0)
                               & ~merged[k] & ~merged[j] & ~taken)
                        z[k] = jnp.where(hit, z[k] * z[j], z[k])
                        o[k] = jnp.where(hit[None, :], o[k] * o[j], o[k])
                        taken = taken | hit
                    z[j] = jnp.where(taken, 1.0, z[j])
                    o[j] = jnp.where(taken[None, :], 1.0, o[j])
                    merged[j] = merged[j] | taken

                # reachable real leaves and their values
                internal_ok = [jnp.where(anc_valid_j[:, j],
                                         sf_t[anc_node[:, j]] >= 0, True)
                               for j in range(depth)]
                reach = internal_ok[0]
                for j in range(1, depth):
                    reach = reach & internal_ok[j]
                leaf_mask = (reach & (sf_t < 0)).astype(jnp.float32)
                vmask = v_t * leaf_mask                           # (M,)

                # expected value: cover-weighted leaf average
                zprod = leaf_mask
                for j in range(depth):
                    zprod = zprod * z[j]
                base = jnp.sum(v_t * zprod)

                # per-entry phi via the leave-one-out path polynomial
                phi = jnp.zeros((n, num_f), jnp.float32)
                for i in range(depth):
                    coeffs = [jnp.ones((n, m), jnp.float32)]
                    for j in range(depth):
                        if j == i:
                            continue
                        nxt = []
                        for lv in range(len(coeffs) + 1):
                            term = jnp.zeros((n, m), jnp.float32)
                            if lv < len(coeffs):
                                term = term + coeffs[lv] * z[j][None, :]
                            if lv > 0:
                                term = term + coeffs[lv - 1] * o[j]
                            nxt.append(term)
                        coeffs = nxt
                    psi = coeffs[0] * wgt[0]
                    for lv in range(1, depth):
                        psi = psi + coeffs[lv] * wgt[lv]
                    amount = vmask[None, :] * (o[i] - z[i][None, :]) * psi
                    amount = amount * (u[i] >= 0)[None, :]
                    phi = phi.at[:, jnp.maximum(u[i], 0)].add(amount)

                cls = tree_idx % n_cls
                acc = acc.at[:, cls, :num_f].add(phi)
                acc = acc.at[:, cls, num_f].add(base)
                return acc, None

            acc = jnp.zeros((n, n_cls, num_f + 1), dtype=jnp.float32)
            acc = acc.at[:, :, num_f].add(self.init_score)
            acc, _ = jax.lax.scan(one_tree, acc, jnp.arange(self.num_trees, dtype=jnp.int32))
            return (acc[:, 0] if n_cls == 1
                    else acc.reshape(n, n_cls * (num_f + 1)))

        return contribs

    def contrib_saabas_fn(self):
        """Per-feature contributions, last column of each block = the
        expected value; multiclass returns per-class blocks
        ``(N, K*(F+1))`` like :meth:`contrib_fn`.

        Saabas-style path attribution: each split credits
        value(child) - value(node) to its split feature — the cheap
        single-traversal approximation kept alongside the exact
        TreeSHAP in :meth:`contrib_fn`.
        """
        import jax
        import jax.numpy as jnp

        sf = jnp.asarray(self.split_feature)
        nv = jnp.asarray(self.node_value)
        tw = jnp.asarray(self.tree_weights)
        depth, num_f = self.max_depth, self.num_features
        k = max(self.num_class, 1)
        route = self._go_left_fn()

        def contribs(x):
            x = jnp.asarray(x)
            n = x.shape[0]

            def one_tree(acc, tree_idx):
                node = jnp.zeros(n, dtype=jnp.int32)
                c = jnp.zeros((n, num_f), dtype=jnp.float32)
                base = nv[tree_idx][0]
                for _ in range(depth):
                    feat = sf[tree_idx][node]
                    is_leaf = feat < 0
                    fx = jnp.take_along_axis(
                        x, jnp.maximum(feat, 0)[:, None], axis=1)[:, 0]
                    go_left = route(tree_idx, node, fx)
                    child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
                    child = jnp.where(is_leaf, node, child)
                    delta = (nv[tree_idx][child] - nv[tree_idx][node]) * tw[tree_idx]
                    upd = jnp.where(is_leaf, 0.0, delta)
                    c = c.at[jnp.arange(n, dtype=jnp.int32), jnp.maximum(feat, 0)].add(upd)
                    node = child
                cls = tree_idx % k
                acc = acc.at[:, cls, :num_f].add(c)
                acc = acc.at[:, cls, num_f].add(base * tw[tree_idx])
                return acc, None

            acc = jnp.zeros((n, k, num_f + 1), dtype=jnp.float32)
            acc = acc.at[:, :, num_f].add(self.init_score)
            acc, _ = jax.lax.scan(one_tree, acc, jnp.arange(self.num_trees, dtype=jnp.int32))
            return (acc[:, 0] if k == 1
                    else acc.reshape(n, k * (num_f + 1)))

        return contribs

    # -- importances --------------------------------------------------------
    def feature_importances(self, importance_type: str = "split") -> np.ndarray:
        """'split' = #splits per feature; 'gain' approximated by squared
        value-delta weighted by node count (getFeatureImportances analog,
        LightGBMModelMethods.scala:13)."""
        out = np.zeros(self.num_features, dtype=np.float64)
        sf = self.split_feature
        internal = sf >= 0
        if importance_type == "split":
            np.add.at(out, sf[internal], 1.0)
            return out
        for t in range(self.num_trees):
            for m in np.nonzero(internal[t])[0]:
                left, right = 2 * m + 1, 2 * m + 2
                if right >= self.num_nodes:
                    continue
                # variance-reduction proxy for split gain
                gain = (self.count[t, left] * self.node_value[t, left] ** 2
                        + self.count[t, right] * self.node_value[t, right] ** 2
                        - self.count[t, m] * self.node_value[t, m] ** 2)
                out[sf[t, m]] += max(gain, 0.0)
        return out

    # -- LightGBM model-string interop --------------------------------------
    def save_model_string(self) -> str:
        """Serialize to LightGBM native text format (compacting the full
        binary layout into LightGBM's explicit child-pointer arrays)."""
        lines = [
            "tree",
            "version=v4",
            f"num_class={self.num_class}",
            f"num_tree_per_iteration={self.num_class}",
            "label_index=0",
            f"max_feature_idx={self.num_features - 1}",
            f"objective={self.objective}",
            "feature_names=" + " ".join(
                self.feature_names or
                [f"Column_{i}" for i in range(self.num_features)]),
            "feature_infos=" + " ".join("none" for _ in range(self.num_features)),
            "",
        ]
        for t in range(self.num_trees):
            lines.extend(self._tree_to_text(t))
            lines.append("")
        lines.append("end of trees")
        lines.append("")
        # non-standard but harmless trailer keys for lossless reload
        lines.append(f"init_score={self.init_score!r}")
        lines.append(f"max_depth_layout={self.max_depth}")
        lines.append("tree_weights=" + " ".join(repr(float(w)) for w in self.tree_weights))
        return "\n".join(lines)

    def _tree_to_text(self, t: int) -> List[str]:
        sf, tb, tv, nv, cnt = (self.split_feature[t], self.threshold_bin[t],
                               self.threshold_value[t], self.node_value[t],
                               self.count[t])
        dt_known = self.decision_type is not None
        dt = (self.decision_type[t] if dt_known
              else np.zeros_like(sf, dtype=np.int8))
        # map full-layout slots to LightGBM internal/leaf numbering (BFS)
        internal_ids: Dict[int, int] = {}
        leaf_ids: Dict[int, int] = {}
        order: List[int] = []
        stack = [0]
        while stack:
            m = stack.pop(0)
            if sf[m] >= 0:
                internal_ids[m] = len(internal_ids)
                order.append(m)
                stack.extend([2 * m + 1, 2 * m + 2])
            else:
                leaf_ids[m] = len(leaf_ids)
        n_int = len(internal_ids)

        def child_code(m: int) -> int:
            return internal_ids[m] if sf[m] >= 0 else ~leaf_ids[m]

        split_feature, threshold, left, right = [], [], [], []
        internal_value, internal_count, decision = [], [], []
        cat_boundaries: List[int] = [0]
        cat_words: List[int] = []
        for m in order:
            split_feature.append(int(sf[m]))
            is_cat = bool(dt[m] & 1)
            if is_cat:
                # categorical: threshold stores the index into
                # cat_boundaries/cat_threshold (LightGBM layout)
                words = [int(w) for w in self.cat_bitset[t, m]]
                threshold.append(float(len(cat_boundaries) - 1))
                cat_words.extend(words)
                cat_boundaries.append(len(cat_words))
                decision.append(1)
            else:
                threshold.append(float(tv[m]))
                # preserve imported bits exactly; pre-decision_type
                # boosters export 10 (default-left + NaN-missing:
                # training routes the missing bin left)
                decision.append(int(dt[m]) if dt_known else 10)
            left.append(child_code(2 * m + 1))
            right.append(child_code(2 * m + 2))
            internal_value.append(float(nv[m]))
            internal_count.append(int(cnt[m]))
        leaves = sorted(leaf_ids, key=lambda m: leaf_ids[m])
        leaf_value = [float(nv[m] * self.tree_weights[t]) for m in leaves]
        leaf_count = [int(cnt[m]) for m in leaves]
        num_cat = len(cat_boundaries) - 1
        out = [
            f"Tree={t}",
            f"num_leaves={max(len(leaves), 1)}",
            f"num_cat={num_cat}",
            "split_feature=" + " ".join(map(str, split_feature)),
            "split_gain=" + " ".join("0" for _ in range(n_int)),
            "threshold=" + " ".join(repr(v) for v in threshold),
            "decision_type=" + " ".join(map(str, decision)),
            "left_child=" + " ".join(map(str, left)),
            "right_child=" + " ".join(map(str, right)),
            "leaf_value=" + " ".join(repr(v) for v in leaf_value),
            "leaf_weight=" + " ".join("0" for _ in range(len(leaves))),
            "leaf_count=" + " ".join(map(str, leaf_count)),
            "internal_value=" + " ".join(repr(v) for v in internal_value),
            "internal_weight=" + " ".join("0" for _ in range(n_int)),
            "internal_count=" + " ".join(map(str, internal_count)),
            "is_linear=0",
            "shrinkage=1",
        ]
        if num_cat:
            out.insert(out.index("is_linear=0"),
                       "cat_boundaries=" + " ".join(map(str, cat_boundaries)))
            out.insert(out.index("is_linear=0"),
                       "cat_threshold=" + " ".join(map(str, cat_words)))
        return out

    @staticmethod
    def load_model_string(text: str) -> "BoosterArrays":
        header: Dict[str, str] = {}
        tree_blocks: List[Dict[str, str]] = []
        current: Optional[Dict[str, str]] = None
        for line in text.splitlines():
            line = line.strip()
            if not line or line == "tree":
                continue
            if line == "end of trees":
                current = None  # trailer keys belong to the header
                continue
            if line.startswith("Tree="):
                current = {}
                tree_blocks.append(current)
                continue
            if "=" in line:
                k, v = line.split("=", 1)
                (current if current is not None else header)[k] = v
        num_features = int(header["max_feature_idx"]) + 1
        num_class = int(header.get("num_class", "1"))

        # depth needed for the full layout
        def tree_depth(blk: Dict[str, str]) -> int:
            if "left_child" not in blk or not blk["left_child"].strip():
                return 1
            left = list(map(int, blk["left_child"].split()))
            right = list(map(int, blk["right_child"].split()))

            def rec(code: int) -> int:
                if code < 0:
                    return 0
                return 1 + max(rec(left[code]), rec(right[code]))

            return max(rec(0), 1)

        depth = max((tree_depth(b) for b in tree_blocks), default=1)
        if "max_depth_layout" in header:
            depth = max(depth, int(header["max_depth_layout"]))
        m_slots = 2 ** (depth + 1) - 1
        n_trees = len(tree_blocks)
        sf = np.full((n_trees, m_slots), -1, dtype=np.int32)
        # model strings carry raw-value thresholds only: stamp the bin
        # thresholds invalid (-1 routes nothing left) so predict_binned
        # refuses instead of silently mis-routing
        tb = np.full((n_trees, m_slots), -1, dtype=np.int32)
        tv = np.full((n_trees, m_slots), np.inf, dtype=np.float64)
        nv = np.zeros((n_trees, m_slots), dtype=np.float32)
        cnt = np.zeros((n_trees, m_slots), dtype=np.float32)
        weights = np.ones(n_trees, dtype=np.float32)
        if "tree_weights" in header:
            weights = np.asarray(list(map(float, header["tree_weights"].split())),
                                 dtype=np.float32)
        # size the runtime bitset: widest cat node across all trees
        max_words = 0
        for blk in tree_blocks:
            if int(blk.get("num_cat", "0")) > 0:
                bounds = list(map(int, blk["cat_boundaries"].split()))
                max_words = max(max_words,
                                max(bounds[i + 1] - bounds[i]
                                    for i in range(len(bounds) - 1)))
        # decision_type is kept for every imported model: numerical
        # nodes need their default-left / missing-type bits at predict
        dt = np.zeros((n_trees, m_slots), np.int8)
        bitset = (np.zeros((n_trees, m_slots, max_words), np.uint32)
                  if max_words else None)
        for t, blk in enumerate(tree_blocks):
            n_leaves = int(blk.get("num_leaves", "1"))
            leaf_value = list(map(float, blk["leaf_value"].split()))
            leaf_count = list(map(float, blk.get(
                "leaf_count", " ".join("0" * 1 for _ in range(n_leaves))).split())) \
                if blk.get("leaf_count") else [0.0] * n_leaves
            if n_leaves == 1 or "split_feature" not in blk or not blk["split_feature"].strip():
                nv[t, 0] = leaf_value[0] / max(weights[t], 1e-30)
                cnt[t, 0] = leaf_count[0] if leaf_count else 0
                continue
            split_feature = list(map(int, blk["split_feature"].split()))
            threshold = list(map(float, blk["threshold"].split()))
            left = list(map(int, blk["left_child"].split()))
            right = list(map(int, blk["right_child"].split()))
            internal_value = list(map(float, blk["internal_value"].split()))
            internal_count = list(map(float, blk["internal_count"].split()))
            decision = (list(map(int, blk["decision_type"].split()))
                        if blk.get("decision_type") else [2] * len(split_feature))
            cat_bounds = (list(map(int, blk["cat_boundaries"].split()))
                          if int(blk.get("num_cat", "0")) > 0 else [])
            cat_words = (list(map(int, blk["cat_threshold"].split()))
                         if cat_bounds else [])

            def place(code: int, slot: int, t=t, split_feature=split_feature,
                      threshold=threshold, left=left, right=right,
                      internal_value=internal_value,
                      internal_count=internal_count,
                      leaf_value=leaf_value, leaf_count=leaf_count,
                      decision=decision, cat_bounds=cat_bounds,
                      cat_words=cat_words):
                if code < 0:
                    leaf = ~code
                    nv[t, slot] = leaf_value[leaf] / max(weights[t], 1e-30)
                    cnt[t, slot] = leaf_count[leaf] if leaf < len(leaf_count) else 0
                    return
                sf[t, slot] = split_feature[code]
                dt[t, slot] = np.int8(decision[code])
                if decision[code] & 1:
                    cat_idx = int(threshold[code])
                    lo, hi = cat_bounds[cat_idx], cat_bounds[cat_idx + 1]
                    tv[t, slot] = np.nan
                    bitset[t, slot, :hi - lo] = np.asarray(
                        cat_words[lo:hi], dtype=np.int64).astype(np.uint32)
                else:
                    tv[t, slot] = threshold[code]
                nv[t, slot] = internal_value[code]
                cnt[t, slot] = internal_count[code]
                place(left[code], 2 * slot + 1)
                place(right[code], 2 * slot + 2)

            place(0, 0)
        return BoosterArrays(
            split_feature=sf, threshold_bin=tb, threshold_value=tv,
            node_value=nv, count=cnt, tree_weights=weights,
            max_depth=depth, num_features=num_features, num_class=num_class,
            objective=header.get("objective", "regression"),
            init_score=float(header.get("init_score", "0.0")),
            feature_names=header.get("feature_names", "").split() or None,
            decision_type=dt, cat_bitset=bitset,
        )

    def slice_iterations(self, start_iteration: int = 0,
                         num_iteration: int = -1) -> "BoosterArrays":
        """Sub-ensemble over boosting iterations [start, start+num)
        (LightGBM predict's start_iteration/num_iteration; trees are
        interleaved per class, so iteration i owns trees
        [i*K, (i+1)*K)). ``init_score`` stays included — it is a
        separate additive constant here, not part of any iteration.
        ``num_iteration <= 0`` means to the end (LightGBM predict semantics)."""
        k = max(self.num_class, 1)
        total = self.num_trees // k
        if not 0 <= start_iteration <= total:
            raise ValueError(
                f"start_iteration {start_iteration} outside [0, {total}]")
        # LightGBM predict semantics: num_iteration <= 0 selects all
        stop = (total if num_iteration <= 0
                else min(total, start_iteration + num_iteration))
        sl = slice(start_iteration * k, stop * k)
        return BoosterArrays(
            split_feature=self.split_feature[sl],
            threshold_bin=self.threshold_bin[sl],
            threshold_value=self.threshold_value[sl],
            node_value=self.node_value[sl],
            count=self.count[sl],
            tree_weights=self.tree_weights[sl],
            max_depth=self.max_depth,
            num_features=self.num_features,
            num_class=self.num_class,
            objective=self.objective,
            init_score=self.init_score,
            feature_names=self.feature_names,
            decision_type=(None if self.decision_type is None
                           else self.decision_type[sl]),
            cat_bitset=(None if self.cat_bitset is None
                        else self.cat_bitset[sl]),
        )

    @staticmethod
    def concat(a: "BoosterArrays", b: "BoosterArrays") -> "BoosterArrays":
        """Concatenate ensembles (warm-start continuation): pad both to
        the deeper full-tree layout, keep ``a``'s base/init metadata."""
        if a.num_class != b.num_class:
            raise ValueError("cannot concat boosters with different num_class")
        if a.num_features != b.num_features:
            raise ValueError("cannot concat boosters with different feature counts")
        depth = max(a.max_depth, b.max_depth)
        slots = 2 ** (depth + 1) - 1

        def pad(x: np.ndarray, fill) -> np.ndarray:
            if x.shape[1] == slots:
                return x
            out = np.full((x.shape[0], slots), fill, dtype=x.dtype)
            out[:, :x.shape[1]] = x
            return out

        dt = bitset = None
        if a.decision_type is not None or b.decision_type is not None:
            # a dt-less side's numerical splits behave as default-left
            # with NaN missing (its training routed NaN left); dt=0
            # would flip them under the dt-path routing
            def synth_dt(x):
                return np.where(x.split_feature >= 0, 10, 0).astype(np.int8)

            dt_a = (a.decision_type if a.decision_type is not None
                    else synth_dt(a))
            dt_b = (b.decision_type if b.decision_type is not None
                    else synth_dt(b))
            dt = np.concatenate([pad(dt_a, 0), pad(dt_b, 0)])
            w_a = a.cat_bitset.shape[2] if a.cat_bitset is not None else 1
            w_b = b.cat_bitset.shape[2] if b.cat_bitset is not None else 1
            words = max(w_a, w_b)
            bitset = np.zeros((dt.shape[0], slots, words), np.uint32)
            if a.cat_bitset is not None:
                bitset[:a.num_trees, :a.num_nodes, :w_a] = a.cat_bitset
            if b.cat_bitset is not None:
                bitset[a.num_trees:, :b.num_nodes, :w_b] = b.cat_bitset

        return BoosterArrays(
            split_feature=np.concatenate([pad(a.split_feature, -1),
                                          pad(b.split_feature, -1)]),
            threshold_bin=np.concatenate([pad(a.threshold_bin, 0),
                                          pad(b.threshold_bin, 0)]),
            threshold_value=np.concatenate([pad(a.threshold_value, np.inf),
                                            pad(b.threshold_value, np.inf)]),
            node_value=np.concatenate([pad(a.node_value, 0.0),
                                       pad(b.node_value, 0.0)]),
            count=np.concatenate([pad(a.count, 0.0), pad(b.count, 0.0)]),
            tree_weights=np.concatenate([a.tree_weights, b.tree_weights]),
            max_depth=depth,
            num_features=a.num_features,
            num_class=a.num_class,
            objective=b.objective,
            init_score=a.init_score,
            feature_names=a.feature_names or b.feature_names,
            decision_type=dt, cat_bitset=bitset,
        )

    # -- generic state dict (for Model persistence) -------------------------
    def state_dict(self) -> Dict[str, Any]:
        return {
            "split_feature": self.split_feature,
            "threshold_bin": self.threshold_bin,
            "threshold_value": self.threshold_value,
            "node_value": self.node_value,
            "node_count": self.count,
            "tree_weights": self.tree_weights,
            "booster_meta": {
                "max_depth": self.max_depth,
                "num_features": self.num_features,
                "num_class": self.num_class,
                "objective": self.objective,
                "init_score": self.init_score,
                "feature_names": self.feature_names,
            },
            **({"decision_type": self.decision_type,
                "cat_bitset": self.cat_bitset}
               if self.decision_type is not None else {}),
        }

    @staticmethod
    def from_state_dict(state: Dict[str, Any]) -> "BoosterArrays":
        meta = state["booster_meta"]
        return BoosterArrays(
            split_feature=np.asarray(state["split_feature"]),
            threshold_bin=np.asarray(state["threshold_bin"]),
            threshold_value=np.asarray(state["threshold_value"]),
            node_value=np.asarray(state["node_value"]),
            count=np.asarray(state["node_count"]),
            tree_weights=np.asarray(state["tree_weights"]),
            max_depth=meta["max_depth"],
            num_features=meta["num_features"],
            num_class=meta["num_class"],
            objective=meta["objective"],
            init_score=meta["init_score"],
            feature_names=meta.get("feature_names"),
            decision_type=(np.asarray(state["decision_type"])
                           if state.get("decision_type") is not None else None),
            cat_bitset=(np.asarray(state["cat_bitset"]).astype(np.uint32)
                        if state.get("cat_bitset") is not None else None),
        )


@dataclass
class DerivedBinning:
    """Per-feature threshold tables recovered from an imported model's
    splits (``BoosterArrays.derive_binning``). ``transform`` bins raw
    features for ``predict_binned_fn``: ``bin(x) = 1 + #{T_i < x}``,
    with NaN / zero-as-missing values mapped per the model's (uniform)
    per-feature policy and refused where the model mixes directions.
    """

    thresholds: List[np.ndarray]    # per feature, sorted unique float64
    nan_bin: np.ndarray             # (F,) where NaN lands; -1 = refuse
    zero_bin: np.ndarray            # (F,) where exact 0.0 lands;
                                    # -1 = compares normally, -2 = refuse
    num_bins: int                   # max bin id + 1 (dtype sizing)

    @property
    def dtype(self):
        from mmlspark_tpu.ops.ingest import binned_ingest_dtype
        return binned_ingest_dtype(self.num_bins)

    def transform(self, x: np.ndarray) -> np.ndarray:
        # Not delegated to BinMapper.transform (the native
        # mmls_bin_matrix path): that binning fixes NaN -> bin 0 and has
        # no zero-as-missing sentinel, while here both land per the
        # model's per-feature policy — the searchsorted formula below is
        # defined by derive_binning's bin(x) = 1 + #{T_i < x} contract,
        # not borrowed from BinMapper.
        x = np.asarray(x)
        n, f = x.shape
        if f != len(self.thresholds):
            raise ValueError(f"expected {len(self.thresholds)} features, "
                             f"got {f}")
        out = np.empty((n, f), dtype=self.dtype)
        for j, tf in enumerate(self.thresholds):
            col = np.asarray(x[:, j], dtype=np.float64)
            bins = 1 + np.searchsorted(tf, col, side="left")
            nan_mask = np.isnan(col)
            if nan_mask.any():
                if self.nan_bin[j] < 0:
                    raise ValueError(
                        f"feature {j}: this model mixes NaN default "
                        "directions across nodes, which a per-feature "
                        "bin id cannot express — use predict_fn for "
                        "rows with NaN in this column")
                bins[nan_mask] = self.nan_bin[j]
            if self.zero_bin[j] != -1:
                zmask = col == 0.0
                if zmask.any():
                    if self.zero_bin[j] == -2:
                        raise ValueError(
                            f"feature {j}: this model mixes "
                            "zero-as-missing directions across nodes — "
                            "use predict_fn for rows with 0.0 in this "
                            "column")
                    bins[zmask] = self.zero_bin[j]
            out[:, j] = bins
        return out
