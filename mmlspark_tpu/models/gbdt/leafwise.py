"""Leaf-wise (best-first) tree growth.

LightGBM's native growth policy (arXiv:1706.08359 §2;
serial_tree_learner.cpp Split/BeforeTrain loop): instead of splitting
every node of a level, repeatedly split the single open leaf with the
highest gain, capped by ``num_leaves``. Depth-wise growth with the
within-level leaf budget (trainer.make_build_tree) approximates this
under a fixed-depth layout; for deep-and-narrow trees
(num_leaves << 2^max_depth) best-first allocates its leaf budget where
the gain actually is.

The frontier is a dynamically-shaped priority queue, which doesn't fit
the fixed-shape compiled builder, so this builder runs on the HOST
(routed through ``_train_loop`` like DART) and calls the level-
histogram kernels one node at a time (width=1, node membership as the
``live`` mask — the native kernel skips dead rows before touching
their bin row, so masking is the compaction). Sibling histograms come
from the subtraction trick: only the smaller child is histogrammed.

Determinism: the heap is keyed (-gain, slot), so equal gains split the
lower slot id first, and ``np.argmax`` picks the first of tied
(feature, bin) candidates — repeated fits are bit-identical for any
histogram formulation (pinned by tests/gbdt/test_leafwise.py).

Trees are emitted in the same full-layout 6-tuple contract as
``make_build_tree`` (children of slot s at 2s+1 / 2s+2), so the
booster, predictors and model export are policy-agnostic.

Unsupported configs (categorical_features, monotone_constraints,
extra_trees, feature_fraction_by_node, sharded learners) fall back to
depthwise with a warning in ``train``.
"""

from __future__ import annotations

import heapq
from typing import Any, Callable, Dict

import numpy as np

from mmlspark_tpu.models.gbdt import trainer as _trainer

_HIST1_CACHE: Dict[Any, Callable] = {}


def _get_hist1(n: int, f: int, b: int, formulation: str) -> Callable:
    """Compiled single-node histogram: full-N call with node membership
    as the live mask (static shapes — one compile per (n, f, b))."""
    import jax
    import jax.numpy as jnp

    def make():
        def h1(bn, g, hs, lv):
            local = jnp.zeros(n, jnp.int32)
            return _trainer._level_histogram(
                bn, g, hs, lv, local, 1, f, b,
                formulation=formulation)[0]
        return jax.jit(h1)

    return _trainer._cache_put(_HIST1_CACHE, (n, f, b, formulation),
                               make)


def make_build_tree_leafwise(num_features: int, total_bins: int, cfg):
    """Host best-first builder with the compiled builders' signature:
    (binned, grad, hess, valid, feat_mask, remaining_leaves, key=None)
    -> (split_feature, threshold_bin, node_value, count, decision_type,
    bin_go_left) as numpy arrays in the full heap layout."""
    import jax.numpy as jnp

    depth_cap = cfg.effective_depth
    num_slots = 2 ** (depth_cap + 1) - 1
    lam1, lam2 = float(cfg.lambda_l1), float(cfg.lambda_l2)
    min_child = float(cfg.min_data_in_leaf)
    min_hess = float(cfg.min_sum_hessian_in_leaf)
    min_gain = float(cfg.min_gain_to_split)
    num_bits = 6 if cfg.zero_as_missing else 10
    f, b = num_features, total_bins
    formulation = _trainer.resolve_histogram_formulation(
        total_bins, in_shard_map=False)

    def leaf_obj(g, h):
        g_adj = np.sign(g) * np.maximum(np.abs(g) - lam1, 0.0)
        denom = h + lam2 + 1e-30
        return -g_adj / denom, g_adj * g_adj / denom

    def best_split(hist, fmask):
        """hist (F,B,3) float64 -> (gain, feat, bin, lstats, rstats) or
        None. Mirrors the depthwise numerical scan (ordered cumsum,
        min_child/min_hess/min_gain guards, last bin excluded)."""
        cum = hist.cumsum(axis=1)
        tot = cum[:, -1:, :]
        gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
        gt, ht, ct = tot[..., 0], tot[..., 1], tot[..., 2]
        gr, hr, cr = gt - gl, ht - hl, ct - cl
        _, score_l = leaf_obj(gl, hl)
        _, score_r = leaf_obj(gr, hr)
        _, score_p = leaf_obj(gt, ht)
        gain = 0.5 * (score_l + score_r - score_p)
        ok = ((cl >= min_child) & (cr >= min_child)
              & (hl >= min_hess) & (hr >= min_hess)
              & (gain > min_gain) & (fmask[:, None] > 0))
        ok[:, -1] = False
        gain = np.where(ok, gain, -np.inf)
        fb = int(np.argmax(gain))        # first max: deterministic ties
        bg = gain.reshape(-1)[fb]
        if not np.isfinite(bg):
            return None
        feat, tbin = divmod(fb, b)
        lstats = hist[feat, :tbin + 1, :].sum(axis=0)
        rstats = hist[feat].sum(axis=0) - lstats
        return float(bg), int(feat), int(tbin), lstats, rstats

    def build_tree(binned, grad, hess, valid, feat_mask,
                   remaining_leaves, key=None):
        n = int(binned.shape[0])
        hist1 = _get_hist1(n, f, b, formulation)
        grad_j = jnp.asarray(grad, jnp.float32)
        hess_j = jnp.asarray(hess, jnp.float32)
        valid_np = np.asarray(valid, np.float32)
        fmask = np.asarray(feat_mask, np.float32)
        max_leaves = int(np.asarray(remaining_leaves))
        binned_np = np.asarray(binned)

        def node_hist(member_f32):
            h = hist1(binned, grad_j, hess_j, jnp.asarray(member_f32))
            return np.asarray(h, np.float64)

        split_feature = np.full(num_slots, -1, np.int32)
        threshold_bin = np.zeros(num_slots, np.int32)
        node_value = np.zeros(num_slots, np.float32)
        node_count = np.zeros(num_slots, np.float32)
        decision_type = np.zeros(num_slots, np.int8)
        bin_go_left = np.zeros((num_slots, b), bool)

        live = valid_np > 0
        node_of_row = np.zeros(n, np.int32)

        g64 = np.asarray(grad, np.float64)
        h64 = np.asarray(hess, np.float64)
        root_g = float((g64 * valid_np).sum())
        root_h = float((h64 * valid_np).sum())
        rv, _ = leaf_obj(np.float64(root_g), np.float64(root_h))
        if cfg.max_delta_step > 0:
            rv = np.clip(rv, -cfg.max_delta_step, cfg.max_delta_step)
        node_value[0] = rv
        node_count[0] = valid_np.sum()

        root_hist = node_hist(valid_np)
        heap = []       # (-gain, slot): slot ids break gain ties
        info = {}       # slot -> (hist, depth, feat, bin, ls, rs)
        cand = best_split(root_hist, fmask)
        if cand is not None:
            gain, feat, tbin, ls, rs = cand
            heapq.heappush(heap, (-gain, 0))
            info[0] = (root_hist, 0, feat, tbin, ls, rs)

        leaves = 1
        while heap and leaves < max_leaves:
            _, s = heapq.heappop(heap)
            hist, d, feat, tbin, ls, rs = info.pop(s)
            split_feature[s] = feat
            threshold_bin[s] = tbin
            decision_type[s] = num_bits
            bin_go_left[s] = np.arange(b) <= tbin
            lslot, rslot = 2 * s + 1, 2 * s + 2

            members = live & (node_of_row == s)
            go_left = binned_np[:, feat] <= tbin
            node_of_row[members] = np.where(go_left[members], lslot,
                                            rslot)

            lval, _ = leaf_obj(ls[0], ls[1])
            rval, _ = leaf_obj(rs[0], rs[1])
            if cfg.path_smooth > 0:
                pv = node_value[s]
                wl = ls[2] / (ls[2] + cfg.path_smooth)
                wr = rs[2] / (rs[2] + cfg.path_smooth)
                lval = lval * wl + pv * (1.0 - wl)
                rval = rval * wr + pv * (1.0 - wr)
            if cfg.max_delta_step > 0:
                lval = np.clip(lval, -cfg.max_delta_step,
                               cfg.max_delta_step)
                rval = np.clip(rval, -cfg.max_delta_step,
                               cfg.max_delta_step)
            node_value[lslot], node_value[rslot] = lval, rval
            node_count[lslot], node_count[rslot] = ls[2], rs[2]
            leaves += 1

            if d + 1 < depth_cap:
                # histogram the smaller child; sibling by subtraction
                small = lslot if ls[2] <= rs[2] else rslot
                hist_small = node_hist(
                    (live & (node_of_row == small)).astype(np.float32))
                hist_big = hist - hist_small
                # float cancellation: clamp derived hess/count for the
                # guards, as the depthwise builder does
                hist_big[..., 1] = np.maximum(hist_big[..., 1], 0.0)
                hist_big[..., 2] = np.maximum(hist_big[..., 2], 0.0)
                pair = ((lslot, hist_small) if small == lslot
                        else (lslot, hist_big),
                        (rslot, hist_small) if small == rslot
                        else (rslot, hist_big))
                for cslot, chist in pair:
                    c = best_split(chist, fmask)
                    if c is not None:
                        cgain, cfeat, cbin, cls_, crs = c
                        heapq.heappush(heap, (-cgain, cslot))
                        info[cslot] = (chist, d + 1, cfeat, cbin, cls_,
                                       crs)

        return (split_feature, threshold_bin, node_value, node_count,
                decision_type, bin_go_left)

    return build_tree
