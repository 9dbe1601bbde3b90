"""Voting-parallel and feature-parallel GBDT tree builders.

Parity: LightGBM's three distributed tree learners selected by the
``parallelism`` param (lightgbm/.../LightGBMParams.scala:25-29,
top-K constant LightGBMConstants.scala:22-24):

- ``data_parallel`` — rows sharded, FULL per-level histograms
  all-reduced. Implemented by the default builder: rows carry a ``dp``
  sharding and XLA inserts the reduction (trainer.py).
- ``voting_parallel`` — rows sharded on ``dp``, but instead of reducing
  every feature's histogram, each device VOTES for its locally top-K
  features per node; the vote tally is psum'd, the global top-2K
  candidate features are chosen, and ONLY their histograms are psum'd
  (bandwidth ∝ 2K·bins instead of F·bins).
- ``feature_parallel`` — features sharded on ``fp``; every device holds
  all rows, builds histograms for its feature slice, and the per-node
  best split is combined with an all-gather of the (tiny) per-shard
  best gains. Row routing for a winning feature owned by one shard is
  broadcast with a masked psum.

The builders return the serial builder's (make_build_tree) numerical
SoA tree arrays and, like it, ``node``: the slot each row settled in,
sharded as the rows of ``binned`` are (over ``dp``; replicated under
``fp``). ``trainer._with_bin_mask`` completes the contract, and they
plug into the same boosting loop.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from mmlspark_tpu.core.sanitizer import record_collective
from mmlspark_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS


def _leaf_objective_fns(cfg):
    import jax.numpy as jnp

    lam1, lam2 = cfg.lambda_l1, cfg.lambda_l2

    def leaf_objective(g, h):
        g_adj = jnp.sign(g) * jnp.maximum(jnp.abs(g) - lam1, 0.0)
        value = -g_adj / (h + lam2 + 1e-30)
        score = g_adj * g_adj / (h + lam2 + 1e-30)
        return value, score

    return leaf_objective


def _split_gains(hist, leaf_objective, cfg, b):
    """hist (width, f, B, 3) -> (gain (width,f,B) with -inf where invalid,
    plus cum stats for child extraction)."""
    import jax.numpy as jnp

    min_child = float(cfg.min_data_in_leaf)
    min_hess = cfg.min_sum_hessian_in_leaf
    min_gain = cfg.min_gain_to_split

    cum = jnp.cumsum(hist, axis=2)
    tot = cum[:, :, -1:, :]
    gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
    gt, ht, ct = tot[..., 0], tot[..., 1], tot[..., 2]
    gr, hr, cr = gt - gl, ht - hl, ct - cl
    _, score_l = leaf_objective(gl, hl)
    _, score_r = leaf_objective(gr, hr)
    _, score_p = leaf_objective(gt, ht)
    gain = 0.5 * (score_l + score_r - score_p)
    ok = ((cl >= min_child) & (cr >= min_child)
          & (hl >= min_hess) & (hr >= min_hess)
          & (gain > min_gain))
    ok &= jnp.arange(b, dtype=jnp.int32)[None, None, :] < b - 1
    return jnp.where(ok, gain, -jnp.inf), cum


def _check_vma(total_bins: int) -> bool:
    """shard_map's static varying-axes checker, on by default. Two
    histogram backends defeat it (checker limitations, not correctness
    issues — jax's own error message recommends this switch):

    - the pallas kernel's INTERPRET-mode discharge creates constants
      inside the manual trace that the checker refuses to mix with
      dp-varying refs, so the builders turn it off exactly when that
      kernel is selected AND the backend will interpret it (non-TPU);
      on TPU the kernel lowers opaquely through Mosaic with its output
      vma declared, so the checker stays on for the production path;
    - the native CPU kernel is a host callback whose result the
      checker may treat as axis-invariant even though each shard
      computes its own local histogram; the psum on the returned
      histogram still executes either way.
    """
    from mmlspark_tpu.models.gbdt.hist_pallas import (
        resolve_pallas_interpret)
    from mmlspark_tpu.models.gbdt.trainer import (
        resolve_histogram_formulation)
    choice = resolve_histogram_formulation(total_bins, in_shard_map=True)
    if choice == "native":
        return False
    return not (choice == "pallas" and resolve_pallas_interpret())


def _histogram(binned, grad, hess, live, local, width, f, b):
    # one shared formulation for every tree learner; these builders run
    # inside shard_map, which constrains the choice (see helper doc).
    # With MMLSPARK_TPU_PALLAS_HIST=1 this selects the pallas kernel
    # per-shard (local rows only; the psum on the returned histogram is
    # unchanged) — the multi-chip path for the flagship op.
    from mmlspark_tpu.models.gbdt.trainer import (_level_histogram,
                                                  resolve_hist_quant)

    # quantized accumulation is a serial-fit path (the psum would sum
    # per-shard dequantized f32 anyway, erasing the int32 win); resolve
    # here only so a sharded fit with HIST_QUANT set warns once that
    # the knob is being ignored rather than silently mislabeling an A/B
    resolve_hist_quant(in_shard_map=True)
    return _level_histogram(binned, grad, hess, live, local, width, f, b,
                            in_shard_map=True)


def make_build_tree_voting(num_features: int, total_bins: int, cfg,
                           mesh) -> Callable:
    """Voting-parallel builder: shard_map over ``dp``; same signature as
    the serial builder — (binned, grad, hess, valid, feat_mask,
    remaining_leaves) with ROW-SHARDED binned/grad/hess/valid. Returns
    (split_feature, threshold_bin, node_value, count, node), ``node``
    row-sharded."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.core.jax_compat import shard_map
    from mmlspark_tpu.models.gbdt.trainer import route_level

    depth = cfg.effective_depth
    num_slots = 2 ** (depth + 1) - 1
    b = total_bins
    f = num_features
    top_k = max(int(cfg.top_k), 1)
    cand = min(2 * top_k, f)  # global candidate count (top-2K merge)
    leaf_objective = _leaf_objective_fns(cfg)

    def local_fn(binned, grad, hess, valid, feat_mask, remaining_leaves):
        n = binned.shape[0]
        node = jnp.zeros(n, dtype=jnp.int32)
        done = jnp.zeros(n, dtype=jnp.bool_)
        split_feature = jnp.full(num_slots, -1, dtype=jnp.int32)
        threshold_bin = jnp.zeros(num_slots, dtype=jnp.int32)
        node_value = jnp.zeros(num_slots, dtype=jnp.float32)
        node_count = jnp.zeros(num_slots, dtype=jnp.float32)

        root = jnp.stack([jnp.sum(grad * valid), jnp.sum(hess * valid),
                          jnp.sum(valid)])
        record_collective("psum", DATA_AXIS, root.shape, root.dtype)
        root = jax.lax.psum(root, DATA_AXIS)
        rv, _ = leaf_objective(root[0], root[1])
        node_value = node_value.at[0].set(rv)
        node_count = node_count.at[0].set(root[2])
        remaining = remaining_leaves - 1

        for d in range(depth):
            level_start = 2 ** d - 1
            width = 2 ** d
            local = jnp.clip(node - level_start, 0, width - 1)
            live = (~done).astype(grad.dtype) * valid

            hist = _histogram(binned, grad, hess, live, local, width, f, b)

            # ---- local voting: top-K features by local best gain -------
            local_gain, _ = _split_gains(hist, leaf_objective, cfg, b)
            local_gain = jnp.where(feat_mask[None, :, None] > 0,
                                   local_gain, -jnp.inf)
            per_feat = jnp.max(local_gain, axis=2)          # (width, f)
            _, top_feats = jax.lax.top_k(per_feat, min(top_k, f))
            votes = jnp.sum(jax.nn.one_hot(top_feats, f), axis=1)
            record_collective("psum", DATA_AXIS, votes.shape,
                              votes.dtype)
            votes = jax.lax.psum(votes, DATA_AXIS)          # (width, f)
            # deterministic tie-break toward lower feature ids
            votes = votes - jnp.arange(f, dtype=jnp.int32)[None, :] * 1e-6
            _, cand_feats = jax.lax.top_k(votes, cand)      # (width, cand)

            # ---- reduce ONLY candidate histograms ----------------------
            hist_cand = jnp.take_along_axis(
                hist, cand_feats[:, :, None, None], axis=1)
            record_collective("psum", DATA_AXIS, hist_cand.shape,
                              hist_cand.dtype)
            hist_cand = jax.lax.psum(hist_cand, DATA_AXIS)

            gain_cand, cum_cand = _split_gains(hist_cand, leaf_objective,
                                               cfg, b)
            cand_mask = jnp.take_along_axis(
                jnp.broadcast_to(feat_mask[None, :], (width, f)),
                cand_feats, axis=1)
            gain_cand = jnp.where(cand_mask[:, :, None] > 0,
                                  gain_cand, -jnp.inf)
            flat = gain_cand.reshape(width, cand * b)
            best_cb = jnp.argmax(flat, axis=1)
            best_gain = jnp.take_along_axis(flat, best_cb[:, None], 1)[:, 0]
            best_cand = (best_cb // b).astype(jnp.int32)
            best_bin = (best_cb % b).astype(jnp.int32)
            best_feat = jnp.take_along_axis(
                cand_feats, best_cand[:, None], 1)[:, 0].astype(jnp.int32)

            can_split = jnp.isfinite(best_gain)
            order = jnp.argsort(-jnp.where(can_split, best_gain, -jnp.inf))
            rank = jnp.zeros(width, dtype=jnp.int32).at[order].set(
                jnp.arange(width, dtype=jnp.int32))
            do_split = can_split & (rank < remaining)
            remaining = remaining - jnp.sum(do_split.astype(jnp.int32))

            slots = level_start + jnp.arange(width, dtype=jnp.int32)
            split_feature = split_feature.at[slots].set(
                jnp.where(do_split, best_feat, -1))
            threshold_bin = threshold_bin.at[slots].set(
                jnp.where(do_split, best_bin, 0))

            sel = jnp.arange(width, dtype=jnp.int32)
            cum_best = cum_cand[sel, best_cand]          # (width, B, 3)
            left_stats = jnp.take_along_axis(
                cum_best, best_bin[:, None, None], axis=1)[:, 0, :]
            tot_best = cum_best[:, -1, :]
            right_stats = tot_best - left_stats
            lval, _ = leaf_objective(left_stats[:, 0], left_stats[:, 1])
            rval, _ = leaf_objective(right_stats[:, 0], right_stats[:, 1])
            lslots, rslots = 2 * slots + 1, 2 * slots + 2
            node_value = node_value.at[lslots].set(
                jnp.where(do_split, lval, 0.0))
            node_value = node_value.at[rslots].set(
                jnp.where(do_split, rval, 0.0))
            node_count = node_count.at[lslots].set(
                jnp.where(do_split, left_stats[:, 2], 0.0))
            node_count = node_count.at[rslots].set(
                jnp.where(do_split, right_stats[:, 2], 0.0))

            # ---- route local rows (all features present locally) -------
            node, done = route_level(binned, node, done, local, do_split,
                                     best_feat, best_bin)

        return split_feature, threshold_bin, node_value, node_count, node

    row = P(DATA_AXIS)
    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), row, row, row, P(), P()),
        out_specs=(P(), P(), P(), P(), row),
        check_vma=_check_vma(total_bins))


def hist_reduction_bytes(num_features: int, total_bins: int, depth: int,
                         dp: int, sharded: bool) -> int:
    """Analytic per-device histogram-reduction payload for ONE tree:
    bytes of reduced histogram each replica materializes across all
    levels (f32 stats triple per (node, feature, bin) cell), plus — in
    the sharded mode — the small winner-combine tensors (the gathered
    per-shard gains and the masked-psum broadcast of the winning
    feature/bin/child-stat tuples). This is the quantity the
    reduce-scatter drops by ~dp: the full-psum path delivers the whole
    (width, F, B, 3) tensor to every replica per level, the sharded
    path only its F/dp feature slice."""
    f_pad = ((num_features + dp - 1) // dp) * dp
    total = 0
    for d in range(depth):
        width = 2 ** d
        full = width * num_features * total_bins * 3 * 4
        if not sharded:
            total += full
            continue
        slice_bytes = width * f_pad * total_bins * 3 * 4 // dp
        combine = (dp * width * 4          # all_gather of per-shard gains
                   + 2 * width * 4         # best_feat/best_bin psums
                   + 2 * width * 3 * 4)    # left/total child-stat psums
        total += slice_bytes + combine
    return total


def make_build_tree_data_parallel(num_features: int, total_bins: int,
                                  cfg, mesh,
                                  shard_hist: bool = True) -> Callable:
    """Data-parallel builder with a reduce-scattered histogram:
    shard_map over ``dp`` with ROW-SHARDED binned/grad/hess/valid (the
    same signature as the serial builder; returns (split_feature,
    threshold_bin, node_value, count, node), ``node`` row-sharded, the
    padded rows' slots included). Instead of materializing the
    full ``(width, F, B, 3)`` reduced histogram on every replica (the
    GSPMD full-``psum`` path), the per-level histogram is
    ``psum_scatter``'d across ``dp`` so each replica receives only its
    contiguous feature slice, split gain/threshold selection runs on
    the owned slice locally, and only the winning (feature, bin, gain,
    child-stats) tuples are combined — per-chip histogram memory and
    reduction bytes drop ~dp× (the cross-replica sharded-update scheme
    of arXiv:2004.13336 applied to histogram reduction).

    ``shard_hist=False`` builds the explicit full-``psum`` twin — same
    per-shard histogram partials, full reduction, full local selection
    — used by the parity tests to pin the reduce-scatter path bitwise
    against the full reduction.

    Bitwise contract with the serial builder: the split-selection math
    below mirrors the serial numerical path op-for-op (cumsum gains,
    masked-sum child stats, first-max argmax tie-break, path_smooth /
    max_delta_step handling), and features are sharded in contiguous
    ascending slices so the cross-shard winner combine (lowest shard
    wins ties, first flat index within a shard) reproduces the serial
    flat argmax exactly. Features are zero-padded to a multiple of dp;
    padded columns carry zero stats and a zeroed feat_mask, so they
    never win. Unsupported configs (categorical/monotone/extra_trees/
    per-node feature sampling) are screened by
    ``trainer._hist_shard_supported``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.core.jax_compat import shard_map
    from mmlspark_tpu.models.gbdt.trainer import route_level
    from mmlspark_tpu.parallel.mesh import axis_size

    depth = cfg.effective_depth
    num_slots = 2 ** (depth + 1) - 1
    b = total_bins
    f = num_features
    dp = axis_size(mesh, DATA_AXIS)
    f_pad = ((f + dp - 1) // dp) * dp
    f_loc = f_pad // dp
    leaf_objective = _leaf_objective_fns(cfg)
    path_smooth = float(cfg.path_smooth)
    max_delta_step = float(cfg.max_delta_step)

    def _clip_delta(v):
        if max_delta_step > 0:
            return jnp.clip(v, -max_delta_step, max_delta_step)
        return v

    # the reduction + split-selection step is chosen HERE, outside the
    # traced body, so every rank traces one unconditional collective
    # sequence (GL006: no collectives under a branch)

    def _sharded_select(hist, feat_mask, shard, width):
        # ---- reduce-scatter: each replica receives ONLY its feature
        # slice of the summed histogram -------------------------------
        feat_off = shard * f_loc
        own_ids = feat_off + jnp.arange(f_loc, dtype=jnp.int32)
        # owned-slice feat mask: zero past F, so padded columns (and
        # per-tree-masked features) never win
        own_mask = jnp.where(own_ids < f,
                             feat_mask[jnp.minimum(own_ids, f - 1)], 0.0)
        hist_p = jnp.pad(hist, ((0, 0), (0, f_pad - f), (0, 0), (0, 0)))
        record_collective("psum_scatter", DATA_AXIS, hist_p.shape,
                          hist_p.dtype)
        hist_loc = jax.lax.psum_scatter(
            hist_p, DATA_AXIS, scatter_dimension=1, tiled=True)

        # ---- owned-slice split selection (serial math on the slice;
        # first-max flat argmax within the slice) ---------------------
        gain, _ = _split_gains(hist_loc, leaf_objective, cfg, b)
        gain = jnp.where(own_mask[None, :, None] > 0, gain, -jnp.inf)
        flat = gain.reshape(width, f_loc * b)
        loc_fb = jnp.argmax(flat, axis=1)
        loc_gain = jnp.take_along_axis(flat, loc_fb[:, None], 1)[:, 0]
        loc_feat = (loc_fb // b).astype(jnp.int32) + feat_off
        loc_bin = (loc_fb % b).astype(jnp.int32)

        # ---- combine per-shard bests: slices are ascending, so argmax
        # over shards (first max) == the serial flat argmax -----------
        record_collective("all_gather", DATA_AXIS, loc_gain.shape,
                          loc_gain.dtype)
        gains_all = jax.lax.all_gather(loc_gain, DATA_AXIS)
        winner = jnp.argmax(gains_all, axis=0)              # (width,)
        best_gain = jnp.max(gains_all, axis=0)
        i_am_winner = winner == shard
        zero = jnp.zeros_like(loc_feat)
        record_collective("psum", DATA_AXIS, loc_feat.shape,
                          loc_feat.dtype)
        record_collective("psum", DATA_AXIS, loc_bin.shape,
                          loc_bin.dtype)
        best_feat = jax.lax.psum(
            jnp.where(i_am_winner, loc_feat, zero), DATA_AXIS)
        best_bin = jax.lax.psum(
            jnp.where(i_am_winner, loc_bin, zero), DATA_AXIS)

        # ---- child stats: winner supplies (serial masked-sum
        # formulation), masked psums broadcast ------------------------
        sel = jnp.arange(width, dtype=jnp.int32)
        loc_best_idx = (loc_fb // b).astype(jnp.int32)
        hist_best = hist_loc[sel, loc_best_idx]      # (width, B, 3)
        bin_ids = jnp.arange(b, dtype=jnp.int32)
        left_mask = bin_ids[None, :] <= loc_bin[:, None]
        left_loc = jnp.sum(hist_best * left_mask[..., None], axis=1)
        tot_loc = jnp.sum(hist_best, axis=1)
        record_collective("psum", DATA_AXIS, left_loc.shape,
                          left_loc.dtype)
        record_collective("psum", DATA_AXIS, tot_loc.shape,
                          tot_loc.dtype)
        left_stats = jax.lax.psum(
            jnp.where(i_am_winner[:, None], left_loc, 0.0), DATA_AXIS)
        tot_stats = jax.lax.psum(
            jnp.where(i_am_winner[:, None], tot_loc, 0.0), DATA_AXIS)
        return best_feat, best_bin, best_gain, left_stats, tot_stats

    def _full_select(hist, feat_mask, shard, width):
        # full-psum twin: every replica reduces the whole histogram and
        # selects identically (serial math on the full tensor)
        del shard
        record_collective("psum", DATA_AXIS, hist.shape, hist.dtype)
        hist_full = jax.lax.psum(hist, DATA_AXIS)
        gain, _ = _split_gains(hist_full, leaf_objective, cfg, b)
        gain = jnp.where(feat_mask[None, :, None] > 0, gain, -jnp.inf)
        flat = gain.reshape(width, f * b)
        best_fb = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best_fb[:, None], 1)[:, 0]
        best_feat = (best_fb // b).astype(jnp.int32)
        best_bin = (best_fb % b).astype(jnp.int32)
        sel = jnp.arange(width, dtype=jnp.int32)
        hist_best = hist_full[sel, best_feat]        # (width, B, 3)
        bin_ids = jnp.arange(b, dtype=jnp.int32)
        left_mask = bin_ids[None, :] <= best_bin[:, None]
        left_stats = jnp.sum(hist_best * left_mask[..., None], axis=1)
        tot_stats = jnp.sum(hist_best, axis=1)
        return best_feat, best_bin, best_gain, left_stats, tot_stats

    select = _sharded_select if shard_hist else _full_select

    def local_fn(binned, grad, hess, valid, feat_mask, remaining_leaves):
        n = binned.shape[0]
        shard = jax.lax.axis_index(DATA_AXIS)

        node = jnp.zeros(n, dtype=jnp.int32)
        done = jnp.zeros(n, dtype=jnp.bool_)
        split_feature = jnp.full(num_slots, -1, dtype=jnp.int32)
        threshold_bin = jnp.zeros(num_slots, dtype=jnp.int32)
        node_value = jnp.zeros(num_slots, dtype=jnp.float32)
        node_count = jnp.zeros(num_slots, dtype=jnp.float32)

        root = jnp.stack([jnp.sum(grad * valid), jnp.sum(hess * valid),
                          jnp.sum(valid)])
        record_collective("psum", DATA_AXIS, root.shape, root.dtype)
        root = jax.lax.psum(root, DATA_AXIS)
        rv, _ = leaf_objective(root[0], root[1])
        node_value = node_value.at[0].set(_clip_delta(rv))
        node_count = node_count.at[0].set(root[2])
        remaining = remaining_leaves - 1

        for d in range(depth):
            level_start = 2 ** d - 1
            width = 2 ** d
            local = jnp.clip(node - level_start, 0, width - 1)
            live = (~done).astype(grad.dtype) * valid

            hist = _histogram(binned, grad, hess, live, local, width, f, b)

            (best_feat, best_bin, best_gain,
             left_stats, tot_stats) = select(hist, feat_mask, shard,
                                             width)
            right_stats = tot_stats - left_stats

            can_split = jnp.isfinite(best_gain)
            order = jnp.argsort(-jnp.where(can_split, best_gain, -jnp.inf))
            rank = jnp.zeros(width, dtype=jnp.int32).at[order].set(
                jnp.arange(width, dtype=jnp.int32))
            do_split = can_split & (rank < remaining)
            remaining = remaining - jnp.sum(do_split.astype(jnp.int32))

            slots = level_start + jnp.arange(width, dtype=jnp.int32)
            split_feature = split_feature.at[slots].set(
                jnp.where(do_split, best_feat, -1))
            threshold_bin = threshold_bin.at[slots].set(
                jnp.where(do_split, best_bin, 0))

            lval, _ = leaf_objective(left_stats[:, 0], left_stats[:, 1])
            rval, _ = leaf_objective(right_stats[:, 0], right_stats[:, 1])
            if path_smooth > 0:
                pv = node_value[slots]
                wl = left_stats[:, 2] / (left_stats[:, 2] + path_smooth)
                wr = right_stats[:, 2] / (right_stats[:, 2] + path_smooth)
                lval = lval * wl + pv * (1.0 - wl)
                rval = rval * wr + pv * (1.0 - wr)
            lval = _clip_delta(lval)
            rval = _clip_delta(rval)
            lslots, rslots = 2 * slots + 1, 2 * slots + 2
            node_value = node_value.at[lslots].set(
                jnp.where(do_split, lval, 0.0))
            node_value = node_value.at[rslots].set(
                jnp.where(do_split, rval, 0.0))
            node_count = node_count.at[lslots].set(
                jnp.where(do_split, left_stats[:, 2], 0.0))
            node_count = node_count.at[rslots].set(
                jnp.where(do_split, right_stats[:, 2], 0.0))

            # ---- route local rows (all features present locally) -------
            node, done = route_level(binned, node, done, local, do_split,
                                     best_feat, best_bin)

        # every shard computed identical tree state (all cross-shard
        # values went through psum/all_gather); pmax is an identity that
        # marks them dp-invariant so out_specs=P() typechecks
        for v in (split_feature, threshold_bin, node_value, node_count):
            record_collective("pmax", DATA_AXIS, v.shape, v.dtype)
        return tuple(jax.lax.pmax(v, DATA_AXIS) for v in
                     (split_feature, threshold_bin, node_value,
                      node_count)) + (node,)

    row = P(DATA_AXIS)
    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), row, row, row, P(), P()),
        out_specs=(P(), P(), P(), P(), row),
        check_vma=_check_vma(total_bins))


def make_build_tree_feature_parallel(num_features: int, total_bins: int,
                                     cfg, mesh) -> Callable:
    """Feature-parallel builder: shard_map over ``fp``; binned and
    feat_mask are FEATURE-SHARDED, rows replicated (and so is the
    returned ``node``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.core.jax_compat import pcast_varying, shard_map

    depth = cfg.effective_depth
    num_slots = 2 ** (depth + 1) - 1
    b = total_bins
    fp = dict(zip(mesh.axis_names, mesh.devices.shape))[FEATURE_AXIS]
    if num_features % fp:
        raise ValueError(f"feature_parallel needs features ({num_features}) "
                         f"divisible by fp ({fp})")
    f_loc = num_features // fp
    leaf_objective = _leaf_objective_fns(cfg)

    def local_fn(binned_loc, grad, hess, valid, feat_mask_loc,
                 remaining_leaves):
        n = binned_loc.shape[0]
        shard = jax.lax.axis_index(FEATURE_AXIS)
        feat_off = shard * f_loc

        node = jnp.zeros(n, dtype=jnp.int32)
        done = jnp.zeros(n, dtype=jnp.bool_)
        split_feature = jnp.full(num_slots, -1, dtype=jnp.int32)
        threshold_bin = jnp.zeros(num_slots, dtype=jnp.int32)
        node_value = jnp.zeros(num_slots, dtype=jnp.float32)
        node_count = jnp.zeros(num_slots, dtype=jnp.float32)

        root_g = jnp.sum(grad * valid)
        root_h = jnp.sum(hess * valid)
        root_c = jnp.sum(valid)
        rv, _ = leaf_objective(root_g, root_h)
        node_value = node_value.at[0].set(rv)
        node_count = node_count.at[0].set(root_c)
        remaining = remaining_leaves - 1

        # row state must be fp-varying for the routing psum trick
        node = pcast_varying(node, (FEATURE_AXIS,))
        done = pcast_varying(done, (FEATURE_AXIS,))

        for d in range(depth):
            level_start = 2 ** d - 1
            width = 2 ** d
            local = jnp.clip(node - level_start, 0, width - 1)
            live = (~done).astype(grad.dtype) * pcast_varying(
                valid, (FEATURE_AXIS,))

            hist = _histogram(
                binned_loc,
                pcast_varying(grad, (FEATURE_AXIS,)),
                pcast_varying(hess, (FEATURE_AXIS,)),
                live, local, width, f_loc, b)

            gain, cum = _split_gains(hist, leaf_objective, cfg, b)
            gain = jnp.where(feat_mask_loc[None, :, None] > 0, gain,
                             -jnp.inf)
            flat = gain.reshape(width, f_loc * b)
            loc_fb = jnp.argmax(flat, axis=1)
            loc_gain = jnp.take_along_axis(flat, loc_fb[:, None], 1)[:, 0]
            loc_feat = (loc_fb // b).astype(jnp.int32) + feat_off
            loc_bin = (loc_fb % b).astype(jnp.int32)

            # ---- combine per-shard bests (tiny all-gather) -------------
            record_collective("all_gather", FEATURE_AXIS,
                              loc_gain.shape, loc_gain.dtype)
            gains_all = jax.lax.all_gather(loc_gain, FEATURE_AXIS)  # (P, w)
            winner = jnp.argmax(gains_all, axis=0)                  # (w,)
            best_gain = jnp.max(gains_all, axis=0)
            i_am_winner = winner == shard
            zero = jnp.zeros_like(loc_feat)
            record_collective("psum", FEATURE_AXIS, loc_feat.shape,
                              loc_feat.dtype)
            record_collective("psum", FEATURE_AXIS, loc_bin.shape,
                              loc_bin.dtype)
            best_feat = jax.lax.psum(
                jnp.where(i_am_winner, loc_feat, zero), FEATURE_AXIS)
            best_bin = jax.lax.psum(
                jnp.where(i_am_winner, loc_bin, zero), FEATURE_AXIS)

            can_split = jnp.isfinite(best_gain)
            order = jnp.argsort(-jnp.where(can_split, best_gain, -jnp.inf))
            rank = jnp.zeros(width, dtype=jnp.int32).at[order].set(
                jnp.arange(width, dtype=jnp.int32))
            do_split = can_split & (rank < remaining)
            remaining = remaining - jnp.sum(do_split.astype(jnp.int32))

            slots = level_start + jnp.arange(width, dtype=jnp.int32)
            split_feature = split_feature.at[slots].set(
                jnp.where(do_split, best_feat, -1))
            threshold_bin = threshold_bin.at[slots].set(
                jnp.where(do_split, best_bin, 0))

            # ---- child stats: winner shard supplies, psum broadcasts ---
            sel = jnp.arange(width, dtype=jnp.int32)
            loc_best_feat_idx = (loc_fb // b).astype(jnp.int32)
            cum_best = cum[sel, loc_best_feat_idx]        # (width, B, 3)
            left_loc = jnp.take_along_axis(
                cum_best, loc_bin[:, None, None], axis=1)[:, 0, :]
            tot_loc = cum_best[:, -1, :]
            record_collective("psum", FEATURE_AXIS, left_loc.shape,
                              left_loc.dtype)
            record_collective("psum", FEATURE_AXIS, tot_loc.shape,
                              tot_loc.dtype)
            left_stats = jax.lax.psum(
                jnp.where(i_am_winner[:, None], left_loc, 0.0), FEATURE_AXIS)
            tot_stats = jax.lax.psum(
                jnp.where(i_am_winner[:, None], tot_loc, 0.0), FEATURE_AXIS)
            right_stats = tot_stats - left_stats
            lval, _ = leaf_objective(left_stats[:, 0], left_stats[:, 1])
            rval, _ = leaf_objective(right_stats[:, 0], right_stats[:, 1])
            lslots, rslots = 2 * slots + 1, 2 * slots + 2
            node_value = node_value.at[lslots].set(
                jnp.where(do_split, lval, 0.0))
            node_value = node_value.at[rslots].set(
                jnp.where(do_split, rval, 0.0))
            node_count = node_count.at[lslots].set(
                jnp.where(do_split, left_stats[:, 2], 0.0))
            node_count = node_count.at[rslots].set(
                jnp.where(do_split, right_stats[:, 2], 0.0))

            # ---- routing: winning feature's owner decides, psum shares -
            nfeat = best_feat[local]                     # global feature id
            local_id = nfeat - feat_off
            mine = (local_id >= 0) & (local_id < f_loc)
            nbin_loc = jnp.take_along_axis(
                binned_loc, jnp.clip(local_id, 0, f_loc - 1)[:, None],
                1)[:, 0]
            go_left_vote = jnp.where(
                mine, (nbin_loc <= best_bin[local]).astype(jnp.int32), 0)
            record_collective("psum", FEATURE_AXIS,
                              go_left_vote.shape, go_left_vote.dtype)
            go_left = jax.lax.psum(go_left_vote, FEATURE_AXIS) > 0
            nsplit = do_split[local]
            child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
            newly_done = ~nsplit & ~done
            node = jnp.where(done | ~nsplit, node, child)
            done = done | newly_done

        # every shard computed identical values (all cross-shard state went
        # through psum); pmax is an identity that marks them fp-invariant
        # so out_specs=P() typechecks; ``node`` too: rows are replicated
        # over fp and every shard routed them by the same psum'd vote
        outs = (split_feature, threshold_bin, node_value, node_count, node)
        for v in outs:
            record_collective("pmax", FEATURE_AXIS, v.shape, v.dtype)
        return tuple(jax.lax.pmax(v, FEATURE_AXIS) for v in outs)

    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, FEATURE_AXIS), P(), P(), P(), P(FEATURE_AXIS),
                  P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=_check_vma(total_bins))
