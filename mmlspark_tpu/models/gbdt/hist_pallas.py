"""Pallas TPU kernel for the GBDT per-level histogram.

The flagship hot op (SURVEY.md §2.7 row 1: the native histogram pass
behind LightGBM's ``LGBM_BoosterUpdateOneIter``, reference
``lightgbm/src/main/scala/com/microsoft/azure/synapse/ml/lightgbm/booster/LightGBMBooster.scala:355``).
XLA lowers the ``segment_sum`` formulation in ``trainer._level_histogram``
through a generic scatter; this kernel restructures the op for the TPU
memory system instead of scattering at all:

1. Inside a row block the per-feature histogram is an equality-compare
   one-hot (rows x bins, built on the VPU) contracted against a
   (stats x rows) matrix on the MXU — bin accumulation becomes a
   matmul, the operation shape TPUs are built for, instead of a
   data-dependent scatter. The product is ONE bf16 x bf16 -> float32
   pass (``HIST_PRODUCT``): the one-hot is 0/1, exact in bf16, and the
   float32 grad and hess go in as three bf16 parts each (``split3``:
   ``hi + mid + lo == x`` to the bit), so every product is exact and
   the MXU's float32 accumulator adds exact terms; the parts' sums are
   added back a block, before the accumulator (``_feature_sums``).
2. Nodes are told apart in one of two ways, chosen by the call's static
   shapes alone, the level's ``width`` and the feature count ``f``
   (``level_feed``; nothing a user sets):

   - **in place** (up to 32 nodes whatever the matrix; up to
     ``IN_PLACE_MAX_WIDTH`` for as many features as the crossing of the
     two paths allows; never past ``IN_PLACE_VMEM_BUDGET``): the grid
     runs over ``binned`` in the order its rows lie. Each block takes
     its (R, F)
     rows of bins and its (8, R) block of stats (grad*live, hess*live,
     live, node index: element-wise writes, no gather), and the node is
     a mask: the left operand is the node-expanded stats, row
     ``s * wq + w`` being ``stats[s] * (node == w)``, seven bf16 rows
     a node (three parts of grad and of hess, and the count). One
     accumulator for the whole level stays in VMEM across the grid and
     is written to HBM once. No sort, no slot map, no copy of the
     matrix a level.
   - **sorted** (every other level): rows are grouped by node (one
     ``argsort`` of the node index a level), each node's segment padded
     to whole row blocks and gathered into that layout, so every grid
     step works on rows of ONE node; a scalar-prefetched
     ``block -> node`` map routes each step's output tile, which stays
     in VMEM across the run of blocks that share a node. Its MXU work
     does not grow with the width; its feed (the sort and two gathers
     of every row) costs twice the kernel.

3. Both paths take ``binned`` row-major: the device keeps a u8 (N, F)
   array column-major, so XLA makes one row-major copy (F byte columns
   padded to 128 lanes) where the kernel is called; inside the tree step
   the six levels share one such copy a tree.

What it costs (TPU v5e, one level at 20M x 28 x 255, my chip run, PR 38):
the table beside ``IN_PLACE_MAX_WIDTH`` below. The kernel is NOT
bandwidth-bound: a level reads 2.6 GB of lane-padded bins and 0.6 GB of
stats (4 ms at the HBM peak) and takes 0.14 to 0.35 s in place at the
widths of a 63-leaf tree, 0.36 s sorted, 30 to 80 times its floor
(``hist_kernel_roofline`` about 2.4%). The time goes to the kernel's
inside: building an (R, 256) one-hot a feature from (R, 1) lane slices
on the VPU (what 0.138 s at widths 1 to 8 is), and past 64 rows of left
operand the MXU's one pass over them (0.0015 s a row; what grows with
the width in place). A float32 product would make Mosaic split BOTH
operands into bf16 parts, the one-hot too, whose second and third parts
are zero: six passes and 0.33 s flat where one pass and 0.14 do
(PERF.md §6, PR 38). ROADMAP S1 lists what is left there.

The kernel accumulates in float32 in block order; results match the
XLA formulations exactly on integer-valued grad/hess (no rounding) and
to float-sum tolerance otherwise. ``tests/gbdt/test_hist_pallas.py``
pins both in interpret mode, and ``chip_smoke.py`` checks the second at
bench dimensions on whatever Mosaic compiled.
"""

from __future__ import annotations

import functools

import numpy as np

_SPAD = 8        # stats rows (grad, hess, count) padded to a sublane tile
# the kernel's product, as ``hist_stats["hist_product"]`` records it: one
# bf16 pass over float32 stats split into three bf16 parts (``split3``)
HIST_PRODUCT = "bf16x3"
_BIN_PAD = 256   # bin axis padded to two full lane tiles
# Which levels take the in-place path (``level_feed``), read off one
# level on a TPU v5e, arrays passed as arguments, seconds a call
# (tools/hist_level_ab.py; my chip runs, PR 38), in place | sorted.
# 20M rows x 28 features x 255 bins:
#   width   1: 0.151 | 2.071      width  32: 0.363 | 1.348
#   width   2: 0.151 | 1.522      width  64: 0.695 | 1.546
#   width   4: 0.151 | 1.398      width 128: 1.366 | 1.761
#   width   8: 0.154 | 1.301      width 256: 3.114 | 1.783
#   width  16: 0.193 | 1.343
# 4M rows, by feature count:
#   f= 28  width 128: 0.274 | 0.351      width 256: 0.623 | 0.356
#   f= 64  width  64: 0.308 | 0.378      width 128: 0.699 | 0.420
#   f= 84  width  64: 0.482 | 0.430      width 128: 0.892 | 0.472
#   f=136  width  32: 0.330 | 0.523      width  64: 0.737 | 0.561
#          width 128: 1.404 | 0.613
#   f=200  width  64: 1.051 | 0.727      width 128: 2.029 | 0.779
# The in-place kernel takes 0.138 s (20M x 28) up to 64 rows of left
# operand (the one-hot's build on the VPU) and from 112 rows on follows
# them at 0.0015 s a row, the MXU's one pass (seven rows a node: 0.180 s
# at width 16, 0.350 at 32, 0.68 at 64, 1.35 at 128, 3.10 at 256), and
# all of it a feature: 2.4 to 2.5 ms a feature at width 32 and 4M rows,
# 4.8 to 5.7 at 64, 9.7 to 10.9 at 128, at 28 to 200 features. The
# sorted kernel takes 2.6 ms a feature at every width (0.358 s at
# 20M x 28), and its feed 0.17 to 0.28 s at 4M rows (0.94 to 1.71 s at
# 20M) whatever the feature count. So up to 32 nodes in place costs no
# more a feature than the sorted kernel and has no feed: in place
# whatever the matrix. Past that it pays ``f`` times the rows beyond,
# against a feed that does not grow with ``f``: the paths cross where
# ``f * (width - 32)`` is about 2,700 (f=28 between widths 128 and 256,
# f=64 between 64 and 128, f=136 between 32 and 64, in the table), and
# 28 x (128 - 32), the widest case measured to win (by 22%), is the
# bound. Its other end, f=84 at width 64, reads 12% slower in place
# (5.7 ms a feature there, where f=64 reads 4.75 and f=136 5.4): one
# constant for both widths costs that band, f 77 to 84 at width 64. No
# level wider than 128 was seen to win at any feature count measured.
IN_PLACE_FREE_WIDTH = 32
IN_PLACE_MAX_WIDTH = 128
IN_PLACE_MAX_EXTRA = 28 * (IN_PLACE_MAX_WIDTH - IN_PLACE_FREE_WIDTH)
# What the in-place kernel may ask of VMEM: no more than a v5e has. Its
# accumulator holds the whole level, ``4 * f * rows * 256`` bytes
# (``_in_place_vmem`` asks for it twice and 24 MiB), so it grows with
# the feature count as well as the width, and at 32 nodes and under,
# where no crossing bounds ``f``, this does: width 32 to 554 features,
# 16 to 1,109, 8 to 2,218. The largest ask run on the chip is 174 MiB
# (f=200 at width 128, through ``_in_place_level_histogram``: it
# compiled and was right), so nothing under the budget has been seen to
# fail. A level past it takes the sorted path, whose accumulator is one
# (F, 8, 256) tile a node.
IN_PLACE_VMEM_BUDGET = 128 << 20


def pallas_histogram_enabled() -> bool:
    """Default ON on the TPU backend, opt-in elsewhere: with the
    sharded histogram reduction no longer assuming a replicated
    histogram (parallel_modes.make_build_tree_data_parallel), the
    Mosaic kernel is the production per-shard path on TPU.
    MMLSPARK_TPU_PALLAS_HIST=1/0 forces either way (off-TPU the kernel
    runs in interpret mode — correctness testing, not a default)."""
    import jax

    from mmlspark_tpu.core.env import env_flag
    return env_flag("MMLSPARK_TPU_PALLAS_HIST",
                    default=jax.default_backend() == "tpu")


def resolve_pallas_interpret() -> bool:
    """Whether the kernel runs through the Pallas interpreter: never on
    the TPU backend (Mosaic compiles it), always elsewhere unless
    MMLSPARK_TPU_PALLAS_FORCE_COMPILE takes the Mosaic path off-TPU
    (the AOT lowering tests validate the exact on-TPU combination).
    One resolution shared by the kernel entry point, the shard_map
    checker policy and the fit's ``hist_stats`` provenance."""
    import jax

    from mmlspark_tpu.core.env import env_flag
    return (jax.default_backend() != "tpu"
            and not env_flag("MMLSPARK_TPU_PALLAS_FORCE_COMPILE"))


def split3(x):
    """float32 -> (hi, mid, lo), three bfloat16-valued float32 arrays
    with ``hi + mid + lo == x`` to the bit: each part takes the next 8
    bits of the 24-bit significand (round to nearest; the differences
    are exact in float32), above the subnormal range. A part converts
    to bfloat16 without loss, so a product of a part with 0 or 1 is
    exact in one MXU pass. A value bf16 already holds (0, 1, a small
    integer) has ``mid == lo == 0``."""
    import jax.numpy as jnp

    def bf16(v):  # a rounding, not an autocast: the result is float32
        return v.astype(jnp.bfloat16).astype(  # graftlint: disable=GL015
            jnp.float32)

    hi = bf16(x)
    mid = bf16(x - hi)
    return hi, mid, bf16(x - hi - mid)


def _feature_sums(parts, bins_ref, fi: int, bin_pad: int, rows: int,
                  low_rows: int):
    """(rows, bins) sums of one feature over a row block, the product
    both kernels share: ``parts`` (M, R) bfloat16 holds the ``rows``
    stats rows' hi parts, then the mid and the lo parts of their first
    ``low_rows`` (then padding to whole bf16 tiles), against the
    feature's (R, bin_pad) one-hot. 0 and 1 are exact in bfloat16 and so
    is every part, so the one default-precision pass is exact up to the
    MXU's float32 accumulation; the three parts' sums are added here."""
    import jax
    import jax.numpy as jnp

    iota_b = jax.lax.broadcasted_iota(jnp.int32, (1, bin_pad), 1)
    col = bins_ref[:, fi:fi + 1].astype(jnp.int32)      # (R, 1)
    # (R, bin_pad); 0 and 1 lose nothing in bfloat16
    eq = (col == iota_b).astype(jnp.bfloat16)  # graftlint: disable=GL015
    s = jax.lax.dot_general(
        parts, eq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (M, bin_pad)
    mid, lo = rows, rows + low_rows
    low = s[:low_rows] + s[mid:lo] + s[lo:lo + low_rows]
    if low_rows == rows:
        return low
    return jnp.concatenate([low, s[low_rows:rows]])


def _bf16_parts(hi, mid, lo):
    """The left operand of ``_feature_sums``: the blocks stacked by
    rows, padded to whole (16, 128) bfloat16 tiles, cast (no loss: every
    value is a part of ``split3``)."""
    import jax.numpy as jnp

    blocks = [hi, mid, lo]
    short = -sum(b.shape[0] for b in blocks) % 16
    if short:
        blocks.append(jnp.zeros((short, hi.shape[1]), jnp.float32))
    return jnp.concatenate(blocks).astype(  # graftlint: disable=GL015
        jnp.bfloat16)


def _hist_kernel(bn_ref, bins_ref, data_ref, out_ref, *, num_features: int,
                 bin_pad: int):
    """Sorted path. One row block (all rows belong to node ``bn_ref[i]``):
    add the block's per-feature (stats, bins) sums into the node's
    accumulator.
    """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    node = bn_ref[i]
    prev = bn_ref[jnp.maximum(i - 1, 0)]
    first = (i == 0) | (node != prev)

    # (SPAD, R) stats: the three parts of every row (the count's mid
    # and lo are zero), no expansion
    parts = _bf16_parts(*split3(data_ref[...]))
    for fi in range(num_features):
        s = _feature_sums(parts, bins_ref, fi, bin_pad, _SPAD, _SPAD)

        @pl.when(first)
        def _init(fi=fi, s=s):
            out_ref[0, fi] = s

        @pl.when(jnp.logical_not(first))
        def _acc(fi=fi, s=s):
            out_ref[0, fi] += s


def _in_place_rows(width: int):
    """Rows of the in-place kernel's accumulator: ``3 * wq`` (grad, hess
    and count of each of ``wq`` nodes, ``wq`` the power of two at or
    above ``width`` so that a row's node and stat are a mask and a shift
    of its index), padded to whole sublane tiles; and of them the rows
    that have a mid and a lo part (grad and hess, ``2 * wq``, padded
    likewise: the count is 0 or 1, one part).
    -> (rows, low_rows, log2(wq))."""
    shift = max(width - 1, 0).bit_length()

    def pad(v):
        return -(-v // _SPAD) * _SPAD

    return pad(3 << shift), pad(2 << shift), shift


def _in_place_vmem(f: int, rows: int) -> int:
    """Bytes of VMEM the in-place kernel asks for: the level's
    accumulator (f, rows, 256) float32 twice, as Pallas buffers every
    block twice (2 x 2.75 MB at 28 features and width 32, 2 x 11 MB at
    width 128), and 24 MiB for the row blocks, the expanded stats and
    one feature's one-hot and product."""
    return 2 * 4 * f * rows * _BIN_PAD + (24 << 20)


def _expand_by_node(stats, node, rows: int, shift: int):
    """(rows, R): row ``s * wq + w`` is ``stats[s]`` where the row's
    node is ``w``, else 0 (``s`` 0 to 2: grad, hess, count)."""
    import jax
    import jax.numpy as jnp

    shape = (rows, stats.shape[1])
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    stat = row >> shift
    picked = jnp.where(
        stat == 0, jnp.broadcast_to(stats[0:1], shape),
        jnp.where(stat == 1, jnp.broadcast_to(stats[1:2], shape),
                  jnp.broadcast_to(stats[2:3], shape)))
    return jnp.where(
        (stat < 3) & ((row & ((1 << shift) - 1)) == node),
        picked, 0.0)


def _hist_kernel_in_place(bins_ref, data_ref, out_ref, *, num_features: int,
                          bin_pad: int, rows: int, low_rows: int,
                          shift: int, n: int):
    """In-place path. One row block as it lies in ``binned``, whatever
    nodes its rows belong to: the stats are split into their bf16 parts,
    each part expanded by node (``_expand_by_node``: seven rows a node,
    the hi parts of grad, hess and count, then the mid and the lo parts
    of grad and hess) and contracted against the same per-feature
    one-hot as the sorted path, into one accumulator for the whole level
    that stays in VMEM across the grid."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    data = data_ref[...]                                # (SPAD, R) f32
    r = data.shape[1]
    if n % r:
        # the ragged last block reads past both operands: whatever lies
        # there is no row (a one-hot of any byte is 0 or 1, so zero
        # stats add nothing)
        lane = jax.lax.broadcasted_iota(jnp.int32, data.shape, 1)
        data = jnp.where(i * r + lane < n, data, 0.0)
    # row 3 of the stats carries the node index (exact in float32)
    node = data[3:4].astype(jnp.int32)
    hi, mid, lo = split3(data)
    parts = _bf16_parts(_expand_by_node(hi, node, rows, shift),
                        _expand_by_node(mid, node, low_rows, shift),
                        _expand_by_node(lo, node, low_rows, shift))

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    for fi in range(num_features):
        s = _feature_sums(parts, bins_ref, fi, bin_pad, rows, low_rows)
        out_ref[fi] += s.reshape(rows // _SPAD, _SPAD, bin_pad)


def _level_stats(grad, hess, live, local):
    """(SPAD, N) float32: grad*live, hess*live, live and the node index
    of each row, zeros in the other sublanes. Element-wise writes:
    nothing is gathered."""
    import jax.numpy as jnp

    rows = [grad * live, hess * live, live, local]
    stats = jnp.stack([v.astype(jnp.float32) for v in rows])
    return jnp.pad(stats, ((0, _SPAD - len(rows)), (0, 0)))


def _in_place_level_histogram(binned, grad, hess, live, local, *, width: int,
                              f: int, b: int, block_rows: int,
                              interpret: bool):
    """Sort-free level histogram: the grid runs over ``binned`` in the
    order its rows lie, and the kernel masks the ragged last block. No
    copy of the matrix is made."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mmlspark_tpu.core.jax_compat import (operand_vma,
                                              shape_dtype_struct)

    n = binned.shape[0]
    r = block_rows
    rows, low_rows, shift = _in_place_rows(width)
    with jax.named_scope("gbdt.hist.feed"):
        data = _level_stats(grad, hess, live, local)

    vma = operand_vma(binned, grad, hess, live, local)
    kernel = functools.partial(_hist_kernel_in_place, num_features=f,
                               bin_pad=_BIN_PAD, rows=rows,
                               low_rows=low_rows, shift=shift, n=n)
    out_block = (f, rows // _SPAD, _SPAD, _BIN_PAD)
    with jax.named_scope("gbdt.hist"):
        out = pl.pallas_call(
            kernel,
            out_shape=shape_dtype_struct(out_block, jnp.float32, vma=vma),
            grid=(-(-n // r),),
            in_specs=[pl.BlockSpec((r, f), lambda i: (i, 0)),
                      pl.BlockSpec((_SPAD, r), lambda i: (0, i))],
            out_specs=pl.BlockSpec(out_block, lambda i: (0, 0, 0, 0)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_in_place_vmem(f, rows)),
            interpret=interpret,
            name="gbdt_level_hist",
        )(binned, data)
        # (f, rows/8, 8, BIN_PAD) -> (3, wq, f, BIN_PAD) -> (width, f, b, 3)
        out = out.reshape(f, rows, _BIN_PAD)[:, :3 << shift]
        out = out.reshape(f, 3, 1 << shift, _BIN_PAD)[:, :, :width, :b]
        return jnp.transpose(out, (2, 0, 3, 1))


def _sorted_level_histogram(binned, grad, hess, live, local, *, width: int,
                            f: int, b: int, block_rows: int,
                            interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = binned.shape[0]
    r = block_rows
    # static upper bound on padded row blocks: every node adds at most
    # one partial block, empty nodes still get one (so every output
    # tile is zero-initialized by its first visit)
    nb = n // r + width + 1

    # device scopes (op_name metadata, no effect on the program):
    # ``gbdt.hist.feed`` is the sort by node, the slot map and the two
    # gathers that lay rows out for the kernel; ``gbdt.hist`` the kernel
    with jax.named_scope("gbdt.hist.feed"):
        local = local.astype(jnp.int32)
        counts = jnp.bincount(local, length=width)                  # (width,)
        offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
        blocks_per_node = jnp.maximum((counts + r - 1) // r, 1)
        cum_blocks = jnp.cumsum(blocks_per_node).astype(jnp.int32)  # (width,)
        order = jnp.argsort(local).astype(jnp.int32)

        block_node = jnp.clip(
            jnp.searchsorted(cum_blocks, jnp.arange(nb, dtype=jnp.int32),
                             side="right"),
            0, width - 1).astype(jnp.int32)

        # padded slot -> source row (n = dummy zero row)
        slot = jnp.arange(nb * r, dtype=jnp.int32)
        blk = slot // r
        w = block_node[blk]
        base = jnp.where(w > 0, cum_blocks[jnp.maximum(w - 1, 0)], 0)
        row_in_node = (blk - base) * r + (slot % r)
        valid = (row_in_node >= 0) & (row_in_node < counts[w])
        sorted_pos = jnp.clip(offsets[w] + row_in_node, 0, n - 1)
        src = jnp.where(valid, order[sorted_pos], n)

        bins_pad = jnp.concatenate(
            [binned, jnp.zeros((1, f), binned.dtype)])[src]          # (nb*r, f)
        stats = jnp.zeros((_SPAD, n + 1), jnp.float32)
        stats = stats.at[0, :n].set((grad * live).astype(jnp.float32))
        stats = stats.at[1, :n].set((hess * live).astype(jnp.float32))
        stats = stats.at[2, :n].set(live.astype(jnp.float32))
        data = stats[:, src]                                         # (SPAD, nb*r)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((r, f), lambda i, bn: (i, 0)),
            pl.BlockSpec((_SPAD, r), lambda i, bn: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, f, _SPAD, _BIN_PAD),
                               lambda i, bn: (bn[i], 0, 0, 0)),
    )
    from mmlspark_tpu.core.jax_compat import (operand_vma,
                                              shape_dtype_struct)
    vma = operand_vma(binned, grad, hess, live, local)
    kernel = functools.partial(_hist_kernel, num_features=f,
                               bin_pad=_BIN_PAD)
    with jax.named_scope("gbdt.hist"):
        out = pl.pallas_call(
            kernel,
            out_shape=shape_dtype_struct((width, f, _SPAD, _BIN_PAD),
                                         jnp.float32, vma=vma),
            grid_spec=grid_spec,
            interpret=interpret,
            name="gbdt_level_hist",
        )(block_node, bins_pad, data)
        # (width, f, SPAD, BIN_PAD) -> (width, f, b, 3)
        return jnp.transpose(out[:, :, :3, :b], (0, 1, 3, 2))


def level_feed(width: int, f: int) -> str:
    """The path a level of ``width`` nodes over ``f`` features takes:
    the call's static shapes decide, nothing a user sets. In place
    where that is the faster by the measurements beside
    ``IN_PLACE_MAX_WIDTH`` (up to 32 nodes always; wider while ``f``
    times the nodes past 32 stays under the crossing) and the level's
    accumulator fits the kernel's share of VMEM; else sorted."""
    rows, _, shift = _in_place_rows(width)
    extra = f * max((1 << shift) - IN_PLACE_FREE_WIDTH, 0)
    in_place = (width <= IN_PLACE_MAX_WIDTH and extra <= IN_PLACE_MAX_EXTRA
                and _in_place_vmem(f, rows) <= IN_PLACE_VMEM_BUDGET)
    return "in_place" if in_place else "sorted"


def feed_by_path(widths, f: int) -> dict:
    """How many of a tree's histogram calls, of these widths over ``f``
    features, take each path: what ``hist_stats["hist_feed"]``
    records."""
    paths = [level_feed(w, f) for w in widths]
    return {p: paths.count(p) for p in ("in_place", "sorted")}


def _pallas_level_histogram(binned, grad, hess, live, local, *, width: int,
                            f: int, b: int, block_rows: int,
                            interpret: bool):
    # under shard_map (the voting/feature tree learners) the output
    # varies over whatever mesh axes the inputs vary over — both paths
    # declare the union so a check_vma-enabled enclosing shard_map
    # accepts the per-shard call on the Mosaic (compiled) path; outside
    # shard_map every vma is empty and this is a no-op. The interpret
    # path instead runs with the enclosing shard_map's checker off (see
    # parallel_modes._check_vma): interpret discharges the kernel body
    # into the manual trace, where kernel-internal constants trip the
    # checker.
    import jax.numpy as jnp

    if binned.shape[0] == 0:
        return jnp.zeros((width, f, b, 3), jnp.float32)
    path = (_in_place_level_histogram
            if level_feed(width, f) == "in_place"
            else _sorted_level_histogram)
    return path(binned, grad, hess, live, local, width=width, f=f, b=b,
                block_rows=block_rows, interpret=interpret)


_JIT_CACHE = {}


def pallas_level_histogram(binned, grad, hess, live, local, width, f, b,
                           block_rows: int = 512, interpret=None):
    """Drop-in for ``trainer._level_histogram``: (N, F) bins + per-row
    stats -> (width, F, B, 3) grad/hess/count sums. Also safe to call
    from inside an enclosing jit/shard_map (the cached jit collapses
    into the outer trace)."""
    import jax

    if b > _BIN_PAD:
        raise ValueError(
            f"pallas histogram kernel supports at most {_BIN_PAD} bins, "
            f"got {b}; use the XLA formulation for wider bin counts")
    if interpret is None:
        interpret = resolve_pallas_interpret()
    key = (int(width), int(f), int(b), int(block_rows), bool(interpret))
    if key not in _JIT_CACHE:
        w, nf, nb, br, it = key
        _JIT_CACHE[key] = jax.jit(functools.partial(
            _pallas_level_histogram, width=w, f=nf, b=nb, block_rows=br,
            interpret=it))
    return _JIT_CACHE[key](binned, grad, hess, live, local)


def pallas_level_histogram_quant(binned, grad_q, hess_q, live, local,
                                 width, f, b, gscale_inv, hscale_inv,
                                 block_rows: int = 512, interpret=None):
    """Quantized-gradient entry point (MMLSPARK_TPU_HIST_QUANT): int16/
    int8 grad/hess with shared per-round pow2 scales. int * pow2 is
    exact in float32, so dequantizing up front feeds the kernel the
    SAME values the int32-accumulating native kernel sums, and such a
    value splits into bf16 parts (``split3``) as exactly as any other
    float32 (an int8 quantum is one part, an int16 one two) — the three
    backends agree to f32 accumulation order, which is the same parity
    contract as the unquantized path. A native-int MXU accumulation (an
    int8 operand layout and a per-block rescale) is one of the
    directions left for the kernel's inside: the kernel is bound by its
    own VPU and MXU work, not by reading the binned matrix (the cost
    note in the module docstring; ROADMAP S1, direction 4)."""
    import jax.numpy as jnp

    grad = grad_q.astype(jnp.float32) * gscale_inv
    hess = hess_q.astype(jnp.float32) * hscale_inv
    return pallas_level_histogram(binned, grad, hess,
                                  live.astype(jnp.float32), local,
                                  width, f, b, block_rows=block_rows,
                                  interpret=interpret)
