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
   data-dependent scatter.
2. Nodes are told apart in one of two ways, chosen by the level's static
   ``width`` alone (``level_feed``; nothing a user sets):

   - **in place** (``width <= IN_PLACE_MAX_WIDTH``): the grid runs over
     ``binned`` in the order its rows lie. Each block takes its (R, F)
     rows of bins and its (8, R) block of stats (grad*live, hess*live,
     live, node index: element-wise writes, no gather), and the node is
     a mask: the left operand is the node-expanded stats, row
     ``s * wq + w`` being ``stats[s] * (node == w)``. One accumulator
     for the whole level stays in VMEM across the grid and is written
     to HBM once. No sort, no slot map, no copy of the matrix a level.
   - **sorted** (wider levels): rows are grouped by node (one
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

What it costs (TPU v5e, one level at 20M x 28 x 255, my chip run, PR 30):
the table beside ``IN_PLACE_MAX_WIDTH`` below. The kernel is NOT
bandwidth-bound: a level reads 2.6 GB of lane-padded bins and 0.6 GB of
stats (4 ms at the HBM peak) and takes 0.33 to 0.92 s in place, 0.52 s
sorted, two orders of magnitude over its floor (``hist_kernel_roofline``
about 1%). The time goes to the kernel's inside: building an (R, 256)
one-hot a feature from (R, 1) lane slices on the VPU (what 0.33 s at
width 1 is), and the product at ``HIGHEST`` (six bf16 passes; what grows
with the width in place). ROADMAP S1 lists what is left there.

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
_BIN_PAD = 256   # bin axis padded to two full lane tiles
# Widest level that takes the in-place path. Set by one level at
# 20M x 28 x 255 on a TPU v5e, arrays passed as arguments, seconds a call
# (tools/hist_level_ab.py; my chip run, PR 30), in place | sorted:
#   width   1: 0.344 | 2.230      width  64: 1.769 | 1.705
#   width   8: 0.384 | 1.460      width 128: 3.876 | 1.921
#   width  32: 0.929 | 1.507      width 256: 7.346 | 1.943
# The in-place kernel's time follows the rows of its left operand (3 x
# width: 0.33 s at 8 rows, 0.92 s at 96, 7.33 s at 768: the MXU's six
# passes at HIGHEST); the sorted kernel takes 0.52 s at every width and
# its feed 0.94 to 1.71 s. They cross between 32 and 64.
IN_PLACE_MAX_WIDTH = 32


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


def _feature_sums(stats, bins_ref, fi: int, bin_pad: int):
    """(rows of stats, bins) sums of one feature over a row block: the
    (S, R) stats against the feature's (R, bin_pad) one-hot, the product
    both kernels share."""
    import jax
    import jax.numpy as jnp

    iota_b = jax.lax.broadcasted_iota(jnp.int32, (1, bin_pad), 1)
    col = bins_ref[:, fi:fi + 1].astype(jnp.int32)      # (R, 1)
    eq = (col == iota_b).astype(jnp.float32)            # (R, bin_pad)
    # HIGHEST: at default precision the MXU rounds the f32 stats to
    # bf16 (measured on the v5e, PR 22: counts stay exact, grad/hess
    # sums come out up to 0.12 off at 2M rows), which breaks the
    # float-sum parity contract with the XLA formulations; the
    # one-hot operand is exact in bf16, so the multi-pass product
    # is exact and only the accumulation order differs
    return jax.lax.dot_general(
        stats, eq, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


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

    data = data_ref[...].astype(jnp.float32)           # (SPAD, R)
    for fi in range(num_features):
        s = _feature_sums(data, bins_ref, fi, bin_pad)  # (SPAD, bin_pad)

        @pl.when(first)
        def _init(fi=fi, s=s):
            out_ref[0, fi] = s

        @pl.when(jnp.logical_not(first))
        def _acc(fi=fi, s=s):
            out_ref[0, fi] += s


def _in_place_rows(width: int):
    """Rows of the in-place kernel's left operand: ``3 * wq`` (grad, hess
    and count of each of ``wq`` nodes, ``wq`` the power of two at or
    above ``width`` so that a row's node and stat are a mask and a shift
    of its index), padded to whole sublane tiles. -> (rows, log2(wq))."""
    shift = max(int(width) - 1, 0).bit_length()
    rows = 3 << shift
    return -(-rows // _SPAD) * _SPAD, shift


def _hist_kernel_in_place(bins_ref, data_ref, out_ref, *, num_features: int,
                          bin_pad: int, rows: int, shift: int, n: int):
    """In-place path. One row block as it lies in ``binned``, whatever
    nodes its rows belong to: the stats are expanded by node (row
    ``s * wq + w`` is stat ``s`` where the row's node is ``w``, else 0)
    and contracted against the same per-feature one-hot as the sorted
    path, into one accumulator for the whole level that stays in VMEM
    across the grid."""
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
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, r), 0)
    stat = row >> shift
    # row 3 of the stats carries the node index (exact in float32)
    node = jnp.broadcast_to(data[3:4].astype(jnp.int32), (rows, r))
    picked = jnp.where(
        stat == 0, jnp.broadcast_to(data[0:1], (rows, r)),
        jnp.where(stat == 1, jnp.broadcast_to(data[1:2], (rows, r)),
                  jnp.broadcast_to(data[2:3], (rows, r))))
    expanded = jnp.where(
        (stat < 3) & ((row & ((1 << shift) - 1)) == node), picked, 0.0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    for fi in range(num_features):
        s = _feature_sums(expanded, bins_ref, fi, bin_pad)  # (rows, bin_pad)
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
    rows, shift = _in_place_rows(width)
    with jax.named_scope("gbdt.hist.feed"):
        data = _level_stats(grad, hess, live, local)

    vma = operand_vma(binned, grad, hess, live, local)
    kernel = functools.partial(_hist_kernel_in_place, num_features=f,
                               bin_pad=_BIN_PAD, rows=rows, shift=shift,
                               n=n)
    out_block = (f, rows // _SPAD, _SPAD, _BIN_PAD)
    # the accumulator (2.75 MB at width 32) twice, as Pallas buffers every
    # block twice, and room for the row blocks, the expanded stats and
    # one feature's one-hot and product
    vmem_limit = 2 * 4 * f * rows * _BIN_PAD + (24 << 20)
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
                vmem_limit_bytes=vmem_limit),
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


def level_feed(width: int) -> str:
    """The path a level of ``width`` nodes takes: a static shape decides,
    nothing a user sets."""
    return "in_place" if width <= IN_PLACE_MAX_WIDTH else "sorted"


def feed_by_path(widths) -> dict:
    """How many of a tree's histogram calls, of these widths, take each
    path: what ``hist_stats["hist_feed"]`` records."""
    paths = [level_feed(w) for w in widths]
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
    path = (_in_place_level_histogram if level_feed(width) == "in_place"
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
    exact in float32, so dequantizing up front feeds the f32 matmul
    kernel the SAME values the int32-accumulating native kernel sums —
    the three backends agree to f32 accumulation order, which is the
    same parity contract as the unquantized path. A native-int MXU
    accumulation (an int8 operand layout and a per-block rescale) is one
    of the directions left for the kernel's inside: the kernel is bound
    by its own VPU and MXU work, not by reading the binned matrix (the
    cost note in the module docstring; ROADMAP S1, direction 4)."""
    import jax.numpy as jnp

    grad = grad_q.astype(jnp.float32) * gscale_inv
    hess = hess_q.astype(jnp.float32) * hscale_inv
    return pallas_level_histogram(binned, grad, hess,
                                  live.astype(jnp.float32), local,
                                  width, f, b, block_rows=block_rows,
                                  interpret=interpret)
