"""Pallas TPU kernel for the GBDT per-level histogram.

The flagship hot op (SURVEY.md §2.7 row 1: the native histogram pass
behind LightGBM's ``LGBM_BoosterUpdateOneIter``, reference
``lightgbm/src/main/scala/com/microsoft/azure/synapse/ml/lightgbm/booster/LightGBMBooster.scala:355``).
XLA lowers the ``segment_sum`` formulation in ``trainer._level_histogram``
through a generic scatter; this kernel restructures the op for the TPU
memory system instead of scattering at all:

1. Rows are grouped by tree node (one ``argsort`` of the node index per
   level) and each node's segment is padded to a whole number of
   ``block_rows`` row blocks, so every grid step works on rows of ONE
   node.
2. A scalar-prefetched ``block -> node`` map routes each grid step's
   output block: the (node, F, stats, bins) accumulator tile stays in
   VMEM across the consecutive run of blocks that share a node (the
   output index map is constant over that run) and is flushed to HBM
   once per node, not once per row.
3. Inside a block the per-feature histogram is an equality-compare
   one-hot (rows x bins, built on the VPU) contracted against the
   (stats x rows) matrix on the MXU — bin accumulation becomes a
   matmul, the operation shape TPUs are built for, instead of a
   data-dependent scatter.

Cost per row block per feature: R*B compares + an (S, R) @ (R, B)
matmul. With B=256 padded bins that is ~1.5 KFLOP per (row, feature)
update — far below MXU throughput, so the level histogram is
bandwidth-bound on reading the binned matrix, which is the roofline.

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


def _hist_kernel(bn_ref, bins_ref, data_ref, out_ref, *, num_features: int,
                 bin_pad: int):
    """One row block (all rows belong to node ``bn_ref[i]``): add the
    block's per-feature (stats, bins) sums into the node's accumulator.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    node = bn_ref[i]
    prev = bn_ref[jnp.maximum(i - 1, 0)]
    first = (i == 0) | (node != prev)

    data = data_ref[...].astype(jnp.float32)           # (SPAD, R)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (1, bin_pad), 1)
    for fi in range(num_features):
        col = bins_ref[:, fi:fi + 1].astype(jnp.int32)  # (R, 1)
        eq = (col == iota_b).astype(jnp.float32)        # (R, bin_pad)
        # HIGHEST: at default precision the MXU rounds the f32 stats to
        # bf16 (measured on the v5e, PR 22: counts stay exact, grad/hess
        # sums come out up to 0.12 off at 2M rows), which breaks the
        # float-sum parity contract with the XLA formulations; the
        # one-hot operand is exact in bf16, so the multi-pass product
        # is exact and only the accumulation order differs
        s = jax.lax.dot_general(
            data, eq, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)         # (SPAD, bin_pad)

        @pl.when(first)
        def _init(fi=fi, s=s):
            out_ref[0, fi] = s

        @pl.when(jnp.logical_not(first))
        def _acc(fi=fi, s=s):
            out_ref[0, fi] += s


def _pallas_level_histogram(binned, grad, hess, live, local, *, width: int,
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
    # under shard_map (the voting/feature tree learners) the output
    # varies over whatever mesh axes the inputs vary over — declare the
    # union so a check_vma-enabled enclosing shard_map accepts the
    # per-shard call on the Mosaic (compiled) path; outside shard_map
    # every vma is empty and this is a no-op. The interpret path
    # instead runs with the enclosing shard_map's checker off (see
    # parallel_modes._check_vma): interpret discharges the kernel body
    # into the manual trace, where kernel-internal constants trip the
    # checker.
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
    same parity contract as the unquantized path. (A native-int MXU
    accumulation would need an int8 operand layout and per-block
    rescale; not worth it while the kernel is bandwidth-bound on the
    binned matrix, see the cost note in the module docstring.)"""
    import jax.numpy as jnp

    grad = grad_q.astype(jnp.float32) * gscale_inv
    hess = hess_q.astype(jnp.float32) * hscale_inv
    return pallas_level_histogram(binned, grad, hess,
                                  live.astype(jnp.float32), local,
                                  width, f, b, block_rows=block_rows,
                                  interpret=interpret)
