"""Long-context attention: blockwise, ring, and Ulysses (all-to-all).

The reference has NO sequence parallelism (SURVEY.md §5 — grep-verified
absent); its long-input story is chunking transformers only. This module
is the TPU-native long-context design mandated by the build brief:

- :func:`blockwise_attention` — single-device memory-efficient attention
  (online-softmax over KV blocks, flash-attention recurrence) as a
  ``lax.scan``; O(block) memory instead of O(n²).
- :func:`ring_attention` — sequence sharded over the ``sp`` mesh axis;
  KV blocks rotate around the ring via ``lax.ppermute`` (ICI
  neighbor exchange) while each device accumulates its queries' online
  softmax. Communication overlaps compute; no device ever holds the
  full sequence.
- :func:`ulysses_attention` — DeepSpeed-Ulysses style: ``all_to_all``
  swaps the sequence shard for a head shard, full attention runs per
  head group, then a second ``all_to_all`` restores sequence sharding.
  Cheaper collectives for models with many heads; requires
  heads % sp == 0.

All three produce results identical (up to float tolerance) to dense
softmax attention; tests check this on an 8-device CPU mesh.

Shapes follow (batch, seq, heads, head_dim). Causal masking uses global
positions, so sharded and dense results agree.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

from mmlspark_tpu.core.sanitizer import record_collective
from mmlspark_tpu.parallel.mesh import SEQUENCE_AXIS

_NEG_INF = -1e30


def _block_attend(q, k, v, out, row_max, row_sum, q_offset, k_offset,
                  causal: bool, scale: float, *, q_positions=None,
                  kv_lengths=None):
    """One online-softmax accumulation step.

    q: (b, nq, h, d); k/v: (b, nk, h, d); out/row_max/row_sum are the
    running accumulators. Returns updated (out, row_max, row_sum).
    ``q_positions`` (b, nq) gives each row's own query positions in
    place of ``q_offset``; ``kv_lengths`` (b,) masks each row's keys
    from that index on (a cache filled so far).
    """
    import jax.numpy as jnp

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    nq, nk = q.shape[1], k.shape[1]
    k_pos = k_offset + jnp.arange(nk)
    if causal and q_positions is not None:
        mask = q_positions[:, None, :, None] >= k_pos
        scores = jnp.where(mask, scores, _NEG_INF)
    elif causal:
        q_pos = q_offset + jnp.arange(nq)
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None, :, :], scores, _NEG_INF)
    if kv_lengths is not None:
        scores = jnp.where(k_pos < kv_lengths[:, None, None, None], scores,
                           _NEG_INF)

    blk_max = jnp.max(scores, axis=-1)                      # (b, h, q)
    new_max = jnp.maximum(row_max, blk_max)
    # rescale previous accumulators to the new max
    correction = jnp.exp(row_max - new_max)
    p = jnp.exp(scores - new_max[..., None])                # (b, h, q, k)
    new_sum = row_sum * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    new_out = out * correction.transpose(0, 2, 1)[..., None] + pv
    return new_out, new_max, new_sum


def _streamed_attend(q, k, v, out, row_max, row_sum, q_offset, k_offset,
                     causal: bool, scale: float, block_size: int = 512):
    """Online-softmax accumulation over ``k``/``v`` in sub-blocks, so
    the materialized score tile is (nq, block_size) instead of
    (nq, nk) — ring attention's per-rotation attend stays linear in
    the rotated chunk length at any sequence scale."""
    import jax
    import jax.numpy as jnp

    nk = k.shape[1]
    # divisor-fit block, exactly as blockwise_attention: awkward chunk
    # lengths stream at the largest fitting divisor; prime-ish lengths
    # take one dense tile rather than a column-at-a-time scan
    block = min(block_size, nk)
    while nk % block:
        block -= 1
    if block < min(block_size, nk) // 4:
        block = nk
    n_blocks = nk // block
    if n_blocks == 1:
        return _block_attend(q, k, v, out, row_max, row_sum,
                             q_offset, k_offset, causal, scale)
    b = k.shape[0]
    kb = k.reshape(b, n_blocks, block, *k.shape[2:]).transpose(
        1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, block, *v.shape[2:]).transpose(
        1, 0, 2, 3, 4)

    def step(carry, blk):
        out, row_max, row_sum, i = carry
        kk, vv = blk
        out, row_max, row_sum = _block_attend(
            q, kk, vv, out, row_max, row_sum, q_offset,
            k_offset + i * block, causal, scale)
        return (out, row_max, row_sum, i + 1), None

    from mmlspark_tpu.core.jax_compat import operand_vma, pcast_varying
    i0 = jnp.asarray(0)
    i0 = pcast_varying(
        i0, tuple(sorted(operand_vma(q, k, v, out, row_max, row_sum))))
    (out, row_max, row_sum, _), _ = jax.lax.scan(
        step, (out, row_max, row_sum, i0), (kb, vb))
    return out, row_max, row_sum


def blockwise_attention(q, k, v, block_size: int = 512,
                        causal: bool = False, *, scale=None,
                        q_positions=None, kv_lengths=None, kv_map=None,
                        kv_limit=None):
    """Memory-efficient attention via lax.scan over KV blocks.

    ``scale`` replaces ``1 / sqrt(d)``; ``q_positions`` and
    ``kv_lengths`` are ``_block_attend``'s (a ragged batch over a
    cache). With ``kv_map``, ``k`` and ``v`` are any ``(b, nk, ...)``
    arrays and ``kv_map(k block, v block)`` gives the block's ``(b,
    block, h, d)`` keys and ``(b, block, h, d_v)`` values inside the
    scan: compressed keys and values are then expanded a block at a
    time and never whole. ``kv_limit`` (a traced scalar) says that no
    row attends to a position from there on (a cache filled so far):
    the blocks beyond are not visited, which changes no result, only
    the work (forward only: the loop's length is then not static)."""
    import jax
    import jax.numpy as jnp

    b, n, h, d = q.shape
    nk = k.shape[1]
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    # largest divisor of nk that fits the requested block: any kv
    # length streams (the scan needs equal blocks; a 704-long sequence
    # gets 352-wide blocks rather than a ValueError). Awkward lengths
    # whose divisors are all tiny (primes) take one dense tile instead
    # of degenerating into a column-at-a-time scan.
    block = min(block_size, nk)
    while nk % block:
        block -= 1
    if block < min(block_size, nk) // 4:
        block = nk
    n_blocks = nk // block
    k_blocks = jnp.moveaxis(
        k.reshape((b, n_blocks, block) + k.shape[2:]), 1, 0)
    v_blocks = jnp.moveaxis(
        v.reshape((b, n_blocks, block) + v.shape[2:]), 1, 0)
    d_v = (v.shape[-1] if kv_map is None else
           jax.eval_shape(kv_map, k_blocks[0], v_blocks[0])[1].shape[-1])

    def step(carry, blk):
        out, row_max, row_sum, blk_i = carry
        kb, vb = blk if kv_map is None else kv_map(*blk)
        out, row_max, row_sum = _block_attend(
            q, kb, vb, out, row_max, row_sum,
            q_offset=0, k_offset=blk_i * block, causal=causal, scale=scale,
            q_positions=q_positions, kv_lengths=kv_lengths)
        return (out, row_max, row_sum, blk_i + 1), None

    stats0 = (jnp.zeros((b, n, h, d_v), q.dtype),
              jnp.full((b, h, n), _NEG_INF, q.dtype),
              jnp.zeros((b, h, n), q.dtype))
    # inside a shard_map (e.g. the Ulysses inner attention) the inputs
    # vary over the sp axis, so the freshly-created accumulators must be
    # promoted to the same varying type or the scan carry mismatches
    from mmlspark_tpu.core.jax_compat import operand_vma, pcast_varying
    stats0 = pcast_varying(stats0, tuple(sorted(operand_vma(q, k, v))))
    init = (*stats0, jnp.asarray(0))
    if kv_limit is None:
        (out, row_max, row_sum, _), _ = jax.lax.scan(
            step, init, (k_blocks, v_blocks))
    else:
        def visit(i, carry):
            return step(carry, tuple(jax.lax.dynamic_index_in_dim(
                x, i, axis=0, keepdims=False)
                for x in (k_blocks, v_blocks)))[0]

        out, row_max, row_sum, _ = jax.lax.fori_loop(
            0, jnp.minimum(-(-kv_limit // block), n_blocks), visit, init)
    return out / jnp.maximum(row_sum, 1e-30).transpose(0, 2, 1)[..., None]


def fused_attention(q, k, v, causal: bool = False, block_size: int = 512):
    """Single-device attention through the fastest available path: the
    Pallas flash kernel on TPU (mmlspark_tpu.parallel.flash), else the
    XLA blockwise scan."""
    from mmlspark_tpu.parallel.flash import flash_attention, flash_available

    n, nk = q.shape[1], k.shape[1]
    if flash_available() and n % 128 == 0 and nk % 128 == 0:
        return flash_attention(q, k, v, causal=causal)
    return blockwise_attention(q, k, v, block_size=block_size,
                               causal=causal)


def ring_attention(q, k, v, mesh, causal: bool = False,
                   axis_name: str = SEQUENCE_AXIS):
    """Sequence-parallel attention: KV rotates around the ``sp`` ring.

    Inputs are GLOBAL arrays (b, n, h, d); the shard_map shards them on
    the sequence axis. Each of the P devices holds n/P queries and
    rotates its KV shard P times via ``ppermute``, accumulating online
    softmax. Equivalent to dense attention on the full sequence.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.core.jax_compat import pcast_varying, shard_map

    n = q.shape[1]
    sp = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    if n % sp:
        raise ValueError(f"sequence {n} not divisible by sp={sp}")
    chunk = n // sp
    scale = 1.0 / (q.shape[-1] ** 0.5)

    spec = P(None, axis_name, None, None)

    def local(qc, kc, vc):
        # qc/kc/vc: (b, n/P, h, d) — this device's shard
        idx = jax.lax.axis_index(axis_name)
        b, nq, h, d = qc.shape
        q_off = idx * chunk

        def step(i, carry):
            out, row_max, row_sum, kb, vb = carry
            # the KV block currently held started at device (idx - i)
            src = (idx - i) % sp
            out, row_max, row_sum = _streamed_attend(
                qc, kb, vb, out, row_max, row_sum,
                q_offset=q_off, k_offset=src * chunk,
                causal=causal, scale=scale)
            # rotate KV to the next device (neighbor exchange on ICI)
            perm = [(j, (j + 1) % sp) for j in range(sp)]
            record_collective("ppermute", axis_name, kb.shape, kb.dtype)
            record_collective("ppermute", axis_name, vb.shape, vb.dtype)
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            return out, row_max, row_sum, kb, vb

        # accumulators must be marked sp-varying for the fori_loop carry
        # (they start shard-invariant but the updates differ per shard)
        stats0 = pcast_varying(
            (jnp.full((b, h, nq), _NEG_INF, qc.dtype),
             jnp.zeros((b, h, nq), qc.dtype)), (axis_name,))
        init = (jnp.zeros_like(qc), *stats0, kc, vc)
        out, row_max, row_sum, _, _ = jax.lax.fori_loop(0, sp, step, init)
        return out / jnp.maximum(row_sum, 1e-30).transpose(0, 2, 1)[..., None]

    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def ulysses_attention(q, k, v, mesh, causal: bool = False,
                      axis_name: str = SEQUENCE_AXIS):
    """All-to-all sequence parallelism (Ulysses): trade the sequence
    shard for a head shard, run full attention per head group, swap back.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mmlspark_tpu.core.jax_compat import pcast_varying, shard_map

    b, n, h, d = q.shape
    sp = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    if h % sp:
        raise ValueError(f"heads {h} not divisible by sp={sp}")
    if n % sp:
        raise ValueError(f"sequence {n} not divisible by sp={sp}")
    scale = 1.0 / (d ** 0.5)
    spec = P(None, axis_name, None, None)

    def local(qc, kc, vc):
        # (b, n/P, h, d) --all_to_all--> (b, n, h/P, d)
        def seq_to_heads(x):
            record_collective("all_to_all", axis_name, x.shape, x.dtype)
            return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                      concat_axis=1, tiled=True)

        def heads_to_seq(x):
            record_collective("all_to_all", axis_name, x.shape, x.dtype)
            return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                      concat_axis=2, tiled=True)

        qh, kh, vh = seq_to_heads(qc), seq_to_heads(kc), seq_to_heads(vc)
        # memory-efficient inner attention: the head-group sees the FULL
        # sequence here, so a dense (n, n) score matrix would defeat the
        # point of sequence parallelism at long context — fused_attention
        # streams KV blocks (XLA blockwise; the Pallas flash kernel when
        # enabled on TPU, which is legal per-shard inside this shard_map)
        out = fused_attention(qh, kh, vh, causal=causal)
        return heads_to_seq(out)

    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def dense_attention(q, k, v, causal: bool = False):
    """Reference dense softmax attention (for tests/verification)."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
    if causal:
        nq, nk = q.shape[1], k.shape[1]
        mask = jnp.arange(nq)[:, None] >= jnp.arange(nk)[None, :]
        scores = jnp.where(mask[None, None, :, :], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
