"""Latent attention (MLA: DeepSeek-V2, arXiv:2405.04434) over a cache
of compressed keys and values.

A position's cache entry is its normed latent ``c`` (``kv_lora_rank``
values) and its rotated shared key ``r`` (``qk_rope_head_dim``); a
head's keys and values are ``[c W_uk[h]; r]`` and ``c W_uv[h]``. The
two forms of one formula:

- ``latent_prefill`` (a stretch of tokens): keys and values are
  expanded from the cache a block of positions at a time inside
  ``attention.blockwise_attention``'s loop (never whole, and only the
  blocks some row has filled), and the stretch's queries attend to
  every cached position up to their own;
- ``latent_decode`` (one token): ``W_uk`` is absorbed into the query
  and ``W_uv`` applied to the attended latent, so a step reads the
  cache's latents alone. The latent is key and value at once (a
  position's key is ``[c; r]`` and its value ``c``, shared by all
  heads), so on the TPU one Pallas kernel (``name="latent_decode"``)
  reads each filled position once for both, a block of positions at a
  time with a running maximum and sum, skips the blocks no row of its
  group has filled, and never writes the scores out. Elsewhere its
  ``jax.numpy`` twin runs (``use_pallas``), which reads the whole
  capacity.

The cache is ``{"c": (B, capacity, rank), "r": (B, capacity, rope)}``
in the model's dtype. ``cache_write`` puts a stretch's entries at each
row's own position (one token a row: one scatter); a padded position
writes nothing, and every softmax masks the positions a row has not
filled, scores and values both, so what lies beyond a row's fill is
never read into a result. Softmax and accumulation are float32; the
operands of each product are in the model's dtype.
"""

from __future__ import annotations

import math
import functools
from typing import Mapping, Optional

import numpy as np

from mmlspark_tpu.parallel.retention import use_pallas

KV_BLOCK = 128       # cached positions expanded a step of the scan
DECODE_ROWS = 8      # rows a grid step of the decode kernel
DECODE_BLOCK = 256   # cached positions a grid step of the decode kernel


def yarn_frequencies(dim: int, theta: float, scaling: Mapping) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN (arXiv:2309.00071):
    dimensions that turn more than ``beta_fast`` times over the original
    context keep their frequency, those under ``beta_slow`` turns are
    divided by ``factor``, a linear ramp between."""
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return base.astype(np.float32)
    original = scaling["original_max_position_embeddings"]

    def dimension_of(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dimension_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (base / scaling["factor"] * ramp + base * (1 - ramp)).astype(
        np.float32)


def softmax_scale(qk_dim: int, scaling: Mapping, yarn_scaled: bool) -> float:
    """``qk_dim ** -0.5``, times ``(0.1 ln factor + 1) ** 2`` under
    YaRN where the model says so (``use_mla_scaling_factor``)."""
    scale = qk_dim ** -0.5
    if scaling and yarn_scaled:
        m = 0.1 * scaling.get("mscale_all_dim", 1) * math.log(
            scaling["factor"]) + 1.0
        scale *= m * m
    return scale


def rotary_interleaved(x, positions, frequencies):
    """``x``: ``(B, T, ..., dim)``; ``positions``: ``(B, T)``. Element
    ``2 j`` turns with ``2 j + 1`` (``rope_interleave``)."""
    import jax.numpy as jnp

    angle = positions.astype(jnp.float32)[..., None] * frequencies
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3)
                          + angle.shape[-1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def init_cache(batch: int, capacity: int, rank: int, rope: int, dtype):
    import jax.numpy as jnp

    return {"c": jnp.zeros((batch, capacity, rank), dtype),
            "r": jnp.zeros((batch, capacity, rope), dtype)}


def cache_write(cache, c, r, pos, lengths):
    """``c``, ``r``: ``(B, T, ...)`` entries of a stretch whose first
    position is ``pos`` ``(B,)``; the first ``lengths`` of each row are
    real and are written, the rest leave the cache as it was. One
    token a row is one scatter (a row without a real token writes
    beyond the capacity, which drops it); a longer stretch is a slice a
    row."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    b, t = c.shape[:2]

    def put_token(old, new):
        at = jnp.where(lengths > 0, pos, old.shape[1])
        return old.at[jnp.arange(b), at].set(
            placement_cast(new[:, 0], old.dtype), mode="drop",
            unique_indices=True, indices_are_sorted=True)

    def put_stretch(old, new):
        real = (jnp.arange(t)[None, :] < lengths[:, None])[..., None]
        new = placement_cast(new, old.dtype)
        was = jax.vmap(lambda o, s: jax.lax.dynamic_slice_in_dim(
            o, s, t, axis=0))(old, pos)
        return jax.vmap(lambda o, n, s: jax.lax.dynamic_update_slice_in_dim(
            o, n, s, axis=0))(old, jnp.where(real, new, was), pos)

    put = put_token if t == 1 else put_stretch
    return {"c": put(cache["c"], c), "r": put(cache["r"], r)}


def _operand(x, dtype):
    """``x`` as an operand of a product in ``dtype``, kept float32 (a
    pair of converts XLA may drop as excess precision)."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def latent_prefill(q_n, q_r, cache, w_uk, w_uv, pos, lengths, *,
                   scale: float, dtype):
    """``q_n``: ``(B, T, heads, nope)``; ``q_r``: ``(B, T, heads, rope)``
    rotated; ``cache`` already holds the stretch (``cache_write``);
    ``w_uk``: ``(rank, heads, nope)``; ``w_uv``: ``(rank, heads, d_v)``;
    ``pos``: ``(B,)`` the stretch's first position. Returns ``(B, T,
    heads, d_v)`` float32."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.attention import blockwise_attention
    from mmlspark_tpu.parallel.shard_rules import placement_cast

    t, heads = q_n.shape[1:3]
    both = placement_cast(jnp.concatenate([w_uk, w_uv], axis=-1), dtype)

    def expand(c, r):
        kv = jnp.einsum("blc,chd->blhd", c, both,
                        preferred_element_type=jnp.float32)
        k_n, v = kv[..., :w_uk.shape[-1]], kv[..., w_uk.shape[-1]:]
        k_r = jnp.broadcast_to(r.astype(jnp.float32)[:, :, None, :],
                               r.shape[:2] + (heads, r.shape[-1]))
        return (_operand(jnp.concatenate([k_n, k_r], axis=-1), dtype),
                _operand(v, dtype))

    q = _operand(jnp.concatenate([q_n, q_r], axis=-1), dtype)
    return blockwise_attention(
        q, cache["c"], cache["r"], block_size=KV_BLOCK, causal=True,
        scale=scale, q_positions=pos[:, None] + jnp.arange(t),
        kv_lengths=pos + lengths, kv_map=expand,
        kv_limit=jnp.max(pos + lengths))


def _product(spec, a, b, dtype):
    """``einsum`` with both operands in ``dtype``, float32 out."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    return jnp.einsum(spec, placement_cast(a, dtype),
                      placement_cast(b, dtype),
                      preferred_element_type=jnp.float32)


def _decode_jnp(q_c, q_r, cache, pos, scale, dtype):
    """The attended latent ``(B, heads, rank)`` float32 over the whole
    capacity; ``q_c``: the queries with ``W_uk`` absorbed."""
    import jax
    import jax.numpy as jnp

    c, r = cache["c"], cache["r"]
    scores = (_product("bhc,blc->bhl", q_c, c, dtype)
              + _product("bhr,blr->bhl", q_r, r, dtype)) * scale
    filled = jnp.arange(c.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(filled[:, None, :], scores, -1e30), axis=-1)
    return _product("bhl,blc->bhc", p,
                    jnp.where(filled[..., None], c, jnp.zeros_like(c)), dtype)


def _decode_kernel(pos_ref, last_ref, qc_ref, qr_ref, c_ref, r_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, rows: int, block: int,
                   scale: float):
    """A group of ``rows`` rows against one block of cached positions:
    each row's ``(heads, rank + rope)`` queries against the block's
    entries, running maximum ``m``, sum ``l`` and attended latent
    ``acc`` in float32, the block's latents also the values. A block
    past the group's farthest position is not read (its index is held
    at the last block in use, which is not fetched again) and does no
    work."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    g, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j <= last_ref[g])
    def _():
        along = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1)
        down = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, 1), 0)
        contract = (((1,), (1,)), ((), ()))
        for i in range(rows):
            at = pos_ref[g * rows + i]
            # the values of positions not filled are zero, so that what
            # lies there (anything: a partial last block reads beyond
            # the array) meets its zero weight as zero
            c = jnp.where(down <= at, c_ref[i],
                          jnp.zeros_like(c_ref[i])).astype(qc_ref.dtype)
            s = (jax.lax.dot_general(qc_ref[i], c, contract,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[i],
                                       r_ref[i].astype(qr_ref.dtype),
                                       contract,
                                       preferred_element_type=jnp.float32))
            s = jnp.where(along <= at, s * scale, -1e30)  # (heads, block)
            m_old = m_ref[i]                                # lane-equal
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            shrink = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[i] = shrink * l_ref[i] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[i] = shrink[:, :1] * acc_ref[i] + jnp.dot(
                p.astype(c.dtype), c, preferred_element_type=jnp.float32)
            m_ref[i] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for i in range(rows):
            o_ref[i] = acc_ref[i] / l_ref[i][:, :1]


def _decode_pallas(q_c, q_r, cache, pos, scale, dtype, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    c, r = cache["c"], cache["r"]
    b, heads, rank = q_c.shape
    capacity, rope = r.shape[1:]
    rows = next(n for n in (DECODE_ROWS, 4, 2, 1) if b % n == 0)
    block = min(DECODE_BLOCK, capacity)
    pos = pos.astype(jnp.int32)
    last = jnp.max(pos.reshape(b // rows, rows), axis=1) // block

    def by_row(*shape):
        return pl.BlockSpec((rows,) + shape, lambda g, j, pos, last: (g, 0, 0))

    def by_block(width):
        return pl.BlockSpec(
            (rows, block, width),
            lambda g, j, pos, last: (g, jnp.minimum(j, last[g]), 0))

    return pl.pallas_call(
        functools.partial(_decode_kernel, rows=rows, block=block,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rows, pl.cdiv(capacity, block)),
            in_specs=[by_row(heads, rank), by_row(heads, rope),
                      by_block(rank), by_block(rope)],
            out_specs=by_row(heads, rank),
            scratch_shapes=[pltpu.VMEM((rows, heads, 128), jnp.float32),
                            pltpu.VMEM((rows, heads, 128), jnp.float32),
                            pltpu.VMEM((rows, heads, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_decode",
    )(pos, last, placement_cast(q_c, dtype), placement_cast(q_r, dtype),
      c, r)


def latent_decode(q_n, q_r, cache, w_uk, w_uv, pos, *, scale: float, dtype,
                  pallas: Optional[bool] = None, interpret: bool = False):
    """One token a row, at position ``pos`` ``(B,)`` (already in the
    cache). ``q_n``: ``(B, heads, nope)``; ``q_r``: ``(B, heads,
    rope)``. Returns ``(B, heads, d_v)`` float32."""
    q_c = _product("bhd,chd->bhc", q_n, w_uk, dtype)   # W_uk absorbed
    if use_pallas() if pallas is None else pallas:
        attended = _decode_pallas(q_c, q_r, cache, pos, scale, dtype,
                                  interpret)
    else:
        attended = _decode_jnp(q_c, q_r, cache, pos, scale, dtype)
    return _product("bhc,chd->bhd", attended, w_uv, dtype)
