"""Latent attention (MLA: DeepSeek-V2, arXiv:2405.04434) over a cache
of compressed keys and values.

A position's cache entry is its normed latent ``c`` (``kv_lora_rank``
values) and its rotated shared key ``r`` (``qk_rope_head_dim``); a
head's keys and values are ``[c W_uk[h]; r]`` and ``c W_uv[h]``. The
two forms of one formula:

- ``latent_prefill`` (a stretch of tokens): keys and values are
  expanded from the cache a block of positions at a time inside
  ``attention.blockwise_attention``'s scan (never whole), and the
  stretch's queries attend to every cached position up to their own;
- ``latent_decode`` (one token): ``W_uk`` is absorbed into the query
  and ``W_uv`` applied to the attended latent, so a step reads the
  cache's latents alone, once for the scores and once for the values.

The cache is ``{"c": (B, capacity, rank), "r": (B, capacity, rope)}``
in the model's dtype. ``cache_write`` puts a stretch's entries at each
row's own position; a padded position writes nothing, and every softmax
masks the positions a row has not filled. Softmax and accumulation are
float32; the operands of each product are in the model's dtype.
"""

from __future__ import annotations

import math
from typing import Mapping

import numpy as np

KV_BLOCK = 128       # cached positions expanded a step of the scan


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
    real and are written, the rest leave the cache as it was."""
    import jax
    import jax.numpy as jnp

    t = c.shape[1]
    real = (jnp.arange(t)[None, :] < lengths[:, None])[..., None]

    def put(old, new):
        from mmlspark_tpu.parallel.shard_rules import placement_cast

        new = placement_cast(new, old.dtype)
        was = jax.vmap(lambda o, s: jax.lax.dynamic_slice_in_dim(
            o, s, t, axis=0))(old, pos)
        return jax.vmap(lambda o, n, s: jax.lax.dynamic_update_slice_in_dim(
            o, n, s, axis=0))(old, jnp.where(real, new, was), pos)

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
        kv_lengths=pos + lengths, kv_map=expand)


def latent_decode(q_n, q_r, cache, w_uk, w_uv, pos, *, scale: float, dtype):
    """One token a row, at position ``pos`` ``(B,)`` (already in the
    cache). ``q_n``: ``(B, heads, nope)``; ``q_r``: ``(B, heads,
    rope)``. Returns ``(B, heads, d_v)`` float32."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    def product(spec, a, b):
        return jnp.einsum(spec, placement_cast(a, dtype),
                          placement_cast(b, dtype),
                          preferred_element_type=jnp.float32)

    c, r = cache["c"], cache["r"]
    q_c = product("bhd,chd->bhc", q_n, w_uk)          # W_uk absorbed
    scores = (product("bhc,blc->bhl", q_c, c)
              + product("bhr,blr->bhl", q_r, r)) * scale
    filled = jnp.arange(c.shape[1])[None, None, :] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(filled, scores, -1e30), axis=-1)
    return product("bhc,chd->bhd", product("bhl,blc->bhc", p, c), w_uv)
