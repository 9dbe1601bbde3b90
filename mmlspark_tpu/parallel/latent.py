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
  every cached position up to their own. On the TPU, from
  ``PREFILL_KERNEL_MIN_ROWS`` query rows a key head a step
  (``prefill_kernel``: every latent model's prefill here, Motif's full
  layers' 5,120 and the ungrouped models' 128-256) one Pallas kernel
  (``name="latent_prefill"``) does the same a query tile at a time:
  the block's keys and values expanded in VMEM from the latent, the
  tile's scores, running maximum and sum and float32 accumulator kept
  there, the blocks past a row's last real query not read; its block
  and the key heads a grid step follow the step's shapes
  (``prefill_form``);
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

A window layer (``window_prefill``) sees only the ``window`` most
recent positions and keeps them in a ring of ``window`` slots, position
``p`` in slot ``p mod window`` (``ring_entries``): a stretch's queries are
cut into blocks of ``window`` and each reads two blocks of keys, the
ring or the stretch's block before and its own; a decode step reads
the ring through ``latent_decode`` as a cache of ``window`` positions
(a softmax does not care about the slots' order, and each key was
rotated when it was written).

The cache is ``{"c": (B, capacity, rank), "r": (B, capacity, rope)}``
in the model's dtype. ``write_entries`` puts a stretch's entries at each
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

from mmlspark_tpu.parallel.mosaic import operand, use_pallas

KV_BLOCK = 128       # cached positions expanded a step of the scan
DECODE_ROWS = 8      # rows a grid step of the decode kernel
DECODE_BLOCK = 256   # cached positions a grid step of the decode kernel
# One Motif full layer's group (4 rows x 1,024 queries, 80 heads over 16
# key heads, rank 512) on a TPU v5e, ms a call, fills 4-8k / 8-15k
# (tools/latent_decode_ab.py --form prefill): the scan 147 / 259; block x
# tile 512x512 16.5 / 27.6, 512x1024 17.4 / 29.8, 512x256 19.6 / 33.3,
# 384x1024 27.1 / 47.2, 256x1024 33.5 / 58.7, 256x256 33.1 / 57.9,
# 1024x1024 38.4 / 44.3
PREFILL_BLOCK = 512  # most cached positions a grid step of the prefill kernel
PREFILL_TILE = 512   # queries a row a grid step of the prefill kernel
# One layer's group of ungrouped heads (one query head a key head), ms
# summed over a cell's steps weighted by its run visits a step, TPU v5e
# (tools/latent_decode_ab.py --form prefill --at, PERF.md section 6):
# block x key heads a grid step, against the scan's 259 / 78 / 347
# for 32 rows x 128 queries x 64 heads over 1,280 and 768 positions and
# 16 x 256 x 32 over 2,080: 256x8 174 / 49 / 202, 512x8 189 / 54 / 167,
# 128x8 181 / 51 / 277, 256x4 182 / 52 / 207, 512x4 195 / 55 / 172,
# 256x2 210 / 58 / 220, 256x1 262 / 70 / 253, 512x1 248 / 67 / 219: a
# block of twice the tile (the step's 128 or 256 queries), 8 key heads
PREFILL_KEY_HEADS = 8
# query rows a key head a step (t x heads / key heads) from which the
# prefill takes the kernel; below, the scan. Measured from 128 up (in
# the chosen form the kernel won at every step of the three cells
# above); fewer rows were never measured
PREFILL_KERNEL_MIN_ROWS = 128


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


def write_entries(cache, new, pos, lengths):
    """Each array of ``new`` (``(B, T, ...)``, a stretch from ``pos``)
    into the array of ``cache`` of its name, at each row's own
    positions: the first ``lengths`` of a row are written, the rest
    leave the cache as it was. One token a row is one scatter (a row
    without a real token writes beyond the capacity, which drops it); a
    longer stretch is a slice a row."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    b, t = next(iter(new.values())).shape[:2]

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
    return {name: put(cache[name], entries) for name, entries in new.items()}


def prefill_kernel(t: int, heads: int, key_heads: int) -> bool:
    """Whether a prefill of ``t`` queries a row over ``heads`` query heads
    grouped on ``key_heads`` takes the kernel: on the TPU, where a key
    head has at least ``PREFILL_KERNEL_MIN_ROWS`` query rows a step."""
    return use_pallas() and t * heads // key_heads >= PREFILL_KERNEL_MIN_ROWS


def prefill_form(t: int, per: int, groups: int, capacity: int):
    """``(block, tile, key heads a grid step)`` of the prefill kernel for
    ``t`` queries a row and ``per`` query heads on each of ``groups`` key
    heads, over a cache of ``capacity`` positions: a block of at most
    twice the tile (``t`` rounded up to 8), of which a first step, from
    position 0, fills at most ``t``; and where a key head has one query
    head, ``PREFILL_KEY_HEADS`` key heads a grid step (the most of them
    that divide ``groups``), so that a block is expanded to their keys
    and values in one wider product and read once for all."""
    tile = min(PREFILL_TILE, -(-t // 8) * 8)
    each = math.gcd(groups, PREFILL_KEY_HEADS) if per == 1 else 1
    return min(PREFILL_BLOCK, 2 * tile, capacity), tile, each


def latent_prefill(q_n, q_r, cache, w_uk, w_uv, pos, lengths, *,
                   scale: float, dtype, pallas: Optional[bool] = None,
                   interpret: bool = False):
    """``q_n``: ``(B, T, heads, nope)``; ``q_r``: ``(B, T, heads, rope)``
    rotated; ``cache`` already holds the stretch (``write_entries``);
    ``w_uk``: ``(rank, heads, nope)``; ``w_uv``: ``(rank, heads, d_v)``,
    or of fewer key heads over which the query heads are grouped
    (``attention._block_attend``); ``pos``: ``(B,)`` the stretch's first
    position. Returns ``(B, T, heads, d_v)`` float32. The kernel where
    ``prefill_kernel`` says so (``pallas`` pins a form): it gives a row
    without a real token in the stretch zeros, the scan the softmax of
    whatever it may see; the real queries' are the same."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.attention import blockwise_attention

    t, heads = q_n.shape[1:3]
    if (prefill_kernel(t, heads, w_uk.shape[1]) if pallas is None
            else pallas):
        return _prefill_pallas(q_n, q_r, cache, w_uk, w_uv, pos, lengths,
                               scale, dtype, interpret)
    expand = _expander(w_uk, w_uv, dtype)
    q = operand(jnp.concatenate([q_n, q_r], axis=-1), dtype)
    return blockwise_attention(
        q, cache["c"], cache["r"], block_size=KV_BLOCK, causal=True,
        scale=scale, q_positions=pos[:, None] + jnp.arange(t),
        kv_lengths=pos + lengths, kv_map=expand,
        kv_limit=jnp.max(pos + lengths))


def _expander(w_uk, w_uv, dtype):
    """``expand(c, r)``: the keys ``(B, L, key heads, nope + rope)`` and
    values ``(B, L, key heads, d_v)`` of cached entries, both operands
    in ``dtype``."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    heads = w_uk.shape[1]
    both = placement_cast(jnp.concatenate([w_uk, w_uv], axis=-1), dtype)

    def expand(c, r):
        kv = jnp.einsum("blc,chd->blhd", c, both,
                        preferred_element_type=jnp.float32)
        k_n, v = kv[..., :w_uk.shape[-1]], kv[..., w_uk.shape[-1]:]
        k_r = jnp.broadcast_to(r.astype(jnp.float32)[:, :, None, :],
                               r.shape[:2] + (heads, r.shape[-1]))
        return (operand(jnp.concatenate([k_n, k_r], axis=-1), dtype),
                operand(v, dtype))

    return expand


def _prefill_kernel(pos_ref, fill_ref, last_ref, qn_ref, qr_ref, c_ref,
                    r_ref, wk_ref, wv_ref, o_ref, m_ref, l_ref, acc_ref, *,
                    tile: int, block: int, scale: float, per: int):
    """One row's tile of ``tile`` queries, for the query heads of ``E``
    key heads (``per`` a key head), against one block of ``block`` cached
    positions: the block's latents expanded to the ``E`` key heads' keys
    and values in one product each (float32 out, rounded to the operands'
    dtype), the scores as the nope and rope parts' two products, running
    maximum ``m``, sum ``l`` and output ``acc`` in float32, ``p`` rounded
    to the operands' dtype for its product with the values. A block past
    the tile's last real query is not read (its index is held at the last
    block in use, which is not fetched again) and does no work; a block
    wholly before the tile's first query and the row's fill is not
    masked."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    tiles = pl.num_programs(2)
    last, fill = last_ref[b * tiles + i], fill_ref[b]
    first = pos_ref[b] + i * tile                  # the tile's first query
    dtype = qn_ref.dtype
    contract = (((1,), (1,)), ((), ()))

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(masked: bool):
        c = c_ref[...].astype(dtype)
        k_n = jnp.dot(c, wk_ref[...],
                      preferred_element_type=jnp.float32).astype(dtype)
        v = jnp.dot(c, wv_ref[...], preferred_element_type=jnp.float32)
        r = r_ref[...].astype(dtype)
        if masked:
            # values of positions not filled are zero, so that what lies
            # there (anything: a partial last block reads beyond the
            # array) meets its zero weight as zero
            down = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            v = jnp.where(down < fill, v, 0.0)
            along = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
            seen = (along <= q_pos) & (along < fill)       # (tile, block)
        v = v.astype(dtype)
        each = qn_ref.shape[0] // per           # key heads a grid step
        nope, d_v = k_n.shape[1] // each, v.shape[1] // each
        for h in range(qn_ref.shape[0]):
            g = h // per
            k_h = k_n if each == 1 else k_n[:, g * nope:(g + 1) * nope]
            s = (jax.lax.dot_general(qn_ref[h], k_h, contract,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[h], r, contract,
                                       preferred_element_type=jnp.float32)
                 ) * scale
            if masked:
                s = jnp.where(seen, s, -1e30)
            m_old = m_ref[h]                                 # lane-equal
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            shrink = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[h] = shrink * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = shrink[:, :1] * acc_ref[h] + jnp.dot(
                p.astype(dtype),
                v if each == 1 else v[:, g * d_v:(g + 1) * d_v],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    whole = ((j + 1) * block <= first + 1) & ((j + 1) * block <= fill)

    @pl.when((j <= last) & whole)
    def _():
        attend(masked=False)

    @pl.when((j <= last) & jnp.logical_not(whole))
    def _():
        attend(masked=True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for h in range(qn_ref.shape[0]):
            # a tile that did no work has l = 0 and acc = 0: zeros
            o_ref[h] = acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-30)


def _prefill_pallas(q_n, q_r, cache, w_uk, w_uv, pos, lengths, scale, dtype,
                    interpret: bool):
    """``latent_prefill`` as one kernel over (row, key heads, query tile)
    and the cache's blocks, in the form ``prefill_form`` gives: ``E`` key
    heads a grid step, the queries laid out their ``E × J`` heads at a
    time, ``(B, key heads / E, E × J, T, .)``, and the weights ``E`` key
    heads side by side, ``(key heads / E, rank, E × .)``. A tile's last
    block is that of its last real query; a tile with none (a row without
    a real token in the stretch, or past a row's real ones) reads nothing
    and gives zeros."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    c, r = cache["c"], cache["r"]
    b, t, heads, nope = q_n.shape
    rope, (rank, groups, d_v) = q_r.shape[-1], w_uv.shape
    per = heads // groups
    capacity = c.shape[1]
    block, tile, each = prefill_form(t, per, groups, capacity)
    tiles = -(-t // tile)
    steps, width = groups // each, each * per     # grid steps, query heads

    def by_head(x):
        # (B, T, heads, d) -> (B, key heads / E, E x J, tiles x tile, d)
        x = placement_cast(x, dtype).reshape(b, t, steps, width, -1)
        x = jnp.pad(x, ((0, 0), (0, tiles * tile - t)) + ((0, 0),) * 3)
        return jnp.transpose(x, (0, 2, 3, 1, 4))

    def side_by_side(w):
        # (rank, key heads, d) -> (key heads / E, rank, E x d)
        w = jnp.transpose(placement_cast(w, dtype), (1, 0, 2))
        if each == 1:
            return w
        return jnp.transpose(w.reshape(steps, each, rank, -1),
                             (0, 2, 1, 3)).reshape(steps, rank, -1)

    pos = pos.astype(jnp.int32)
    fill = pos + lengths.astype(jnp.int32)
    start = jnp.arange(tiles, dtype=jnp.int32)[None, :] * tile
    real = jnp.minimum(start + tile, lengths[:, None]) - 1   # last real query
    last = jnp.where(real >= start, (pos[:, None] + real) // block,
                     -1).reshape(-1)

    def by_tile(d):
        return pl.BlockSpec((None, None, width, tile, d),
                            lambda g0, g, i, j, *_: (g0, g, 0, i, 0))

    def by_block(d):
        return pl.BlockSpec(
            (None, block, d),
            lambda g0, g, i, j, pos, fill, last: (
                g0, jnp.minimum(j, jnp.maximum(last[g0 * tiles + i], 0)), 0))

    def by_key_head(d):
        return pl.BlockSpec((None, rank, each * d),
                            lambda g0, g, i, j, *_: (g, 0, 0))

    # what a grid step holds, in bytes: the scratch, the output's and the
    # queries' two buffers, the cache's and the weights' two, and a few
    # (tile, block) float32 scores alive at once
    item = jnp.dtype(dtype).itemsize
    vmem = (4 * width * tile * (2 * 128 + 3 * d_v)
            + 2 * item * (width * tile * (nope + 128) + block * (rank + 128)
                          + each * rank * (nope + d_v))
            + 4 * 4 * tile * block)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, tile=tile, block=block,
                          scale=scale, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, steps, tiles, pl.cdiv(capacity, block)),
            in_specs=[by_tile(nope), by_tile(rope), by_block(rank),
                      by_block(rope), by_key_head(nope), by_key_head(d_v)],
            out_specs=by_tile(d_v),
            scratch_shapes=[pltpu.VMEM((width, tile, 128), jnp.float32),
                            pltpu.VMEM((width, tile, 128), jnp.float32),
                            pltpu.VMEM((width, tile, d_v), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, steps, width, tiles * tile, d_v),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        interpret=interpret,
        name="latent_prefill",
    )(pos, fill, last, by_head(q_n), by_head(q_r), c, r,
      side_by_side(w_uk), side_by_side(w_uv))
    out = jnp.transpose(out, (0, 3, 1, 2, 4))[:, :t]
    return out.reshape(b, t, heads, d_v)


def ring_slots(pos, window: int):
    """``(B, window)``: the position each slot of a ring holds before a
    stretch that starts at ``pos`` ``(B,)``, the latest below ``pos`` of
    the slot's class mod ``window``; negative where the slot is empty."""
    import jax.numpy as jnp

    last = pos[:, None] - 1
    return last - (last - jnp.arange(window)[None, :]) % window


def ring_entries(ring, new, pos, lengths):
    """Each array of ``new`` (``(B, T, ...)``, a stretch from ``pos``)
    into the ring's array of its name, ``window`` slots: position ``p``
    goes to slot ``p mod window``, so of a stretch longer than the ring
    its last ``window`` real positions stay. One token a row is one
    scatter (``write_entries`` at the slot); a longer stretch a gather,
    a slot, of the stretch's latest real entry of the slot's class where
    it has one."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    t = next(iter(new.values())).shape[1]
    window = next(iter(ring.values())).shape[1]
    if t == 1:
        return write_entries(ring, new, pos % window, lengths)
    end = (pos + lengths - 1)[:, None]                 # last real position
    index = (lengths[:, None] - 1
             - (end - jnp.arange(window)[None, :]) % window)

    def put(old, new):
        got = jnp.take_along_axis(
            new, jnp.clip(index, 0, t - 1)[..., None], axis=1)
        return jnp.where((index >= 0)[..., None],
                         placement_cast(got, old.dtype), old)

    return {name: put(ring[name], entries) for name, entries in new.items()}


def window_read(pos, window: int):
    """Cached positions a window layer's attention has read for a row
    that has absorbed ``pos`` tokens, prompt and generated: position
    ``p`` reads the ``min(p + 1, window)`` most recent, its own
    included."""
    import jax.numpy as jnp

    return jnp.where(pos <= window, pos * (pos + 1) // 2,
                     window * (window + 1) // 2 + (pos - window) * window)


def window_prefill(q_n, q_r, ring, c, r, w_uk, w_uv, pos, lengths, *,
                   scale: float, dtype):
    """A stretch under a window of ``window`` positions (the ring's
    slots), each query's own included: the stretch's queries against
    the ring as it was before the stretch (``ring_entries`` comes after)
    and the stretch's own entries ``c``, ``r`` ``(B, T, ...)``. The
    queries are cut into blocks of ``window``; block ``i`` reads two
    blocks of keys, the ring (``i`` = 0) or the stretch's block ``i -
    1``, and the stretch's block ``i``: a block outside every one of its
    queries' windows is never read. Keys and values are expanded once
    (``_expander``; the query heads grouped over the key heads as in
    ``latent_prefill``). Returns ``(B, T, heads, d_v)`` float32."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    b, t, heads = q_n.shape[:3]
    window, groups = ring["c"].shape[1], w_uk.shape[1]
    blocks = -(-t // window)
    span = blocks * window

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, span - t)) + ((0, 0),) * (x.ndim - 2))

    def band(x):
        # (B, window + span, ...) -> (B, blocks, 2 window, ...): the two
        # blocks of keys of each block of queries
        def cut(y):
            return y.reshape((b, blocks, window) + y.shape[2:])
        return jnp.concatenate([cut(x[:, :span]), cut(x[:, window:])], axis=2)

    q = padded(operand(jnp.concatenate([q_n, q_r], axis=-1), dtype))
    q = q.reshape(b, blocks, window, groups, heads // groups, -1)
    k, v = _expander(w_uk, w_uv, dtype)(
        *(jnp.concatenate([ring[name], padded(placement_cast(
            x, ring[name].dtype))], axis=1)
          for name, x in (("c", c), ("r", r))))
    stretch = jnp.arange(span)[None, :]
    slots = ring_slots(pos, window)
    k_pos = band(jnp.concatenate([slots, pos[:, None] + stretch], axis=1))
    k_real = band(jnp.concatenate([slots >= 0, stretch < lengths[:, None]],
                                  axis=1))
    q_pos = (pos[:, None] + stretch).reshape(b, blocks, window, 1)
    seen = (k_real[:, :, None, :] & (k_pos[:, :, None, :] <= q_pos)
            & (k_pos[:, :, None, :] > q_pos - window))  # (B, blocks, q, k)
    scores = jnp.einsum("bnqgjd,bnkgd->bngjqk", q, band(k)) * scale
    p = jax.nn.softmax(jnp.where(seen[:, :, None, None], scores, -1e30),
                       axis=-1)
    o = jnp.einsum("bngjqk,bnkgd->bnqgjd", operand(p, dtype), band(v))
    return o.reshape(b, span, heads, -1)[:, :t]


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
