"""Grouped-query attention over a plain key/value cache, full or over a
window with a learned sink logit a head (MiMo-V2's layers).

``heads`` query heads attend over ``key heads`` key and value heads,
``heads / key heads`` consecutive query heads a key head. A key is
``rope`` rotated dims followed by ``nope`` that pass as they are; a
value has ``d_v`` dims. A position's cache entry is its key heads'
rotated parts, their other parts and their values, each flattened over
the key heads:

    {"k_r": (B, L, key heads x rope), "k_n": (B, L, key heads x nope),
     "v": (B, L, key heads x d_v)}

in the model's dtype, ``L`` the capacity of a full layer's cache or the
window of a window layer's ring (position ``p`` in slot ``p mod
window``). Flattened over the heads, each array's last axis is a
multiple of the TPU's 128 lanes at MiMo's widths (4 or 8 key heads of
64, 128 and 128), so a block of positions is one read and no lane is
padding. Writes are ``latent.write_entries`` and ``latent.ring_entries``.

The two forms of one formula, ``o_h = sum_j p_hj v_g(h),j`` with
``p_hj = exp(s_hj) / (exp(b_h) + sum_j exp(s_hj))``, ``b_h`` the head's
sink logit where the layer has one (else the plain softmax):

- ``kv_decode`` (one token a row): on the TPU the Pallas kernel
  ``kv_decode``, flash-decoding over blocks of cached positions. A row's
  query heads are the rows of one tile, each laid on its own key head's
  lanes (zeros on the others'), so a block of keys and values is read
  once for all the heads and the scores are one product; the running
  maximum starts at the sink logit and the running sum at one, which
  puts the sink in the denominator. Blocks past a group of rows'
  farthest position are not read. Elsewhere its ``jax.numpy`` twin.
- ``kv_prefill`` (a stretch of a full layer): on the TPU the Pallas
  kernel ``kv_prefill`` over (row, key heads, query tile) and blocks of the
  cache: scores, running maximum and sum and the float32 accumulator
  stay in VMEM, the blocks past a tile's last real query are not read
  (as ``latent.latent_prefill``'s kernel). Elsewhere
  ``attention.blockwise_attention``'s scan over the cache, its query
  heads grouped as ``attention._block_attend`` groups them.
- ``window_prefill`` (a stretch of a window layer, ``jax.numpy``): the
  stretch's queries in blocks of ``window``, each against two blocks of
  keys, the ring or the stretch's block before and its own.

Scores, softmax and accumulation are float32; the operands of each
product are in the model's dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

from mmlspark_tpu.parallel.mosaic import operand, use_pallas

DECODE_ROWS = 8      # rows a grid step of the decode kernel
DECODE_BLOCK = 256   # cached positions a grid step of the decode kernel
PREFILL_BLOCK = 512  # most cached positions a grid step of the prefill kernel
PREFILL_TILE = 256   # queries a row a grid step of the prefill kernel
LANES = 128          # a key head's group of a grid step fills whole lanes
NAMES = ("k_r", "k_n", "v")


def init_cache(batch: int, length: int, key_heads: int, rope: int,
               nope: int, d_v: int, dtype):
    import jax.numpy as jnp

    return {name: jnp.zeros((batch, length, key_heads * width), dtype)
            for name, width in zip(NAMES, (rope, nope, d_v))}


def entries(k, v, rope: int):
    """A stretch's cache entries from its keys ``(B, T, key heads, rope
    + nope)`` (rotated) and values ``(B, T, key heads, d_v)``."""
    b, t = k.shape[:2]
    return {"k_r": k[..., :rope].reshape(b, t, -1),
            "k_n": k[..., rope:].reshape(b, t, -1),
            "v": v.reshape(b, t, -1)}


def _keys(cache, key_heads: int):
    """``(keys (B, L, key heads, rope + nope), values (B, L, key heads,
    d_v))`` of cached entries, as stored."""
    import jax.numpy as jnp

    def heads(x):
        return x.reshape(x.shape[:2] + (key_heads, -1))

    return (jnp.concatenate([heads(cache["k_r"]), heads(cache["k_n"])],
                            axis=-1), heads(cache["v"]))


def _softmax(scores, sink):
    """Softmax over the last axis with ``exp(sink)`` in the denominator
    where ``sink`` (broadcast to the scores less their last axis) is
    given; masked scores are -1e30."""
    import jax
    import jax.numpy as jnp

    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    top = jnp.maximum(jnp.max(scores, axis=-1), sink)
    p = jnp.exp(scores - top[..., None])
    return p / (jnp.sum(p, axis=-1) + jnp.exp(sink - top))[..., None]


# -- decode -------------------------------------------------------------


def _decode_jnp(q, cache, pos, scale, dtype, sink):
    """``(B, heads, d_v)`` float32 over the whole length, masked past
    ``pos``."""
    import jax.numpy as jnp

    b, heads, width = q.shape
    k, v = _keys(cache, _key_heads(cache, width))
    groups = k.shape[2]
    q = operand(q, dtype).reshape(b, groups, heads // groups, -1)
    scores = jnp.einsum("bgjd,blgd->bgjl", q, operand(k, dtype)) * scale
    filled = jnp.arange(k.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(filled[:, None, None, :], scores, -1e30)
    p = _softmax(scores, None if sink is None
                 else sink.reshape(1, groups, heads // groups))
    v = jnp.where(filled[..., None, None], v, jnp.zeros_like(v))
    o = jnp.einsum("bgjl,blgd->bgjd", operand(p, dtype), operand(v, dtype))
    return o.reshape(b, heads, -1)


def _key_heads(cache, width: int) -> int:
    """The key heads of a cache whose keys are ``width`` wide a head."""
    return (cache["k_r"].shape[-1] + cache["k_n"].shape[-1]) // width


def _by_key_head(x, groups: int):
    """``(B, heads, d)`` -> ``(B, heads, groups x d)``: each query head's
    ``d`` dims on its key head's lanes, zeros on the others'."""
    import jax.numpy as jnp

    b, heads, d = x.shape
    own = (jnp.arange(heads)[:, None] // (heads // groups)
           == jnp.arange(groups)[None, :])
    return (x[:, :, None, :] * own[None, :, :, None].astype(x.dtype)
            ).reshape(b, heads, groups * d)


def _decode_kernel(pos_ref, last_ref, qr_ref, qn_ref, sink_ref, kr_ref,
                   kn_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, rows: int,
                   block: int, scale: float, groups: int, sink: bool):
    """A group of ``rows`` rows against one block of cached positions:
    each row's query heads, laid on their key heads' lanes, against the
    block's keys in one product for all heads, running maximum ``m``
    (from the sink logit), sum ``l`` (from one, with a sink) and the
    values of every key head ``acc`` in float32; at the end each head
    keeps its own key head's values. A block past the group's farthest
    position is not read (its index is held at the last block in use,
    which is not fetched again) and does no work."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    g, j = pl.program_id(0), pl.program_id(1)
    heads = qr_ref.shape[1]
    per, d_v = heads // groups, o_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        if sink:
            m_ref[...] = jnp.broadcast_to(sink_ref[...][None], m_ref.shape)
            l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
        else:
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
        dtype = qr_ref.dtype
        for i in range(rows):
            at = pos_ref[g * rows + i]
            s = (jax.lax.dot_general(qr_ref[i], kr_ref[i].astype(dtype),
                                     contract,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qn_ref[i], kn_ref[i].astype(dtype),
                                       contract,
                                       preferred_element_type=jnp.float32))
            s = jnp.where(along <= at, s * scale, -1e30)  # (heads, block)
            # the values of positions not filled are zero, so that what
            # lies there (anything: a partial last block reads beyond
            # the array) meets its zero weight as zero
            v = jnp.where(down <= at, v_ref[i],
                          jnp.zeros_like(v_ref[i])).astype(dtype)
            m_old = m_ref[i]                                # lane-equal
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            shrink = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[i] = shrink * l_ref[i] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[i] = shrink[:, :1] * acc_ref[i] + jnp.dot(
                p.astype(dtype), v, preferred_element_type=jnp.float32)
            m_ref[i] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for i in range(rows):
            for k in range(groups):
                mine = slice(k * per, (k + 1) * per)
                o_ref[i, mine, :] = (acc_ref[i, mine, k * d_v:(k + 1) * d_v]
                                     / l_ref[i, mine, :1])


def _decode_pallas(q, cache, pos, scale, dtype, sink, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    b, heads, width = q.shape
    length, rope_lanes = cache["k_r"].shape[1:]
    nope_lanes, v_lanes = cache["k_n"].shape[-1], cache["v"].shape[-1]
    groups = _key_heads(cache, width)
    rope, d_v = rope_lanes // groups, v_lanes // groups
    rows = next(n for n in (DECODE_ROWS, 4, 2, 1) if b % n == 0)
    block = min(DECODE_BLOCK, length)
    pos = pos.astype(jnp.int32)
    last = jnp.max(pos.reshape(b // rows, rows), axis=1) // block
    q = placement_cast(q, dtype)
    q_r, q_n = (_by_key_head(x, groups) for x in (q[..., :rope],
                                                   q[..., rope:]))
    sinks = jnp.broadcast_to(
        (jnp.zeros((heads,), jnp.float32) if sink is None
         else sink.astype(jnp.float32))[:, None], (heads, 128))

    def by_row(*shape):
        return pl.BlockSpec((rows,) + shape,
                            lambda g, j, pos, last: (g,) + (0,) * len(shape))

    def by_block(lanes):
        return pl.BlockSpec(
            (rows, block, lanes),
            lambda g, j, pos, last: (g, jnp.minimum(j, last[g]), 0))

    item = jnp.dtype(dtype).itemsize
    vmem = (2 * item * rows * (block * (rope_lanes + nope_lanes + v_lanes)
                               + heads * (rope_lanes + nope_lanes))
            + 4 * rows * heads * (2 * 128 + v_lanes + 2 * d_v)
            + 4 * 4 * heads * block)
    return pl.pallas_call(
        functools.partial(_decode_kernel, rows=rows, block=block,
                          scale=scale, groups=groups,
                          sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rows, pl.cdiv(length, block)),
            in_specs=[by_row(heads, rope_lanes), by_row(heads, nope_lanes),
                      pl.BlockSpec((heads, 128),
                                   lambda g, j, pos, last: (0, 0)),
                      by_block(rope_lanes), by_block(nope_lanes),
                      by_block(v_lanes)],
            out_specs=by_row(heads, d_v),
            scratch_shapes=[pltpu.VMEM((rows, heads, 128), jnp.float32),
                            pltpu.VMEM((rows, heads, 128), jnp.float32),
                            pltpu.VMEM((rows, heads, v_lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, d_v), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        interpret=interpret,
        name="kv_decode",
    )(pos, last, q_r, q_n, sinks, cache["k_r"], cache["k_n"], cache["v"])


def kv_decode(q, cache, pos, *, scale: float, dtype, sink=None,
              pallas: Optional[bool] = None, interpret: bool = False):
    """One token a row. ``q``: ``(B, heads, rope + nope)`` rotated;
    ``cache`` (or a ring) holds the token already; ``pos`` ``(B,)``: the
    last slot a row reads (its position in a cache; in a ring the last
    filled slot, all of them once the ring has turned); ``sink``:
    ``(heads,)`` or ``None``. Returns ``(B, heads, d_v)`` float32."""
    if use_pallas() if pallas is None else pallas:
        return _decode_pallas(q, cache, pos, scale, dtype, sink, interpret)
    return _decode_jnp(q, cache, pos, scale, dtype, sink)


# -- a full layer's prefill ----------------------------------------------


def prefill_form(t: int, groups: int, rope: int, nope: int, d_v: int,
                 capacity: int):
    """``(block, tile, key heads a grid step)`` of the prefill kernel for
    ``t`` queries a row over ``groups`` key heads: a tile of at most
    ``PREFILL_TILE`` queries (``t`` rounded up to 8), a block of at most
    ``PREFILL_BLOCK`` positions, and the fewest key heads a step whose
    rotated, other and value dims each fill whole lanes (``LANES``; all
    of them where none do)."""
    tile = min(PREFILL_TILE, -(-t // 8) * 8)
    each = next((e for e in range(1, groups + 1) if groups % e == 0
                 and all(e * d % LANES == 0 for d in (rope, nope, d_v))),
                groups)
    return min(PREFILL_BLOCK, 2 * tile, capacity), tile, each


def kv_prefill(q, cache, pos, lengths, *, scale: float, dtype,
               pallas: Optional[bool] = None, interpret: bool = False):
    """A full layer's stretch. ``q``: ``(B, T, heads, rope + nope)``
    rotated; ``cache`` already holds the stretch (``latent.write_entries``);
    ``pos``: ``(B,)`` the stretch's first position. Returns ``(B, T,
    heads, d_v)`` float32. The kernel on the TPU (``pallas`` pins a
    form): it gives a row without a real token in the stretch zeros, the
    scan the softmax of whatever it may see; the real queries' are the
    same."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.attention import blockwise_attention

    t, width = q.shape[1], q.shape[-1]
    groups = _key_heads(cache, width)
    if use_pallas() if pallas is None else pallas:
        return _prefill_pallas(q, cache, pos, lengths, scale, dtype,
                               interpret)
    k, v = _keys(cache, groups)
    return blockwise_attention(
        operand(q, dtype), operand(k, dtype), operand(v, dtype),
        block_size=PREFILL_BLOCK, causal=True, scale=scale,
        q_positions=pos[:, None] + jnp.arange(t), kv_lengths=pos + lengths,
        kv_limit=jnp.max(pos + lengths))


def _prefill_kernel(pos_ref, fill_ref, last_ref, qr_ref, qn_ref, kr_ref,
                    kn_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                    tile: int, block: int, scale: float, per: int):
    """One row's tile of ``tile`` queries, for the query heads of ``E``
    key heads (``per`` a key head), against one block of ``block``
    cached positions: a head's scores as its rotated part's product
    (its query on its key head's lanes of the ``E``, zeros on the
    others') plus its other part's against its key head's lanes, running
    maximum ``m``, sum ``l`` and output ``acc`` in float32, ``p`` rounded
    to the operands' dtype for its product with the values. A block past
    the tile's last real query is not read (its index is held at the
    last block in use, which is not fetched again) and does no work; a
    block wholly before the tile's first query and the row's fill is not
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
    nope, d_v = qn_ref.shape[-1], o_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(masked: bool):
        k_r = kr_ref[...].astype(dtype)
        k_n = kn_ref[...].astype(dtype)
        v = v_ref[...]
        if masked:
            # values of positions not filled are zero, so that what lies
            # there (anything: a partial last block reads beyond the
            # array) meets its zero weight as zero
            down = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            v = jnp.where(down < fill, v, jnp.zeros_like(v))
            along = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
            seen = (along <= q_pos) & (along < fill)       # (tile, block)
        v = v.astype(dtype)
        for e in range(qn_ref.shape[0] // per):
            k_e = k_n[:, e * nope:(e + 1) * nope]
            v_e = v[:, e * d_v:(e + 1) * d_v]

            def head(h, carry, k_e=k_e, v_e=v_e):
                s = (jax.lax.dot_general(qr_ref[h], k_r, contract,
                                         preferred_element_type=jnp.float32)
                     + jax.lax.dot_general(qn_ref[h], k_e, contract,
                                           preferred_element_type=jnp.float32)
                     ) * scale
                if masked:
                    s = jnp.where(seen, s, -1e30)
                m_old = m_ref[h]                             # lane-equal
                m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
                shrink = jnp.exp(m_old - m_new)
                p = jnp.exp(s - m_new[:, :1])
                l_ref[h] = shrink * l_ref[h] + jnp.sum(p, axis=1,
                                                       keepdims=True)
                acc_ref[h] = shrink[:, :1] * acc_ref[h] + jnp.dot(
                    p.astype(dtype), v_e, preferred_element_type=jnp.float32)
                m_ref[h] = m_new
                return carry

            # a loop over the key head's query heads: Mosaic compiles one
            # copy of the body, where a copy a head (32 a grid step, and
            # both masks) took it 21 s
            jax.lax.fori_loop(e * per, (e + 1) * per, head, 0)

    whole = ((j + 1) * block <= first + 1) & ((j + 1) * block <= fill)

    @pl.when((j <= last) & whole)
    def _():
        attend(masked=False)

    @pl.when((j <= last) & jnp.logical_not(whole))
    def _():
        attend(masked=True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        # a tile that did no work has l = 0 and acc = 0: zeros
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...][:, :, :1], 1e-30)


def _prefill_pallas(q, cache, pos, lengths, scale, dtype, interpret: bool):
    """``kv_prefill`` as one kernel over (row, key heads, query tile)
    and the cache's blocks, in the form ``prefill_form`` gives: ``E`` key
    heads a grid step, the queries laid out their ``E × J`` heads at a
    time, ``(B, key heads / E, E × J, T, .)``, the rotated part of each
    on its key head's lanes of the ``E``. A tile's last block is that of
    its last real query; a tile with none (a row without a real token in
    the stretch, or past a row's real ones) reads nothing and gives
    zeros."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    b, t, heads, width = q.shape
    capacity, rope_lanes = cache["k_r"].shape[1:]
    groups = _key_heads(cache, width)
    rope = rope_lanes // groups
    nope, d_v = width - rope, cache["v"].shape[-1] // groups
    per = heads // groups
    block, tile, each = prefill_form(t, groups, rope, nope, d_v, capacity)
    tiles = -(-t // tile)
    steps, rows = groups // each, each * per      # grid steps, query heads

    def by_head(x):
        # (B, T, heads, d) -> (B, key heads / E, E x J, tiles x tile, d)
        x = placement_cast(x, dtype).reshape(b, t, steps, rows, -1)
        x = jnp.pad(x, ((0, 0), (0, tiles * tile - t)) + ((0, 0),) * 3)
        return jnp.transpose(x, (0, 2, 3, 1, 4))

    # the rotated parts on their key heads' lanes of the E a step
    own = (jnp.arange(rows)[:, None] // per == jnp.arange(each)[None, :])
    q_r = (q[..., :rope].reshape(b, t, steps, rows, 1, rope)
           * own[:, :, None].astype(q.dtype)).reshape(b, t, heads,
                                                      each * rope)
    pos = pos.astype(jnp.int32)
    fill = pos + lengths.astype(jnp.int32)
    start = jnp.arange(tiles, dtype=jnp.int32)[None, :] * tile
    real = jnp.minimum(start + tile, lengths[:, None]) - 1   # last real query
    last = jnp.where(real >= start, (pos[:, None] + real) // block,
                     -1).reshape(-1)

    def by_tile(d):
        return pl.BlockSpec((None, None, rows, tile, d),
                            lambda g0, g, i, j, *_: (g0, g, 0, i, 0))

    def by_block(d):
        return pl.BlockSpec(
            (None, block, each * d),
            lambda g0, g, i, j, pos, fill, last: (
                g0, jnp.minimum(j, jnp.maximum(last[g0 * tiles + i], 0)), g))

    # what a grid step holds, in bytes: the scratch, the output's and the
    # queries' two buffers, the cache's two, and a few (tile, block)
    # float32 scores alive at once
    item = jnp.dtype(dtype).itemsize
    vmem = (4 * rows * tile * (2 * 128 + 3 * d_v)
            + 2 * item * (rows * tile * (each * rope + nope)
                          + block * each * (rope + nope + d_v))
            + 4 * 4 * tile * block)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, tile=tile, block=block,
                          scale=scale, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, steps, tiles, pl.cdiv(capacity, block)),
            in_specs=[by_tile(each * rope), by_tile(nope), by_block(rope),
                      by_block(nope), by_block(d_v)],
            out_specs=by_tile(d_v),
            scratch_shapes=[pltpu.VMEM((rows, tile, 128), jnp.float32),
                            pltpu.VMEM((rows, tile, 128), jnp.float32),
                            pltpu.VMEM((rows, tile, d_v), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, steps, rows, tiles * tile, d_v),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        interpret=interpret,
        name="kv_prefill",
    )(pos, fill, last, by_head(q_r), by_head(q[..., rope:]), cache["k_r"],
      cache["k_n"], cache["v"])
    out = jnp.transpose(out, (0, 3, 1, 2, 4))[:, :t]
    return out.reshape(b, t, heads, d_v)


# -- a window layer's prefill --------------------------------------------


def window_prefill(q, ring, new, pos, lengths, *, scale: float, dtype,
                   sink=None):
    """A stretch under a window of ``window`` positions (the ring's
    slots), each query's own included: the stretch's queries ``(B, T,
    heads, rope + nope)`` against the ring as it was before the stretch
    (``latent.ring_entries`` after) and the stretch's own ``entries``
    ``new``. The queries are cut into blocks of ``window``; block ``i``
    reads two blocks of keys, the ring (``i`` = 0) or the stretch's
    block ``i - 1``, and the stretch's block ``i``: a block outside
    every one of its queries' windows is never read. ``sink``:
    ``(heads,)`` or ``None``. Returns ``(B, T, heads, d_v)`` float32."""
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.latent import ring_slots
    from mmlspark_tpu.parallel.shard_rules import placement_cast

    b, t, heads, width = q.shape
    window = ring["v"].shape[1]
    groups = _key_heads(ring, width)
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

    q = padded(operand(q, dtype)).reshape(b, blocks, window, groups,
                                           heads // groups, width)
    k, v = _keys({name: jnp.concatenate(
        [ring[name], padded(placement_cast(new[name], ring[name].dtype))],
        axis=1) for name in NAMES}, groups)
    stretch = jnp.arange(span)[None, :]
    slots = ring_slots(pos, window)
    k_pos = band(jnp.concatenate([slots, pos[:, None] + stretch], axis=1))
    k_real = band(jnp.concatenate([slots >= 0, stretch < lengths[:, None]],
                                  axis=1))
    q_pos = (pos[:, None] + stretch).reshape(b, blocks, window, 1)
    seen = (k_real[:, :, None, :] & (k_pos[:, :, None, :] <= q_pos)
            & (k_pos[:, :, None, :] > q_pos - window))  # (B, blocks, q, k)
    scores = jnp.einsum("bnqgjd,bnkgd->bngjqk", q,
                        operand(band(k), dtype)) * scale
    p = _softmax(jnp.where(seen[:, :, None, None], scores, -1e30),
                 None if sink is None else
                 sink.reshape(1, 1, groups, heads // groups, 1))
    o = jnp.einsum("bngjqk,bnkgd->bnqgjd", operand(p, dtype),
                   operand(band(v), dtype))
    return o.reshape(b, span, heads, -1)[:, :t]

