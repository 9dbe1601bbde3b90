"""Sparse experts on one chip's share: routing over every published
expert, drop-free dispatch to the experts held here, one grouped
product over them, and the weighted sum back into the tokens
(DeepSeek-V3, arXiv:2412.19437).

``route`` scores a token against all ``n`` experts (``sigmoid`` of a
float32 product), takes the ``top_k`` largest of score plus selection
bias, and weighs the chosen by their scores, normalised and scaled.
``grouped_experts`` then serves the (token, expert) pairs whose expert
lies in ``held = (first, count)``; what the experts held elsewhere
would add is left out (their chips add it).

**Dispatch** is by sorting, with no capacity and no drop: the pairs are
ordered by expert (pairs of other chips' experts and of padded tokens
last), each held expert's run is cut into tiles of ``tile`` pairs (its
last tile padded), and a loop runs over exactly the tiles in use: a
tile gathers its tokens, multiplies them with its one expert's three
matrices (a slice of the stacked ``(experts, in, out)`` leaves) and
adds the weighted rows into the output. Work follows the pairs routed
here (plus at most one tile of padding an expert), never tokens times
experts held.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple


class Routing(NamedTuple):
    experts: "jax.Array"   # noqa: F821  (N, top_k) int32, of all n
    weights: "jax.Array"   # noqa: F821  (N, top_k) float32


def route(x, router, bias, *, top_k: int, scale: float) -> Routing:
    """``x``: ``(N, hidden)`` float32; ``router``: ``(hidden, n)``;
    ``bias``: ``(n,)``, added to the scores for the choice only."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, experts, axis=1)
    weights = scale * picked / picked.sum(axis=1, keepdims=True)
    return Routing(experts.astype(jnp.int32), weights)


def swiglu(x, gate, up, down, *, dtype, limit=None):
    """``(silu(min(x gate, limit)) * clip(x up, +-limit)) down``:
    operands in ``dtype``, float32 accumulation and element-wise."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    def product(a, w):
        return jnp.matmul(placement_cast(a, dtype), placement_cast(w, dtype),
                          preferred_element_type=jnp.float32)

    g, u = product(x, gate), product(x, up)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return product(jax.nn.silu(g) * u, down)


def tile_rows(tokens: int, top_k: int, n: int) -> int:
    """Pairs a tile: about the pairs one of ``n`` experts expects from
    ``tokens`` tokens, a power of two in [64, 256]. Not under 64: a
    tile's time is the read of its expert's three matrices whatever its
    rows until the products take as long (some 240 rows in bfloat16 on
    a v5e), so a smaller tile only reads a busy expert once more for
    every few pairs (a decode step expects 4 pairs an expert and a
    skewed router sends one 60)."""
    expected = max(tokens * top_k // max(n, 1), 1)
    tile = 64
    while tile < min(expected, 256):
        tile *= 2
    return tile


def grouped_experts(x, routing: Routing, valid, gate, up, down, *,
                    held: Tuple[int, int], tile: int, dtype, limit=None):
    """The held experts' part of the layer's output.

    ``x``: ``(N, hidden)`` float32; ``valid``: ``(N,)`` bool, false at
    padded tokens (which route nowhere); ``gate``, ``up``: ``(count,
    hidden, width)``; ``down``: ``(count, width, hidden)``; ``tile``:
    pairs a tile (``tile_rows``). Returns
    ``(y, pairs, dropped)``: ``y`` ``(N, hidden)`` float32; ``pairs``
    ``(count,)`` int32, the pairs each held expert served; ``dropped``
    the pairs routed here that no tile served (0: the loop covers every
    tile in use).
    """
    import jax
    import jax.numpy as jnp

    n_tokens, top_k = routing.experts.shape
    first, count = held
    with jax.named_scope("lm.moe.dispatch"):
        local = routing.experts.reshape(-1) - first
        here = ((local >= 0) & (local < count)
                & jnp.repeat(valid, top_k))
        key = jnp.where(here, local, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        pairs = jnp.sum(key[:, None] == jnp.arange(count)[None, :],
                        axis=0, dtype=jnp.int32)
        run_start = jnp.cumsum(pairs) - pairs          # in sorted order
        tiles = -(-pairs // tile)
        tile_end = jnp.cumsum(tiles)                   # (count,)
        flat_weights = routing.weights.reshape(-1)

    def one_tile(t, carry):
        y, served = carry
        with jax.named_scope("lm.moe.dispatch"):
            e = jnp.searchsorted(tile_end, t, side="right").astype(
                jnp.int32)
            offset = (t - (tile_end[e] - tiles[e])) * tile + jnp.arange(tile)
            real = offset < pairs[e]
            pair = order[jnp.clip(run_start[e] + offset, 0,
                                  order.shape[0] - 1)]
            token = pair // top_k
            rows = jnp.take(x, token, axis=0)
        with jax.named_scope("lm.moe.experts"):
            out = swiglu(rows, *(jax.lax.dynamic_index_in_dim(
                w, e, axis=0, keepdims=False) for w in (gate, up, down)),
                dtype=dtype, limit=limit)
        with jax.named_scope("lm.moe.combine"):
            weight = jnp.where(real, flat_weights[pair], 0.0)
            y = y.at[token].add(weight[:, None] * out)
        return y, served + jnp.sum(real, dtype=jnp.int32)

    y, served = jax.lax.fori_loop(
        0, tile_end[-1], one_tile,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32)))
    return y, pairs, jnp.sum(pairs) - served
