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
tile gathers its tokens and multiplies them with its one expert's
three matrices (a slice of the stacked ``(experts, in, out)`` leaves).
Work follows the pairs routed here (plus at most one tile of padding
an expert), never tokens times experts held.

**Combine**, by the held share ``count / n`` (``one_pass_combine``):
where the chip holds a small share, each tile adds its weighted rows
into the output by token (a scatter-add a tile); where it holds
``ONE_PASS_MIN_SHARE`` of the router's experts or more, each tile
writes its weighted rows in place into a buffer in the pairs' sorted
order, and one pass after the loop sums a token's ``top_k`` rows back
(it reads every pair's row, so it pays only where most pairs are
served here).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple


class Routing(NamedTuple):
    experts: "jax.Array"   # noqa: F821  (N, top_k) int32, of all n
    weights: "jax.Array"   # noqa: F821  (N, top_k) float32


class PolyNorm(NamedTuple):
    """The PolyNorm activation (arXiv:2411.03884) in place of SiLU on
    the gate: ``scale (w0 n(u^3) + w1 n(u^2) + w2 n(u) + clip(b,
    +-clamp))``, ``n(v) = v / sqrt(mean(v^2) + eps)`` over the
    intermediate width. ``weight`` ``(..., 3)`` and ``bias`` ``(...,
    1)``: one MLP's, or stacked an expert (``grouped_experts`` takes
    expert ``e``'s)."""
    weight: "jax.Array"    # noqa: F821
    bias: "jax.Array"      # noqa: F821
    scale: float
    clamp: float
    eps: float

    def __call__(self, u):
        import jax
        import jax.numpy as jnp

        w = self.weight.astype(jnp.float32)
        b = jnp.clip(self.bias.astype(jnp.float32), -self.clamp, self.clamp)

        def n(v):
            return v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                                     + self.eps)

        return self.scale * (w[0] * n(u * u * u) + w[1] * n(u * u)
                             + w[2] * n(u) + b[0])

    def expert(self, e):
        """Expert ``e``'s of a stacked one."""
        import jax

        return self._replace(**{k: jax.lax.dynamic_index_in_dim(
            getattr(self, k), e, axis=0, keepdims=False)
            for k in ("weight", "bias")})


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


def swiglu(x, gate, up, down, *, dtype, limit=None, poly=None):
    """``(silu(min(x gate, limit)) * clip(x up, +-limit)) down``:
    operands in ``dtype``, float32 accumulation and element-wise. With
    ``poly`` (a :class:`PolyNorm`) the gate's activation is that in
    place of SiLU."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.shard_rules import placement_cast

    def product(a, w):
        return jnp.matmul(placement_cast(a, dtype), placement_cast(w, dtype),
                          preferred_element_type=jnp.float32)

    g, u = product(x, gate), product(x, up)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return product((jax.nn.silu(g) if poly is None else poly(g)) * u, down)


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


# The held share from which the one-pass combine is taken. The one-layer
# A/B at the cell's widths (tools/experts_combine_ab.py; PERF.md §6,
# PR 40) has the one pass win from about 0.55 at 4,096 tokens and from
# under 1/16 at 64; the two partial-hold cells run 1/16 and 1/32.
ONE_PASS_MIN_SHARE = 0.75


def one_pass_combine(count: int, n: int) -> bool:
    """Whether ``grouped_experts`` holding ``count`` of the router's
    ``n`` experts combines in one pass after the tile loop (else a
    scatter-add a tile). Static: shapes alone decide."""
    return count >= ONE_PASS_MIN_SHARE * n


def grouped_experts(x, routing: Routing, valid, gate, up, down, *,
                    held: Tuple[int, int], n: int, tile: int, dtype,
                    limit=None, poly=None):
    """The held experts' part of the layer's output.

    ``x``: ``(N, hidden)`` float32; ``valid``: ``(N,)`` bool, false at
    padded tokens (which route nowhere); ``gate``, ``up``: ``(count,
    hidden, width)``; ``down``: ``(count, width, hidden)``; ``n``: the
    router's width, which with ``count`` chooses the combine
    (``one_pass_combine``); ``tile``: pairs a tile (``tile_rows``);
    ``poly``: a :class:`PolyNorm` stacked an expert, the experts'
    activation in place of SiLU. Returns ``(y, pairs, dropped)``: ``y``
    ``(N, hidden)`` float32; ``pairs`` ``(count,)`` int32, the pairs
    each held expert served; ``dropped`` the pairs routed here that no
    tile served (0: the loop covers every tile in use).
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

    def one_tile(t):
        """Tile ``t``: its expert, its rows' offsets in the expert's
        run, which are real, their tokens, and the weighted output."""
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
                dtype=dtype, limit=limit,
                poly=None if poly is None else poly.expert(e))
        with jax.named_scope("lm.moe.combine"):
            weight = jnp.where(real, flat_weights[pair], 0.0)
            return e, offset, real, token, weight[:, None] * out

    def add_tile(t, carry):
        y, served = carry
        _, _, real, token, weighted = one_tile(t)
        with jax.named_scope("lm.moe.combine"):
            y = y.at[token].add(weighted)
        return y, served + jnp.sum(real, dtype=jnp.int32)

    def write_tile(t, carry):
        # in ascending tiles an expert's padded tail lands on the next
        # expert's first rows, which its first tile then writes; the
        # last expert's lands on the ``tile`` rows of slack
        buf, served = carry
        e, offset, real, _, weighted = one_tile(t)
        with jax.named_scope("lm.moe.combine"):
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, weighted, run_start[e] + offset[0], axis=0)
        return buf, served + jnp.sum(real, dtype=jnp.int32)

    if not one_pass_combine(count, n):
        y, served = jax.lax.fori_loop(
            0, tile_end[-1], add_tile,
            (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32)))
        return y, pairs, jnp.sum(pairs) - served
    with jax.named_scope("lm.moe.combine"):
        buf = jnp.zeros((order.shape[0] + tile, x.shape[1]), jnp.float32)
    buf, served = jax.lax.fori_loop(
        0, tile_end[-1], write_tile, (buf, jnp.zeros((), jnp.int32)))
    with jax.named_scope("lm.moe.combine"):
        y = _sum_back(buf, order, jnp.sum(pairs), n_tokens, top_k)
    return y, pairs, jnp.sum(pairs) - served


def _sum_back(buf, order, written, n_tokens: int, top_k: int):
    """``(n_tokens, hidden)``: each token's ``top_k`` rows of ``buf``
    (pair ``token × top_k + k`` lies at row ``inverse(order)``) summed
    left to right in ascending row, which is ascending expert, as the
    tile loop adds them; a row past the ``written`` ones (a padded
    token's pair, an expert's held elsewhere, a tile's padding) gives 0
    by a select, whatever the row holds. XLA fuses no gather into its
    consumer, so the sum is a rolled loop of ``top_k`` steps, a gather
    and an add each: one gathered ``(n_tokens, hidden)`` array at a
    time, never ``(n_tokens, top_k, hidden)``."""
    import jax
    import jax.numpy as jnp

    at = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype),
        unique_indices=True)
    at = jnp.sort(at.reshape(n_tokens, top_k), axis=1)

    def row(k):
        at_k = jax.lax.dynamic_index_in_dim(at, k, axis=1, keepdims=False)
        return jnp.where((at_k < written)[:, None],
                         jnp.take(buf, at_k, axis=0, mode="clip"), 0.0)

    return jax.lax.fori_loop(1, top_k, lambda k, y: y + row(k), row(0))
