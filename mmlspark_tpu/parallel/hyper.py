"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): a residual path of ``n`` streams
a token in place of one.

A token's stream is ``X`` (``n`` vectors of the model's width). Around
a sub-layer ``F`` with its own leaves ``phi``, ``alpha``, ``b_pre``,
``b_post`` and ``b_res``:

    x^ = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)      (no learned scale)
    [p | q | r] = x^ phi,  phi: (n width, 2 n + n^2)
    H_pre = sigmoid(alpha[0] p + b_pre)                 (n,)
    H_post = 2 sigmoid(alpha[1] q + b_post)             (n,)
    M = exp(clip(alpha[2] mat(r) + b_res, clamp))       (n, n), row-major
    H_res = ``iters`` rounds on M of: columns over (column sums + eps),
            then rows over (row sums + eps)   -- nearly doubly stochastic
    u = sum_j H_pre[j] X[j];  y = F(norm(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

All of it is float32. The streams are a tuple of ``n`` arrays ``(...,
width)``, each of the model's usual shape, and a coefficient is ``(n,
...)`` or ``(n, n, ...)`` with the tokens last: the tokens fill the
lanes in the Sinkhorn rounds, and no array has ``n`` among its last two
axes. A sub-layer makes two passes over the streams:
:func:`hc_coefficients` and :func:`hc_read` (norm, projection and read:
the un-scaled norm commutes with the product, so ``x phi`` and ``sum
x^2`` can come from one read of ``x``), and :func:`hc_write`. The
passes stand under the scopes ``lm.hc.mix``, ``lm.hc.read`` and
``lm.hc.write``; whoever calls them stands under ``lm.hc``.
"""

from __future__ import annotations

from typing import Tuple


def sinkhorn(m, iters: int, eps: float):
    """``m``: ``(n, n, ...)`` positive, rows on the first axis.
    ``iters`` rounds of: each column over its sum plus ``eps``, then
    each row over its sum plus ``eps`` (the paper's ``T_r(T_c(.))``).
    A round's sums are written entry by entry, so that it is
    element-wise over the tokens and one kernel (as reductions over the
    leading axes the compiler launched four a round), and the rounds
    are a loop it keeps rolled (unrolled into one expression, twelve
    sub-layers of twenty rounds took it over twenty minutes)."""
    import jax
    import jax.numpy as jnp

    n = m.shape[0]

    def one_round(_, m):
        m = [[m[i, j] for j in range(n)] for i in range(n)]
        below = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
        m = [[m[i][j] / below[j] for j in range(n)] for i in range(n)]
        return jnp.stack([jnp.stack([entry / (sum(row) + eps)
                                     for entry in row]) for row in m])

    return jax.lax.fori_loop(0, iters, one_round, m)


def hc_coefficients(x, phi, alpha, b_pre, b_post, b_res, *, norm_eps: float,
                    iters: int, eps: float, clamp: Tuple[float, float]):
    """``(H_pre (n, ...), H_post (n, ...), H_res (n, n, ...))`` of the
    streams ``x`` (``n`` arrays ``(..., width)``)."""
    import jax
    import jax.numpy as jnp

    n, width = len(x), x[0].shape[-1]
    with jax.named_scope("lm.hc.mix"):
        x = [stream.astype(jnp.float32) for stream in x]
        phi = phi.astype(jnp.float32).reshape(n, width, -1)
        alpha = alpha.astype(jnp.float32)
        projected = sum(jnp.matmul(x[j], phi[j],
                                   precision=jax.lax.Precision.HIGHEST)
                        for j in range(n))
        mean_square = sum(jnp.sum(x[j] * x[j], axis=-1)
                          for j in range(n)) / (n * width)
        projected = jnp.moveaxis(
            projected * jax.lax.rsqrt(mean_square + norm_eps)[..., None],
            -1, 0)                                      # (2 n + n^2, ...)
        tokens = (1,) * (projected.ndim - 1)

        def bias(b):
            return b.astype(jnp.float32).reshape(b.shape + tokens)

        p, q, r = projected[:n], projected[n:2 * n], projected[2 * n:]
        h_pre = jax.nn.sigmoid(alpha[0] * p + bias(b_pre))
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * q + bias(b_post))
        logits = alpha[2] * r.reshape((n, n) + r.shape[1:]) + bias(b_res)
        h_res = sinkhorn(jnp.exp(jnp.clip(logits, *clamp)), iters, eps)
    return h_pre, h_post, h_res


def hc_read(x, h_pre):
    """What the sub-layer reads: ``sum_j H_pre[j] x[j]``, ``(...,
    width)``. It leaves through an ``optimization_barrier``: without
    one the compiler fuses this pass over the streams into the
    sub-layer's own pre-norm, and the device's seconds for it would
    stand under the sub-layer's scope, not the path's."""
    import jax

    with jax.named_scope("lm.hc.read"):
        return jax.lax.optimization_barrier(
            sum(h_pre[j][..., None] * x[j] for j in range(len(x))))


def hc_write(x, y, h_post, h_res):
    """The streams after the sub-layer's output ``y`` ``(..., width)``:
    ``x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y``. ``y`` enters
    through an ``optimization_barrier``, for the reason ``hc_read``'s
    result leaves through one: the write-back is not to be fused into
    the sub-layer's last product."""
    import jax

    n = len(x)
    with jax.named_scope("lm.hc.write"):
        y = jax.lax.optimization_barrier(y)
        return tuple(sum(h_res[i, j][..., None] * x[j] for j in range(n))
                     + h_post[i][..., None] * y for i in range(n))
