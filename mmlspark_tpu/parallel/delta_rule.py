"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464): linear-time
sequence mixing whose state is one ``d_k x d_v`` matrix a value head.

For a value head with key ``k_t`` (L2-normalised), value ``v_t``, decay
``g_t`` in (0, 1] and write strength ``beta_t`` in (0, 1), and the
query ``q_t`` that reads it:

    S <- g_t S;   u_t = beta_t (v_t - S^T k_t);   S <- S + k_t u_t^T
    o_t = S^T q_t

Key head ``j`` serves the value heads ``j * group .. (j + 1) * group -
1`` (``group = value heads / key heads``). ``delta_step`` is that
recurrence for one new token; ``delta_prefill`` the chunked form over
a stretch of tokens: inside a chunk of ``C`` tokens the ``u_t`` solve
the unit lower-triangular system of the WY representation,

    (I + A) U = beta V - diag(beta Gamma) K S_0,
    A[t, j] = beta_t (Gamma_t / Gamma_j) (k_t . k_j),  j < t,

with ``Gamma_t`` the running product of the chunk's decays; across
chunks the state is carried: ``o_t = Gamma_t S_0^T q_t + sum_{j <= t}
(Gamma_t / Gamma_j) (k_j . q_t) u_j``, ``S_C = Gamma_C S_0 + sum_j
(Gamma_C / Gamma_j) k_j u_j^T``.

**State layout**: ``(B, value heads, d_k, d_v)`` float32 always; the
value's axis is the lane axis, so a decode step is elementwise on
``(d_k, d_v)`` tiles with the key and the query as columns and the
value as a row, and passes over the state once, in place.

The one-token step exists in ``jax.numpy`` (any backend; the kernel's
oracle) and as the Pallas kernel ``gdn_decode``; the platform decides
which runs (Mosaic on the TPU, ``jax.numpy`` elsewhere). The chunked
form is ``jax.numpy`` alone. Every float32 product that feeds or reads
the state runs at ``precision=HIGHEST``; the kernel has no matrix
product at all.

Padding never touches the state: a padded position has ``log g = 0``
and ``beta = 0``, so ``S`` passes through it unchanged.
"""

from __future__ import annotations

import functools
from typing import Optional

from mmlspark_tpu.parallel.retention import use_pallas

HEAD_BLOCK = 16      # value heads a launch of the decode kernel's grid
LANES = 128


def init_state(batch: int, value_heads: int, d_k: int, d_v: int):
    import jax.numpy as jnp

    return jnp.zeros((batch, value_heads, d_k, d_v), jnp.float32)


def _per_value_head(x, value_heads: int):
    """``(B, key heads, d) -> (B, value heads, d)``."""
    import jax.numpy as jnp

    return jnp.repeat(x, value_heads // x.shape[1], axis=1)


# ---------------------------------------------------------------------
# one new token


def _step_jnp(q, k, v, g, beta, s):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    s = s * g[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, s,
                                          precision=hi))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, s, precision=hi), s


def _decode_kernel(kq_ref, v_ref, g_ref, beta_ref, s_ref, s_out, o_ref,
                   *, heads: int):
    """``heads`` value heads of one sequence: each head's state tile is
    read once, decayed, corrected and written once (in place), and read
    out for its query while it is in registers. ``kq_ref`` holds the
    heads' keys as its first ``heads`` lanes and their queries as the
    next (a column a head, so that either broadcasts over the value's
    lanes); values, decays and write strengths ride as rows."""
    import jax.numpy as jnp

    kq = kq_ref[0, 0]                                 # (d_k, 128)
    for j in range(heads):
        key = kq[:, j:j + 1]                          # (d_k, 1)
        query = kq[:, heads + j:heads + j + 1]
        s = s_ref[0, j] * g_ref[0, j:j + 1, :]        # (d_k, d_v)
        read = jnp.sum(s * key, axis=0, keepdims=True)
        u = beta_ref[0, j:j + 1, :] * (v_ref[0, j:j + 1, :] - read)
        s = s + key * u
        s_out[0, j] = s
        o_ref[0, j:j + 1, :] = jnp.sum(s * query, axis=0, keepdims=True)


def _step_pallas(q, k, v, g, beta, s, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, d_k, d_v = s.shape
    block = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads
    blocks = heads // block
    # keys and queries as columns: (B, blocks, d_k, lanes), a head a lane
    cols = jnp.concatenate(
        [jnp.swapaxes(x.reshape(b, blocks, block, d_k), 2, 3)
         for x in (k, q)], axis=3)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, LANES - 2 * block),))

    def rows(x):                                      # lane-equal rows
        return jnp.broadcast_to(x[..., None], (b, heads, d_v))

    def by_head(*shape):
        return pl.BlockSpec((1, block) + shape,
                            lambda i, h: (i, h) + (0,) * len(shape))

    s, o = pl.pallas_call(
        functools.partial(_decode_kernel, heads=block),
        grid=(b, blocks),
        in_specs=[pl.BlockSpec((1, 1, d_k, LANES),
                               lambda i, h: (i, h, 0, 0)),
                  by_head(d_v), by_head(d_v), by_head(d_v),
                  by_head(d_k, d_v)],
        out_specs=[by_head(d_k, d_v), by_head(d_v)],
        out_shape=[jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, heads, d_v), jnp.float32)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gdn_decode",
    )(cols, v, rows(g), rows(beta), s)
    return o, s


def delta_step(q, k, v, log_g, beta, s, *, pallas: Optional[bool] = None,
               interpret: bool = False):
    """One token a sequence. ``q``, ``k``: ``(B, key heads, d_k)``, the
    key L2-normalised and the query scaled; ``v``: ``(B, value heads,
    d_v)``; ``log_g``, ``beta``: ``(B, value heads)``; ``s``: the state.
    Returns ``(o, s)``, ``o`` ``(B, value heads, d_v)`` float32."""
    import jax.numpy as jnp

    heads = v.shape[1]
    q = _per_value_head(q.astype(jnp.float32), heads)
    k = _per_value_head(k.astype(jnp.float32), heads)
    g = jnp.exp(log_g.astype(jnp.float32))
    args = (q, k, v.astype(jnp.float32), g, beta.astype(jnp.float32), s)
    if use_pallas() if pallas is None else pallas:
        return _step_pallas(*args, interpret)
    return _step_jnp(*args)


# ---------------------------------------------------------------------
# a stretch of tokens


def _chunk(s, xs):
    """One chunk of ``C`` tokens. ``s``: ``(B, kh, G, d_k, d_v)``;
    ``q``, ``k``: ``(B, kh, C, d_k)``; ``v``: ``(B, kh, G, C, d_v)``;
    ``log_g``, ``beta``: ``(B, kh, G, C)``. Returns ``(s, o)``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    q, k, v, log_g, beta = xs
    c, d_v = q.shape[2], v.shape[-1]
    cum = jnp.cumsum(log_g, axis=-1)                  # log Gamma_t
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              0.0))                   # Gamma_t / Gamma_j
    kk = jnp.einsum("bhtd,bhsd->bhts", k, k, precision=hi)[:, :, None]
    a = jnp.where(jnp.tril(lower, -1), beta[..., None] * kk * decay, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v,
         (beta * jnp.exp(cum))[..., None] * k[:, :, None]], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    u = solved[..., :d_v] - jnp.einsum(
        "bhgtk,bhgkv->bhgtv", solved[..., d_v:], s, precision=hi)
    qk = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=hi)[:, :, None]
    o = (jnp.einsum("bhgtk,bhgkv->bhgtv",
                    jnp.exp(cum)[..., None] * q[:, :, None], s,
                    precision=hi)
         + jnp.einsum("bhgts,bhgsv->bhgtv",
                      jnp.where(lower, qk * decay, 0.0), u, precision=hi))
    left = jnp.exp(cum[..., -1:] - cum)               # Gamma_C / Gamma_j
    s = (jnp.exp(cum[..., -1])[..., None, None] * s
         + jnp.einsum("bhgsk,bhgsv->bhgkv", left[..., None] * k[:, :, None],
                      u, precision=hi))
    return s, o


def delta_prefill(q, k, v, log_g, beta, lengths, s, *, chunk: int = 64):
    """A stretch of ``T`` tokens a sequence, ``lengths`` of them real
    (the rest is padding at the end and leaves the state alone).
    ``q``, ``k``: ``(B, T, key heads, d_k)``; ``v``: ``(B, T, value
    heads, d_v)``; ``log_g``, ``beta``: ``(B, T, value heads)``;
    ``lengths``: ``(B,)``. ``T`` is cut into chunks of ``chunk`` tokens
    (the last one padded). Returns ``(o, s)``, ``o`` ``(B, T, value
    heads, d_v)`` float32."""
    import jax
    import jax.numpy as jnp

    b, t, kh, d_k = q.shape
    heads, d_v = v.shape[2:]
    group = heads // kh
    c = min(chunk, t)
    n = -(-t // c)
    valid = jnp.arange(n * c)[None, :] < lengths[:, None]     # (B, T')

    def lay(x, gate=False):
        """``(B, T, h, ...) -> (n, B, h, C, ...)``; a gate is zeroed at
        the padded positions."""
        x = x.astype(jnp.float32)
        x = jnp.pad(x, [(0, 0), (0, n * c - t)] + [(0, 0)] * (x.ndim - 2))
        if gate:
            x = jnp.where(valid[..., None], x, 0.0)
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    def grouped(x):
        """``(n, B, heads, C, ...) -> (n, B, kh, G, C, ...)``."""
        return x.reshape((n, b, kh, group) + x.shape[3:])

    xs = (lay(q), lay(k), grouped(lay(v)), grouped(lay(log_g, True)),
          grouped(lay(beta, True)))
    s, o = jax.lax.scan(_chunk, s.reshape(b, kh, group, d_k, d_v), xs)
    # (n, B, kh, G, C, d_v) -> (B, T, heads, d_v)
    o = jnp.moveaxis(o.reshape(n, b, heads, c, d_v), 0, 1)
    o = jnp.moveaxis(o, 2, 3).reshape(b, n * c, heads, d_v)
    return o[:, :t], s.reshape(b, heads, d_k, d_v)
