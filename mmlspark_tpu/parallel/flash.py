"""Pallas TPU flash-attention kernel.

The hand-scheduled hot-op layer SURVEY.md §2.7 mandates for the
long-context path: one fused kernel per (batch, head, q-block) keeps
the online-softmax accumulators in VMEM and streams KV blocks through
the MXU — no (n, n) score materialization, no HBM round trips between
the matmul, softmax and weighted-sum stages (the XLA fallback in
:mod:`mmlspark_tpu.parallel.attention` pays one HBM pass per scan
step's carry).

Numerics match :func:`~mmlspark_tpu.parallel.attention.dense_attention`
to float tolerance; CPU tests run the same kernel in interpret mode.
"""

from __future__ import annotations

import functools

import numpy as np

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  scale: float, q_block: int):
    """One (batch*head, q-block) program: stream KV blocks, online
    softmax in f32 VMEM registers."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32) * scale          # (block_q, d)
    nk = k_ref.shape[1]
    iq = pl.program_id(1)
    q_pos = iq * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    (q.shape[0], 1), 0)

    def body(i, carry):
        acc, m, l = carry
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = q @ kb.T                                   # (block_q, block_k)
        if causal:
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        blk_max = jnp.max(s, axis=1)
        new_m = jnp.maximum(m, blk_max)
        p = jnp.exp(s - new_m[:, None])
        corr = jnp.exp(m - new_m)
        new_l = l * corr + jnp.sum(p, axis=1)
        new_acc = acc * corr[:, None] + p @ vb
        return new_acc, new_m, new_l

    d = q.shape[1]
    acc0 = jnp.zeros((q.shape[0], d), jnp.float32)
    m0 = jnp.full((q.shape[0],), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk // block_k, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


_JIT_CACHE = {}


def flash_attention(q, k, v, block_q: int = 128, block_k: int = 128,
                    causal: bool = False, interpret: bool = False):
    """Fused attention: q/k/v (batch, seq, heads, head_dim) -> same
    shape. Sequence lengths must divide the block sizes; the whole
    per-(batch, head) K/V stream lives in VMEM, so ``seq * head_dim``
    is bounded by VMEM (~1M f32 elements per operand)."""
    import jax

    key = (block_q, block_k, causal, interpret)
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = jax.jit(functools.partial(
            _flash_call, block_q=block_q, block_k=block_k, causal=causal,
            interpret=interpret))
    return _JIT_CACHE[key](q, k, v)


def _flash_call(q, k, v, *, block_q: int, block_k: int, causal: bool,
                interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, n, h, d = q.shape
    nk = k.shape[1]
    block_q = min(block_q, n)
    block_k = min(block_k, nk)
    if n % block_q or nk % block_k:
        raise ValueError(f"seq lengths ({n}, {nk}) must be divisible by "
                         f"blocks ({block_q}, {block_k})")
    scale = 1.0 / (d ** 0.5)
    # (b, n, h, d) -> (b*h, n, d): one grid row per (batch, head)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, n, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, nk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, nk, d)

    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=scale, q_block=block_q)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, n, d), q.dtype),
        grid=(b * h, n // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda ib, iq: (ib, iq, 0)),
            pl.BlockSpec((1, nk, d), lambda ib, iq: (ib, 0, 0)),
            pl.BlockSpec((1, nk, d), lambda ib, iq: (ib, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda ib, iq: (ib, iq, 0)),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, h, n, d).transpose(0, 2, 1, 3)


def flash_available() -> bool:
    """True when the compiled kernel should be used: a real TPU backend
    AND the MMLSPARK_TPU_FLASH=1 opt-in. The kernel has only ever been
    exercised in interpret mode and nothing in the package calls it
    (ROADMAP D5), so until a compile on the chip and an A/B against
    blockwise_attention are recorded in PERF.md, production paths
    default to the known-good XLA formulation rather than first-contact
    a Mosaic compile."""
    import jax

    from mmlspark_tpu.core.env import env_flag
    return jax.default_backend() == "tpu" and env_flag("MMLSPARK_TPU_FLASH")
