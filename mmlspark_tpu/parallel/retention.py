"""Power retention (arXiv:2507.04239): attention-free sequence mixing
whose state is the symmetric square of the keys.

For a key-value head with keys ``k_t``, values ``v_t`` and gates
``g_t`` (all of width ``d``), and the query heads that read it:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = S_t^T phi(s q_t) / (z_t . phi(s q_t) + eps)

``phi`` is the symmetric square, ``phi(x) . phi(y) = (x . y) ** 2``, so
the same ``y`` is ``sum_r a[t, r] v_r / (sum_r a[t, r] + eps)`` with
``a[t, r] = prod_{u=r+1..t} g_u * (s q_t . k_r) ** 2``: quadratic in the
length inside a chunk of tokens, linear across chunks through the
state. ``retention_prefill`` is that chunked form over a whole prompt,
``retention_step`` the recurrence for one new token.

**The packing of phi.** The ``d (d + 1) / 2`` distinct products
``x_a x_b`` are held as ``d / 2 + 1`` rows of ``d``, row ``j`` being
``x * roll(x, -j) * w_j`` (every unordered pair at circular distance
``j``), ``w_0 = 1``, ``w_j = sqrt(2)`` and ``w_{d/2} = 1`` (that row
holds each of its pairs twice). At ``d = 128`` that is 65 x 128 = 8320
entries for the 8256 distinct ones: every row is one lane-aligned roll,
no gather, 0.8% of redundancy.

**State layout**: ``{"s": (B, kv, d/2+1, d_v, d), "z": (B, kv, d/2+1,
d)}``, float32 always. The last axis of ``s`` is phi's lane axis, the
one before it the value's, so that a decode step is elementwise on
``(d_v, d)`` tiles with phi as a row and the value as a column, and a
prefill chunk is one ``A @ B^T`` (read) and one ``A^T @ B`` (update) a
row of phi (the quadratic part inside a chunk is plain XLA).

Each operation exists once in ``jax.numpy`` (any backend; the kernels'
oracle) and once as a Pallas kernel (``retention_prefill``,
``retention_decode``). The platform decides which runs, as it does for
the histogram kernel: Mosaic on the TPU, ``jax.numpy`` elsewhere.
Every float32 product that feeds or reads the state runs at
``precision=HIGHEST`` (Mosaic's default rounds float32 operands to
bfloat16, which interpret mode cannot show: PERF.md, PR 22).

Padding never touches the state: a padded position has ``log g = 0``
and ``k = 0``, so ``S`` and ``z`` pass through it unchanged.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import numpy as np

from mmlspark_tpu.parallel.mosaic import use_pallas

EPS = 1e-6
State = Dict[str, "jax.Array"]  # noqa: F821


def phi_rows(d: int) -> int:
    return d // 2 + 1


def state_shapes(batch: int, kv_heads: int, d: int) -> Dict[str, tuple]:
    r = phi_rows(d)
    return {"s": (batch, kv_heads, r, d, d), "z": (batch, kv_heads, r, d)}


def init_state(batch: int, kv_heads: int, d: int) -> State:
    import jax.numpy as jnp

    return {k: jnp.zeros(shape, jnp.float32)
            for k, shape in state_shapes(batch, kv_heads, d).items()}


@functools.lru_cache(maxsize=None)
def _phi_tables(d: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(select, weight)``: ``x @ select`` is ``roll(x, -j)`` for every
    row ``j`` side by side (a one-hot product is exact at HIGHEST), and
    ``weight`` the rows' ``w_j``, both flattened to ``(d/2+1) * d``."""
    r = phi_rows(d)
    select = np.zeros((d, r, d), np.float32)
    lanes = np.arange(d)
    for j in range(r):
        select[(lanes + j) % d, j, lanes] = 1.0
    weight = np.full((r, d), math.sqrt(2.0), np.float32)
    weight[0] = weight[d // 2] = 1.0
    return select.reshape(d, r * d), weight.reshape(r * d)


def phi(x):
    """``(..., d) -> (..., d/2+1, d)``, float32."""
    import jax
    import jax.numpy as jnp

    d = x.shape[-1]
    select, weight = _phi_tables(d)
    x = x.astype(jnp.float32)
    rolled = jnp.matmul(x, select, precision=jax.lax.Precision.HIGHEST)
    out = jnp.tile(x, phi_rows(d)) * rolled * weight
    return out.reshape(x.shape[:-1] + (phi_rows(d), d))


def _grouped(q, kv_heads: int):
    """``(B, ..., heads, d) -> (B, ..., kv, group, d)``: query head ``i``
    reads key-value head ``i // group``."""
    heads = q.shape[-2]
    return q.reshape(q.shape[:-2] + (kv_heads, heads // kv_heads,
                                     q.shape[-1]))


# ---------------------------------------------------------------------
# one new token


def _step_jnp(phiq, phik, v, g, state):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    s = (g[..., None, None, None] * state["s"]
         + v[:, :, None, :, None] * phik[:, :, :, None, :])
    num = jnp.einsum("bhgjr,bhjvr->bhgv", phiq, s, precision=hi)
    return num, s


def _pad8(n: int) -> int:
    return -(-n // 8) * 8


def _decode_kernel(g_ref, v_ref, phi_ref, s_ref, s_out, acc_out,
                   *, rows: int, group: int, d: int, sub: int):
    """One block of ``rows`` phi rows of one (sequence, kv head): the
    state tile is read once, updated, written once (in place), and
    read out for the ``group`` query heads while it is in registers.
    ``phi_ref`` holds the queries' phi in its first ``group`` sublanes
    and the key's in the next."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_out[...] = jnp.zeros_like(acc_out)

    g = g_ref[0, 0, 0:1, :]                           # (1, d)
    for lo in range(0, d, sub):                       # value rows
        v = v_ref[0, 0, lo:lo + sub, :]               # (sub, d), lane-equal
        acc = [jnp.zeros((sub, d), jnp.float32) for _ in range(group)]
        for j in range(rows):
            new = (g * s_ref[0, 0, j, lo:lo + sub, :]
                   + v * phi_ref[0, 0, j, group:group + 1, :])
            s_out[0, 0, j, lo:lo + sub, :] = new
            for i in range(group):
                acc[i] = acc[i] + new * phi_ref[0, 0, j, i:i + 1, :]
        for i in range(group):
            acc_out[0, 0, i, lo:lo + sub, :] += acc[i]


def _row_block(r: int, limit: int) -> int:
    """Largest divisor of ``r`` not above ``limit``."""
    return max(b for b in range(1, min(r, limit) + 1) if r % b == 0)


def _step_pallas(phiq, phik, v, g, state, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kv, r, d = phik.shape
    group = phiq.shape[2]
    rows = _row_block(r, 13)
    sub = min(8, d)
    # every operand in whole (8, 128) tiles: a vector rides as 8 equal
    # sublanes, the phis of a kv head share one tile a row
    wide = _pad8(group + 1)
    g_tile = jnp.broadcast_to(g[:, :, None, None], (b, kv, 8, d))
    v_tile = jnp.broadcast_to(v[:, :, :, None], (b, kv, d, d))
    phis = jnp.concatenate(
        [jnp.swapaxes(phiq, 2, 3), phik[:, :, :, None, :],
         jnp.zeros((b, kv, r, wide - group - 1, d), jnp.float32)], axis=3)
    kernel = functools.partial(_decode_kernel, rows=rows, group=group, d=d,
                               sub=sub)
    s, acc = pl.pallas_call(
        kernel,
        grid=(b, kv, r // rows),
        in_specs=[
            pl.BlockSpec((1, 1, 8, d), lambda i, h, j: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, d, d), lambda i, h, j: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, rows, wide, d),
                         lambda i, h, j: (i, h, j, 0, 0)),
            pl.BlockSpec((1, 1, rows, d, d), lambda i, h, j: (i, h, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rows, d, d), lambda i, h, j: (i, h, j, 0, 0)),
            pl.BlockSpec((1, 1, group, d, d), lambda i, h, j: (i, h, 0, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(state["s"].shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, kv, group, d, d), jnp.float32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="retention_decode",
    )(g_tile, v_tile, phis, state["s"])
    return acc.sum(axis=-1), s


def retention_step(q, k, v, log_g, state: State, *, scale: float,
                   eps: float = EPS, pallas: Optional[bool] = None,
                   interpret: bool = False):
    """One token a sequence. ``q``: ``(B, heads, d)``; ``k``, ``v``:
    ``(B, kv, d)``; ``log_g``: ``(B, kv)``. Returns ``(y, state)``,
    ``y`` ``(B, heads, d)`` float32."""
    import jax
    import jax.numpy as jnp

    kv = k.shape[1]
    g = jnp.exp(log_g.astype(jnp.float32))
    phik = phi(k)                                     # (B, kv, r, d)
    phiq = phi(_grouped(q, kv) * scale)               # (B, kv, group, r, d)
    v = v.astype(jnp.float32)
    if use_pallas() if pallas is None else pallas:
        num, s = _step_pallas(phiq, phik, v, g, state, interpret)
    else:
        num, s = _step_jnp(phiq, phik, v, g, state)
    z = g[..., None, None] * state["z"] + phik
    den = jnp.einsum("bhgjr,bhjr->bhg", phiq, z,
                     precision=jax.lax.Precision.HIGHEST)
    y = num / (den[..., None] + eps)
    return y.reshape(q.shape), {"s": s, "z": z}


# ---------------------------------------------------------------------
# a chunk of tokens


def _chunk_inside(q, k, v, cum):
    """The quadratic part: what the chunk's own keys and values give its
    queries. ``q``: ``(B, kv, group, C, d)`` scaled; ``k``, ``v``: ``(B,
    kv, C, d)``; ``cum``: ``(B, kv, C)`` running sum of ``log g`` in the
    chunk. Returns ``(num, den)``, ``num`` ``(B, kv, group, C, d)``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    c = k.shape[2]
    mask = jnp.tril(jnp.ones((c, c), bool))
    score = jnp.einsum("bhgtd,bhsd->bhgts", q, k, precision=hi)
    decay = jnp.where(mask, cum[..., :, None] - cum[..., None, :], 0.0)
    a = jnp.where(mask, jnp.exp(decay)[:, :, None] * score * score, 0.0)
    return (jnp.einsum("bhgts,bhsv->bhgtv", a, v, precision=hi),
            a.sum(axis=-1))


def _chunk_jnp(q, k, v, cum, state):
    """What the state carried into the chunk gives its queries, and the
    chunk folded into the state. Returns ``(num, den, state)``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    carried = jnp.exp(cum)[:, :, None, :]             # (B, kv, 1, C)
    phiq = phi(q)                                     # (B, kv, g, C, r, d)
    num = carried[..., None] * jnp.einsum(
        "bhgtjr,bhjvr->bhgtv", phiq, state["s"], precision=hi)
    den = carried * jnp.einsum(
        "bhgtjr,bhjr->bhgt", phiq, state["z"], precision=hi)
    left = jnp.exp(cum[..., -1:] - cum)               # decay to chunk's end
    phik = phi(k) * left[..., None, None]             # (B, kv, C, r, d)
    total = jnp.exp(cum[..., -1])
    s = (total[..., None, None, None] * state["s"]
         + jnp.einsum("bhsjr,bhsv->bhjvr", phik, v, precision=hi))
    z = total[..., None, None] * state["z"] + phik.sum(axis=2)
    return num, den, {"s": s, "z": z}


def _prefill_kernel(q_ref, k_ref, v_ref, carried_ref, left_ref, total_ref,
                    s_ref, z_ref, out, s_out, z_out, *, rows: int, d: int):
    """One block of phi rows of one (sequence, kv head) for one chunk:
    its rows of the state are read for the queries, then the chunk's
    keys and values are folded into them, in place. ``out`` gathers
    over the blocks: lanes ``[0, d)`` the queries' read of ``S``, lanes
    ``[d, 2 d)`` of ``z`` (summed over lanes outside). Every operand is
    whole (8, 128) tiles: ``z`` rides as 8 equal sublanes a row."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    block = pl.program_id(2)

    @pl.when(block == 0)
    def _():
        out[...] = jnp.zeros_like(out)

    q = q_ref[0, 0]                                   # (group * c, d)
    k = k_ref[0, 0]                                   # (c, d)
    left = left_ref[0, 0]                             # (c, d), lane-equal
    total = total_ref[0, 0, 0:1, :]                   # (1, d), lane-equal
    v_left = v_ref[0, 0] * left                       # (c, d_v)
    num = jnp.zeros(q.shape, f32)
    den = jnp.zeros(q.shape, f32)
    for j in range(rows):
        row = block * rows + j
        shift = jax.lax.rem(d - row, d)
        w = jnp.where((row == 0) | (row == d // 2), 1.0,
                      math.sqrt(2.0)).astype(f32)
        phiq = q * pltpu.roll(q, shift, 1) * w
        phik = k * pltpu.roll(k, shift, 1) * w
        # phiq @ S_j^T, then S_j += v_left^T @ phik
        num = num + jax.lax.dot_general(
            phiq, s_ref[0, 0, j], (((1,), (1,)), ((), ())),
            precision=hi, preferred_element_type=f32)
        den = den + phiq * z_ref[0, 0, j, 0:1, :]
        s_out[0, 0, j] = total * s_ref[0, 0, j] + jax.lax.dot_general(
            v_left, phik, (((0,), (0,)), ((), ())),
            precision=hi, preferred_element_type=f32)
        z_new = total * z_ref[0, 0, j, 0:1, :] + jnp.sum(
            phik * left, axis=0, keepdims=True)
        z_out[0, 0, j] = jnp.broadcast_to(z_new, (8, d))
    carried = carried_ref[0, 0]                       # (group * c, d)
    out[0, 0, :, 0:d] += carried * num
    out[0, 0, :, d:2 * d] += carried * den


def _chunk_pallas(q, k, v, cum, state, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kv, group, c, d = q.shape
    r = phi_rows(d)
    rows = _row_block(r, 13)
    q2 = q.reshape(b, kv, group * c, d)
    carried = jnp.broadcast_to(
        jnp.tile(jnp.exp(cum), (1, 1, group))[..., None],
        (b, kv, group * c, d))
    left = jnp.broadcast_to(jnp.exp(cum[..., -1:] - cum)[..., None],
                            (b, kv, c, d))            # decay to chunk's end
    total = jnp.broadcast_to(jnp.exp(cum[..., -1])[..., None, None],
                             (b, kv, 8, d))
    z8 = jnp.broadcast_to(state["z"][:, :, :, None, :], (b, kv, r, 8, d))
    kernel = functools.partial(_prefill_kernel, rows=rows, d=d)

    def whole(*shape):
        return pl.BlockSpec((1, 1) + shape,
                            lambda i, h, j: (i, h) + (0,) * len(shape))

    def by_row(*shape):
        return pl.BlockSpec((1, 1, rows) + shape,
                            lambda i, h, j: (i, h, j) + (0,) * len(shape))

    out, s, z = pl.pallas_call(
        kernel,
        grid=(b, kv, r // rows),
        in_specs=[whole(group * c, d), whole(c, d), whole(c, d),
                  whole(group * c, d), whole(c, d), whole(8, d),
                  by_row(d, d), by_row(8, d)],
        out_specs=[whole(group * c, 2 * d), by_row(d, d), by_row(8, d)],
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, group * c, 2 * d), jnp.float32),
            jax.ShapeDtypeStruct(state["s"].shape, jnp.float32),
            jax.ShapeDtypeStruct((b, kv, r, 8, d), jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="retention_prefill",
    )(q2, k, v, carried, left, total, state["s"], z8)
    return (out[..., :d].reshape(b, kv, group, c, d),
            out[..., d:].sum(axis=-1).reshape(b, kv, group, c),
            {"s": s, "z": z[:, :, :, 0, :]})


def retention_prefill(q, k, v, log_g, lengths, state: State, *,
                      scale: float, chunk: int = 128, eps: float = EPS,
                      pallas: Optional[bool] = None,
                      interpret: bool = False):
    """A stretch of ``T`` tokens a sequence, ``lengths`` of them real
    (the rest is padding at the end and leaves the state alone).
    ``q``: ``(B, T, heads, d)``; ``k``, ``v``: ``(B, T, kv, d)``;
    ``log_g``: ``(B, T, kv)``; ``lengths``: ``(B,)``. ``T`` is cut into
    chunks of ``chunk`` tokens (the last one padded). Returns ``(y,
    state)``, ``y`` ``(B, T, heads, d)`` float32."""
    import jax
    import jax.numpy as jnp

    b, t, heads, d = q.shape
    kv = k.shape[2]
    c = min(chunk, t)
    n = -(-t // c)
    valid = (jnp.arange(n * c)[None, :] < lengths[:, None])   # (B, T')

    def lay(x, fill_mask=True):
        """``(B, T, h, ...) -> (n, B, h, C, ...)``, padding zeroed."""
        x = x.astype(jnp.float32)
        x = jnp.pad(x, [(0, 0), (0, n * c - t)] + [(0, 0)] * (x.ndim - 2))
        if fill_mask:
            x = jnp.where(valid.reshape(valid.shape + (1,) * (x.ndim - 2)),
                          x, 0.0)
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    qs = lay(q * scale, fill_mask=False)              # (n, B, heads, C, d)
    qs = qs.reshape(n, b, kv, heads // kv, c, d)
    ks, vs, gs = lay(k), lay(v), lay(log_g)           # gs: (n, B, kv, C)
    run = _chunk_pallas if (use_pallas() if pallas is None else pallas) \
        else None

    def body(carry, xs):
        qc, kc, vc, gc = xs
        cum = jnp.cumsum(gc, axis=-1)
        num, den = _chunk_inside(qc, kc, vc, cum)
        if run is None:
            more, under, carry = _chunk_jnp(qc, kc, vc, cum, carry)
        else:
            more, under, carry = run(qc, kc, vc, cum, carry, interpret)
        return carry, (num + more) / ((den + under)[..., None] + eps)

    state, y = jax.lax.scan(body, state, (qs, ks, vs, gs))
    # (n, B, kv, group, C, d) -> (B, T, heads, d)
    y = jnp.moveaxis(y.reshape(n, b, heads, c, d), 0, 1)
    y = jnp.moveaxis(y, 2, 3).reshape(b, n * c, heads, d)
    return y[:, :t], state
