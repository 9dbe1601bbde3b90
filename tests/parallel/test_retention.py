"""Power retention: the chunked prefill and the one-token step against
the plain reference's quadratic form (``benchmark/reference/brumby.py``),
and the Pallas kernels in interpret mode against the ``jax.numpy`` forms.
"""

import numpy as np
import pytest

from benchmark.lookup import load_module
from mmlspark_tpu.parallel import retention as R

B, H, KV, D = 2, 4, 2, 16
SCALE = D ** -0.5
# float32 on both sides; the recurrence sums in another order than the
# quadratic form, over at most 40 terms of size one: a few ulps of 1
TOL = 5e-6


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "brumby")


def _inputs(t, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, t, H, D)).astype(np.float32)
    k = rng.standard_normal((B, t, KV, D)).astype(np.float32)
    v = rng.standard_normal((B, t, KV, D)).astype(np.float32)
    log_g = np.log(rng.uniform(0.9, 0.999, (B, t, KV))).astype(np.float32)
    return q, k, v, log_g


def _quadratic(reference, q, k, v, log_g, power=2):
    return np.stack([reference.retention(q[b], k[b], v[b], log_g[b], power,
                                         SCALE, R.EPS)
                     for b in range(len(q))])


@pytest.mark.parametrize("d", [16, 128])
def test_phi_is_the_symmetric_square(d):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((5, d)).astype(np.float32)
    y = rng.standard_normal((5, d)).astype(np.float32)
    px, py = np.asarray(R.phi(x)), np.asarray(R.phi(y))
    assert px.shape == (5, d // 2 + 1, d)
    want = (x * y).sum(-1) ** 2
    assert np.abs((px * py).sum((-1, -2)) - want).max() < 1e-4 * want.max()


@pytest.mark.parametrize("t,chunk", [(32, 8), (32, 16), (37, 8), (37, 5),
                                     (37, 37), (37, 64)])
def test_chunked_prefill_equals_the_quadratic_form(reference, t, chunk):
    """Chunk sizes that do and do not divide the length."""
    import jax.numpy as jnp

    q, k, v, log_g = _inputs(t)
    want = _quadratic(reference, q, k, v, log_g)
    got, _ = R.retention_prefill(q, k, v, log_g, jnp.full((B,), t),
                                 R.init_state(B, KV, D), scale=SCALE,
                                 chunk=chunk, pallas=False)
    assert np.abs(np.asarray(got) - want).max() < TOL


def test_steps_continue_a_prefill_through_the_state(reference):
    import jax.numpy as jnp

    t, p = 37, 20
    q, k, v, log_g = _inputs(t)
    want = _quadratic(reference, q, k, v, log_g)
    _, state = R.retention_prefill(
        q[:, :p], k[:, :p], v[:, :p], log_g[:, :p], jnp.full((B,), p),
        R.init_state(B, KV, D), scale=SCALE, chunk=8, pallas=False)
    for i in range(p, t):
        y, state = R.retention_step(q[:, i], k[:, i], v[:, i], log_g[:, i],
                                    state, scale=SCALE, pallas=False)
        assert np.abs(np.asarray(y) - want[:, i]).max() < TOL


def test_padding_does_not_touch_the_state():
    import jax.numpy as jnp

    t, real = 37, 11
    q, k, v, log_g = _inputs(t)
    padded_y, padded = R.retention_prefill(
        q, k, v, log_g, jnp.array([t, real]), R.init_state(B, KV, D),
        scale=SCALE, chunk=8, pallas=False)
    alone_y, alone = R.retention_prefill(
        q[1:, :real], k[1:, :real], v[1:, :real], log_g[1:, :real],
        jnp.array([real]), R.init_state(1, KV, D), scale=SCALE, chunk=8,
        pallas=False)
    for key in ("s", "z"):
        assert np.array_equal(np.asarray(padded[key][1]),
                              np.asarray(alone[key][0]))
    assert np.array_equal(np.asarray(padded_y[1, :real]),
                          np.asarray(alone_y[0]))


@pytest.mark.parametrize("chunk", [8, 16])
def test_prefill_kernel_in_interpret_mode_equals_jax_numpy(chunk):
    import jax.numpy as jnp

    q, k, v, log_g = _inputs(32)
    lengths = jnp.array([32, 19])
    outs = [R.retention_prefill(q, k, v, log_g, lengths,
                                R.init_state(B, KV, D), scale=SCALE,
                                chunk=chunk, pallas=pallas, interpret=True)
            for pallas in (False, True)]
    (y0, s0), (y1, s1) = outs
    assert np.abs(np.asarray(y0) - np.asarray(y1))[0].max() < TOL
    assert np.abs(np.asarray(y0) - np.asarray(y1))[1, :19].max() < TOL
    for key in ("s", "z"):
        assert np.abs(np.asarray(s0[key]) - np.asarray(s1[key])).max() < TOL


def test_decode_kernel_in_interpret_mode_equals_jax_numpy():
    import jax.numpy as jnp

    q, k, v, log_g = _inputs(12)
    _, state = R.retention_prefill(
        q[:, :8], k[:, :8], v[:, :8], log_g[:, :8], jnp.full((B,), 8),
        R.init_state(B, KV, D), scale=SCALE, chunk=8, pallas=False)
    a = b = state
    for i in range(8, 12):
        ya, a = R.retention_step(q[:, i], k[:, i], v[:, i], log_g[:, i], a,
                                 scale=SCALE, pallas=False)
        yb, b = R.retention_step(q[:, i], k[:, i], v[:, i], log_g[:, i], b,
                                 scale=SCALE, pallas=True, interpret=True)
        assert np.abs(np.asarray(ya) - np.asarray(yb)).max() < TOL
    assert np.abs(np.asarray(a["s"]) - np.asarray(b["s"])).max() < TOL


@pytest.mark.parametrize("fault", ["gate", "power", "bfloat16_state"])
def test_the_comparison_can_fail(reference, fault):
    """A gate moved by 1%, power 3 for 2, a state kept in bfloat16: each
    is further from the reference than the tolerance by orders."""
    import jax
    import jax.numpy as jnp

    t, p = 37, 20
    q, k, v, log_g = _inputs(t)
    if fault == "power":
        want = _quadratic(reference, q, k, v, log_g, power=3)
    elif fault == "gate":
        want = _quadratic(reference, q, k, v, log_g * 1.01)
    else:
        want = _quadratic(reference, q, k, v, log_g)
    _, state = R.retention_prefill(
        q[:, :p], k[:, :p], v[:, :p], log_g[:, :p], jnp.full((B,), p),
        R.init_state(B, KV, D), scale=SCALE, chunk=8, pallas=False)
    worst = 0.0
    for i in range(p, t):
        if fault == "bfloat16_state":
            state = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), state)
        y, state = R.retention_step(q[:, i], k[:, i], v[:, i], log_g[:, i],
                                    state, scale=SCALE, pallas=False)
        worst = max(worst, float(np.abs(np.asarray(y) - want[:, i]).max()))
    assert worst > 100 * TOL
