"""Gated delta rule: the chunked prefill and the one-token step against
the plain reference's token-by-token recurrence
(``benchmark/reference/gigachat3_5.py``), and the Pallas kernel in
interpret mode against the ``jax.numpy`` form."""

import numpy as np
import pytest

from benchmark.lookup import load_module
from mmlspark_tpu.parallel import delta_rule as D

B, KH, H, DK, DV = 2, 2, 4, 16, 16
# float32 on both sides; the chunked form sums in another order than
# the recurrence and solves a triangular system of at most 64 rows
TOL = 5e-6


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "gigachat3_5")


def _inputs(t, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, t, KH, DK)).astype(np.float32) * DK ** -0.5
    k = rng.standard_normal((B, t, KH, DK)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((B, t, H, DV)).astype(np.float32)
    log_g = np.log(rng.uniform(0.9, 0.999, (B, t, H))).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (B, t, H)).astype(np.float32)
    return q, k, v, log_g, beta


def _recurrence(reference, q, k, v, log_g, beta, grouping=None):
    group = H // KH
    heads = grouping or [i // group for i in range(H)]
    return np.stack([np.asarray(reference.delta_rule(
        q[b][:, heads], k[b][:, heads], v[b], log_g[b], beta[b]))
        for b in range(B)])


@pytest.mark.parametrize("t,chunk", [(32, 8), (32, 16), (37, 8), (37, 5),
                                     (37, 37), (37, 64), (130, 64)])
def test_chunked_prefill_equals_the_recurrence(reference, t, chunk):
    """Chunk sizes that do and do not divide the length."""
    import jax.numpy as jnp

    q, k, v, log_g, beta = _inputs(t)
    want = _recurrence(reference, q, k, v, log_g, beta)
    got, _ = D.delta_prefill(q, k, v, log_g, beta, jnp.full((B,), t),
                             D.init_state(B, H, DK, DV), chunk=chunk)
    assert np.abs(np.asarray(got) - want).max() < TOL
    # value head i reads key head i // 2, not i % 2
    wrong = _recurrence(reference, q, k, v, log_g, beta, [0, 1, 0, 1])
    assert np.abs(np.asarray(got) - wrong).max() > 1e-2


def test_steps_continue_a_prefill_through_the_state(reference):
    import jax.numpy as jnp

    t, p = 37, 20
    q, k, v, log_g, beta = _inputs(t)
    want = _recurrence(reference, q, k, v, log_g, beta)
    _, s = D.delta_prefill(q[:, :p], k[:, :p], v[:, :p], log_g[:, :p],
                           beta[:, :p], jnp.full((B,), p),
                           D.init_state(B, H, DK, DV), chunk=8)
    for i in range(p, t):
        o, s = D.delta_step(q[:, i], k[:, i], v[:, i], log_g[:, i],
                            beta[:, i], s, pallas=False)
        assert np.abs(np.asarray(o) - want[:, i]).max() < TOL


def test_padding_does_not_touch_the_state():
    import jax.numpy as jnp

    q, k, v, log_g, beta = _inputs(37)
    lengths = jnp.array([20, 37])
    got, s = D.delta_prefill(q, k, v, log_g, beta, lengths,
                             D.init_state(B, H, DK, DV), chunk=8)
    short = [a[:, :20] for a in (q, k, v, log_g, beta)]
    alone, s_alone = D.delta_prefill(*short, jnp.array([20, 20]),
                                     D.init_state(B, H, DK, DV), chunk=8)
    assert np.array_equal(np.asarray(s)[0], np.asarray(s_alone)[0])
    assert np.array_equal(np.asarray(got)[0, :20], np.asarray(alone)[0])


@pytest.mark.parametrize("heads,d", [(4, 16), (32, 128)])
def test_the_decode_kernel_equals_the_jnp_step(heads, d):
    """Interpret mode, at a head count under and over one grid block."""
    import jax

    rng = np.random.default_rng(3)
    kh = heads // 2
    q = rng.standard_normal((B, kh, d)).astype(np.float32)
    k = rng.standard_normal((B, kh, d)).astype(np.float32)
    v = rng.standard_normal((B, heads, d)).astype(np.float32)
    log_g = np.log(rng.uniform(0.9, 0.999, (B, heads))).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (B, heads)).astype(np.float32)
    s = rng.standard_normal((B, heads, d, d)).astype(np.float32)
    want_o, want_s = D.delta_step(q, k, v, log_g, beta, s, pallas=False)
    got_o, got_s = jax.jit(lambda *a: D.delta_step(
        *a, pallas=True, interpret=True))(q, k, v, log_g, beta, s)
    for got, want in ((got_o, want_o), (got_s, want_s)):
        want = np.asarray(want)
        assert np.abs(np.asarray(got) - want).max() < 5e-6 * np.abs(
            want).max()
