"""The hyper-connection residual path (``parallel/hyper.py``): the
Sinkhorn projection, the ranges of the coefficients, the two passes
against the equations written out in numpy, and the path with one
stream against the plain residual."""

import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.parallel import hyper as H

N, WIDTH, ROWS, T = 4, 32, 3, 5
SETTINGS = dict(norm_eps=1e-6, iters=20, eps=1e-6, clamp=(-30.0, 30.0))


def _leaves(seed=0, n=N, width=WIDTH):
    """Leaves off the trivial, as the benchmark's builder makes them."""
    rng = np.random.default_rng(seed)

    def normal(*shape, std=1.0):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    return dict(phi=normal(n * width, 2 * n + n * n,
                           std=(n * width) ** -0.5),
                alpha=0.6 + normal(3, std=0.2), b_pre=normal(n, std=0.2),
                b_post=normal(n, std=0.2),
                b_res=np.eye(n, dtype=np.float32) + normal(n, n, std=0.5))


def _streams(seed=1, n=N):
    rng = np.random.default_rng(seed)
    return tuple(rng.standard_normal((ROWS, T, WIDTH)).astype(np.float32)
                 for _ in range(n))


def _sums(h_res):
    h_res = np.asarray(h_res)
    return h_res.sum(axis=0), h_res.sum(axis=1)     # columns', rows'


EXTREMES = {
    # beyond the clamp: exp(1e4) would be inf and the rounds nan
    "all high": np.full((N, N), 1e4, np.float32),
    "all low": np.full((N, N), -1e4, np.float32),
    "a permutation": np.where(np.eye(N)[[2, 0, 3, 1]] > 0, 1e4, -1e4).astype(
        np.float32),
    "the identity": np.where(np.eye(N) > 0, 1e4, -1e4).astype(np.float32),
}


def test_random_logits_come_out_doubly_stochastic():
    """Logits normal at 0.5: 20 rounds leave every row and every column
    of every token within 1e-5 of 1; 1 round does not. The rounds
    converge by the matrix's spread, not by a count: at 1.0 (wider than
    the builder's 0.8 about the identity) 99 tokens in 100 are within
    1e-5 after 20 rounds and the worst of 2,000 is some 1e-4 off, which
    is what the model's 20 rounds leave there too."""
    rng = np.random.default_rng(4)
    logits = rng.standard_normal((N, N, 2000)).astype(np.float32)
    m = jnp.exp(jnp.asarray(0.5 * logits))
    columns, rows = _sums(H.sinkhorn(m, 20, 1e-6))
    assert np.abs(columns - 1).max() < 1e-5 and np.abs(rows - 1).max() < 1e-5
    columns, _ = _sums(H.sinkhorn(m, 1, 1e-6))
    assert np.abs(columns - 1).max() > 0.05
    columns, rows = _sums(H.sinkhorn(jnp.exp(jnp.asarray(logits)), 20, 1e-6))
    assert np.abs(rows - 1).max() < 1e-5
    off = np.abs(columns - 1).max(axis=0)
    assert np.percentile(off, 99) < 1e-5 and off.max() < 1e-2


@pytest.mark.parametrize("case", sorted(EXTREMES))
def test_logits_at_the_clamp_come_out_doubly_stochastic_and_finite(case):
    leaves = dict(_leaves(), alpha=np.zeros(3, np.float32),
                  b_res=EXTREMES[case])
    _, _, h_res = H.hc_coefficients(_streams(), **leaves, **SETTINGS)
    assert np.isfinite(np.asarray(h_res)).all()
    columns, rows = _sums(h_res)
    assert np.abs(columns - 1).max() < 1e-5 and np.abs(rows - 1).max() < 1e-5
    if case == "a permutation":
        assert np.allclose(np.asarray(h_res)[..., 0, 0],
                           np.eye(N)[[2, 0, 3, 1]], atol=1e-6)


def test_the_coefficients_lie_in_their_ranges_and_move_with_the_token():
    x = _streams()
    h_pre, h_post, h_res = map(np.asarray, H.hc_coefficients(
        x, **_leaves(), **SETTINGS))
    assert h_pre.shape == h_post.shape == (N, ROWS, T)
    assert h_res.shape == (N, N, ROWS, T)
    assert 0 < h_pre.min() and h_pre.max() < 1
    assert 0 < h_post.min() and h_post.max() < 2
    assert 0 < h_res.min() and h_res.max() < 1
    for h in (h_pre, h_post, h_res):                # no two tokens alike
        assert h.reshape(-1, ROWS * T).std(axis=1).min() > 1e-3
    # and far outside: still inside the open ranges' closure
    wide = dict(_leaves(), b_pre=np.full(N, 50.0, np.float32),
                b_post=np.full(N, -50.0, np.float32))
    h_pre, h_post, _ = H.hc_coefficients(x, **wide, **SETTINGS)
    assert float(h_pre.max()) <= 1 and float(h_post.min()) >= 0


def test_the_two_passes_are_the_equations():
    """Against numpy, token by token: the norm over all ``n C`` values
    before the projection (here it is applied after), ``mat`` row-major,
    the read and the write-back."""
    x, leaves = _streams(), _leaves()
    h_pre, h_post, h_res = H.hc_coefficients(x, **leaves, **SETTINGS)
    u = np.asarray(H.hc_read(x, h_pre))
    y = np.random.default_rng(9).standard_normal(
        (ROWS, T, WIDTH)).astype(np.float32)
    out = [np.asarray(s) for s in H.hc_write(x, y, h_post, h_res)]
    stacked = np.stack(x, axis=2).astype(np.float64)    # (ROWS, T, N, WIDTH)
    for b in range(ROWS):
        for t in range(T):
            token = stacked[b, t]
            flat = token.reshape(-1)
            flat = flat / np.sqrt(np.mean(flat * flat) + 1e-6)
            p, q, r = np.split(flat @ leaves["phi"].astype(np.float64),
                               [N, 2 * N])
            a = leaves["alpha"].astype(np.float64)
            pre = 1 / (1 + np.exp(-(a[0] * p + leaves["b_pre"])))
            post = 2 / (1 + np.exp(-(a[1] * q + leaves["b_post"])))
            m = np.exp(np.clip(a[2] * r.reshape(N, N) + leaves["b_res"],
                               -30, 30))
            for _ in range(20):
                m = m / (m.sum(axis=0, keepdims=True) + 1e-6)
                m = m / (m.sum(axis=1, keepdims=True) + 1e-6)
            assert np.abs(np.asarray(h_pre)[:, b, t] - pre).max() < 1e-5
            assert np.abs(np.asarray(h_post)[:, b, t] - post).max() < 1e-5
            assert np.abs(np.asarray(h_res)[:, :, b, t] - m).max() < 1e-5
            assert np.abs(u[b, t] - pre @ token).max() < 1e-5
            want = m @ token + post[:, None] * y[b, t][None, :]
            got = np.stack([s[b, t] for s in out])
            assert np.abs(got - want).max() < 1e-5


def test_one_stream_that_reads_and_writes_whole_is_the_plain_residual():
    """``n = 1``, ``alpha = 0``, ``b_pre = 30``, ``b_post = 0``: the
    sub-layer reads ``h``, and ``h + y`` comes back to 1e-6 of ``h``: a
    1 x 1 ``M`` settles at ``1 - hc_eps`` under the rounds whatever
    ``b_res`` is (each divides by the entry plus ``hc_eps``)."""
    (h,) = _streams(n=1)
    leaves = dict(_leaves(n=1), alpha=np.zeros(3, np.float32),
                  b_pre=np.full(1, 30.0, np.float32),
                  b_post=np.zeros(1, np.float32))
    h_pre, h_post, h_res = H.hc_coefficients((h,), **leaves, **SETTINGS)
    assert np.abs(np.asarray(h_res) - (1 - 1e-6)).max() < 2e-7
    u = H.hc_read((h,), h_pre)
    assert np.abs(np.asarray(u) - h).max() < 1e-6
    y = np.tanh(np.asarray(u))[..., ::-1]           # some sub-layer
    (out,) = H.hc_write((h,), jnp.asarray(y.copy()), h_post, h_res)
    assert np.abs(np.asarray(out) - (h + y)).max() < 1.2e-6 * np.abs(h).max()
