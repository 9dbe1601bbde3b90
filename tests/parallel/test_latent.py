"""Latent attention: the expanded prefill over the cache (through
``attention.blockwise_attention``) and the absorbed decode against the
plain reference's one formula (``benchmark/reference/gigachat3_5.py``),
the cache's ragged writes, and the rotary pairing."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lookup import load_module
from mmlspark_tpu.parallel import latent as L

B, HEADS, RANK, NOPE, ROPE, DV = 2, 4, 32, 16, 8, 16
SCALE = 0.21
TOL = 2e-6


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "gigachat3_5")


def _inputs(t, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    return (normal(B, t, HEADS, NOPE), normal(B, t, HEADS, ROPE),
            normal(B, t, RANK), normal(B, t, ROPE),
            normal(RANK, HEADS, NOPE) * 0.2, normal(RANK, HEADS, DV) * 0.2)


def _dense(q_n, q_r, c, r, w_uk, w_uv):
    """The expanded form over a whole sequence, in numpy."""
    t = c.shape[0]
    k = np.concatenate([np.einsum("tc,chd->thd", c, w_uk),
                        np.broadcast_to(r[:, None], (t, HEADS, ROPE))], -1)
    q = np.concatenate([q_n, q_r], -1)
    scores = np.einsum("thd,shd->hts", q, k) * SCALE
    scores = np.where(np.tril(np.ones((t, t), bool)), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", p, np.einsum("tc,chd->thd", c, w_uv))


def test_prefill_in_stretches_then_absorbed_decode_equal_the_expanded_form():
    t, cut = 29, (0, 8, 16, 23)            # stretches of 8, 8, 7; then steps
    q_n, q_r, c, r, w_uk, w_uv = _inputs(t)
    want = np.stack([_dense(q_n[b], q_r[b], c[b], r[b], w_uk, w_uv)
                     for b in range(B)])
    cache = L.init_cache(B, 40, RANK, ROPE, jnp.float32)
    for lo, hi in zip(cut, cut[1:]):
        pos, n = jnp.full((B,), lo), jnp.full((B,), hi - lo)
        cache = L.cache_write(cache, c[:, lo:hi], r[:, lo:hi], pos, n)
        got = L.latent_prefill(q_n[:, lo:hi], q_r[:, lo:hi], cache, w_uk,
                               w_uv, pos, n, scale=SCALE, dtype=jnp.float32)
        assert np.abs(np.asarray(got) - want[:, lo:hi]).max() < TOL
    for i in range(cut[-1], t):
        pos = jnp.full((B,), i)
        cache = L.cache_write(cache, c[:, i:i + 1], r[:, i:i + 1], pos,
                              jnp.ones((B,), jnp.int32))
        got = L.latent_decode(q_n[:, i], q_r[:, i], cache, w_uk, w_uv, pos,
                              scale=SCALE, dtype=jnp.float32)
        assert np.abs(np.asarray(got) - want[:, i]).max() < TOL


def test_a_ragged_batch_writes_and_attends_at_each_rows_own_position():
    """Row 0 has absorbed 5 tokens and adds 3 of a stretch of 6; row 1
    has absorbed 11 and adds all 6: padded positions write nothing, and
    what the cache held beyond a row's fill is never attended to."""
    q_n, q_r, c, r, w_uk, w_uv = _inputs(17, seed=1)
    cache = L.init_cache(B, 24, RANK, ROPE, jnp.float32)
    before = jnp.array([5, 11])
    cache = L.cache_write(cache, c[:, :11], r[:, :11], jnp.zeros((B,), int),
                          before)
    junk = {k: v.at[:, 12:].set(7.0) for k, v in cache.items()}
    junk = {k: v.at[0, 5:].set(7.0) for k, v in junk.items()}
    real = jnp.array([3, 6])
    # each row's stretch continues its own sequence
    c_new = np.stack([c[0, 5:11], c[1, 11:17]])
    r_new = np.stack([r[0, 5:11], r[1, 11:17]])
    qn = np.stack([q_n[0, 5:11], q_n[1, 11:17]])
    qr = np.stack([q_r[0, 5:11], q_r[1, 11:17]])
    out = {}
    for name, held in (("clean", cache), ("junk", junk)):
        written = L.cache_write(held, c_new, r_new, before, real)
        out[name] = np.asarray(L.latent_prefill(
            qn, qr, written, w_uk, w_uv, before, real, scale=SCALE,
            dtype=jnp.float32))
        # row 0 wrote 3 entries and no more
        assert np.array_equal(np.asarray(written["c"])[0, 8:],
                              np.asarray(held["c"])[0, 8:])
    want0 = _dense(q_n[0, :8], q_r[0, :8], c[0, :8], r[0, :8], w_uk, w_uv)
    want1 = _dense(q_n[1], q_r[1], c[1], r[1], w_uk, w_uv)
    for got in out.values():
        assert np.abs(got[0, :3] - want0[5:8]).max() < TOL
        assert np.abs(got[1] - want1[11:17]).max() < TOL


def test_rotary_pairs_neighbours_and_follows_the_yarn_frequencies(reference):
    scaling = {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
               "mscale_all_dim": 1, "original_max_position_embeddings": 64}
    freq = L.yarn_frequencies(ROPE, 1e5, scaling)
    assert np.allclose(freq, reference.yarn_frequencies(ROPE, 1e5, scaling))
    plain = L.yarn_frequencies(ROPE, 1e5, {})
    assert freq[0] == plain[0] and np.isclose(freq[-1], plain[-1] / 8)
    x = np.random.default_rng(2).standard_normal((1, 5, 3, ROPE)).astype(
        np.float32)
    got = np.asarray(L.rotary_interleaved(x, jnp.arange(5)[None], freq))
    want = np.asarray(reference.rotary(x[0], freq, interleave=True))
    assert np.abs(got[0] - want).max() < 1e-6
    halves = np.asarray(reference.rotary(x[0], freq, interleave=False))
    assert np.abs(got[0] - halves).max() > 0.1
    # a rotation: norms of the pairs are kept
    assert np.allclose(np.linalg.norm(got, axis=-1),
                       np.linalg.norm(x, axis=-1), atol=1e-5)
    assert np.isclose(L.softmax_scale(24, scaling, True),
                      24 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
