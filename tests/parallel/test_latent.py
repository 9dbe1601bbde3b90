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


# -- the decode kernel (interpreted) and the one-token write -------------

ROWS, WIDE_RANK, WIDE_ROPE = 16, 128, 64        # two groups of 8 rows


def _decode_inputs(capacity, seed=3):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    cache = {"c": normal(ROWS, capacity, WIDE_RANK),
             "r": normal(ROWS, capacity, WIDE_ROPE)}
    # ragged; row 0 at position 0, row 1 at the last one (a full cache),
    # rows 2 and 3 on either side of the first block boundary
    pos = rng.integers(0, capacity, ROWS).astype(np.int32)
    pos[:4] = [0, capacity - 1, min(127, capacity - 2), min(128, capacity - 1)]
    return (cache, pos, normal(ROWS, HEADS, NOPE), normal(ROWS, HEADS,
                                                          WIDE_ROPE),
            normal(WIDE_RANK, HEADS, NOPE) * 0.2,
            normal(WIDE_RANK, HEADS, DV) * 0.2)


def _poisoned(cache, pos):
    """NaN wherever a row has not filled."""
    out = {k: np.array(v) for k, v in cache.items()}
    for b, p in enumerate(pos):
        for v in out.values():
            v[b, p + 1:] = np.nan
    return {k: jnp.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("capacity", [300, 256, 128, 100],
                         ids=["a partial last block", "whole blocks",
                              "one block", "under a block"])
def test_decode_kernel_equals_its_twin_and_reads_nothing_past_a_row(capacity):
    cache, pos, q_n, q_r, w_uk, w_uv = _decode_inputs(capacity)
    kw = dict(scale=SCALE, dtype=jnp.float32)
    clean = {k: jnp.asarray(v) for k, v in cache.items()}
    want = np.asarray(L.latent_decode(q_n, q_r, clean, w_uk, w_uv, pos,
                                      pallas=False, **kw))
    assert np.isfinite(want).all()
    dirty = _poisoned(cache, pos)
    for pallas in (True, False):          # the twin masks its values too
        got = np.asarray(L.latent_decode(
            q_n, q_r, dirty, w_uk, w_uv, pos, pallas=pallas,
            interpret=pallas, **kw))
        assert np.abs(got - want).max() < 5 * TOL, pallas


def test_decode_kernel_rounds_its_operands_as_the_twin_does():
    """A bfloat16 cache and model: queries, cache and softmax weights
    are rounded to bfloat16 in both, so they differ by the order of the
    accumulation and by where the weights are normalised (before the
    rounding in the twin, after the sum in the kernel)."""
    cache, pos, q_n, q_r, w_uk, w_uv = _decode_inputs(256, seed=4)
    cache = {k: jnp.asarray(v, jnp.bfloat16) for k, v in cache.items()}
    kw = dict(scale=SCALE, dtype=jnp.bfloat16)
    twin = np.asarray(L.latent_decode(q_n, q_r, cache, w_uk, w_uv, pos,
                                      pallas=False, **kw))
    kernel = np.asarray(L.latent_decode(q_n, q_r, cache, w_uk, w_uv, pos,
                                        pallas=True, interpret=True, **kw))
    exact = np.asarray(L.latent_decode(
        q_n, q_r, {k: v.astype(jnp.float32) for k, v in cache.items()},
        w_uk, w_uv, pos, pallas=False, scale=SCALE, dtype=jnp.float32))
    scale = np.abs(exact).max()
    assert np.abs(kernel - twin).max() < 0.01 * scale
    assert np.abs(kernel - exact).max() < 0.02 * scale


@pytest.mark.parametrize("rows", [5, 8], ids=["odd rows", "a group"])
def test_the_one_scatter_write_equals_a_slice_a_row(rows):
    rng = np.random.default_rng(5)
    capacity = 12
    cache = {"c": jnp.asarray(rng.standard_normal((rows, capacity, RANK)),
                              jnp.float32),
             "r": jnp.asarray(rng.standard_normal((rows, capacity, ROPE)),
                              jnp.float32)}
    c = rng.standard_normal((rows, 1, RANK)).astype(np.float32)
    r = rng.standard_normal((rows, 1, ROPE)).astype(np.float32)
    pos = rng.integers(0, capacity, rows).astype(np.int32)
    pos[0], pos[1] = 0, capacity - 1
    real = np.ones(rows, np.int32)
    real[2] = 0                                  # a padded row writes nothing
    got = L.cache_write(cache, c, r, jnp.asarray(pos), jnp.asarray(real))
    # the write of a stretch (a slice a row), given the token and a
    # padded second position: what a one-token write was before
    two = L.cache_write(
        {k: jnp.pad(v, ((0, 0), (0, 1), (0, 0))) for k, v in cache.items()},
        np.concatenate([c, c], 1), np.concatenate([r, r], 1),
        jnp.asarray(pos), jnp.asarray(real))
    want = {k: np.array(v) for k, v in cache.items()}
    for b in range(rows):
        if real[b]:
            want["c"][b, pos[b]], want["r"][b, pos[b]] = c[b, 0], r[b, 0]
    for k in ("c", "r"):
        assert np.array_equal(np.asarray(got[k]), want[k])
        assert np.array_equal(np.asarray(two[k])[:, :capacity], want[k])


def test_a_bounded_prefill_visits_only_the_blocks_in_use():
    """``kv_limit`` changes no number: the blocks it leaves out are the
    ones every row masks."""
    from mmlspark_tpu.parallel.attention import blockwise_attention

    rng = np.random.default_rng(6)
    q = rng.standard_normal((2, 4, 3, 8)).astype(np.float32)
    k = rng.standard_normal((2, 64, 3, 8)).astype(np.float32)
    v = rng.standard_normal((2, 64, 3, 8)).astype(np.float32)
    v[:, 21:] = np.nan                          # never visited: block 16
    kw = dict(block_size=16, causal=True,
              q_positions=jnp.array([[5, 6, 7, 8], [12, 13, 14, 15]]),
              kv_lengths=jnp.array([9, 16]))
    bounded = np.asarray(blockwise_attention(q, k, v, kv_limit=jnp.array(16),
                                             **kw))
    whole = np.asarray(blockwise_attention(q, k, np.nan_to_num(v), **kw))
    assert np.array_equal(bounded, whole)
