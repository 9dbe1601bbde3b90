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
        cache = L.write_entries(cache, {"c": c[:, lo:hi], "r": r[:, lo:hi]},
                                pos, n)
        got = L.latent_prefill(q_n[:, lo:hi], q_r[:, lo:hi], cache, w_uk,
                               w_uv, pos, n, scale=SCALE, dtype=jnp.float32)
        assert np.abs(np.asarray(got) - want[:, lo:hi]).max() < TOL
    for i in range(cut[-1], t):
        pos = jnp.full((B,), i)
        cache = L.write_entries(cache, {"c": c[:, i:i + 1],
                                        "r": r[:, i:i + 1]}, pos,
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
    cache = L.write_entries(cache, {"c": c[:, :11], "r": r[:, :11]},
                            jnp.zeros((B,), int), before)
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
        written = L.write_entries(held, {"c": c_new, "r": r_new}, before,
                                  real)
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
    got = L.write_entries(cache, {"c": c, "r": r}, jnp.asarray(pos),
                          jnp.asarray(real))
    # the write of a stretch (a slice a row), given the token and a
    # padded second position: what a one-token write was before
    two = L.write_entries(
        {k: jnp.pad(v, ((0, 0), (0, 1), (0, 0))) for k, v in cache.items()},
        {"c": np.concatenate([c, c], 1), "r": np.concatenate([r, r], 1)},
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


# -- the prefill kernel (interpreted) against the scan ------------------

PREFILL_T, PREFILL_CAPACITY = 24, 100     # blocks of 16, the last partial
# row 0 a first step; row 1 a later one whose fill (50) ends inside a
# block; row 2 without a real token; row 3 a later step, all real, a
# tile of it starting one short of a block's last position
PREFILL_POS, PREFILL_LENGTHS = [0, 37, 50, 62], [24, 13, 0, 24]


def _prefill_inputs(heads, groups, dtype, seed=7):
    rng = np.random.default_rng(seed)

    def normal(*shape, std=1.0):
        return jnp.asarray(rng.standard_normal(shape) * std, dtype)

    b = len(PREFILL_POS)
    cache = {"c": normal(b, PREFILL_CAPACITY, WIDE_RANK),
             "r": normal(b, PREFILL_CAPACITY, WIDE_ROPE)}
    return (normal(b, PREFILL_T, heads, 128),
            normal(b, PREFILL_T, heads, WIDE_ROPE), cache,
            normal(WIDE_RANK, groups, 128, std=0.1),
            normal(WIDE_RANK, groups, 128, std=0.1))


def _prefill(inputs, pallas, dtype, pos=PREFILL_POS, **kw):
    q_n, q_r, cache, w_uk, w_uv = inputs
    return np.asarray(L.latent_prefill(
        q_n, q_r, cache, w_uk, w_uv, jnp.array(pos),
        jnp.array(PREFILL_LENGTHS), scale=0.1, dtype=dtype, pallas=pallas,
        interpret=pallas, **kw), np.float32)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 16 positions and tiles of 8 queries: three tiles a row,
    seven blocks a cache, the causal edge inside a block."""
    monkeypatch.setattr(L, "PREFILL_BLOCK", 16)
    monkeypatch.setattr(L, "PREFILL_TILE", 8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,groups,each,first", [
    (10, 2, 1, False), (4, 4, 4, False), (4, 4, 4, True), (8, 8, 2, False)],
    ids=["grouped", "ungrouped", "ungrouped first step",
         "two key heads a step"])
def test_prefill_kernel_equals_the_scan_and_reads_nothing_past_a_row(
        small_blocks, monkeypatch, heads, groups, each, first, dtype):
    """Every real query's output is the scan's, with the cache past each
    row's fill poisoned for the kernel (the scan would read the NaN into
    a zero weight); a row without a real token gives zeros. In bfloat16
    the two round the same operands and differ by where ``p`` is
    rounded (the scan's product on the CPU keeps it float32) and by the
    order of the sums. Ungrouped heads go ``PREFILL_KEY_HEADS`` key heads
    a grid step (``prefill_form``: all four, or two of eight), a block
    expanded for them in one product and each head's keys and values a
    slice of it; a first step puts
    every row at position 0 in the rule's own form, one tile of 24
    queries and one block of 48 positions, of which a row fills 24, 13
    or none."""
    pos = PREFILL_POS
    monkeypatch.setattr(L, "PREFILL_KEY_HEADS", each)
    if first:
        monkeypatch.setattr(L, "PREFILL_BLOCK", 512)
        monkeypatch.setattr(L, "PREFILL_TILE", 512)
        pos = [0] * len(PREFILL_POS)
        assert L.prefill_form(PREFILL_T, 1, groups, PREFILL_CAPACITY)[:2] == (
            2 * PREFILL_T, PREFILL_T)
    assert L.prefill_form(PREFILL_T, heads // groups, groups,
                          PREFILL_CAPACITY)[2] == each
    inputs = _prefill_inputs(heads, groups, dtype)
    want = _prefill(inputs, False, dtype, pos)
    poisoned = {k: np.array(v, np.float32) for k, v in inputs[2].items()}
    for row, (p, n) in enumerate(zip(pos, PREFILL_LENGTHS)):
        for v in poisoned.values():
            v[row, p + n:] = np.nan
    got = _prefill(inputs[:2] + ({k: jnp.asarray(v, dtype)
                                  for k, v in poisoned.items()},)
                   + inputs[3:], True, dtype, pos)
    scale = np.abs(want).max()
    tol = 5 * TOL if dtype == jnp.float32 else 0.01
    for row, n in enumerate(PREFILL_LENGTHS):
        assert np.abs(got[row, :n] - want[row, :n]).max(initial=0) < (
            tol * scale), row
    assert np.isfinite(got).all()
    assert not got[2].any()                 # no real token: zeros


def test_prefill_kernel_attends_to_the_diagonal_and_to_the_fill(
        small_blocks):
    """The key at a query's own position and the last filled one are
    seen: a kernel that masked either (one block too few, or the causal
    edge off by one) moves a query's output by far more than rounding."""
    inputs = _prefill_inputs(10, 2, jnp.float32, seed=8)
    q_n, q_r, cache, w_uk, w_uv = inputs
    want = _prefill(inputs, False, jnp.float32)
    got = _prefill(inputs, True, jnp.float32)
    # row 0 from position 0: its first query sees one key alone, itself
    assert np.abs(got[0, 0] - want[0, 0]).max() < 5 * TOL
    v0 = np.einsum("c,chd->hd", np.asarray(cache["c"])[0, 0],
                   np.asarray(w_uv))
    assert np.abs(got[0, 0] - np.repeat(v0, 5, axis=0)).max() < 5 * TOL
    # every query whose own position opens a block, or whose row's last
    # filled position does
    for row, (p, n) in enumerate(zip(PREFILL_POS, PREFILL_LENGTHS)):
        for q in range(n):
            if (p + q) % 16 == 0 or q == n - 1:
                assert np.abs(got[row, q] - want[row, q]).max() < 5 * TOL


def test_the_rule_takes_the_kernel_at_motifs_shape_alone(monkeypatch):
    """On the TPU the kernel is taken by the shapes alone, now at every
    latent cell's: Motif's full layers (1,024 queries a step, 80 heads
    over 16 key heads: 5,120 rows a key head), Kimi's, Xing's and
    GigaChat's latent layers (128 and 256 queries, no grouping); window
    layers never run the latent prefill, and under 128 rows a key head
    the scan stays. Off the TPU nothing takes it. The form follows the
    step's shapes: Motif's is the one it had, blocks and tiles of 512
    and one key head a grid step; the ungrouped shapes take blocks of
    twice their queries and eight key heads a grid step."""
    from benchmark.lookup import load_json
    from tests.dl.kernel_calls import layer_kernel_calls

    names = ("motif_3_beta", "kimi_k2_6", "xing4_0_29b_a4b",
             "gigachat3_5_432b", "brumby_14b")
    cells = []
    for name in names:
        file = load_json("configs", name + ".json")
        cells.append(({k: file[k] for k in file["model_keys"]},
                      file["prefillChunk"]))

    def fused():
        # each layer's prefill step at the cell's widths and chunk
        return [layer_kernel_calls(config, chunk) for config, chunk in cells]

    assert fused() == [0] * 5
    monkeypatch.setattr(L, "use_pallas", lambda: True)
    assert fused() == [2, 5, 6, 1, 0]
    assert L.prefill_kernel(1024, 80, 16)
    for t, heads in ((128, 64), (256, 32)):
        assert L.prefill_kernel(t, heads, heads)
    assert not L.prefill_kernel(64, 64, 64)
    assert not L.prefill_kernel(16, 80, 16)
    # (t, query heads a key head, key heads, capacity) -> form
    assert L.prefill_form(1024, 5, 16, 16512) == (512, 512, 1)
    assert L.prefill_form(128, 1, 64, 1280) == (256, 128, 8)
    assert L.prefill_form(128, 1, 64, 768) == (256, 128, 8)
    assert L.prefill_form(256, 1, 32, 2080) == (512, 256, 8)
