"""Grouped-query attention over a plain key/value cache
(``parallel/kv.py``) at a small size on the CPU, float32: the decode
and the full layers' prefill, each kernel interpreted and each
``jax.numpy`` twin, and the window prefill, against a loop a query head
written from the formula; the ring's writes past its window."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.parallel import kv, latent

B, HEADS, GROUPS, ROPE, NOPE, D_V, LENGTH = 4, 8, 2, 8, 16, 16, 40
SCALE = (ROPE + NOPE) ** -0.5


def _naive(q, keys, values, seen, sink):
    """One row's query heads ``(heads, d)`` against its keys ``(L,
    groups, d)`` where ``seen`` ``(L,)``; ``exp(sink[h])`` in the
    denominator where ``sink`` is given."""
    per = len(q) // keys.shape[1]
    out = []
    for h in range(len(q)):
        s = np.where(seen, keys[:, h // per] @ q[h] * SCALE, -np.inf)
        top = max(s.max(), -np.inf if sink is None else sink[h])
        e = np.exp(s - top)
        total = e.sum() + (0.0 if sink is None else np.exp(sink[h] - top))
        out.append(e / total @ values[:, h // per])
    return np.stack(out)


@pytest.fixture(scope="module")
def cache():
    rng = np.random.default_rng(0)
    return {name: jnp.asarray(rng.standard_normal((B, LENGTH, GROUPS * w)),
                              jnp.float32)
            for name, w in zip(kv.NAMES, (ROPE, NOPE, D_V))}


def _as_heads(cache):
    keys = np.concatenate(
        [np.asarray(cache["k_r"]).reshape(B, -1, GROUPS, ROPE),
         np.asarray(cache["k_n"]).reshape(B, -1, GROUPS, NOPE)], axis=-1)
    return keys, np.asarray(cache["v"]).reshape(B, -1, GROUPS, D_V)


SINK = np.random.default_rng(1).standard_normal(HEADS).astype(np.float32) + 1


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("sink", [None, SINK], ids=["plain", "sink"])
def test_decode_equals_the_formula(cache, pallas, sink):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((B, HEADS, ROPE + NOPE)),
                    jnp.float32)
    pos = np.array([0, 5, 17, LENGTH - 1])
    got = np.asarray(kv.kv_decode(
        q, cache, jnp.asarray(pos), scale=SCALE, dtype=jnp.float32,
        sink=None if sink is None else jnp.asarray(sink), pallas=pallas,
        interpret=True))
    keys, values = _as_heads(cache)
    for b in range(B):
        want = _naive(np.asarray(q[b]), keys[b], values[b],
                      np.arange(LENGTH) <= pos[b], sink)
        assert np.abs(got[b] - want).max() < 1e-5


@pytest.mark.parametrize("lanes", [None, 16],
                         ids=["every key head a step", "two a step"])
@pytest.mark.parametrize("pallas", [False, True], ids=["scan", "kernel"])
def test_a_full_layers_prefill_equals_the_formula(cache, pallas, lanes,
                                                  monkeypatch):
    if lanes:
        monkeypatch.setattr(kv, "LANES", lanes)
    t = 12
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, t, HEADS, ROPE + NOPE)),
                    jnp.float32)
    pos, lengths = np.array([0, 3, 20, 7]), np.array([12, 5, 0, 9])
    assert kv.prefill_form(t, GROUPS, ROPE, NOPE, D_V, LENGTH)[2] == (
        2 if lanes else GROUPS)
    got = np.asarray(kv.kv_prefill(
        q, cache, jnp.asarray(pos), jnp.asarray(lengths), scale=SCALE,
        dtype=jnp.float32, pallas=pallas, interpret=True))
    keys, values = _as_heads(cache)
    for b in range(B):
        for i in range(lengths[b]):
            want = _naive(np.asarray(q[b, i]), keys[b], values[b],
                          np.arange(LENGTH) <= pos[b] + i, None)
            assert np.abs(got[b, i] - want).max() < 1e-5
    if pallas:
        assert np.abs(got[2]).max() == 0.0     # no real token: zeros


def test_a_window_prefill_reads_the_ring_and_the_stretch(cache):
    """A ring of 8 slots holding each row's positions before the
    stretch (``latent.ring_entries`` of its prompt), then a stretch of
    12 from there: each query against its window of 8, its own included,
    with the sink; the ring afterwards holds each row's last 8
    positions."""
    window, t = 8, 12
    rng = np.random.default_rng(4)
    whole = {name: jnp.asarray(rng.standard_normal((B, LENGTH, x.shape[-1])),
                               jnp.float32) for name, x in cache.items()}
    pos, lengths = np.array([0, 3, 20, 9]), np.array([12, 5, 0, 9])
    ring = kv.init_cache(B, window, GROUPS, ROPE, NOPE, D_V, jnp.float32)
    ring = latent.ring_entries(
        ring, {n: x[:, :pos.max()] for n, x in whole.items()},
        jnp.zeros(B, jnp.int32), jnp.asarray(pos))
    new = {n: jnp.stack([x[b, pos[b]:pos[b] + t] for b in range(B)])
           for n, x in whole.items()}
    q = jnp.asarray(rng.standard_normal((B, t, HEADS, ROPE + NOPE)),
                    jnp.float32)
    got = np.asarray(kv.window_prefill(
        q, ring, new, jnp.asarray(pos), jnp.asarray(lengths), scale=SCALE,
        dtype=jnp.float32, sink=jnp.asarray(SINK)))
    keys, values = _as_heads(whole)
    at = np.arange(LENGTH)
    for b in range(B):
        for i in range(lengths[b]):
            p = pos[b] + i
            want = _naive(np.asarray(q[b, i]), keys[b], values[b],
                          (at <= p) & (at > p - window), SINK)
            assert np.abs(got[b, i] - want).max() < 1e-5
    after = latent.ring_entries(ring, new, jnp.asarray(pos),
                                jnp.asarray(lengths))
    for b in range(B):
        end = pos[b] + lengths[b]
        for p in range(max(end - window, 0), end):
            assert np.array_equal(np.asarray(after["v"][b, p % window]),
                                  np.asarray(whole["v"][b, p]))


def test_the_kernels_are_named_for_the_trace(cache):
    """The decode and prefill kernels carry the names the benchmark's
    readers find in a device trace."""
    q1 = jax.ShapeDtypeStruct((B, HEADS, ROPE + NOPE), jnp.float32)
    qt = jax.ShapeDtypeStruct((B, 16, HEADS, ROPE + NOPE), jnp.float32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)

    def names(fn, *args):
        return [e.params["name"] for e in jax.make_jaxpr(fn)(*args).eqns
                if e.primitive.name == "pallas_call"]

    assert names(lambda q, p: kv.kv_decode(
        q, cache, p, scale=SCALE, dtype=jnp.float32, pallas=True), q1,
        pos) == ["kv_decode"]
    assert names(lambda q, p: kv.kv_prefill(
        q, cache, p, p, scale=SCALE, dtype=jnp.float32, pallas=True), qt,
        pos) == ["kv_prefill"]
