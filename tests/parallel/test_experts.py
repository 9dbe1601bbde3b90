"""Sparse experts on a share: routing, drop-free grouped dispatch
against the plain reference's loop over the experts
(``benchmark/reference/gigachat3_5.py``), and the guide's share test:
the shares' routed parts and the shared expert counted once add up to
the uncut layer."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lookup import load_module
from mmlspark_tpu.parallel import experts as E

HIDDEN, WIDTH, N, TOP_K = 32, 16, 16, 4
TOL = 2e-6


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "gigachat3_5")


def _layer(seed=0, skew=0.0):
    """Reference-named weights of one expert layer holding all ``N``
    experts; ``skew`` tilts the selection bias towards the low ones."""
    rng = np.random.default_rng(seed)

    def normal(*shape, std=0.2):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    return {"router": normal(HIDDEN, N),
            "router_bias": (normal(N, std=0.05)
                            - skew * np.arange(N, dtype=np.float32)),
            "experts_gate": normal(N, HIDDEN, WIDTH),
            "experts_up": normal(N, HIDDEN, WIDTH),
            "experts_down": normal(N, WIDTH, HIDDEN),
            "shared_gate": normal(HIDDEN, WIDTH),
            "shared_up": normal(HIDDEN, WIDTH),
            "shared_down": normal(WIDTH, HIDDEN)}


def _cfg(first, stop, **more):
    return dict(num_experts_per_tok=TOP_K, routed_scaling_factor=2.5,
                norm_topk_prob=True, swiglu_limit=0.5,
                n_routed_experts=stop - first, experts_held=[first, stop],
                **more)


def _program(x, m, first, stop, valid=None, tile=8):
    """The program's routed part for the experts ``[first, stop)``."""
    routing = E.route(jnp.asarray(x), m["router"], m["router_bias"],
                      top_k=TOP_K, scale=2.5)
    valid = jnp.ones(len(x), bool) if valid is None else valid
    return E.grouped_experts(
        jnp.asarray(x), routing, valid, m["experts_gate"][first:stop],
        m["experts_up"][first:stop], m["experts_down"][first:stop],
        held=(first, stop - first), tile=tile, dtype=jnp.float32, limit=0.5)


def _held(m, first, stop):
    return dict(m, **{k: m[k][first:stop] for k in
                      ("experts_gate", "experts_up", "experts_down")})


@pytest.mark.parametrize("skew", [0.0, 0.05])
@pytest.mark.parametrize("tile", [8, 32])
def test_grouped_dispatch_equals_the_loop_over_the_experts(reference, skew,
                                                           tile):
    """Under an even and under a skewed router (most pairs on a few
    experts, some experts with none): nothing is dropped, whatever the
    tile."""
    m = _layer(skew=skew)
    x = np.random.default_rng(1).standard_normal((70, HIDDEN)).astype(
        np.float32)
    first, stop = 2, 9
    y, pairs, dropped = _program(x, m, first, stop, tile=tile)
    want, _ = reference.expert_layer(x, _held(m, first, stop),
                                     _cfg(first, stop), "highest")
    shared = reference.swiglu(x, m["shared_gate"], m["shared_up"],
                              m["shared_down"], 0.5, "highest")
    assert np.abs(np.asarray(y) - (np.asarray(want) - shared)).max() < TOL
    assert int(dropped) == 0
    chosen = np.asarray(E.route(jnp.asarray(x), m["router"], m["router_bias"],
                                top_k=TOP_K, scale=2.5).experts)
    assert np.array_equal(np.asarray(pairs),
                          [(chosen == e).sum() for e in range(first, stop)])
    if skew:
        assert pairs.max() > 4 * max(int(pairs.min()), 1)


def test_padded_tokens_route_nowhere():
    m = _layer()
    x = np.random.default_rng(2).standard_normal((24, HIDDEN)).astype(
        np.float32)
    valid = jnp.arange(24) < 15
    y, pairs, dropped = _program(x, m, 0, N, valid=valid)
    alone, pairs_alone, _ = _program(x[:15], m, 0, N)
    assert np.abs(np.asarray(y)[:15] - np.asarray(alone)).max() < TOL
    assert not np.asarray(y)[15:].any()
    assert int(pairs.sum()) == 15 * TOP_K == int(pairs_alone.sum())
    assert int(dropped) == 0


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """4 shares of 16 experts: the routed parts summed and the shared
    expert counted once equal the uncut reference's layer output."""
    m = _layer(seed=3)
    x = np.random.default_rng(4).standard_normal((40, HIDDEN)).astype(
        np.float32)
    whole, _ = reference.expert_layer(x, m, _cfg(0, N), "highest")
    shared = reference.swiglu(x, m["shared_gate"], m["shared_up"],
                              m["shared_down"], 0.5, "highest")
    routed = sum(np.asarray(_program(x, m, lo, lo + 4)[0])
                 for lo in range(0, N, 4))
    assert np.abs(routed + np.asarray(shared) - np.asarray(whole)).max() < TOL
    # and the reference's own shares: each is its chip's part alone
    parts = sum(np.asarray(reference.expert_layer(
        x, _held(m, lo, lo + 4), _cfg(lo, lo + 4), "highest")[0]) - shared
        for lo in range(0, N, 4))
    assert np.abs(parts + np.asarray(shared) - np.asarray(whole)).max() < TOL


def test_weights_are_normalised_over_the_chosen_and_scaled():
    m = _layer()
    x = np.random.default_rng(5).standard_normal((9, HIDDEN)).astype(
        np.float32)
    routing = E.route(jnp.asarray(x), m["router"], m["router_bias"],
                      top_k=TOP_K, scale=2.5)
    assert np.allclose(np.asarray(routing.weights).sum(1), 2.5, atol=1e-5)
    scores = 1 / (1 + np.exp(-(x @ m["router"])))
    want = np.argsort(-(scores + m["router_bias"]), axis=1)[:, :TOP_K]
    assert np.array_equal(np.sort(np.asarray(routing.experts), 1),
                          np.sort(want, 1))
    # the bias chooses and does not weigh
    picked = np.take_along_axis(scores, np.asarray(routing.experts), 1)
    assert np.allclose(np.asarray(routing.weights),
                       2.5 * picked / picked.sum(1, keepdims=True), atol=1e-5)


def test_tile_rows_follows_the_expected_pairs_an_expert():
    assert E.tile_rows(128, 8, 256) == 64         # a decode step: 4 pairs
    assert E.tile_rows(16384, 8, 256) == 256      # a prefill step: 512
    assert E.tile_rows(4096, 8, 256) == 128       # a group of it: 128


def test_the_routing_margin_is_a_held_experts_distance_to_the_choice(
        reference):
    """With a router that is the identity on the first ``N`` channels
    the scores are chosen by hand: the margin is how far the nearest
    held expert lies from entering or leaving the chosen ``TOP_K``, and
    a near tie between two experts held elsewhere does not count."""
    m = _layer()
    m["router"] = np.eye(HIDDEN, N, dtype=np.float32)
    m["router_bias"] = np.zeros(N, np.float32)
    logit = np.full((3, HIDDEN), -4.0, np.float32)
    #          chosen four                         the fifth
    logit[0, [0, 1, 2, 3]] = [2.0, 1.5, 1.0, 0.5]; logit[0, 4] = 0.499
    logit[1, [8, 9, 10, 3]] = [2.0, 1.5, 1.0, 0.5]; logit[1, 4] = 0.0
    logit[2, [8, 9, 10, 11]] = [2.0, 1.5, 1.0, 0.5]; logit[2, 12] = 0.499
    logit[2, 0] = -1.0

    def s(v):
        return 1.0 / (1.0 + np.exp(-np.float32(v)))

    _, margin = reference.expert_layer(
        jnp.asarray(logit), _held(m, 0, 4), _cfg(0, 4), "highest")
    # row 0: held expert 3 is the last chosen, 0.001 above the fifth
    # row 1: held expert 3 is chosen, the first left out far below
    # row 2: the near tie is between experts 11 and 12, held elsewhere:
    #        the nearest held expert, 0, lies far below the last chosen
    want = [s(0.5) - s(0.499), s(0.5) - s(0.0), s(0.5) - s(-1.0)]
    assert np.abs(np.asarray(margin) - want).max() < 1e-6
