"""The plain reference of ``xing4_0`` (``benchmark/reference/xing4_0.py``)
on its own, at toy size on the CPU: what it shares with
``reference/kimi_k2.py`` and what it writes itself. (Beside
``test_reference.py``, which a PR that adds a configuration may not
edit; the program against this reference is ``tests/dl/test_hyper_lm.py``.)"""

import numpy as np
import pytest

from benchmark.lookup import load_json, load_module

FILE = load_json("rehearsal", "configs", "tiny_xing.json")
CFG = {k: FILE[k] for k in FILE["model_keys"]}


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "xing4_0")


@pytest.fixture(scope="module")
def weights():
    builder = load_module("builders", "xing4_0")
    return builder.reference_weights(builder.make_weights(11, CFG), CFG)


def _ids(t, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], t).astype(np.int32)


def test_the_reference_shares_no_code_with_the_program(reference):
    with open(reference.__file__) as f:
        source = f.read()
    assert "mmlspark_tpu" not in source.split('"""', 2)[2]
    assert reference.PRECISIONS == ("highest", "bfloat16", "float8_weights",
                                    "float8")


def test_the_forward_is_causal_and_a_batch_is_its_rows(reference, weights):
    ids = _ids(24)
    whole, margin = reference.logits(weights, ids, CFG, margins=True)
    assert whole.shape == (24, CFG["vocab_size"]) and margin.shape == (24,)
    assert np.isfinite(np.asarray(whole)).all()
    assert float(margin.min()) > 0
    # what follows a position moves nothing before it
    front = reference.logits(weights, ids[:10], CFG)
    assert np.abs(np.asarray(front) - np.asarray(whole)[:10]).max() < 1e-5
    at = np.array([3, 9, 23])
    some = reference.logits(weights, ids, CFG, positions=at)
    assert np.array_equal(np.asarray(some), np.asarray(whole)[at])
    pair = reference.logits(weights, np.stack([ids, ids[::-1]]), CFG)
    assert np.array_equal(np.asarray(pair[0]), np.asarray(whole))


def test_the_residual_paths_coefficients_by_hand(reference, weights):
    """One token's coefficients in numpy float64, from the equations in
    the reference's docstring."""
    import jax.numpy as jnp

    n, width = CFG["hc_mult"], CFG["hidden_size"]
    rng = np.random.default_rng(5)
    x = rng.standard_normal((3, n, width)).astype(np.float32)
    hc = weights["layers"][1]["ffn_hc"]
    h_pre, h_post, h_res = map(np.asarray, reference.coefficients(
        jnp.asarray(x), hc, CFG))
    phi = np.asarray(hc["phi"], np.float64)
    alpha = np.asarray(hc["alpha"], np.float64)
    for t in range(3):
        flat = x[t].reshape(-1).astype(np.float64)
        flat = flat / np.sqrt(np.mean(flat * flat) + CFG["rms_norm_eps"])
        p, q, r = np.split(flat @ phi, [n, 2 * n])
        pre = 1 / (1 + np.exp(-(alpha[0] * p + np.asarray(hc["b_pre"]))))
        post = 2 / (1 + np.exp(-(alpha[1] * q + np.asarray(hc["b_post"]))))
        m = np.exp(np.clip(alpha[2] * r.reshape(n, n)
                           + np.asarray(hc["b_res"], np.float64), -30, 30))
        for _ in range(CFG["hc_sinkhorn_iters"]):
            m = m / (m.sum(axis=0, keepdims=True) + CFG["hc_eps"])
            m = m / (m.sum(axis=1, keepdims=True) + CFG["hc_eps"])
        assert np.abs(h_pre[t] - pre).max() < 1e-5
        assert np.abs(h_post[t] - post).max() < 1e-5
        assert np.abs(h_res[t] - m).max() < 1e-5
        assert np.abs(h_res[t].sum(axis=1) - 1).max() < 1e-5   # rows


def test_the_lower_precisions_move_the_logits_in_order(reference, weights):
    ids = _ids(16, seed=2)
    exact = np.asarray(reference.logits(weights, ids, CFG))
    scale = np.abs(exact).max()
    errs = [np.abs(np.asarray(reference.logits(weights, ids, CFG, p))
                   - exact).max() / scale
            for p in ("bfloat16", "float8_weights", "float8")]
    assert 0 < errs[0] < errs[1] < 0.5 and errs[0] < errs[2] < 0.5
    with pytest.raises(ValueError):
        reference.logits(weights, ids, CFG, "float16")
