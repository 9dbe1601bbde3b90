"""``CausalLM`` over ``HybridLM`` read as ``model_type`` ``Motif``
(grouped differential attention on the latent cache, window layers on a
ring beside full ones, PolyNorm MLPs and experts, a residual path of
four streams) at a small size on the CPU, seeded weights, float32,
against the plain reference (``benchmark/reference/motif3.py``, through
the benchmark's own loader so there is one copy); and the programs of
the configurations that were there before, against the text they
traced."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lookup import load_json, load_module
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.logging_utils import SINK
from tests.dl.kernel_calls import prefill_kernel_calls
from tests.dl.root_counts import ROOT_COUNTS

_FILE = load_json("rehearsal", "configs", "tiny_motif.json")
# five layers standing for published 0, 12-15: 0 (dense) and 4 full,
# 1-3 over a window of 8; 10 query heads over 2 key heads; 4 of 8
# experts held
CFG = {k: _FILE[k] for k in _FILE["model_keys"]}
WINDOW = CFG["sliding_window"]
NEW = 6
LENGTHS = [5, 17, 9, 30, 40]      # up to five windows; chunks of 16 cross it
# float32 on both sides, of the logits' scale (about 0.6): ring and
# cache against a mask over the whole sequence, absorbed against
# expanded attention, the maps' difference after the values against
# before, grouped against looped experts. The rows read 2-3e-7
TOL = 1e-5
FAULT = 1e-4


@pytest.fixture(scope="module")
def builder():
    return load_module("builders", "motif3")


@pytest.fixture(scope="module")
def params(builder):
    """Seeded weights (the builder's: PolyNorm and residual leaves off
    the trivial), the projections scaled so that attention, lambda and
    every sub-layer weigh at this hidden size as at the published one
    (``x W`` over 64 normed inputs spreads an eighth of what it does
    over 4,096)."""
    params = builder.make_weights(7, CFG)
    for i in range(CFG["num_hidden_layers"]):
        layer = params["params"][f"layers_{i}"]
        for name in ("q_b_proj", "kv_a_proj", "o_proj", "lambda_proj"):
            layer["mixer"][name]["kernel"] = (
                layer["mixer"][name]["kernel"] * 8.0)
        for name in ("down_proj", "experts_down", "shared_down"):
            if name in layer["ffn"]:
                layer["ffn"][name] = layer["ffn"][name] * 8.0
    return params


def _prompts(lengths=LENGTHS, seed=3):
    rng = np.random.default_rng(seed)
    col = np.empty(len(lengths), dtype=object)
    for i, n in enumerate(lengths):
        col[i] = rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
    return col


def _stage(params, config=CFG, **kw):
    from mmlspark_tpu.dl.causal_lm import CausalLM

    kw = {"batchSize": 4, "prefillChunk": 16, "maxLength": 64, **kw}
    return CausalLM(inputCol="prompt", outputCol="completion",
                    modelConfig=config, maxNewTokens=NEW, **kw).set_weights(
                        params)


@pytest.fixture(scope="module")
def scored(params):
    col = _prompts()
    return col, _stage(params, logitsCol="logits").transform(
        DataFrame({"prompt": col}))


def _error(reference, weights, prompt, tokens, got):
    """Teacher forced: the largest difference from the reference's
    logits at the positions that emitted ``tokens``, over their scale;
    the reference's logits; their least routing margin."""
    ids = np.concatenate([prompt, tokens])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    want, margin = reference.logits(weights, ids, CFG, positions=at,
                                    margins=True)
    want = np.asarray(want)
    return (np.abs(got - want).max() / np.abs(want).max(), want,
            float(np.asarray(margin).min()))


def test_the_layers_kinds_and_the_builders_leaves(builder, params):
    from mmlspark_tpu.dl.backbones import HybridLM, family_config

    blocks = HybridLM._blocks(family_config(CFG))
    assert [b.window() for b in blocks] == [False, True, True, True, False]
    assert [b.sparse() for b in blocks] == [False, True, True, True, True]
    reference = load_module("reference", "motif3")
    assert [reference.is_window(CFG, i) for i in range(5)] == [
        b.window() for b in blocks]
    ffn = params["params"]["layers_1"]["ffn"]
    weight = np.asarray(ffn["experts_poly_weight"])
    bias = np.asarray(ffn["experts_poly_bias"])
    assert weight.shape == (4, 3) and bias.shape == (4, 1)
    assert 0.2 < weight.mean() < 0.45 and weight.std() > 0.05
    assert (bias > CFG["polynorm_bias_clamp"]).mean() > 0.5  # the clamp holds
    assert "router_bias" not in ffn                 # no selection bias


def test_prefill_then_decode_equals_the_references_full_forward(
        builder, params, scored):
    col, out = scored
    reference = load_module("reference", "motif3")
    tokens = np.asarray(out.col("completion"))
    logprobs = np.asarray(out.col("logprobs"))
    assert tokens.shape == logprobs.shape == (len(col), NEW)
    weights = builder.reference_weights(params, CFG)
    for i in range(len(col)):
        err, want, margin = _error(reference, weights, col[i], tokens[i],
                                   np.asarray(out.col("logits")[i]))
        assert margin > 1e-4                    # no choice hangs on an ulp
        assert err < TOL                        # every logit, every position
        assert np.array_equal(want.argmax(-1), tokens[i])
        shifted = want - want.max(-1, keepdims=True)
        want_lp = shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))
        assert np.abs(want_lp[np.arange(NEW), tokens[i]]
                      - logprobs[i]).max() < TOL * np.abs(want).max()


def _window_off_by_one(q_pos, k_pos, window):
    seen = k_pos[None, :] <= q_pos[:, None]
    if window:
        seen &= k_pos[None, :] > q_pos[:, None] - window - 1
    return seen


def _poly(power=True, clamp=True):
    def poly_norm(v, weight, bias, cfg):
        w, b = weight.astype(jnp.float32), bias.astype(jnp.float32)
        if clamp:
            b = jnp.clip(b, -cfg["polynorm_bias_clamp"],
                         cfg["polynorm_bias_clamp"])

        def n(z):
            return z / jnp.sqrt(jnp.mean(z * z, axis=-1, keepdims=True)
                                + cfg["rms_norm_eps"])

        return cfg["polynorm_output_scale"] * (
            (w[0] * n(v ** 3) if power else 0.0) + w[1] * n(v ** 2)
            + w[2] * n(v) + b[0])
    return poly_norm


# what a fault replaces in a copy of the reference of its own
FAULTS = {
    "a window off by one": dict(visible=_window_off_by_one),
    "lambda fixed at its mean": dict(difference=lambda p, lam: (
        p[:, 1:] - jnp.mean(lam) * p[:, :1])),
    "the noise map not subtracted": dict(difference=lambda p, lam: p[:, 1:]),
    "noise heads not shared within a group": dict(
        difference=lambda p, lam: p[:, 1:] - jnp.transpose(
            lam, (1, 2, 0))[..., None] * jnp.roll(p[:, :1], 1, axis=0)),
    "a PolyNorm power dropped": dict(poly_norm=_poly(power=False)),
    "the PolyNorm bias unclamped": dict(poly_norm=_poly(clamp=False)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_with_the_reference_can_fail(builder, params, scored,
                                                    fault):
    col, out = scored
    reference = load_module("reference", "motif3")      # a copy to break
    for name, function in FAULTS[fault].items():
        assert hasattr(reference, name)
        setattr(reference, name, function)
    err, _, _ = _error(reference, builder.reference_weights(params, CFG),
                       col[4], np.asarray(out.col("completion"))[4],
                       np.asarray(out.col("logits")[4]))
    assert err > FAULT


def test_a_ring_written_at_p_not_p_mod_window_fails_the_comparison(
        builder, params, monkeypatch):
    """The program's own fault: a window layer's entries written at
    their position, so that past the window they fall off the ring."""
    from mmlspark_tpu.parallel import latent

    def at_p(ring, new, pos, lengths):
        slot = jnp.arange(ring["c"].shape[1])[None, :] - pos[:, None]
        ok = (slot >= 0) & (slot < lengths[:, None])

        def put(old, new):
            got = jnp.take_along_axis(
                new, jnp.clip(slot, 0, new.shape[1] - 1)[..., None], axis=1)
            return jnp.where(ok[..., None], got.astype(old.dtype), old)

        return {name: put(ring[name], x) for name, x in new.items()}

    monkeypatch.setattr(latent, "ring_entries", at_p)
    col = _prompts()[[4]]
    out = _stage(params, logitsCol="logits").transform(
        DataFrame({"prompt": col}))
    err, _, _ = _error(load_module("reference", "motif3"),
                       builder.reference_weights(params, CFG), col[0],
                       np.asarray(out.col("completion"))[0],
                       np.asarray(out.col("logits")[0]))
    assert err > FAULT


def test_a_row_does_not_change_with_its_rungs_or_its_neighbours(
        params, monkeypatch):
    from mmlspark_tpu.dl.backbones import HybridLM

    col = _prompts()
    base = _stage(params).transform(DataFrame({"prompt": col}))
    tokens = np.asarray(base.col("completion"))
    logprobs = np.asarray(base.col("logprobs"))
    variants = {
        "alone": (_stage(params), [2]),                       # row rung 1
        "other neighbours": (_stage(params), [4, 2, 0]),
        "one prefill step": (_stage(params, prefillChunk=64), [3, 4]),
        "steps shorter than the window": (_stage(params, prefillChunk=4),
                                          [1, 4]),
    }
    for name, (stage, rows) in variants.items():
        out = stage.transform(DataFrame({"prompt": col[rows]}))
        assert np.array_equal(np.asarray(out.col("completion")),
                              tokens[rows]), name
        assert np.abs(np.asarray(out.col("logprobs"))
                      - logprobs[rows]).max() < 2e-5, name
    # a prefill step cut into two groups of rows (4 x 16 tokens over 32)
    monkeypatch.setattr(HybridLM, "GROUP_TOKENS", 32)
    out = _stage(params).transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(out.col("completion")), tokens)
    assert np.abs(np.asarray(out.col("logprobs")) - logprobs).max() < 2e-5


def test_a_window_layers_cache_is_its_ring_whatever_the_length():
    from mmlspark_tpu.dl.backbones import lm_init_state, lm_state_bytes

    for capacity in (16, 1000):
        layers = lm_init_state(CFG, 2, capacity)["layers"]
        assert [("ring" in s) for s in layers] == [False, True, True, True,
                                                   False]
        for i in (1, 2, 3):
            assert layers[i]["ring"]["c"].shape == (2, WINDOW, 32)
            assert layers[i]["ring"]["r"].shape == (2, WINDOW, 8)
        assert layers[4]["c"].shape == (2, capacity, 32)
    small, large = (lm_state_bytes(CFG, 2, n) for n in (16, 1000))
    ring = 3 * 2 * WINDOW * (32 + 8) * 4
    assert small["state"] == large["state"] >= ring
    assert large["cache"] - small["cache"] == 2 * 2 * 984 * (32 + 8) * 4


def _window_read(tokens):
    return sum(min(p + 1, WINDOW) for p in range(tokens))


def test_spans_counts_and_scopes(params):
    from mmlspark_tpu.dl.backbones import lm_init_state, lm_module

    col = _prompts()
    before = len(SINK.events)
    _stage(params).transform(DataFrame({"prompt": col}))
    counts = [r for r in SINK.events[before:]
              if r.get("className") == "CausalLM"][-1]["counts"]
    assert set(counts) == ROOT_COUNTS["tiny_motif"]
    through = [n + NEW - 1 for n in LENGTHS]
    # what the three window layers read; the ring is no cache
    assert counts["window_positions"] == 3 * sum(map(_window_read, through))
    assert counts["cache_positions"] == 2 * sum(through)
    assert counts["hc_sublayer_tokens"] == 2 * 5 * sum(through)
    assert counts["dropped_pairs"] == 0
    # off the TPU the full layers' prefill is the scan, not the kernel
    stage = _stage(params)
    stage._ensure_scorer()
    assert prefill_kernel_calls(stage._program("lm_prefill"), params, 4,
                                64) == 0
    for t in (1, 16):
        text = jax.jit(lm_module(CFG).apply).lower(
            params, jnp.zeros((2, t), jnp.int32), jnp.full((2,), t,
                                                           jnp.int32),
            lm_init_state(CFG, 2, 32)).as_text(debug_info=True)
        for layer in (1, 2, 3):
            assert f"layers_{layer}/lm.mla/mixer/lm.mla.window" in text
        assert "layers_0/lm.mla/mixer/lm.mla.window" not in text
        for layer in range(5):
            assert f"layers_{layer}/lm.mla/mixer/lm.mla.diff" in text
        # a full layer's prefill attention, and nothing else's
        full = [f"layers_{layer}/lm.mla/mixer/lm.mla.full" in text
                for layer in range(5)]
        assert full == ([True, False, False, False, True] if t > 1
                        else [False] * 5)


def test_the_prefill_kernel_in_the_stage_equals_the_scan(params,
                                                        monkeypatch):
    """The whole stage with the full layers' prefill through the Pallas
    kernel, interpreted (the rule's bound lowered to this size), and the
    decode kernel: the tokens and log-probabilities of the scan's; the
    stage's ``lm_prefill`` calls the kernel once for each of the two
    full layers, and for no window layer."""
    from mmlspark_tpu.parallel import latent

    col = _prompts()
    base = _stage(params).transform(DataFrame({"prompt": col}))
    monkeypatch.setattr(latent, "use_pallas", lambda: True)
    monkeypatch.setattr(latent, "PREFILL_KERNEL_MIN_ROWS", 16)
    for name in ("_decode_pallas", "_prefill_pallas"):
        monkeypatch.setattr(latent, name, lambda *a, _real=getattr(
            latent, name): _real(*a[:-1], True))
    stage = _stage(params)
    out = stage.transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(out.col("completion")),
                          np.asarray(base.col("completion")))
    assert np.abs(np.asarray(out.col("logprobs"))
                  - np.asarray(base.col("logprobs"))).max() < 2e-5
    assert prefill_kernel_calls(stage._program("lm_prefill"), params, 4,
                                64) == 2


def test_the_shares_add_up_to_the_uncut_layer():
    """The router's 8 experts in 8 shares of one: what the eight chips
    compute, with the shared expert (which all compute alike) counted
    once, is the uncut reference's expert layer. Attention and the
    residual path are computed alike on every chip and enter once."""
    from mmlspark_tpu.dl.backbones import ExpertFeedForward, family_config

    reference = load_module("reference", "motif3")
    whole = dict(CFG, num_experts=8, experts_held=[0, 8])
    rng = np.random.default_rng(11)

    def normal(*shape, std=0.1, mean=0.0):
        return (mean + rng.standard_normal(shape) * std).astype(np.float32)

    h, w = CFG["hidden_size"], CFG["moe_intermediate_size"]
    ffn = {"router": normal(h, 8, std=0.5 * h ** -0.5),
           "experts_gate": normal(8, h, w), "experts_up": normal(8, h, w),
           "experts_down": normal(8, w, h),
           "experts_poly_weight": normal(8, 3, mean=1 / 3),
           "experts_poly_bias": normal(8, 1, std=0.3, mean=0.8),
           "shared_gate": normal(h, w), "shared_up": normal(h, w),
           "shared_down": normal(w, h),
           "shared_poly_weight": normal(3, mean=1 / 3),
           "shared_poly_bias": normal(1, std=0.3, mean=0.8)}
    x = normal(2, 9, h, std=1.0)
    valid = np.ones((2, 9), bool)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_layer(x.reshape(-1, h), ffn, whole,
                                         "highest")
        shared = reference.mlp(x.reshape(-1, h), ffn["shared_gate"],
                               ffn["shared_up"], ffn["shared_down"],
                               ffn["shared_poly_weight"],
                               ffn["shared_poly_bias"], whole, "highest")
    parts, served = [], 0
    for first in range(8):
        share = family_config(dict(CFG, num_experts=1,
                                   experts_held=[first, first + 1]))
        mine = {k: v[first:first + 1] if k.startswith("experts_") else v
                for k, v in ffn.items()}
        y, (pairs, dropped) = ExpertFeedForward(share).apply(
            {"params": mine}, x, valid)
        assert int(dropped) == 0
        served += int(pairs.sum())
        parts.append(np.asarray(y).reshape(-1, h))
    assert served == 18 * CFG["experts_top_k"]          # every pair, once
    got = sum(parts) - 7 * np.asarray(shared)
    assert np.abs(got - np.asarray(want)).max() < 1e-5 * np.abs(want).max()
    # and one share alone is not the layer
    assert np.abs(parts[0] - np.asarray(want)).max() > 0.05 * np.abs(
        want).max()


# sha256 (first 16 hex digits) of the jaxpr text of lm_prefill (the
# config file's batchSize x maxLength in steps of prefillChunk, 4 new
# tokens) and of lm_generate without and with logits, as the code
# before window layers, differential heads and PolyNorm traced them;
# tiny_xing's since its state lost the one-pass combine's row counter
# (one scalar add a forward call, and its carry, the only change)
PROGRAMS = {
    "tiny_brumby": ("837b64c3f70c1b21", "0bc47b40a613dae4",
                    "b747c8315a2f952d"),
    "tiny_gigachat": ("fbd17f923dc90dc4", "1cb3cd6dd255c4ee",
                      "20f56a50bab67ae0"),
    "tiny_kimi": ("a5dfb809f3852a4d", "1364657d853bbcb7",
                  "314f092870db04b4"),
    "tiny_xing": ("eedb143b0d23d814", "9372db50910b82f2",
                  "b8bc82d0260e74dc"),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_the_configs_before_trace_the_programs_they_traced(name):
    from mmlspark_tpu.dl import causal_lm
    from mmlspark_tpu.dl.backbones import lm_module, lm_param_shapes

    file = load_json("rehearsal", "configs", name + ".json")
    config = {k: file[k] for k in file["model_keys"]}
    module, shapes = lm_module(config), lm_param_shapes(config)
    ids = jax.ShapeDtypeStruct((file["batchSize"], file["maxLength"]),
                               jnp.int32)
    lengths = jax.ShapeDtypeStruct((file["batchSize"],), jnp.int32)
    prefill = causal_lm.lm_prefill_program(module, file["prefillChunk"], 4)
    last, state = jax.eval_shape(prefill, shapes, ids, lengths)
    texts = [str(jax.make_jaxpr(prefill)(shapes, ids, lengths))] + [
        str(jax.make_jaxpr(causal_lm.lm_generate_program(
            module, 4, logits))(shapes, last, state))
        for logits in (False, True)]
    assert tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in texts) == PROGRAMS[name]


# sha256 (first 16 hex digits) of the jaxpr text of the programs the
# latent prefill kernel's rule leaves as they were, at the cells' own
# sizes (the config file's batchSize x maxLength in steps of
# prefillChunk, the cell's new tokens) with every kernel's switch giving
# the TPU's answer: Motif's lm_prefill (its full layers' kernel in the
# form it had: blocks and tiles of 512, one key head a grid step) and
# every cell's lm_generate without and with logits (a decode step is
# latent_decode's), as the code traced them before the ungrouped latent
# layers' prefill took the kernel; Xing's since its state lost the
# one-pass combine's row counter
CELL_PROGRAMS = {
    "motif_3_beta": (128, "5ba59556abe6af39", "99d641fa2d4fe404",
                     "74d47670e05272fe"),
    "kimi_k2_6": (768, None, "ea5537023024c7d8", "a71070541adc28c9"),
    "xing4_0_29b_a4b": (32, None, "a35cb4edc74a4920", "8b95fbb9b3754d3b"),
    "gigachat3_5_432b": (256, None, "d4129d9ce8906c68", "e74ed20992374554"),
}


@pytest.mark.parametrize("name", sorted(CELL_PROGRAMS))
def test_the_programs_the_prefill_rule_leaves_alone_trace_as_before(
        name, monkeypatch):
    from mmlspark_tpu.dl import causal_lm
    from mmlspark_tpu.dl.backbones import lm_module, lm_param_shapes
    from mmlspark_tpu.parallel import delta_rule, latent, retention

    for kernels in (latent, retention, delta_rule):
        monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    new, *pins = CELL_PROGRAMS[name]
    file = load_json("configs", name + ".json")
    config = {k: file[k] for k in file["model_keys"]}
    module, shapes = lm_module(config), lm_param_shapes(config)
    ids = jax.ShapeDtypeStruct((file["batchSize"], file["maxLength"]),
                               jnp.int32)
    lengths = jax.ShapeDtypeStruct((file["batchSize"],), jnp.int32)
    prefill = causal_lm.lm_prefill_program(module, file["prefillChunk"], new)
    last, state = jax.eval_shape(prefill, shapes, ids, lengths)
    texts = [str(jax.make_jaxpr(prefill)(shapes, ids, lengths))] + [
        str(jax.make_jaxpr(causal_lm.lm_generate_program(
            module, new, logits))(shapes, last, state))
        for logits in (False, True)]
    got = [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]
    assert [g if pin else None for g, pin in zip(got, pins)] == pins
