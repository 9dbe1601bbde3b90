"""``CausalLM`` over ``HybridLM`` read as ``model_type`` ``mimo_v2``
(grouped-query attention over a plain key/value cache, window layers
with sink logits on a 128-slot ring beside full ones, sigmoid-routed
experts with a selection bias) at a small size on the CPU, seeded
weights, float32, against the plain reference
(``benchmark/reference/mimo_v2_5.py``, through the benchmark's own
loader so there is one copy); and the programs of two configurations
that were there before, against the text they traced."""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lookup import load_json, load_module
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.logging_utils import SINK
from tests.dl.root_counts import STAGE_COUNTS

_FILE = load_json("rehearsal", "configs", "tiny_mimo.json")
# five layers standing for published 0-3 and 5: 0 (dense) and 5 full over
# 2 key heads, 1-3 over a window of 128 and 4 key heads; 8 query heads;
# 2 of 8 experts held
CFG = {k: _FILE[k] for k in _FILE["model_keys"]}
WINDOW = CFG["sliding_window"]
NEW = 20
LENGTHS = [5, 130, 200, 290]      # the last decodes past position 300
CHUNK = 48                        # divides no rung: the last step overhangs
# float32 on both sides, of the logits' scale (about 0.6): cache and ring
# against a mask over the whole sequence, blocked against whole softmax,
# grouped against looped experts. The rows read 6-8e-7
TOL = 1e-4


@pytest.fixture(scope="module")
def builder():
    return load_module("builders", "mimo_v2_5")


@pytest.fixture(scope="module")
def params(builder):
    """Seeded weights (the builder's: sink logits about 5), the
    projections scaled so that attention and every sub-layer weigh at
    this hidden size as at the published one (``x W`` over 64 normed
    inputs spreads an eighth of what it does over 4,096), and the
    selection bias ten times the builder's, so that a bias taken into
    the weights shows."""
    params = builder.make_weights(7, CFG)
    for i in range(CFG["num_hidden_layers"]):
        layer = params["params"][f"layers_{i}"]
        for name in ("qkv_proj", "o_proj"):
            layer["mixer"][name]["kernel"] = (
                layer["mixer"][name]["kernel"] * 8.0)
        for name in ("down_proj", "experts_down"):
            if name in layer["ffn"]:
                layer["ffn"][name] = layer["ffn"][name] * 8.0
        if "router_bias" in layer["ffn"]:
            layer["ffn"]["router_bias"] = layer["ffn"]["router_bias"] * 10.0
    return params


def _prompts(lengths=LENGTHS, seed=3):
    rng = np.random.default_rng(seed)
    col = np.empty(len(lengths), dtype=object)
    for i, n in enumerate(lengths):
        col[i] = rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
    return col


def _stage(params, **kw):
    from mmlspark_tpu.dl.causal_lm import CausalLM

    kw = {"batchSize": 4, "prefillChunk": CHUNK, "maxLength": 512, **kw}
    return CausalLM(inputCol="prompt", outputCol="completion",
                    modelConfig=CFG, maxNewTokens=NEW, **kw).set_weights(
                        params)


@pytest.fixture(scope="module")
def scored(params):
    col = _prompts()
    return col, _stage(params, logitsCol="logits").transform(
        DataFrame({"prompt": col}))


def _error(reference, weights, prompt, tokens, got):
    """Teacher forced: the largest difference from the reference's
    logits at the positions that emitted ``tokens``, over their scale;
    the reference's logits."""
    ids = np.concatenate([prompt, tokens])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    want = np.asarray(reference.logits(weights, ids, CFG, positions=at))
    return np.abs(got - want).max() / np.abs(want).max(), want


def test_the_layers_kinds_and_leaves(params):
    from mmlspark_tpu.dl.backbones import HybridLM, family_config

    blocks = HybridLM._blocks(family_config(CFG))
    assert [b.window() for b in blocks] == [False, True, True, True, False]
    assert [b.sparse() for b in blocks] == [False, True, True, True, True]
    reference = load_module("reference", "mimo_v2_5")
    assert [reference.is_window(CFG, i) for i in range(5)] == [
        b.window() for b in blocks]
    layers = params["params"]
    assert [("sink" in layers[f"layers_{i}"]["mixer"]) for i in range(5)] \
        == [False, True, True, True, False]
    assert layers["layers_1"]["mixer"]["qkv_proj"]["kernel"].shape == (
        64, 8 * 24 + 4 * (24 + 16))
    assert layers["layers_4"]["mixer"]["qkv_proj"]["kernel"].shape == (
        64, 8 * 24 + 2 * (24 + 16))
    ffn = layers["layers_1"]["ffn"]
    assert "router_bias" in ffn and "shared_gate" not in ffn
    assert ffn["experts_gate"].shape == (2, 64, 32)
    sink = np.asarray(layers["layers_1"]["mixer"]["sink"])
    assert 3.0 < sink.mean() < 7.0 and sink.std() > 0.3


def test_prefill_in_uneven_chunks_then_decode_equals_the_full_forward(
        builder, params, scored):
    """Prompts of 5-290 tokens absorbed in steps of 48 (the cache then
    overhangs the length rung), then 20 tokens decoded through the
    cache and, past position 300, a ring that has turned twice."""
    col, out = scored
    reference = load_module("reference", "mimo_v2_5")
    tokens = np.asarray(out.col("completion"))
    logprobs = np.asarray(out.col("logprobs"))
    assert tokens.shape == logprobs.shape == (len(col), NEW)
    weights = builder.reference_weights(params, CFG)
    for i in range(len(col)):
        err, want = _error(reference, weights, col[i], tokens[i],
                           np.asarray(out.col("logits")[i]))
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


def _bias_in_the_weights(scores, bias, chosen):
    picked = jnp.take_along_axis(scores + bias, chosen, axis=1)
    return picked / picked.sum(axis=1, keepdims=True)


# what a fault replaces in a copy of the reference of its own
FAULTS = {
    "the sink dropped": dict(sink_logit=lambda mixer, cfg, window: None),
    "the window off by one": dict(visible=_window_off_by_one),
    "the rotary bases swapped between kinds": dict(
        rotary_base=lambda cfg, window: (cfg["rope_theta"] if window
                                         else cfg["swa_rope_theta"])),
    "every dim rotated": dict(rotated_dims=lambda cfg, d: d),
    "the value scale left out": dict(value_scale=lambda cfg: 1.0),
    "key heads grouped wrongly": dict(
        key_head=lambda h, heads, key_heads: h % key_heads),
    "the bias used in the weights": dict(route_weights=_bias_in_the_weights),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_with_the_reference_can_fail(builder, params, scored,
                                                    fault):
    col, out = scored
    reference = load_module("reference", "mimo_v2_5")   # a copy to break
    for name, function in FAULTS[fault].items():
        assert hasattr(reference, name)
        setattr(reference, name, function)
    err, _ = _error(reference, builder.reference_weights(params, CFG),
                    col[3], np.asarray(out.col("completion"))[3],
                    np.asarray(out.col("logits")[3]))
    assert err > TOL


@pytest.mark.parametrize("precision", ["highest", "bfloat16"])
def test_the_references_shared_program_is_each_named_precision(precision):
    """The flag by which the two precisions a cell compares share the
    reference's programs: its products are ``reference/kimi_k2.py``'s
    in the named precision, of bfloat16 weights and of float32 ones;
    and an expert that reads only the tokens that chose it (300 of
    them, a step's tail padded) adds what every token through it,
    weighed by the mask, adds."""
    reference = load_module("reference", "mimo_v2_5")
    kimi = load_module("reference", "kimi_k2")
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((300, 64)), jnp.float32)
    flag = jnp.asarray(precision == "bfloat16")
    with jax.default_matmul_precision("highest"):
        for dtype in (jnp.bfloat16, jnp.float32):
            w = jnp.asarray(rng.standard_normal((64, 48)) * 0.1, dtype)
            got = reference._matmul(x, w, flag)
            want = kimi._matmul(x, w, precision)
            assert np.abs(np.asarray(got - want)).max() < 1e-6 * np.abs(
                np.asarray(want)).max()
        ffn = {"router": rng.standard_normal((64, 8)) * 0.06,
               "router_bias": rng.standard_normal(8) * 0.2,
               **{name: rng.standard_normal(shape) * 0.1 for name, shape in (
                   ("experts_gate", (2, 64, 32)), ("experts_up", (2, 64, 32)),
                   ("experts_down", (2, 32, 64)))}}
        ffn = {k: jnp.asarray(v, jnp.float32) for k, v in ffn.items()}
        got, _ = reference.expert_layer(x, ffn, CFG, flag)
        scores = jax.nn.sigmoid(x @ ffn["router"])
        chosen = jax.lax.top_k(scores + ffn["router_bias"],
                               CFG["num_experts_per_tok"])[1]
        weights = reference.route_weights(scores, ffn["router_bias"], chosen)
        want = sum(
            jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=1)[:, None]
            * kimi.swiglu(x, ffn["experts_gate"][e], ffn["experts_up"][e],
                          ffn["experts_down"][e], precision)
            for e in range(2))
    assert np.abs(np.asarray(want)).max() > 0.01
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * np.abs(
        np.asarray(want)).max()


def test_the_shares_add_up_to_the_uncut_layer():
    """The router's 8 experts in 4 shares of 2: what the four chips
    compute, summed, is the uncut reference's expert layer (there is no
    shared expert); one share alone is not."""
    from mmlspark_tpu.dl.backbones import ExpertFeedForward, family_config

    reference = load_module("reference", "mimo_v2_5")
    whole = dict(CFG, n_routed_experts=8, experts_held=[0, 8])
    rng = np.random.default_rng(11)

    def normal(*shape, std=0.1):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    h, w = CFG["hidden_size"], CFG["moe_intermediate_size"]
    ffn = {"router": normal(h, 8, std=0.5 * h ** -0.5),
           "router_bias": normal(8, std=0.2),
           "experts_gate": normal(8, h, w), "experts_up": normal(8, h, w),
           "experts_down": normal(8, w, h)}
    x = normal(2, 9, h, std=1.0)
    valid = np.ones((2, 9), bool)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_layer(x.reshape(-1, h), ffn, whole,
                                         "highest")
    parts, served = [], 0
    for first in range(0, 8, 2):
        share = family_config(dict(CFG, n_routed_experts=2,
                                   experts_held=[first, first + 2]))
        mine = {k: v[first:first + 2] if k.startswith("experts_") else v
                for k, v in ffn.items()}
        y, (pairs, dropped) = ExpertFeedForward(share).apply(
            {"params": mine}, x, valid)
        assert int(dropped) == 0
        served += int(pairs.sum())
        parts.append(np.asarray(y).reshape(-1, h))
    assert served == 18 * CFG["num_experts_per_tok"]    # every pair, once
    got = sum(parts)
    assert np.abs(got - np.asarray(want)).max() < 1e-5 * np.abs(want).max()
    assert np.abs(parts[0] - np.asarray(want)).max() > 0.05 * np.abs(
        want).max()


def test_the_routed_runners_path_gives_the_references_tokens(builder):
    """The cell's own path at the rehearsal's size: the builder's stage,
    weights and frames (``build``, ``make_frames``), one call, and the
    routed runner's comparison (``margin_errors``) against the
    reference, whose greedy tokens the call emitted."""
    cell = load_json("rehearsal", "cells", "tiny_mimo.long_decode.json")
    ctx = types.SimpleNamespace(config=_FILE, cell=cell, seed=3000000019)
    subject = builder.build(ctx)
    traffic = cell["traffic"]
    frame = builder.make_frames(ctx.seed, 1, traffic["frame_rows"], traffic,
                                CFG["vocab_size"])[0]
    subject["model"]._set(logitsCol="logits")
    out = subject["model"].transform(DataFrame({"prompt": frame}))
    tokens = np.asarray(out.col("completion"))
    reference = load_module("reference", "mimo_v2_5")
    errors = load_module("runners", "generate_routed").margin_errors(
        cell["correct"]["margin_min"], {})
    longest = max(len(p) for p in frame) + traffic["new_tokens"]
    for r in range(2):
        err, _ = errors(reference, subject, np.asarray(frame[r]), tokens[r],
                        np.asarray(out.col("logprobs"))[r],
                        np.asarray(out.col("logits")[r]), longest, "highest")
        assert max(err.values()) < TOL
        assert err["argmax_gap"] == 0.0         # the reference's own tokens


def _window_read(tokens):
    return sum(min(p + 1, WINDOW) for p in range(tokens))


def test_caches_counts_and_scopes(params):
    from mmlspark_tpu.dl.backbones import (
        lm_init_state, lm_module, lm_state_bytes)

    layers = lm_init_state(CFG, 2, 1000)["layers"]
    assert [("ring" in s) for s in layers] == [False, True, True, True,
                                               False]
    assert layers[0]["k_r"].shape == (2, 1000, 2 * 8)
    assert layers[0]["k_n"].shape == (2, 1000, 2 * 16)
    assert layers[4]["v"].shape == (2, 1000, 2 * 16)
    assert layers[1]["ring"]["k_n"].shape == (2, WINDOW, 4 * 16)
    small, large = (lm_state_bytes(CFG, 2, n) for n in (16, 1000))
    ring = 3 * 2 * WINDOW * 4 * (24 + 16) * 4
    assert small["state"] == large["state"] >= ring
    assert large["cache"] - small["cache"] == 2 * 2 * 984 * 2 * (24 + 16) * 4

    col = _prompts()
    before = len(SINK.events)
    _stage(params).transform(DataFrame({"prompt": col}))
    counts = [r for r in SINK.events[before:]
              if r.get("className") == "CausalLM"][-1]["counts"]
    assert set(counts) == STAGE_COUNTS | {
        "cache_positions", "cache_capacity", "window_positions",
        "expert_pairs", "expert_pairs_max", "dropped_pairs"}
    through = [n + NEW - 1 for n in LENGTHS]
    assert counts["cache_positions"] == 2 * sum(through)
    assert counts["window_positions"] == 3 * sum(map(_window_read, through))
    assert counts["dropped_pairs"] == 0
    for t in (1, 16):
        text = jax.jit(lm_module(CFG).apply).lower(
            params, jnp.zeros((2, t), jnp.int32), jnp.full((2,), t,
                                                           jnp.int32),
            lm_init_state(CFG, 2, 32)).as_text(debug_info=True)
        for layer, kind in enumerate(["full", "window", "window", "window",
                                      "full"]):
            assert f"layers_{layer}/lm.kv/mixer/lm.kv.{kind}" in text
        assert "lm.mla" not in text


def _kernel_names(program, *args):
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub)

    return sorted(walk(jax.make_jaxpr(program)(*args).jaxpr))


def test_the_kernels_in_the_stage_equal_the_jnp_path(params, monkeypatch):
    """The whole stage with the full layers' prefill and every decode
    step through the Pallas kernels, interpreted: the tokens and
    log-probabilities of the ``jax.numpy`` path; ``lm_prefill`` calls
    ``kv_prefill`` once for each of the two full layers,
    ``lm_generate`` ``kv_decode`` once a layer."""
    from mmlspark_tpu.parallel import kv

    col = _prompts()[[1, 2]]                 # past the window, rung 256
    base = _stage(params).transform(DataFrame({"prompt": col}))
    monkeypatch.setattr(kv, "use_pallas", lambda: True)
    for name in ("_decode_pallas", "_prefill_pallas"):
        monkeypatch.setattr(kv, name, lambda *a, _real=getattr(
            kv, name): _real(*a[:-1], True))
    stage = _stage(params)
    out = stage.transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(out.col("completion")),
                          np.asarray(base.col("completion")))
    assert np.abs(np.asarray(out.col("logprobs"))
                  - np.asarray(base.col("logprobs"))).max() < 2e-5
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    lengths = jax.ShapeDtypeStruct((2,), jnp.int32)
    prefill = stage._program("lm_prefill")
    assert _kernel_names(prefill, stage._weights, ids, lengths) == [
        "kv_prefill"] * 2
    last, state = jax.eval_shape(prefill, stage._weights, ids, lengths)
    assert _kernel_names(stage._program("lm_generate"), stage._weights,
                         last, state) == ["kv_decode"] * 5


# sha256 (first 16 hex digits) of the jaxpr text of lm_prefill (the
# config file's batchSize x maxLength in steps of prefillChunk, 4 new
# tokens) and of lm_generate without and with logits, as the code before
# the key/value cache traced them
PROGRAMS = {
    "tiny_motif": ("3183a311e530370b", "1e1ab947aef9a6ac",
                   "5ce4ca401874fc3f"),
    "tiny_kimi": ("a5dfb809f3852a4d", "1364657d853bbcb7",
                  "314f092870db04b4"),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_the_latent_configs_trace_the_programs_they_traced(name):
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
