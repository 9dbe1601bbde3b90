"""``CausalLM`` over ``HybridLM`` read as ``model_type`` ``kimi_k2``
(latent attention in every layer, plain RMSNorm before each sub-layer
and none after, no attention gate, no clamp; layer 0 over a dense
SwiGLU, the others over sparse experts) at a small size on the CPU,
seeded weights, float32, against the plain reference
(``benchmark/reference/kimi_k2.py``, through the benchmark's own loader
so there is one copy)."""

import numpy as np
import pytest

from benchmark.lookup import load_module
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.logging_utils import SINK
from tests.dl import group_loop

# three layers: 0 over the dense SwiGLU, 1 and 2 over the experts
# 6..11 of 24 (a count that is no power of two)
CFG = dict(
    model_type="kimi_k2", hidden_size=64, vocab_size=256,
    num_hidden_layers=3, rms_norm_eps=1e-5, first_k_dense_replace=1,
    moe_layer_freq=1, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=6, experts_held=[6, 12], router_experts=24,
    num_experts_per_tok=4, routed_scaling_factor=2.827, n_shared_experts=1,
    norm_topk_prob=True, n_group=1, topk_group=1, scoring_func="sigmoid",
    topk_method="noaux_tc", kv_lora_rank=32, q_lora_rank=48,
    qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
    num_attention_heads=4, num_key_value_heads=4, rope_theta=50000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    torch_dtype="float32")
NEW = 6
LENGTHS = [5, 17, 9, 30, 12]
# float32 on both sides: cache against the whole sequence, absorbed
# against expanded attention, grouped against looped experts; of the
# logits' scale (about 1)
TOL = 1e-4


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "kimi_k2")


@pytest.fixture(scope="module")
def builder():
    return load_module("builders", "kimi_k2")


@pytest.fixture(scope="module")
def params(builder):
    """Seeded weights; every mixer's query and key projections eight
    times the builder's 0.02, so that at this hidden size the scores are
    of order one as at the published one (a near-uniform softmax would
    hide a wrong rotary pairing or softmax scale), and every
    sub-layer's output projection eight times, so that a sub-layer
    weighs in the residual beside the embedding as it does at 7,168 (a
    fault in one would else move the logits by a thousandth)."""
    params = builder.make_weights(7, CFG)
    for i in range(CFG["num_hidden_layers"]):
        layer = params["params"][f"layers_{i}"]
        for name in ("q_b_proj", "kv_a_proj", "o_proj"):
            layer["mixer"][name]["kernel"] = (
                layer["mixer"][name]["kernel"] * 8.0)
        for name in ("down_proj", "experts_down", "shared_down"):
            if name in layer["ffn"]:
                layer["ffn"][name] = layer["ffn"][name] * 8.0
    return params


def _prompts(lengths=LENGTHS, seed=1):
    rng = np.random.default_rng(seed)
    col = np.empty(len(lengths), dtype=object)
    for i, n in enumerate(lengths):
        col[i] = rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
    return col


def _stage(params, **kw):
    from mmlspark_tpu.dl.causal_lm import CausalLM

    kw = {"batchSize": 4, "prefillChunk": 8, "maxLength": 64, **kw}
    return CausalLM(inputCol="prompt", outputCol="completion",
                    modelConfig=CFG, maxNewTokens=NEW, **kw).set_weights(
                        params)


@pytest.fixture(scope="module")
def scored(params):
    col = _prompts()
    out = _stage(params, logitsCol="logits").transform(
        DataFrame({"prompt": col}))
    return col, out


def _reference_logits(reference, weights, prompt, tokens, **cfg):
    """Teacher forced: the reference's logits at the positions that
    emitted ``tokens``, and their least routing margin."""
    ids = np.concatenate([prompt, tokens])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    want, margin = reference.logits(weights, ids, dict(CFG, **cfg),
                                    positions=at, margins=True)
    return np.asarray(want), np.asarray(margin)


def test_the_builders_norm_scales_lie_about_one(builder, params):
    p = params["params"]
    for scale in (p["final_norm"], p["layers_1"]["mixer_pre"],
                  p["layers_2"]["mixer"]["kv_a_norm"]):
        scale = np.asarray(scale)
        assert 0.9 < scale.mean() < 1.1 and scale.std() > 0.1
    assert abs(np.asarray(p["layers_1"]["ffn"]["router_bias"]).mean()) < 0.02
    assert "mixer_post" not in p["layers_0"] and "g_proj" not in \
        p["layers_0"]["mixer"]


def test_prefill_then_decode_equals_the_references_full_forward(
        reference, builder, params, scored):
    col, out = scored
    tokens = np.asarray(out.col("completion"))
    logprobs = np.asarray(out.col("logprobs"))
    assert tokens.shape == logprobs.shape == (len(col), NEW)
    weights = builder.reference_weights(params, CFG)
    for i in range(len(col)):
        want, margin = _reference_logits(reference, weights, col[i],
                                         tokens[i])
        assert margin.min() > 1e-5              # no choice hangs on an ulp
        scale = np.abs(want).max()
        got = np.asarray(out.col("logits")[i])
        assert np.abs(got - want).max() < TOL * scale      # every position
        assert np.array_equal(want.argmax(-1), tokens[i])
        shifted = want - want.max(-1, keepdims=True)
        want_lp = shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))
        assert np.abs(want_lp[np.arange(NEW), tokens[i]]
                      - logprobs[i]).max() < TOL * scale


def _norms_read_as_gated(weights):
    """What a program with the gated norm in the plain one's place would
    compute: every norm's weight ``w`` acting as ``2 sigmoid(w)``."""
    def gate(w):
        return 2.0 / (1.0 + np.exp(-np.asarray(w, np.float32)))

    layers = [dict(layer, attn_norm=gate(layer["attn_norm"]),
                   ffn_norm=gate(layer["ffn_norm"]),
                   mixer=dict(layer["mixer"],
                              q_norm=gate(layer["mixer"]["q_norm"]),
                              kv_norm=gate(layer["mixer"]["kv_norm"])))
              for layer in weights["layers"]]
    return dict(weights, layers=layers,
                final_norm=gate(weights["final_norm"]))


def _without_selection_bias(weights):
    return dict(weights, layers=[
        dict(layer, ffn=dict(layer["ffn"], router_bias=np.zeros_like(
            np.asarray(layer["ffn"]["router_bias"], np.float32))))
        if "router_bias" in layer["ffn"] else layer
        for layer in weights["layers"]])


UNSCALED = dict(CFG["rope_scaling"], mscale_all_dim=0)
FAULTS = {
    "softmax scale": (dict(rope_scaling=UNSCALED), None),
    "rotary pairing": (dict(rope_interleave=False), None),
    "router scale": (dict(routed_scaling_factor=1.0), None),
    "selection bias": ({}, _without_selection_bias),
    "gated norm": ({}, _norms_read_as_gated),
    "top-k normalisation": (dict(norm_topk_prob=False), None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_with_the_reference_can_fail(reference, builder,
                                                    params, scored, fault):
    col, out = scored
    tokens = np.asarray(out.col("completion"))
    cfg, move = FAULTS[fault]
    weights = builder.reference_weights(params, CFG)
    if move is not None:
        weights = move(weights)
    want, _ = _reference_logits(reference, weights, col[3], tokens[3], **cfg)
    got = np.asarray(out.col("logits")[3])
    assert np.abs(got - want).max() > 50 * TOL * np.abs(want).max()


def test_a_row_does_not_change_with_its_rungs_or_its_neighbours(
        params, monkeypatch):
    from mmlspark_tpu.dl.backbones import HybridLM

    col = _prompts()
    base = _stage(params).transform(DataFrame({"prompt": col}))
    tokens = np.asarray(base.col("completion"))
    logprobs = np.asarray(base.col("logprobs"))
    variants = {
        "alone": (_stage(params), [2]),                       # row rung 1
        "row rung 8": (_stage(params, batchSize=8), [0, 1, 2, 3, 4]),
        "other neighbours": (_stage(params), [4, 2, 0]),
        "one token a prefill step": (_stage(params, prefillChunk=1), [2, 3]),
        "one prefill step": (_stage(params, prefillChunk=128), [3, 1]),
    }
    for name, (stage, rows) in variants.items():
        out = stage.transform(DataFrame({"prompt": col[rows]}))
        assert np.array_equal(np.asarray(out.col("completion")),
                              tokens[rows]), name
        assert np.abs(np.asarray(out.col("logprobs"))
                      - logprobs[rows]).max() < 2e-5, name
    # a prefill step cut into two groups of rows (4 x 8 tokens over 16)
    monkeypatch.setattr(HybridLM, "GROUP_TOKENS", 16)
    out = _stage(params).transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(out.col("completion")), tokens)
    assert np.abs(np.asarray(out.col("logprobs")) - logprobs).max() < 2e-5
    monkeypatch.undo()
    # a longer length rung (and so a larger cache): row 2 (9 tokens)
    # beside a 200-token prompt
    long_col = _prompts([9, 200], seed=1)
    long_col[0] = col[2]
    out = _stage(params, maxLength=256, batchSize=2).transform(
        DataFrame({"prompt": long_col}))
    assert np.array_equal(np.asarray(out.col("completion"))[0], tokens[2])
    assert np.abs(np.asarray(out.col("logprobs"))[0]
                  - logprobs[2]).max() < 2e-5


def test_the_decode_kernel_in_the_stage_equals_the_twin(params, monkeypatch):
    """The whole stage with the Pallas decode interpreted in place of
    its ``jax.numpy`` twin: the same tokens and log-probabilities."""
    from mmlspark_tpu.parallel import latent

    col = _prompts()
    base = _stage(params).transform(DataFrame({"prompt": col}))
    monkeypatch.setattr(latent, "use_pallas", lambda: True)
    monkeypatch.setattr(
        latent, "_decode_pallas",
        lambda *a, _real=latent._decode_pallas: _real(*a[:-1], True))
    out = _stage(params).transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(out.col("completion")),
                          np.asarray(base.col("completion")))
    assert np.abs(np.asarray(out.col("logprobs"))
                  - np.asarray(base.col("logprobs"))).max() < 2e-5


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """24 experts in 2 shares of 12: what the two chips compute, with
    the shared expert (which both compute alike) counted once, is the
    uncut reference's expert layer."""
    import jax

    from mmlspark_tpu.dl.backbones import ExpertFeedForward

    whole = dict(CFG, n_routed_experts=24, experts_held=[0, 24])
    rng = np.random.default_rng(11)

    def normal(*shape, std=0.1):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    h, w = CFG["hidden_size"], CFG["moe_intermediate_size"]
    ffn = {"router": normal(h, 24, std=0.5 * h ** -0.5),
           "router_bias": normal(24, std=0.02),
           "experts_gate": normal(24, h, w), "experts_up": normal(24, h, w),
           "experts_down": normal(24, w, h), "shared_gate": normal(h, w),
           "shared_up": normal(h, w), "shared_down": normal(w, h)}
    x = normal(2, 9, h, std=1.0)
    valid = np.ones((2, 9), bool)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_layer(x.reshape(-1, h), ffn, whole,
                                         "highest")
        shared = reference.swiglu(x.reshape(-1, h), ffn["shared_gate"],
                                  ffn["shared_up"], ffn["shared_down"],
                                  "highest")
    parts, served = [], 0
    for first in (0, 12):
        share = dict(CFG, n_routed_experts=12,
                     experts_held=[first, first + 12])
        mine = {k: v[first:first + 12] if k.startswith("experts_") else v
                for k, v in ffn.items()}
        y, (pairs, dropped) = ExpertFeedForward(share).apply(
            {"params": mine}, x, valid)
        assert int(dropped) == 0
        served += int(pairs.sum())
        parts.append(np.asarray(y).reshape(-1, h))
    assert served == 18 * CFG["num_experts_per_tok"]   # every pair, once
    got = parts[0] + parts[1] - np.asarray(shared)
    assert np.abs(got - np.asarray(want)).max() < 1e-5 * np.abs(want).max()
    # and one share alone is not the layer
    assert np.abs(parts[0] - np.asarray(want)).max() > 0.05 * np.abs(
        want).max()


@pytest.fixture(scope="module")
def group_loops(params):
    return group_loop.compile_loops(CFG, params)


@pytest.mark.parametrize("ended", sorted(group_loop.ENDED))
@pytest.mark.parametrize("order", group_loop.ORDERS)
def test_the_bounded_group_loop_equals_the_loop_over_all_groups(
        group_loops, order, ended):
    """A prefill step's rows in any order, with zero-length rows at the
    end or in the middle, in steps where no, some and all groups have
    ended: state, counters and hidden rows to the bit."""
    group_loop.check(group_loops, CFG, order, ended)


def test_a_prefill_that_skips_ended_groups_equals_the_one_group_stage(
        params, monkeypatch):
    """Tokens, log-probabilities and the root's ``prefill_visits`` and
    ``prefill_visits_run`` for hand-made lengths."""
    group_loop.check_stage(lambda **kw: _stage(params, **kw), _prompts,
                           monkeypatch)


def test_one_group_is_the_plain_call_with_no_loop(params):
    group_loop.check_one_group(CFG, params)


def test_save_load_spans_and_counts(params, tmp_path):
    from mmlspark_tpu.core.pipeline import PipelineStage
    from mmlspark_tpu.dl.backbones import lm_init_state, lm_state_bytes

    col = _prompts()
    stage = _stage(params)
    before = len(SINK.events)
    first = stage.transform(DataFrame({"prompt": col}))
    record = [r for r in SINK.events[before:]
              if r.get("className") == "CausalLM"][-1]
    counts = record["counts"]
    assert counts["new_tokens"] == 5 * NEW and counts["length_rung"] == 128
    # no recurrent state: 4 rows' positions and the experts' counters
    # (2 layers x 6 held, and the dropped pairs)
    assert counts["state_bytes"] == 4 * 4 + 4 * (2 * 6 + 1)
    # three latent layers: 32 + 8 float32 values a position, 128 + 6
    # positions a row, 4 rows a device batch
    assert counts["cache_bytes"] == 3 * 4 * (128 + NEW) * (32 + 8) * 4
    assert lm_state_bytes(CFG, 4, 134)["cache"] == counts["cache_bytes"]
    # when the call ends a row has filled its prompt and NEW - 1 tokens,
    # in each of the three layers; capacity is the 5 rows' 134 positions
    assert counts["cache_positions"] == 3 * (sum(LENGTHS) + 5 * (NEW - 1))
    assert counts["cache_capacity"] == 3 * 5 * (128 + NEW)
    assert counts["dropped_pairs"] == 0
    through = sum(LENGTHS) + 5 * (NEW - 1)
    assert 0.1 < counts["expert_pairs"] / (through * 2 * 4) < 0.5
    state = lm_init_state(CFG, 4, 128 + NEW)
    assert [sorted(layer) for layer in state["layers"]] == [["c", "r"]] * 3
    assert state["layers"][0]["c"].shape == (4, 128 + NEW, 32)
    assert state["experts"]["pairs"].shape == (2, 6)

    stage.save(str(tmp_path / "lm"))
    loaded = PipelineStage.load(str(tmp_path / "lm"))
    again = loaded.transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(again.col("completion")),
                          np.asarray(first.col("completion")))
    assert np.array_equal(np.asarray(again.col("logprobs")),
                          np.asarray(first.col("logprobs")))


def test_the_scopes_name_the_decode_and_the_write(params):
    """``lm.mla.decode`` and ``lm.mla.write`` stand inside ``lm.mla`` in
    the decode program's text."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.backbones import lm_init_state, lm_module

    module = lm_module(CFG)
    text = jax.jit(module.apply).lower(
        params, jnp.zeros((2, 1), jnp.int32), jnp.ones((2,), jnp.int32),
        lm_init_state(CFG, 2, 16)).as_text(debug_info=True)
    assert "lm.mla/mixer/lm.mla.decode" in text
    assert "lm.mla/mixer/lm.mla.write" in text
    assert "lm.gdn" not in text


def test_a_bfloat16_model_keeps_its_cache_bfloat16(params):
    import jax.numpy as jnp

    from mmlspark_tpu.dl.backbones import lm_init_state
    from mmlspark_tpu.dl.causal_lm import CausalLM

    config = dict(CFG, torch_dtype="bfloat16")
    state = lm_init_state(config, 2, 16)
    assert all(layer["c"].dtype == layer["r"].dtype == jnp.bfloat16
               for layer in state["layers"])
    stage = CausalLM(inputCol="prompt", outputCol="completion",
                     modelConfig=config, maxNewTokens=NEW, batchSize=4,
                     prefillChunk=8).set_weights(params)
    out = stage.transform(DataFrame({"prompt": _prompts()}))
    placed = stage._ensure_scorer()._params["params"]
    assert placed["layers_1"]["ffn"]["experts_gate"].dtype == jnp.bfloat16
    assert placed["layers_0"]["mixer_pre"].dtype == jnp.bfloat16
    base = _stage(params).transform(DataFrame({"prompt": _prompts()}))
    diff = np.abs(np.asarray(out.col("logprobs"))
                  - np.asarray(base.col("logprobs"))).max()
    assert 0 < diff < 0.5
