"""``CausalLM`` over ``HybridLM`` (delta-rule and latent-attention
layers over a dense SwiGLU and sparse experts) at a small size on the
CPU, seeded weights, float32, against the plain reference
(``benchmark/reference/gigachat3_5.py``, through the benchmark's own
loader so there is one copy)."""

import numpy as np
import pytest

from benchmark.lookup import load_module
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.logging_utils import SINK
from tests.dl import group_loop

# layer 0: delta rule over the dense SwiGLU; 1: latent attention; 2, 3:
# delta rule; 1-3 over the experts 4..7 of 16. swiglu_limit 0.1 so that
# the clamp binds
CFG = dict(
    model_type="gigachat3_5", hidden_size=64, vocab_size=256,
    num_hidden_layers=4, rms_norm_eps=1e-6, full_attention_layers=[1],
    first_k_dense_replace=1, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=4, experts_held=[4, 8],
    router_experts=16, num_experts_per_tok=4, routed_scaling_factor=2.5,
    n_shared_experts=1, norm_topk_prob=True, kv_lora_rank=32,
    q_lora_rank=48, qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
    num_attention_heads=4, rope_theta=100000, rope_interleave=True,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 64, "type": "yarn"},
    use_mla_scaling_factor=True, gated_attention=True,
    linear_key_head_dim=16, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_attn_o_norm_eps=1e-6, swiglu_limit=0.1,
    torch_dtype="float32")
NEW = 6
LENGTHS = [5, 17, 9, 30, 12]
# float32 on both sides: chunked against recurrent delta rule, cache
# against the whole sequence, grouped against looped experts; of the
# logits' scale (about 0.5)
TOL = 1e-4


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "gigachat3_5")


@pytest.fixture(scope="module")
def builder():
    return load_module("builders", "gigachat3_5")


@pytest.fixture(scope="module")
def params(builder):
    """Seeded weights; the latent mixer's query and key projections
    eight times the builder's 0.02, so that at this hidden size the
    scores are of order one as at the published one (a near-uniform
    softmax would hide a wrong rotary pairing or softmax scale)."""
    params = builder.make_weights(7, CFG)
    mixer = params["params"]["layers_1"]["mixer"]
    for name in ("q_b_proj", "kv_a_proj"):
        mixer[name]["kernel"] = mixer[name]["kernel"] * 8.0
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


def _swap_key_heads(mixer):
    """Key head 0 takes key head 1's place: the value heads then read
    the other key head (the convolution's taps move with their
    channels)."""
    d = CFG["linear_key_head_dim"]

    def swap(a, lo):
        a = np.array(a)
        a[..., lo:lo + d], a[..., lo + d:lo + 2 * d] = (
            a[..., lo + d:lo + 2 * d].copy(), a[..., lo:lo + d].copy())
        return a

    return dict(mixer, wq=swap(mixer["wq"], 0), wk=swap(mixer["wk"], 0),
                conv=swap(swap(mixer["conv"], 0), 2 * d))


FAULTS = {
    "gate": (dict(gated_attention=False), None),
    "decay": ({}, lambda m: dict(m, dt_bias=m["dt_bias"] + 1.0)
              if "dt_bias" in m else m),
    "beta": ({}, lambda m: dict(m, wb=m["wb"] * 0.5) if "wb" in m else m),
    "grouping": ({}, lambda m: _swap_key_heads(m) if "wq" in m else m),
    "rotary pairing": (dict(rope_interleave=False), None),
    "softmax scale": (dict(use_mla_scaling_factor=False), None),
    "top-k normalisation": (dict(norm_topk_prob=False), None),
    "scaling factor": (dict(routed_scaling_factor=1.0), None),
    "clamp": (dict(swiglu_limit=None), None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_with_the_reference_can_fail(reference, builder,
                                                    params, scored, fault):
    col, out = scored
    tokens = np.asarray(out.col("completion"))
    cfg, move = FAULTS[fault]
    weights = builder.reference_weights(params, CFG)
    if move is not None:
        weights = dict(weights, layers=[
            dict(layer, mixer=move(layer["mixer"]))
            for layer in weights["layers"]])
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
        "a chunk longer than the delta rule's": (
            _stage(params, prefillChunk=128), [3, 1]),
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
    from mmlspark_tpu.dl.backbones import lm_init_state

    col = _prompts()
    stage = _stage(params)
    before = len(SINK.events)
    first = stage.transform(DataFrame({"prompt": col}))
    record = [r for r in SINK.events[before:]
              if r.get("className") == "CausalLM"][-1]
    counts = record["counts"]
    assert counts["new_tokens"] == 5 * NEW and counts["length_rung"] == 128
    # 4 rows: three delta-rule layers of 4 heads x 16 x 16 float32 and a
    # tail of 3 x 128 channels, and the 4-byte position; the experts'
    # counters (3 layers x 4 held, and the dropped pairs)
    assert counts["state_bytes"] == (
        4 * (3 * 4 * (4 * 16 * 16 + 3 * 128) + 4) + 4 * (3 * 4 + 1))
    # one latent layer: 32 + 8 float32 values a position, 128 + 6 of them
    assert counts["cache_bytes"] == 4 * (128 + NEW) * (32 + 8) * 4
    assert counts["dropped_pairs"] == 0
    # 5 rows x (prompt + new - 1) tokens x 3 expert layers x 4 choices,
    # of which about a quarter fall on the 4 of 16 experts held
    through = sum(LENGTHS) + 5 * (NEW - 1)
    assert 0.1 < counts["expert_pairs"] / (through * 3 * 4) < 0.5
    assert counts["expert_pairs_max"] * 12 >= counts["expert_pairs"]
    state = lm_init_state(CFG, 4, 128 + NEW)
    assert state["layers"][1]["c"].shape == (4, 128 + NEW, 32)
    assert state["layers"][0]["s"].shape == (4, 4, 16, 16)

    stage.save(str(tmp_path / "lm"))
    loaded = PipelineStage.load(str(tmp_path / "lm"))
    again = loaded.transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(again.col("completion")),
                          np.asarray(first.col("completion")))
    assert np.array_equal(np.asarray(again.col("logprobs")),
                          np.asarray(first.col("logprobs")))


def test_an_unknown_model_type_is_refused(params):
    from mmlspark_tpu.dl.causal_lm import CausalLM

    with pytest.raises(ValueError, match="model_type"):
        CausalLM(inputCol="prompt", modelConfig=dict(CFG, model_type="nope"),
                 allowRandomWeights=True).transform(
            DataFrame({"prompt": _prompts()}))


def test_a_bfloat16_model_keeps_its_state_float32_and_its_cache_bfloat16(
        params):
    import jax.numpy as jnp

    from mmlspark_tpu.dl.backbones import lm_init_state
    from mmlspark_tpu.dl.causal_lm import CausalLM

    config = dict(CFG, torch_dtype="bfloat16")
    state = lm_init_state(config, 2, 16)
    assert state["layers"][0]["s"].dtype == jnp.float32
    assert state["layers"][0]["conv"].dtype == jnp.float32
    assert state["layers"][1]["c"].dtype == jnp.bfloat16
    stage = CausalLM(inputCol="prompt", outputCol="completion",
                     modelConfig=config, maxNewTokens=NEW, batchSize=4,
                     prefillChunk=8).set_weights(params)
    out = stage.transform(DataFrame({"prompt": _prompts()}))
    placed = stage._ensure_scorer()._params["params"]
    assert placed["layers_1"]["ffn"]["experts_gate"].dtype == jnp.bfloat16
    assert placed["layers_1"]["ffn"]["router"].dtype == jnp.bfloat16
    base = _stage(params).transform(DataFrame({"prompt": _prompts()}))
    diff = np.abs(np.asarray(out.col("logprobs"))
                  - np.asarray(base.col("logprobs"))).max()
    assert 0 < diff < 0.5
