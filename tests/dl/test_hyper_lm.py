"""``CausalLM`` over ``HybridLM`` read as ``model_type`` ``xing4_0`` (the
``kimi_k2`` layer inside a residual path of four streams a token,
``parallel/hyper.py``) at a small size on the CPU, seeded weights,
float32, against the plain reference
(``benchmark/reference/xing4_0.py``, through the benchmark's own loader
so there is one copy); and the configs without ``hc_mult`` against the
block as it was (``tests/dl/plain_block.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lookup import load_json, load_module
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.logging_utils import SINK
from tests.dl import group_loop
from tests.dl.plain_block import PlainLM
from tests.dl.root_counts import ROOT_COUNTS

# three layers: 0 over the dense SwiGLU, 1 and 2 over all 8 experts
CFG = dict(
    model_type="xing4_0", hidden_size=64, vocab_size=256,
    num_hidden_layers=3, rms_norm_eps=1e-6, first_k_dense_replace=1,
    moe_layer_freq=1, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=8, num_experts_per_tok=2, routed_scaling_factor=2,
    n_shared_experts=1, norm_topk_prob=True, n_group=1, topk_group=1,
    scoring_func="sigmoid", topk_method="noaux_tc", kv_lora_rank=32,
    q_lora_rank=48, qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
    num_attention_heads=4, num_key_value_heads=4, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, torch_dtype="float32")
NEW = 6
LENGTHS = [5, 17, 9, 30, 12]
# float32 on both sides, of the logits' scale (about 0.6): cache against
# the whole sequence, absorbed against expanded attention, grouped
# against looped experts, the stream's norm after its projection
# against before it, a rolled loop of rounds against an unrolled one.
# The five rows read 2-3e-7; the smallest fault below reads 4.6e-4
TOL = 1e-5
FAULT = 10 * TOL


@pytest.fixture(scope="module")
def builder():
    return load_module("builders", "xing4_0")


@pytest.fixture(scope="module")
def params(builder):
    """Seeded weights, the residual path's leaves off the trivial (the
    builder's), the projections scaled as ``test_latent_lm.py`` scales
    them so that attention and every sub-layer weigh at this hidden
    size as they do at the published one."""
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


def _prompts(lengths=LENGTHS, seed=3):
    rng = np.random.default_rng(seed)
    col = np.empty(len(lengths), dtype=object)
    for i, n in enumerate(lengths):
        col[i] = rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
    return col


def _stage(params, config=CFG, **kw):
    from mmlspark_tpu.dl.causal_lm import CausalLM

    kw = {"batchSize": 4, "prefillChunk": 8, "maxLength": 64, **kw}
    return CausalLM(inputCol="prompt", outputCol="completion",
                    modelConfig=config, maxNewTokens=NEW, **kw).set_weights(
                        params)


@pytest.fixture(scope="module")
def scored(params):
    col = _prompts()
    return col, _stage(params, logitsCol="logits").transform(
        DataFrame({"prompt": col}))


def _error(reference, weights, prompt, tokens, got, **cfg):
    """Teacher forced: the largest difference from the reference's
    logits at the positions that emitted ``tokens``, over their scale;
    the reference's logits; their least routing margin."""
    ids = np.concatenate([prompt, tokens])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    want, margin = reference.logits(weights, ids, dict(CFG, **cfg),
                                    positions=at, margins=True)
    want = np.asarray(want)
    return (np.abs(got - want).max() / np.abs(want).max(), want,
            float(np.asarray(margin).min()))


def test_the_builders_residual_leaves_are_off_the_trivial(builder, params):
    reference = load_module("reference", "xing4_0")
    weights = builder.reference_weights(params, CFG)
    x = reference.copy_in(jnp.take(weights["embed"], _prompts()[3], axis=0),
                          CFG["hc_mult"])
    for name in ("mixer_hc", "ffn_hc"):
        h_pre, h_post, h_res = map(np.asarray, reference.coefficients(
            x, weights["layers"][1][name], CFG))
        assert 0.05 < h_pre.min() and h_pre.max() < 0.95
        assert 0.1 < h_post.min() and h_post.max() < 1.9
        assert h_res.max() < 0.95 and 0.1 < np.median(h_res) < 0.3
        assert min(h.std(axis=0).min() for h in (h_pre, h_post, h_res)) > .01
        off = np.abs(h_res.sum(axis=1) - 1)         # a column's sum
        assert off.max() < 1e-2 and np.median(off) < 1e-5
    assert sorted(params["params"]["layers_0"]["mixer_hc"]) == [
        "alpha", "b_post", "b_pre", "b_res", "phi"]
    assert params["params"]["layers_2"]["ffn_hc"]["phi"].shape == (4 * 64, 24)


def test_prefill_then_decode_equals_the_references_full_forward(
        builder, params, scored):
    col, out = scored
    reference = load_module("reference", "xing4_0")
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


def _rows_first(m, iters, eps):
    for _ in range(iters):
        m = m / (m.sum(axis=2, keepdims=True) + eps)
        m = m / (m.sum(axis=1, keepdims=True) + eps)
    return m


# what a fault replaces in a copy of the reference of its own
FAULTS = {
    "one Sinkhorn round": (dict(hc_sinkhorn_iters=1), {}),
    "H_post without its 2": ({}, dict(post_gate=jax.nn.sigmoid)),
    "H_res the identity": ({}, dict(sinkhorn=lambda m, iters, eps: (
        jnp.broadcast_to(jnp.eye(m.shape[-1]), m.shape)))),
    "coefficients from the un-normed stream": (
        {}, dict(stream_norm=lambda flat, eps: flat)),
    "the read-out one stream": ({}, dict(read_out=lambda x: x[:, 0])),
    "the copy-in one stream": ({}, dict(copy_in=lambda h, n: jnp.concatenate(
        [h[:, None], jnp.zeros((h.shape[0], n - 1, h.shape[1]))], axis=1))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_with_the_reference_can_fail(builder, params, scored,
                                                    fault):
    col, out = scored
    cfg, replaced = FAULTS[fault]
    reference = load_module("reference", "xing4_0")     # a copy to break
    for name, function in replaced.items():
        assert hasattr(reference, name)
        setattr(reference, name, function)
    err, _, _ = _error(reference, builder.reference_weights(params, CFG),
                       col[3], np.asarray(out.col("completion"))[3],
                       np.asarray(out.col("logits")[3]), **cfg)
    assert err > FAULT


def test_two_faults_the_logits_cannot_show_and_why(builder, params, scored):
    """The read-out a mean: the final norm divides the scale out again.
    Rows before columns: both orders converge to the one doubly
    stochastic matrix, and after 20 rounds they lie as close to each
    other as to it. With 2 rounds the order shows, and the program's is
    the paper's: columns, then rows."""
    col, out = scored
    weights = builder.reference_weights(params, CFG)
    tokens = np.asarray(out.col("completion"))[3]
    got = np.asarray(out.col("logits")[3])
    for name, function in (("read_out", lambda x: x.mean(axis=1)),
                           ("sinkhorn", _rows_first)):
        reference = load_module("reference", "xing4_0")
        setattr(reference, name, function)
        assert _error(reference, weights, col[3], tokens, got)[0] < TOL, name
    two = dict(CFG, hc_sinkhorn_iters=2)
    out = _stage(params, config=two, logitsCol="logits").transform(
        DataFrame({"prompt": col[[3]]}))
    tokens = np.asarray(out.col("completion"))[0]
    got = np.asarray(out.col("logits")[0])
    reference = load_module("reference", "xing4_0")
    assert _error(reference, weights, col[3], tokens, got,
                  hc_sinkhorn_iters=2)[0] < TOL
    reference = load_module("reference", "xing4_0")
    reference.sinkhorn = _rows_first
    assert _error(reference, weights, col[3], tokens, got,
                  hc_sinkhorn_iters=2)[0] > FAULT


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


@pytest.fixture(scope="module")
def group_loops(params):
    return group_loop.compile_loops(CFG, params)


@pytest.mark.parametrize("ended", sorted(group_loop.ENDED))
def test_the_bounded_group_loop_equals_the_loop_over_all_groups(
        group_loops, ended):
    """The four-stream model through ``hidden_in_groups``, which still
    hands back one ``(rows, hidden)`` array: a prefill step with no,
    some and all groups ended against the loop over all groups, state,
    counters and hidden rows to the bit."""
    group_loop.check(group_loops, CFG, "ascending", ended)


def test_one_group_is_the_plain_call_with_no_loop(params):
    group_loop.check_one_group(CFG, params)


def test_spans_counts_and_scopes(params):
    from mmlspark_tpu.dl.backbones import lm_init_state, lm_module

    col = _prompts()
    before = len(SINK.events)
    _stage(params).transform(DataFrame({"prompt": col}))
    counts = [r for r in SINK.events[before:]
              if r.get("className") == "CausalLM"][-1]["counts"]
    assert set(counts) == ROOT_COUNTS["tiny_xing"]
    # every token a row absorbed, prompt and NEW - 1 generated, round
    # both sub-layers of the three layers
    through = sum(LENGTHS) + len(LENGTHS) * (NEW - 1)
    assert counts["hc_sublayer_tokens"] == 2 * 3 * through
    assert counts["cache_positions"] == 3 * through
    assert counts["dropped_pairs"] == 0
    # the path's scopes stand outside the sub-layers' own
    text = jax.jit(lm_module(CFG).apply).lower(
        params, jnp.zeros((2, 1), jnp.int32), jnp.ones((2,), jnp.int32),
        lm_init_state(CFG, 2, 16)).as_text(debug_info=True)
    for scope in ("lm.hc/mixer_hc/lm.hc.mix", "lm.hc/mixer_hc/lm.hc.read",
                  "lm.hc/lm.hc.write", "lm.hc/ffn_hc/lm.hc.mix"):
        assert f"layers_1/{scope}" in text, scope
    assert "lm.mla/lm.hc" not in text and "lm.moe/lm.hc" not in text
    assert "lm.hc/mixer_hc/lm.mla" not in text


def test_a_block_of_one_stream_is_the_plain_block(params):
    """``hc_mult`` 1 with ``alpha`` 0, ``b_pre`` 30 and ``b_post`` 0
    against the same weights without the key: ``h + F(norm(h))`` to
    1e-6 of ``h`` a sub-layer (``H_res`` settles at ``1 - hc_eps``)."""
    from mmlspark_tpu.dl.backbones import HybridBlock, lm_init_state

    plain = {k: v for k, v in CFG.items() if k != "hc_mult"}
    one = dict(CFG, hc_mult=1)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((2, 8, 64)).astype(np.float32))
    positions = jnp.tile(jnp.arange(8)[None], (2, 1))
    lengths = jnp.array([8, 5], jnp.int32)
    state = lm_init_state(plain, 2, 8)["layers"][1]
    weights = jax.tree_util.tree_map(
        jnp.asarray, {k: v for k, v in params["params"]["layers_1"].items()
                      if not k.endswith("_hc")})
    want, _, _ = HybridBlock(plain, 1).apply(
        {"params": weights}, h, positions, lengths, state)
    hc = {"phi": jnp.zeros((64, 3)), "alpha": jnp.zeros(3),
          "b_pre": jnp.full((1,), 30.0), "b_post": jnp.zeros(1),
          "b_res": jnp.zeros((1, 1))}
    (got,), _, _ = HybridBlock(one, 1).apply(
        {"params": dict(weights, mixer_hc=hc, ffn_hc=hc)}, (h,), positions,
        lengths, state)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < (
        2.5e-6 * np.abs(np.asarray(want)).max())


ACCEPTED = ("tiny_kimi", "tiny_gigachat")


@pytest.mark.parametrize("name", ACCEPTED)
def test_a_config_without_streams_builds_the_programs_it_built(
        name, monkeypatch):
    """Parameter tree and the jaxpr text of ``lm_prefill`` (8 rows x 16
    tokens in steps of 8, each in 4 groups) and ``lm_generate`` equal
    under ``HybridLM`` and under the block as it was (``PlainLM``)."""
    from mmlspark_tpu.dl import causal_lm
    from mmlspark_tpu.dl.backbones import HybridLM, lm_init_state, lm_module

    file = load_json("rehearsal", "configs", name + ".json")
    config = {k: file[k] for k in file["model_keys"]}
    assert "hc_mult" not in config
    monkeypatch.setattr(HybridLM, "GROUP_TOKENS", 16)
    ids = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    lengths = jax.ShapeDtypeStruct((8,), jnp.int32)
    texts = []
    for module in (lm_module(config), PlainLM(dict(config))):
        shapes = jax.eval_shape(lambda m=module: m.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32),
            jnp.full((1,), 2, jnp.int32), lm_init_state(config, 1, 2)))
        prefill = causal_lm.lm_prefill_program(module, 8, NEW)
        last, state = jax.eval_shape(prefill, shapes, ids, lengths)
        texts.append((
            str(jax.tree_util.tree_map(lambda x: (x.shape, x.dtype),
                                       shapes)),
            str(jax.make_jaxpr(prefill)(shapes, ids, lengths)),
            str(jax.make_jaxpr(causal_lm.lm_generate_program(
                module, NEW, False))(shapes, last, state))))
    assert type(lm_module(config)) is HybridLM
    assert texts[0][0] == texts[1][0]
    assert texts[0][1] == texts[1][1] and "while" in texts[0][1]
    assert texts[0][2] == texts[1][2]
