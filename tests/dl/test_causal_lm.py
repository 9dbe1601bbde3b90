"""``CausalLM`` over ``HybridLM`` read as ``brumby`` (every layer a
``DecoderBlock``, power retention) at a small size on the CPU, seeded
weights, against the plain reference (``benchmark/reference/brumby.py``,
imported through the benchmark's own loader so there is one copy); and
the root's counts of every family's tiny config."""

import numpy as np
import pytest

from benchmark.lookup import load_json, load_module
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.logging_utils import SINK
from tests.dl import group_loop
from tests.dl.root_counts import ROOT_COUNTS, STAGE_COUNTS

CFG = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, intermediate_size=128, vocab_size=512,
           num_hidden_layers=2, rms_norm_eps=1e-6, rope_theta=1e6,
           torch_dtype="float32")
NEW = 6
LENGTHS = [5, 17, 9, 30, 12]
# float32 on both sides: the program sums through the state's
# recurrence, the reference over the quadratic form; logits are about
# 0.6 in size, so this is a few float32 ulps through two layers
TOL = 2e-5


@pytest.fixture(scope="module")
def reference():
    return load_module("reference", "brumby")


@pytest.fixture(scope="module")
def builder():
    return load_module("builders", "brumby_14b")


@pytest.fixture(scope="module")
def params(builder):
    return builder.make_weights(7, CFG)


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


def _reference_logits(reference, builder, params, prompt, tokens, **cfg):
    """Teacher forced: the reference's logits at the positions that
    emitted ``tokens``."""
    ids = np.concatenate([prompt, tokens])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    return np.asarray(reference.logits(
        builder.reference_weights(params, CFG), ids, dict(CFG, **cfg),
        positions=at))


def test_prefill_then_decode_equals_the_references_full_forward(
        reference, builder, params):
    col = _prompts()
    out = _stage(params, logitsCol="logits").transform(
        DataFrame({"prompt": col}))
    tokens = np.asarray(out.col("completion"))
    logprobs = np.asarray(out.col("logprobs"))
    assert tokens.shape == logprobs.shape == (len(col), NEW)
    for i in range(len(col)):
        want = _reference_logits(reference, builder, params, col[i],
                                 tokens[i])
        got = np.asarray(out.col("logits")[i])
        assert np.abs(got - want).max() < TOL          # every position
        assert np.array_equal(want.argmax(-1), tokens[i])
        shifted = want - want.max(-1, keepdims=True)
        want_lp = shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))
        assert np.abs(want_lp[np.arange(NEW), tokens[i]]
                      - logprobs[i]).max() < TOL


@pytest.mark.parametrize("fault", ["gate", "power", "grouping"])
def test_the_comparison_with_the_reference_can_fail(reference, builder,
                                                    params, fault):
    import jax

    col = _prompts()
    out = _stage(params, logitsCol="logits").transform(
        DataFrame({"prompt": col}))
    tokens = np.asarray(out.col("completion"))
    moved, cfg = params, {}
    if fault == "gate":
        moved = jax.tree_util.tree_map_with_path(
            lambda path, x: x + 0.5 if "g_bias" in str(path) else x, params)
    elif fault == "power":
        cfg = {"retention_power": 3}
    else:                       # query heads read the wrong key-value head
        cfg = {"num_key_value_heads": 4}
        moved = jax.tree_util.tree_map_with_path(
            lambda path, x: (np.concatenate([x, x], axis=-1)
                             if any(n in str(path) for n in
                                    ("k_proj", "v_proj", "g_proj", "g_bias"))
                             else x), params)
    want = _reference_logits(reference, builder, moved, col[3], tokens[3],
                             **cfg)
    assert np.abs(np.asarray(out.col("logits")[3]) - want).max() > 50 * TOL


def test_a_row_does_not_change_with_its_rungs_or_its_neighbours(params):
    col = _prompts()
    base = _stage(params).transform(DataFrame({"prompt": col}))
    tokens = np.asarray(base.col("completion"))
    logprobs = np.asarray(base.col("logprobs"))
    variants = {
        "alone": (_stage(params), [2]),                       # row rung 1
        "row rung 8": (_stage(params, batchSize=8), [0, 1, 2, 3, 4]),
        "other neighbours": (_stage(params), [4, 2, 0]),
        "one token a prefill step": (_stage(params, prefillChunk=1), [2, 3]),
    }
    for name, (stage, rows) in variants.items():
        out = stage.transform(DataFrame({"prompt": col[rows]}))
        assert np.array_equal(np.asarray(out.col("completion")),
                              tokens[rows]), name
        assert np.abs(np.asarray(out.col("logprobs"))
                      - logprobs[rows]).max() < TOL, name
    # a longer length rung: row 2 (9 tokens) beside a 200-token prompt
    long_col = _prompts([9, 200], seed=1)
    long_col[0] = col[2]
    out = _stage(params, maxLength=256, batchSize=2).transform(
        DataFrame({"prompt": long_col}))
    assert np.array_equal(np.asarray(out.col("completion"))[0], tokens[2])
    assert np.abs(np.asarray(out.col("logprobs"))[0]
                  - logprobs[2]).max() < TOL


def test_save_load_spans_and_counts(params, tmp_path):
    from mmlspark_tpu.core.pipeline import PipelineStage

    col = _prompts()
    stage = _stage(params)
    before = len(SINK.events)
    first = stage.transform(DataFrame({"prompt": col}))
    record = [r for r in SINK.events[before:]
              if r.get("className") == "CausalLM"][-1]
    assert record["method"] == "transform"
    names = [s["name"] for s in record["spans"]]
    assert names[0] == "lm.stack" and names[-1] == "lm.columns"
    for name in ("scorer.pad", "scorer.put", "scorer.dispatch",
                 "scorer.fetch"):
        assert names.count(name) == 2         # 5 rows, 4 a device batch
    stack = record["spans"][0]["counts"]
    assert stack["rows"] == 5 and stack["prompt_tokens"] == sum(LENGTHS)
    assert stack["padded_tokens"] == 5 * 128  # every batch on the 128 rung
    counts = record["counts"]
    assert set(counts) == ROOT_COUNTS["tiny_brumby"]
    assert counts["new_tokens"] == 5 * NEW and counts["length_rung"] == 128
    # 4 rows x 2 layers x 2 kv heads x 9 rows of phi x 16 x (16 + 1) x 4 B
    assert counts["state_bytes"] == 4 * 2 * 2 * 9 * 16 * 17 * 4 + 4 * 4
    # no group loop: a visit a prefill step, all run (two device batches
    # of 16 steps of 8 tokens on the 128 rung)
    assert counts["prefill_visits"] == counts["prefill_visits_run"] == 32

    stage.save(str(tmp_path / "lm"))
    loaded = PipelineStage.load(str(tmp_path / "lm"))
    again = loaded.transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(again.col("completion")),
                          np.asarray(first.col("completion")))
    assert np.array_equal(np.asarray(again.col("logprobs")),
                          np.asarray(first.col("logprobs")))


def test_the_prefill_has_no_loop_with_traced_bounds(params):
    """A prefill step of 4 rows x 128 tokens is one group of rows
    (``HybridLM.GROUP_TOKENS`` 4,096): the ``lm_prefill`` is the scan
    over the chunks and nothing in it is a ``while`` (what a
    ``fori_loop`` over the groups that have a token left would be)."""
    import jax
    import jax.numpy as jnp

    stage = _stage(params)
    stage._ensure_scorer()
    text = str(jax.make_jaxpr(stage._program("lm_prefill"))(
        params, jnp.zeros((4, 128), jnp.int32), jnp.full((4,), 9, jnp.int32)))
    assert "scan[" in text and "while[" not in text


def test_a_stage_that_ran_takes_a_new_config_with_its_new_weights(
        builder, params):
    """The two programs close over the module, so a new ``modelConfig``
    with ``set_weights`` builds them anew: the same shapes under
    another ``rope_theta`` and norm give what a fresh stage gives, and
    another depth runs at all."""
    frame = DataFrame({"prompt": _prompts()})
    stage = _stage(params)
    first = np.asarray(stage.transform(frame).col("logprobs"))
    for cfg in (dict(CFG, rope_theta=1e2, rms_norm_eps=1e-1),
                dict(CFG, num_hidden_layers=3)):
        weights = builder.make_weights(7, cfg)
        stage.set("modelConfig", cfg).set_weights(weights)
        again = np.asarray(stage.transform(frame).col("logprobs"))
        fresh = np.asarray(_stage(weights).set("modelConfig", cfg).transform(
            frame).col("logprobs"))
        assert np.array_equal(again, fresh)
        assert not np.allclose(again, first)


def test_text_prompts_and_missing_weights(params):
    from mmlspark_tpu.dl.causal_lm import CausalLM

    out = _stage(params).transform(DataFrame({"prompt": np.array(
        ["the quick brown fox", "jumps over", "the lazy dog again and again"],
        dtype=object)}))
    assert np.asarray(out.col("completion")).shape == (3, NEW)
    assert np.isfinite(np.asarray(out.col("logprobs"))).all()
    with pytest.raises(ValueError, match="no weights"):
        CausalLM(inputCol="prompt", modelConfig=CFG).transform(
            DataFrame({"prompt": _prompts()}))
    seeded = CausalLM(inputCol="prompt", outputCol="completion",
                      modelConfig=CFG, maxNewTokens=2, batchSize=2,
                      allowRandomWeights=True)
    assert np.asarray(seeded.transform(DataFrame(
        {"prompt": _prompts([4, 6])})).col("completion")).shape == (2, 2)


def test_a_bfloat16_model_places_its_weights_in_bfloat16(params):
    """The model states its dtype; the engine places the weights in it
    through ``placement_cast``, with no environment variable."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.causal_lm import CausalLM

    stage = CausalLM(inputCol="prompt", outputCol="completion",
                     modelConfig=dict(CFG, torch_dtype="bfloat16"),
                     maxNewTokens=NEW, batchSize=4,
                     prefillChunk=8).set_weights(params)
    out = stage.transform(DataFrame({"prompt": _prompts()}))
    placed = stage._ensure_scorer()._params["params"]
    assert placed["layers_0"]["q_proj"]["kernel"].dtype == jnp.bfloat16
    assert placed["embedding"].dtype == jnp.bfloat16
    assert placed["final_norm"].dtype == jnp.bfloat16
    assert stage._ensure_scorer().metadata()["infer_autocast"] == "off"
    base = _stage(params).transform(DataFrame({"prompt": _prompts()}))
    # bfloat16 products move the log-probabilities, by little
    diff = np.abs(np.asarray(out.col("logprobs"))
                  - np.asarray(base.col("logprobs"))).max()
    assert 0 < diff < 0.5
    del jax


def test_the_bounded_group_loop_cuts_and_pastes_the_retention_state(
        params):
    """The retention layers through ``hidden_in_groups`` (``GROUP_TOKENS``
    16: 4 groups of 2 rows a step, some of them ended) against the loop
    over all groups, state and hidden rows to the bit; and against the
    plain call of all rows at once, to float32's rounding."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.backbones import lm_init_state, lm_module

    loops = group_loop.compile_loops(CFG, params)
    group_loop.check(loops, CFG, "ascending", "some")
    started, real = group_loop.arrange("shuffled", "some")
    ids = jnp.asarray(np.random.default_rng(5).integers(
        0, CFG["vocab_size"], (2, group_loop.ROWS, group_loop.T)), jnp.int32)
    plain = jax.jit(lambda *a: lm_module(CFG).apply(
        params, *a, method="hidden"))
    state = lm_init_state(CFG, group_loop.ROWS, 3 * group_loop.T)
    _, want = plain(ids[0], started, state)
    want_h, want = plain(ids[1], real, want)
    _, got = loops[1](ids[0], started, state)
    got_h, got = loops[1](ids[1], real, got)
    assert "experts" not in got
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        b = np.asarray(b)
        assert np.abs(np.asarray(a) - b).max() <= 1e-5 * np.abs(b).max()
    got_h, want_h = np.asarray(got_h)[real > 0], np.asarray(want_h)[real > 0]
    assert np.abs(got_h - want_h).max() <= 1e-5 * np.abs(want_h).max()


@pytest.mark.parametrize("name", sorted(ROOT_COUNTS))
def test_the_root_counts_each_familys_keys(name):
    """What the root sums of the model's counts (``HybridLM.counts`` of
    an empty state of the family's tiny rehearsal config) is exactly
    the family's root keys less the stage's own: each family's test
    file holds a transform's root to the whole set, and its values."""
    from mmlspark_tpu.dl.backbones import HybridLM, lm_init_state, lm_module

    file = load_json("rehearsal", "configs", name + ".json")
    config = lm_module({k: file[k] for k in file["model_keys"]}).config
    counts = HybridLM.counts(config, lm_init_state(config, 2, 8))
    assert set(counts) == (ROOT_COUNTS[name] - STAGE_COUNTS
                           - {"expert_pairs_max"})


def test_a_configs_own_block_key_is_not_read():
    """A layer's kind is the family table's alone: a ``block`` key in a
    config (no published config.json has one) changes no layer."""
    from mmlspark_tpu.dl.backbones import (DecoderBlock, HybridBlock,
                                           HybridLM, lm_module)

    file = load_json("rehearsal", "configs", "tiny_kimi.json")
    kimi = {k: file[k] for k in file["model_keys"]}
    for config, block, other in ((CFG, DecoderBlock, "hybrid"),
                                 (kimi, HybridBlock, "retention")):
        for given in (config, dict(config, block=other)):
            layers = HybridLM._blocks(lm_module(given).config)
            assert {type(layer) for layer in layers} == {block}
