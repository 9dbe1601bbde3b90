"""What ``test_hybrid_lm.py`` and ``test_latent_lm.py`` share to hold
``HybridLM.hidden_in_groups``, whose loop runs from the first group of
rows with a real token to the last, to the loop over all groups (the
form it had before, kept here as the oracle): two steps of 8 rows x 8
tokens in 4 groups of 2 rows (``GROUP_TOKENS`` 16), the second one's
lengths arranged by ``ORDERS`` with 0, some or all groups ended; and the
two checks through the whole stage that both files make alike."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.logging_utils import SINK
from mmlspark_tpu.dl.backbones import (HybridLM, lm_hidden, lm_init_state,
                                       lm_module)

ROWS, T, GROUPS = 8, 8, 4
# a step's real tokens a row, ascending as ``length_batches`` hands them
ENDED = {"none": [2, 3, 4, 5, 6, 7, 8, 8], "some": [0, 0, 0, 0, 0, 3, 6, 8],
         "all": [0] * 8}
ORDERS = ("ascending", "descending", "shuffled", "zero rows at the end",
          "zero rows in the middle")


def arrange(order, ended):
    """``(first step's lengths, second step's)``: every row absorbs
    ``T`` tokens in the first step but the zero-length rows (what
    ``scorer.pad`` fills a batch with), which absorb none in either; in
    their cases they take the place of the two shortest rows."""
    real, started = np.sort(np.array(ENDED[ended], np.int32)), np.full(
        ROWS, T, np.int32)
    if order == "descending":
        real = real[::-1].copy()
    elif order == "shuffled":
        real = np.random.default_rng(3).permutation(real)
    elif order == "zero rows at the end":
        real = np.concatenate([real[2:], [0, 0]]).astype(np.int32)
        started[-2:] = 0
    elif order == "zero rows in the middle":
        real = np.concatenate([real[2:4], [0, 0], real[4:]]).astype(np.int32)
        started[2:4] = 0
    return started, real


def over_all_groups(module, params, ids, lengths, state):
    """``hidden_in_groups`` as it was: every group, whatever its rows
    have left."""
    rows, per = ids.shape[0], ids.shape[0] // GROUPS

    def body(g, carry):
        state, out = carry

        def cut(x):
            return jax.lax.dynamic_slice_in_dim(x, g * per, per, axis=0)

        def paste(whole, part):
            return jax.lax.dynamic_update_slice_in_dim(
                whole, part, g * per, axis=0)

        mine = {k: v if k == "experts" else jax.tree_util.tree_map(cut, v)
                for k, v in state.items()}
        h, mine = module.apply(params, cut(ids), cut(lengths), mine,
                               method="hidden")
        state = {k: mine[k] if k == "experts" else
                 jax.tree_util.tree_map(paste, v, mine[k])
                 for k, v in state.items()}
        return state, paste(out, h)

    hidden = module.config["hidden_size"]
    return jax.lax.fori_loop(
        0, GROUPS, body,
        (state, jnp.zeros((rows, hidden), jnp.float32)))[::-1]


def compile_loops(config, params):
    """``(over all groups, bounded)``, each compiled once for a step of
    ``ROWS x T`` with ``GROUP_TOKENS`` 16 while it is traced."""
    module = lm_module(config)
    shapes = (jnp.zeros((ROWS, T), jnp.int32), jnp.zeros((ROWS,), jnp.int32),
              lm_init_state(config, ROWS, 3 * T))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(HybridLM, "GROUP_TOKENS", 16)
        assert HybridLM.row_groups(ROWS, T) == GROUPS
        return tuple(
            jax.jit(lambda *a, f=f: f(module, params, *a)).lower(
                *shapes).compile()
            for f in (over_all_groups, HybridLM.hidden_in_groups))


def check(loops, config, order, ended):
    """The second step by both loops from the state the first left:
    every leaf of the state, the experts' counters among them, and the
    hidden rows equal to the bit. A row with no token in the step has
    no hidden row to compare: the loop over all groups returns what it
    made of the padding, the bounded one may leave zeros, and
    ``lm_prefill`` keeps the row's last either way."""
    whole, bounded = loops
    started, real = arrange(order, ended)
    ids = np.random.default_rng(5).integers(
        0, config["vocab_size"], (2, ROWS, T)).astype(np.int32)
    _, state = whole(ids[0], started, lm_init_state(config, ROWS, 3 * T))
    want_h, want = whole(ids[1], real, state)
    got_h, got = bounded(ids[1], real, state)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(got["pos"]), started + real)
    np.testing.assert_array_equal(np.asarray(got_h)[real > 0],
                                  np.asarray(want_h)[real > 0])
    first, stop = HybridLM.active_groups(real, GROUPS)
    outside = np.ones(ROWS, bool)
    outside[first * 2:stop * 2] = False
    assert not np.asarray(got_h)[outside].any()
    assert np.asarray(got_h)[real > 0].any() == bool(real.any())


RAGGED = [3, 5, 9, 12, 17, 20, 30, 40]


def check_stage(stage, prompts, monkeypatch):
    """Eight ragged prompts as one device batch (``stage(batchSize=8)``
    makes the stage with 8 tokens a prefill step, ``prompts(lengths)``
    the column): in 4 groups of 2 rows a step, of which the prefill
    runs those that have a token left, against the stage whose step is
    one group. The root counts a visit a group and step, and those
    inside the loop's bounds: the groups' longest rows are 5, 12, 20
    and 40 tokens, so the steps from 0, 8, 16, 24 and 32 run 4, 3, 2, 1
    and 1 groups and the other 11 steps of the 128 rung none."""
    col = prompts(RAGGED)
    before = len(SINK.events)
    base = stage(batchSize=8).transform(DataFrame({"prompt": col}))
    monkeypatch.setattr(HybridLM, "GROUP_TOKENS", 16)
    out = stage(batchSize=8).transform(DataFrame({"prompt": col}))
    assert np.array_equal(np.asarray(out.col("completion")),
                          np.asarray(base.col("completion")))
    assert np.abs(np.asarray(out.col("logprobs"))
                  - np.asarray(base.col("logprobs"))).max() < 2e-5
    one, grouped = [r["counts"] for r in SINK.events[before:]
                    if r.get("className") == "CausalLM"]
    assert (one["prefill_visits"], one["prefill_visits_run"]) == (16, 16)
    assert (grouped["prefill_visits"],
            grouped["prefill_visits_run"]) == (16 * 4, 4 + 3 + 2 + 1 + 1)
    # five rows, four a device batch: lengths 5, 9, 12, 17 in 2 groups
    # (2, 2 and 1 run of 16 x 2), and the 30-token row alone, one group
    stage(batchSize=4).transform(
        DataFrame({"prompt": prompts([5, 17, 9, 30, 12])}))
    counts = SINK.events[-1]["counts"]
    assert (counts["prefill_visits"],
            counts["prefill_visits_run"]) == (16 * 2 + 16, 5 + 16)


def check_one_group(config, params):
    """A decode step, and any step of ``GROUP_TOKENS`` tokens or fewer:
    ``lm_hidden`` is ``module.apply`` and traces no loop over groups."""
    module = lm_module(config)
    args = (jnp.zeros((4, 1), jnp.int32), jnp.ones((4,), jnp.int32),
            lm_init_state(config, 4, 16))
    assert str(jax.make_jaxpr(
        lambda *a: lm_hidden(module, params, *a))(*args)) == str(
            jax.make_jaxpr(lambda *a: module.apply(
                params, *a, method="hidden"))(*args))
