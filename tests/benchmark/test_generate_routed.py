"""The routed runner's comparison: maxima over the positions whose
routing margin the reference reports as wide enough, the root mean
square over all, the share kept counted."""

import types

import numpy as np

from benchmark.lookup import load_module


def _reference(want, margin):
    def logits(weights, ids, cfg, precision, positions=None, margins=False):
        assert margins and len(positions) == len(want)
        return want, margin
    return types.SimpleNamespace(logits=logits)


def test_a_flipped_position_counts_only_where_its_margin_is_wide():
    routed = load_module("runners", "generate_routed")
    rng = np.random.default_rng(0)
    want = rng.standard_normal((6, 40)).astype(np.float32)
    tokens = want.argmax(axis=1)
    shifted = want - want.max(1, keepdims=True)
    logprob = (shifted - np.log(np.exp(shifted).sum(1, keepdims=True)))[
        np.arange(6), tokens]
    got = want.copy()
    got[2] += 0.5 * np.abs(want).max()          # position 2 "flipped"
    margin = np.array([0.1, 0.1, 1e-4, 0.1, 0.1, 0.1], np.float32)
    subject = {"weights": None, "model_config": {}}
    prompt = np.arange(5, dtype=np.int32)
    kept = {}
    errors = routed.margin_errors(1e-3, kept)
    out, scale = errors(_reference(want, margin), subject, prompt, tokens,
                        logprob, got, 16, "bfloat16")
    assert scale == np.abs(want).max()
    assert out["logits"] == 0.0 and out["logprob"] < 1e-6
    assert out["logits_rms"] > 0.1              # the rms still sees it
    assert kept == {"bfloat16": [5, 6]}
    # with its margin wide, the same position fails the maxima
    wide = routed.margin_errors(1e-5, kept)
    out, _ = wide(_reference(want, margin), subject, prompt, tokens,
                  logprob, got, 16, "highest")
    assert out["logits"] >= 0.5 and kept["highest"] == [6, 6]
