"""``benchmark/opcount_gdla_lm.py`` against a hand count at the cell's
shapes (``configs/motif_3_beta.json``), against the model's own leaves
at a small size, and the window layers' floor against the prompt's
length."""

import jax
import numpy as np
import pytest

from benchmark import opcount_gdla_lm as o
from benchmark.lookup import load_json

CELL = load_json("configs", "motif_3_beta.json")
TINY = load_json("rehearsal", "configs", "tiny_motif.json")


def test_the_cells_sizes_by_hand():
    c = CELL
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"]) == (
        4096, 80, 192)
    # q_a, q_b (80 heads of 192), kv_a (rank 512 + rope 64), kv_b (16 key
    # heads of 128 + 128), lambda (64 signal heads), gate and output
    mixer = (4096 * 1024 + 1024 * 80 * 192 + 4096 * 576 + 512 * 16 * 256
             + 4096 * 64 + 2 * 4096 * 64 * 128)
    assert mixer == o.mixer_params(c) == 91_750_400
    assert o.dense_params(c) == 3 * 4096 * 12288 == 150_994_944
    assert o.expert_params(c) == 3 * 4096 * 1280 == 15_728_640
    assert o.router_params(c) == 4096 * 384
    assert o.head_params(c) == 4096 * 27520
    assert o.counts(c) == (2, 3, 1, 4)      # full, window, dense, expert
    assert [w for w, _ in o.layer_kinds(c)] == [0, 128, 128, 128, 0]
    assert o.pair_flops(c) == 80 * 2 * (192 + 128) == 51_200
    assert o.position_flops(c) == 80 * 2 * (2 * 512 + 64) == 174_080
    assert o.token_flops(c) == 2 * (5 * mixer + 150_994_944 + 4 * (
        1_572_864 + 15_728_640)) == 1_357_905_920
    hc = 4 * 4096 * (8 + 16) + 3 + 8 + 16
    vectors = 5 * (2 * 4096 + 1024 + 512) + 4 * (1 + 4 * 49) + 4096
    whole = (5 * mixer + 150_994_944
             + 4 * (4096 * 384 + 49 * 15_728_640)
             + 2 * 4096 * 27520 + vectors + 2 * 5 * hc)
    assert o.parameters(c) == whole == 3_928_281_634
    assert 2 * whole / 1e9 == pytest.approx(7.857, abs=1e-3)   # GB, bf16


def test_the_parameters_are_the_models_leaves():
    from mmlspark_tpu.dl.backbones import lm_param_shapes

    config = {k: TINY[k] for k in TINY["model_keys"]}
    leaves = jax.tree_util.tree_leaves(lm_param_shapes(config))
    assert o.parameters(TINY) == sum(int(np.prod(x.shape)) for x in leaves)


def test_the_window_floor_does_not_grow_past_the_window():
    c = CELL
    w = c["sliding_window"]
    assert o.window_read(w, w) == w * (w + 1) / 2
    for length in (129, 4096, 16384):
        # one more token reads one window, whatever came before
        assert o.window_read(length + 1, w) - o.window_read(length, w) == w
    # a decode step of one row: the window layers read three windows and
    # their floor is the same at any context past the window
    steps = [o.window_attention(c, 3 * w, 3 * w) for _ in (4096, 16384)]
    assert steps[0] == steps[1] == (3 * w * 51_200, 3 * w * 1_152)
    # the whole step grows only by the full layers' reads
    a, b = (o.decode_step(c, 16, context, 1.0) for context in (4096, 8192))
    assert b[0] - a[0] == pytest.approx(2 * 16 * 4096 * 174_080)
    assert b[1] - a[1] == pytest.approx(2 * 16 * 4096 * 1_152)
    assert o.decode_window_positions(c, 16, 127) == 3 * 16 * 127 * w


def test_a_generation_by_hand():
    c = CELL
    rows, length, new, pairs = 2, 4096, 3, 100
    prompt, sublayer = rows * length, 2 * 5 * rows * (length + new - 1)
    w = 128
    window = 3 * rows * o.window_read(length + new - 1, w)
    steps = new - 1
    decode_window = 3 * rows * steps * w
    full_prefill = 2 * rows * length * (length + 1) / 2
    full_decode = 2 * (steps * prompt + rows * steps * (steps + 1) / 2)
    want = ((prompt + rows * new - rows) * 1_357_905_920
            + pairs * 2 * 15_728_640
            + sublayer * o.opcount_hc_lm.hc_sublayer(o._hc(c))[0]
            + full_prefill * 51_200 + full_decode * 174_080
            + (window - decode_window) * 51_200 + decode_window * 174_080
            + rows * new * 2 * 4096 * 27520)
    got = o.model_flops(c, prompt, rows * new, rows, pairs, sublayer, window)
    assert got == pytest.approx(want, rel=1e-12)
