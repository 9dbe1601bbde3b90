"""``opcount_hybrid_lm`` against numbers worked by hand and against the
program's own parameter shapes, and the readers the hybrid decoder's
cell adds on a synthetic trace and canned records."""

import types

import pytest

from benchmark import opcount
from benchmark import opcount_hybrid_lm as O
from benchmark import trace_reduce as tr
from benchmark.lookup import load_json, load_module

FILE = load_json("configs", "gigachat3_5_432b.json")
CFG = {k: FILE[k] for k in FILE["model_keys"]}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_sizes_at_the_published_widths_by_hand():
    assert O.kinds(CFG) == (4, 1, 1, 4) and O.held(CFG) == 16
    # q, k 7168 x 4096; v, z 7168 x 8192; b, a 7168 x 64; o 8192 x 7168;
    # 4 taps over 16,384 channels
    assert O.delta_params(CFG) == (7168 * (2 * 4096 + 2 * 8192 + 128)
                                   + 8192 * 7168 + 4 * 16384) == 235_864_064
    # 7168 x 1536, 1536 x 64 x 192, 7168 x 576, 512 x 64 x 256, the
    # output 8192 x 7168 and the gate 7168 x 8192
    assert O.latent_params(CFG) == (
        11_010_048 + 18_874_368 + 4_128_768 + 8_388_608 + 2 * 58_720_256)
    assert O.dense_params(CFG) == 3 * 7168 * 18432 == 396_361_728
    assert O.expert_params(CFG) == 3 * 7168 * 2048 == 44_040_192
    assert O.router_params(CFG) == 7168 * 256
    assert O.head_params(CFG) == 7168 * 16032
    # 64 heads x 128 x 128 and a tail of 3 x 16,384, float32
    assert O.delta_state_bytes(CFG) == 4 * (64 * 128 * 128 + 3 * 16384)
    assert O.cache_entry_bytes(CFG) == 1152
    # the issue's table: 4,731.7M parameters, 9.46 GB
    assert 4.731e9 < O.sizes(CFG)["parameters"] < 4.732e9


def test_the_counts_agree_with_the_programs_own_parameters():
    """At a small size: every matrix of the model is counted once."""
    import jax

    from mmlspark_tpu.dl.backbones import lm_param_shapes

    small = load_json("rehearsal", "configs", "tiny_gigachat.json")
    small = {k: small[k] for k in small["model_keys"]}
    leaves = jax.tree_util.tree_leaves(lm_param_shapes(small))
    matrices = sum(x.size for x in leaves if len(x.shape) > 1)
    assert O.sizes(small)["parameters"] == matrices


def test_a_decode_step_is_bound_by_bytes_experts_and_state():
    # 64 pairs a layer over 16 experts touch 15.7 of them
    assert 15.7 < O.experts_touched(16, 64) < 15.8
    flops, nbytes = O.decode_step(CFG, 128, 0.0, 0.5)
    state = 2 * 128 * 4 * O.delta_state_bytes(CFG)
    experts = 4 * O.expert_params(CFG) * O.experts_touched(16, 64) * 2
    assert 0.32 < state / nbytes < 0.34 and 0.39 < experts / nbytes < 0.42
    seconds, bound = opcount.least_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and 0.0165 < seconds < 0.017     # 16.8 ms
    # a cache of 400 positions a row adds 128 x 400 x 1152 bytes
    assert O.decode_step(CFG, 128, 400.0, 0.5)[1] - nbytes == 128 * 400 * 1152


def test_model_flops_count_real_tokens_and_counted_pairs_once():
    one = O.token_flops(CFG)
    assert one == (2.0 * (4 * 235_864_064 + 159_842_304 + 396_361_728
                          + 4 * (7168 * 256 + 44_040_192))
                   + 4 * 7.0 * 64 * 128 * 128)
    # 2 rows of 5 prompt tokens and 3 new: 14 tokens through the layers,
    # 7 pairs through an expert, each row 7 positions (28 attended
    # pairs), the head for the 6 new tokens
    assert O.attended_pairs(5, 3) == 28
    assert O.model_flops(CFG, 10, 6, 2, 7) == (
        14 * one + 7 * 2.0 * 44_040_192 + 2 * 28 * 64 * 2.0 * 320
        + 6 * 2.0 * 7168 * 16032)
    # the prefill of one position: the issue's 3.54 GFLOP with its
    # 2 pairs in 4 expert layers
    flops, _ = O.prefill(CFG, 1, 1, 0.5)
    assert 3.5e9 < flops - 2.0 * O.head_params(CFG) + 0 < 3.6e9


def test_the_kernels_floor_by_hand():
    flops, nbytes = O.gdn_decode(CFG, 128)
    assert flops == 128 * 7.0 * 64 * 128 * 128
    assert nbytes == 128 * 64 * 4 * (2 * 128 * 128 + 4 * 128 + 2)
    assert opcount.least_seconds(flops, nbytes, PEAK)[1] == "memory"


# -- the readers ---------------------------------------------------------

def _record(start, pairs, busiest):
    return {"className": "CausalLM", "method": "transform", "uid": "u",
            "start_s": start, "end_s": start + 1.0,
            "counts": {"new_tokens": 6, "length_rung": 128,
                       "state_bytes": 1, "cache_bytes": 2,
                       "expert_pairs": pairs, "expert_pairs_max": busiest,
                       "dropped_pairs": 0},
            "spans": [{"name": "lm.stack", "start_s": start,
                       "end_s": start + 0.25, "parent": "CausalLM.transform",
                       "counts": {"rows": 2, "prompt_tokens": 100,
                                  "padded_tokens": 256}}]}


@pytest.fixture
def ctx(monkeypatch):
    from mmlspark_tpu.core.logging_utils import SINK

    calls = [types.SimpleNamespace(
        name="transform_call", start=s - 0.1, end=s + 1.1, in_window=True,
        work={"rows": 2, "new_tokens": 6, "prompt_tokens": 100}, phases={})
        for s in (100.0, 200.0)]
    monkeypatch.setattr(SINK, "events", [_record(100.0, 640, 20),
                                         _record(200.0, 640, 30)])
    ops = []
    for base in (10.0, 20.0):
        ops += [("%fusion.1 = f32[2] fusion()", base, base + 0.10),
                ("%gdn_decode.7 = custom-call()", base + 0.5, base + 0.52),
                ("%gdn_decode.7 = custom-call()", base + 0.6, base + 0.62)]
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=ops, modules=[])
    return types.SimpleNamespace(
        trace=tr.Trace(devices=[dev], annotations=[]), traced_calls=calls,
        counters={"lm_shape": dict(CFG, prefill_chunk=128)}, config=FILE,
        device_kind="TPU v5 lite", window_calls=lambda: calls,
        emit=lambda **facts: None)


def _read(ctx, metric):
    spec = load_json("layers", metric + ".json")
    return load_module("readers", spec["reader"]).read(
        ctx, spec.get("params", {}))


def test_load_imbalance_is_the_busiest_over_the_mean(ctx):
    # 50 pairs on the busiest of 64 (layer, expert) slots over 1280 pairs
    assert _read(ctx, "moe_load_imbalance") == pytest.approx(
        50 * 64 / 1280)


def test_shares_of_the_peak_over_busy_time(ctx):
    peak = opcount.peaks("TPU v5 lite")
    busy = ctx.trace.device(0).busy_s()
    assert busy == pytest.approx(2 * 0.14)
    flops = 2 * O.model_flops(CFG, 100, 6, 2, 640)
    assert _read(ctx, "gigachat_generate_mfu") == pytest.approx(
        100.0 * flops / (peak["bf16_flops_per_s"] * busy))
    per_token = 640 / ((100 + 6 - 2) * 4)
    floor = 2 * (opcount.least_seconds(
        *O.prefill(CFG, 100, 2, per_token), peak)[0]
        + 2 * opcount.least_seconds(
            *O.decode_step(CFG, 2, 51.5, per_token), peak)[0])
    assert _read(ctx, "gigachat_step_roofline") == pytest.approx(
        100.0 * floor / busy)
    # 4 delta-rule layers x 2 decode steps x 2 calls; 4 events of 0.02 s
    launch = opcount.least_seconds(*O.gdn_decode(CFG, 2), peak)[0]
    assert _read(ctx, "gdn_decode_roofline") == pytest.approx(
        100.0 * 4 * 2 * 2 * launch / 0.08)


def test_a_program_without_the_expert_layer_gives_the_readers_nothing(
        ctx, monkeypatch):
    """A parent commit: no record with ``expert_pairs``, no kernel."""
    from mmlspark_tpu.core.logging_utils import SINK

    monkeypatch.setattr(SINK, "events", [])
    for name in ("gigachat_generate_mfu", "gigachat_step_roofline",
                 "gdn_decode_roofline", "moe_load_imbalance"):
        assert _read(ctx, name) is None, name
