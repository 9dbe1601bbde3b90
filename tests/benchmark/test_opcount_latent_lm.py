"""``opcount_latent_lm`` against numbers worked by hand (ISSUE 35's
arithmetic) and against the program's own parameter shapes, and the
readers the cell ``kimi_k2_6.reason`` adds on a synthetic trace and
canned records."""

import types

import pytest

from benchmark import opcount
from benchmark import opcount_latent_lm as O
from benchmark import trace_reduce as tr
from benchmark.lookup import load_json, load_module

FILE = load_json("configs", "kimi_k2_6.json")
CFG = {k: FILE[k] for k in FILE["model_keys"]}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_sizes_at_the_published_widths_by_hand():
    assert O.kinds(CFG) == (1, 4) and O.held(CFG) == 12
    # 7168 x 1536, 1536 x 64 x 192, 7168 x 576, 512 x 64 x 256 and the
    # output 8192 x 7168; no gate
    assert O.latent_params(CFG) == (
        11_010_048 + 18_874_368 + 4_128_768 + 8_388_608 + 58_720_256
    ) == 101_122_048
    assert O.dense_params(CFG) == 3 * 7168 * 18432 == 396_361_728
    assert O.expert_params(CFG) == 3 * 7168 * 2048 == 44_040_192
    assert O.router_params(CFG) == 7168 * 384 == 2_752_512
    assert O.head_params(CFG) == 7168 * 20480
    # the mixer, the router, the shared expert and 12 held: 676.4M
    assert O.expert_layer_params(CFG) == (
        101_122_048 + 2_752_512 + 13 * 44_040_192) == 676_397_056
    assert O.cache_entry_bytes(CFG) == 1152
    assert O.cache_position_bytes(CFG) == 5760
    sizes = O.sizes(CFG)
    # the dense layer 497.5M, four expert layers, embedding and head
    # 293.6M: 3,496.7M parameters, 6.99 GB
    assert sizes["parameters"] == (
        101_122_048 + 396_361_728 + 4 * 676_397_056 + 2 * 146_800_640
    ) == 3_496_673_280
    # 512 rows of 1,280 positions: 3.77 GB
    assert 512 * 1280 * sizes["cache_position_bytes"] == 3_774_873_600


def test_the_counts_agree_with_the_programs_own_parameters():
    """At a small size: every matrix of the model is counted once."""
    import jax

    from mmlspark_tpu.dl.backbones import lm_param_shapes

    small = load_json("rehearsal", "configs", "tiny_kimi.json")
    small = {k: small[k] for k in small["model_keys"]}
    leaves = jax.tree_util.tree_leaves(lm_param_shapes(small))
    matrices = sum(x.size for x in leaves if len(x.shape) > 1)
    assert O.sizes(small)["parameters"] == matrices


def test_a_decode_step_at_512_rows_is_bound_by_bytes():
    # 128 pairs a layer (512 rows x 8 x 12 / 384) touch all 12 held
    assert 11.99 < O.experts_touched(12, 128) <= 12
    # resident: five mixers, the dense SwiGLU, four routers and shared
    # experts, the head: 2.47 GB; the held experts 4.23 GB
    assert O.resident_params(CFG) == (
        5 * 101_122_048 + 396_361_728 + 4 * (2_752_512 + 44_040_192)
        + 146_800_640) == 1_235_943_424
    flops, empty = O.decode_step(CFG, 512, 0.0, 0.25)
    experts = 4 * 44_040_192 * O.experts_touched(12, 128) * 2
    assert empty == pytest.approx(2 * 1_235_943_424 + experts)
    assert 6.69e9 < empty < 6.70e9
    # a cache of 600 positions a row adds 512 x 600 x 5,760 bytes: 1.77 GB
    _, nbytes = O.decode_step(CFG, 512, 600.0, 0.25)
    assert nbytes - empty == 512 * 600 * 5760
    # from the middle of a call (215 + 384 positions) to its end
    # (215 + 767): 8.5-9.6 GB a step, of which the cache 1.8-2.9 GB
    assert 8.4e9 < O.decode_step(CFG, 512, 599.0, 0.25)[1] < 8.5e9
    assert 9.5e9 < O.decode_step(CFG, 512, 982.0, 0.25)[1] < 9.7e9
    seconds, bound = opcount.least_seconds(
        *O.decode_step(CFG, 512, 600.0, 0.25), PEAK)
    assert bound == "memory" and 0.0102 < seconds < 0.0105


def test_model_flops_count_real_tokens_and_counted_pairs_once():
    one = O.token_flops(CFG)
    assert one == 2.0 * (5 * 101_122_048 + 396_361_728
                         + 4 * (2_752_512 + 44_040_192))
    assert O.prefill_pair_flops(CFG) == 64 * 2.0 * 320
    assert O.decode_position_flops(CFG) == 64 * 2.0 * (576 + 512) == 139_264
    # 2 rows of 5 prompt tokens and 3 new: 14 tokens through the layers,
    # 7 pairs through an expert; each row's prompt 15 expanded pairs,
    # its 2 decode steps 6 + 7 cached positions, in 5 layers; the head
    # for the 6 new tokens
    assert O.attended_pairs(5, 1) == 15
    assert O.decode_positions(10, 2, 2) == 2 * (6 + 7)
    assert O.model_flops(CFG, 10, 6, 2, 7) == (
        14 * one + 7 * 2.0 * 44_040_192 + 5 * 2 * 15 * 64 * 2.0 * 320
        + 5 * 26 * 139_264 + 6 * 2.0 * 7168 * 20480)
    # a prompt token: about 2.27 GFLOP with a quarter of a pair in each
    # of the 4 expert layers, so 262,144 padded positions are 0.59 PFLOP
    flops, _ = O.prefill(CFG, 1, 1, 0.25)
    per_token = flops - 2.0 * O.head_params(CFG) - 5 * 64 * 2.0 * 320
    assert 2.26e9 < per_token < 2.28e9
    assert 0.59e15 < 262_144 * per_token < 0.60e15


def test_the_kernels_floor_by_hand():
    # a position: 1,152 bytes against 139 kFLOP, 121 FLOP a byte, half
    # the chip's ridge of 240
    flops, nbytes = O.latent_decode(CFG, 1000.0)
    assert (flops, nbytes) == (1000 * 139_264, 1000 * 1152)
    assert 120 < flops / nbytes < 122
    assert opcount.least_seconds(flops, nbytes, PEAK)[1] == "memory"


# -- the readers ---------------------------------------------------------

ROWS, PROMPT, NEW = 2, 100, 6           # a call: 2 rows, 5 decode steps
FILLED = 5 * (PROMPT + ROWS * (NEW - 1))
CAPACITY = 5 * ROWS * (128 + NEW)


def _record(start, pairs, busiest):
    return {"className": "CausalLM", "method": "transform", "uid": "u",
            "start_s": start, "end_s": start + 1.0,
            "counts": {"new_tokens": ROWS * NEW, "length_rung": 128,
                       "state_bytes": 1, "cache_bytes": 2,
                       "expert_pairs": pairs, "expert_pairs_max": busiest,
                       "dropped_pairs": 0, "cache_positions": FILLED,
                       "cache_capacity": CAPACITY},
            "spans": [{"name": "lm.stack", "start_s": start,
                       "end_s": start + 0.25, "parent": "CausalLM.transform",
                       "counts": {"rows": ROWS, "prompt_tokens": PROMPT,
                                  "padded_tokens": 256}}]}


@pytest.fixture
def ctx(monkeypatch):
    from mmlspark_tpu.core.logging_utils import SINK

    calls = [types.SimpleNamespace(
        name="transform_call", start=s - 0.1, end=s + 1.1, in_window=True,
        work={"rows": ROWS, "new_tokens": ROWS * NEW,
              "prompt_tokens": PROMPT}, phases={})
        for s in (100.0, 200.0)]
    monkeypatch.setattr(SINK, "events", [_record(100.0, 480, 20),
                                         _record(200.0, 480, 30)])
    ops, modules = [], []
    for base in (10.0, 20.0):
        ops += [("%fusion.1 = f32[2] fusion()", base, base + 0.10),
                ("%latent_decode.7 = custom-call()", base + 0.5, base + 0.52),
                ("%latent_decode.9 = custom-call()", base + 0.6, base + 0.62)]
        modules += [("jit_lm_prefill(1)", base, base + 0.3),
                    ("jit_lm_generate(2)", base + 0.4, base + 0.8)]
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=ops, modules=modules)
    return types.SimpleNamespace(
        trace=tr.Trace(devices=[dev], annotations=[]), traced_calls=calls,
        counters={"lm_shape": dict(CFG, prefill_chunk=128)}, config=FILE,
        device_kind="TPU v5 lite", window_calls=lambda: calls,
        emit=lambda **facts: None)


def _read(ctx, metric):
    spec = load_json("layers", metric + ".json")
    return load_module("readers", spec["reader"]).read(
        ctx, spec.get("params", {}))


def test_counters_fill_share_and_load_imbalance(ctx):
    assert _read(ctx, "latent_cache_fill_share") == pytest.approx(
        100.0 * FILLED / CAPACITY)
    # 50 pairs on the busiest of 48 (layer, expert) slots over 960 pairs
    assert _read(ctx, "kimi_moe_load_imbalance") == pytest.approx(
        50 * 48 / 960)


def test_shares_of_the_peak_over_busy_time(ctx):
    peak = opcount.peaks("TPU v5 lite")
    busy = ctx.trace.device(0).busy_s()
    assert busy == pytest.approx(2 * 0.14)
    flops = 2 * O.model_flops(CFG, PROMPT, ROWS * NEW, ROWS, 480)
    assert _read(ctx, "kimi_generate_mfu") == pytest.approx(
        100.0 * flops / (peak["bf16_flops_per_s"] * busy))
    per_token = 480 / ((PROMPT + ROWS * NEW - ROWS) * 4)
    # a step's context: the rows' mean prompt and the mean of 1..5
    floor = 2 * (opcount.least_seconds(
        *O.prefill(CFG, PROMPT, ROWS, per_token), peak)[0]
        + 5 * opcount.least_seconds(
            *O.decode_step(CFG, ROWS, 50.0 + 3.0, per_token), peak)[0])
    assert _read(ctx, "kimi_step_roofline") == pytest.approx(
        100.0 * floor / busy)
    # the kernel: 5 layers x (5 steps x 100 prompt tokens + 2 rows x 15)
    # positions a call, from cache_positions and the steps; 4 events of
    # 0.02 s; the decode scan ran 2 x 0.4 s
    positions = 2 * 5 * (5 * PROMPT + ROWS * 15)
    launch = opcount.least_seconds(*O.latent_decode(CFG, positions), peak)[0]
    assert _read(ctx, "latent_decode_roofline") == pytest.approx(
        100.0 * launch / 0.08)
    assert _read(ctx, "latent_decode_share") == pytest.approx(
        100.0 * 0.08 / 0.8)


def test_a_program_without_the_counts_gives_the_readers_nothing(
        ctx, monkeypatch):
    """A parent commit: no record with ``cache_positions``, no kernel."""
    from mmlspark_tpu.core.logging_utils import SINK

    parent = _record(100.0, 480, 20)
    del parent["counts"]["cache_positions"], parent["counts"]["cache_capacity"]
    for events in ([], [parent]):
        monkeypatch.setattr(SINK, "events", events)
        for name in ("kimi_generate_mfu", "kimi_step_roofline",
                     "latent_decode_roofline", "latent_decode_share",
                     "latent_cache_fill_share", "kimi_moe_load_imbalance"):
            assert _read(ctx, name) is None, name
