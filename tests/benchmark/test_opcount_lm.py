"""``opcount_lm`` against numbers worked by hand, and each reader the
language-model cell adds on a synthetic trace and canned records."""

import types

import pytest

from benchmark import opcount, opcount_lm
from benchmark import trace_reduce as tr
from benchmark.lookup import load_json, load_module

BRUMBY = {k: v for k, v in load_json("configs", "brumby_14b.json").items()
          if isinstance(v, (int, float)) and not isinstance(v, bool)}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_sizes_at_the_published_widths_by_hand():
    # q and o 5120 x 5120, k and v 5120 x 1024, gate 5120 x 8,
    # SwiGLU 3 x 5120 x 17408, two norms of 5120 and two of 128
    assert opcount_lm.layer_params(BRUMBY) == (
        2 * 26_214_400 + 2 * 5_242_880 + 40_960 + 267_386_880 + 10_240 + 256)
    assert opcount_lm.layer_params(BRUMBY) == 330_352_896
    assert opcount_lm.head_params(BRUMBY) == 5120 * 151_936 == 777_912_320
    # 8 kv heads x 8256 distinct products x 128 values x 4 bytes
    assert opcount_lm.phi_width(128) == 8256
    assert opcount_lm.state_bytes(BRUMBY) == 8 * 8256 * 128 * 4 == 33_816_576
    assert opcount_lm.norm_state_bytes(BRUMBY) == 8 * 8256 * 4


def test_a_decode_step_is_bound_by_bytes_and_mostly_state():
    flops, nbytes = opcount_lm.decode_step(BRUMBY, 32)
    weights = (5 * 330_352_896 + 777_912_320) * 2
    state = 2 * 32 * 5 * (33_816_576 + 264_192)
    assert nbytes == weights + state
    assert 0.68 < state / nbytes < 0.70                # the issue's 69%
    seconds, bound = opcount.least_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and 0.0185 < seconds < 0.0195   # 19.1 ms


def test_model_flops_count_real_tokens_once():
    one = opcount_lm.layer_token_flops(BRUMBY)
    matrices = 330_352_896 - 10_240 - 256
    recurrence = (8 * (3 * 128 * 8256 + 2 * 8256)
                  + 40 * (2 * 128 * 8256 + 2 * 8256) + 48 * 2 * 8256)
    assert one == 2.0 * matrices + recurrence
    # 2 rows, 10 prompt tokens, 3 new tokens each: 10 + 6 - 2 tokens go
    # through the layers, the head runs once for each of the 6 new ones
    assert opcount_lm.model_flops(BRUMBY, 10, 6, 2) == (
        14 * 5 * one + 6 * 2.0 * 777_912_320)


def test_kernel_floors_by_hand():
    flops, nbytes = opcount_lm.retention_decode(BRUMBY, 32)
    assert nbytes == 32 * (2 * 33_816_576 + 96 * 128 * 4)
    assert flops == 32 * opcount_lm.retention_token_flops(BRUMBY)
    assert opcount.least_seconds(flops, nbytes, PEAK)[1] == "memory"
    flops, nbytes = opcount_lm.retention_prefill(BRUMBY, 32, 128)
    pairs = 128 * 129 // 2
    assert flops == 32 * (40 * (pairs * 512.0 + 128 * 2.0 * 128 * 8256)
                          + 8 * (128 * 2.0 * 128 * 8256 + 128 * 8256))
    # a chunk of 128 reads and writes the state (2.2 GB) for 2.1 ms of
    # products: still bound by bytes; from about 180 tokens by products
    assert opcount.least_seconds(flops, nbytes, PEAK)[1] == "memory"
    assert opcount.least_seconds(
        *opcount_lm.retention_prefill(BRUMBY, 32, 256), PEAK)[1] == "compute"


# -- the readers ---------------------------------------------------------

def _record(start, rows=2, prompt=300, padded=512, new=6, rung=256):
    return {"className": "CausalLM", "method": "transform", "uid": "u",
            "start_s": start, "end_s": start + 1.0,
            "counts": {"new_tokens": new, "length_rung": rung,
                       "state_bytes": 1},
            "spans": [{"name": "lm.stack", "start_s": start,
                       "end_s": start + 0.25, "parent": "CausalLM.transform",
                       "counts": {"rows": rows, "prompt_tokens": prompt,
                                  "padded_tokens": padded}}]}


@pytest.fixture
def ctx(monkeypatch):
    from mmlspark_tpu.core.logging_utils import SINK

    calls = [types.SimpleNamespace(
        name="transform_call", start=s - 0.1, end=s + 1.1, in_window=True,
        work={"rows": 2, "new_tokens": 6, "prompt_tokens": 300}, phases={})
        for s in (100.0, 200.0)]
    monkeypatch.setattr(SINK, "events", [_record(100.0), _record(200.0)])
    ops = []
    for base in (10.0, 20.0):               # two calls on the device
        ops += [("%fusion.1 = f32[2] fusion()", base, base + 0.10),
                ("%retention_prefill.3 = custom-call()", base + 0.1,
                 base + 0.2),
                ("%retention_prefill.3 = custom-call()", base + 0.2,
                 base + 0.3),
                ("%retention_decode.7 = custom-call()", base + 0.5,
                 base + 0.54),
                ("%retention_decode.7 = custom-call()", base + 0.6,
                 base + 0.64)]
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=ops, modules=[
        ("jit_lm_prefill(1)", 10.0, 10.3), ("jit_lm_generate(2)", 10.5, 11.0),
        ("jit_lm_prefill(1)", 20.0, 20.3), ("jit_lm_generate(2)", 20.5, 21.0)])
    shape = dict(BRUMBY, num_hidden_layers=1, prefill_chunk=128)
    return types.SimpleNamespace(
        trace=tr.Trace(devices=[dev], annotations=[]), traced_calls=calls,
        counters={"lm_shape": shape}, device_kind="TPU v5 lite",
        window_calls=lambda: calls, emit=lambda **facts: None)


def test_pad_share_and_stack_ms_read_the_programs_records(ctx):
    assert load_module("readers", "lm_calls").read(ctx, {}) == pytest.approx(
        100.0 * (512 - 300) / 512)
    spec = load_json("layers", "generate_stack_ms.json")
    assert load_module("readers", spec["reader"]).read(
        ctx, spec["params"]) == pytest.approx(250.0)


def test_module_time_splits_prefill_from_decode(ctx):
    reader = load_module("readers", "module_time")
    share = load_json("layers", "generate_prefill_share.json")["params"]
    assert reader.read(ctx, share) == pytest.approx(100.0 * 0.6 / 1.6)
    per = load_json("layers", "generate_decode_ms_per_token.json")["params"]
    # 1.0 s of lm_generate over 2 calls x (6 / 2 - 1) decode steps
    assert reader.read(ctx, per) == pytest.approx(1000.0 * 1.0 / 4)
    ctx.trace = None
    assert reader.read(ctx, per) is None


def test_kernel_rooflines_are_matched_by_the_kernels_names(ctx):
    reader = load_module("readers", "lm_kernel_roofline")
    shape = ctx.counters["lm_shape"]
    peak = opcount.peaks("TPU v5 lite")
    decode = load_json("layers", "retention_decode_roofline.json")["params"]
    floor, _ = opcount.least_seconds(
        *opcount_lm.retention_decode(shape, 2), peak)
    # one layer, 2 decode steps a call, 2 calls; 4 events of 0.04 s
    assert reader.read(ctx, decode) == pytest.approx(
        100.0 * floor * 4 / 0.16)
    prefill = load_json("layers", "retention_prefill_roofline.json")["params"]
    floor, _ = opcount.least_seconds(
        *opcount_lm.retention_prefill(shape, 2, 128), peak)
    # a 256 rung is 2 chunks a call; 4 events of 0.1 s
    assert reader.read(ctx, prefill) == pytest.approx(
        100.0 * floor * 4 / 0.4)
    ctx.counters = {}
    assert reader.read(ctx, decode) is None


def test_mfu_and_step_roofline_over_busy_time(ctx):
    reader = load_module("readers", "lm_roofline")
    shape = ctx.counters["lm_shape"]
    peak = opcount.peaks("TPU v5 lite")
    busy = ctx.trace.device(0).busy_s()
    assert busy == pytest.approx(2 * 0.38)
    mfu = load_json("layers", "generate_mfu.json")["params"]
    flops = 2 * opcount_lm.model_flops(shape, 300, 6, 2)
    assert reader.read(ctx, mfu) == pytest.approx(
        100.0 * flops / (peak["bf16_flops_per_s"] * busy))
    step = load_json("layers", "generate_step_roofline.json")["params"]
    floor = 2 * (opcount.least_seconds(
        *opcount_lm.prefill(shape, 300, 2), peak)[0]
        + 2 * opcount.least_seconds(
            *opcount_lm.decode_step(shape, 2), peak)[0])
    assert reader.read(ctx, step) == pytest.approx(100.0 * floor / busy)


def test_a_program_without_the_stage_gives_the_readers_nothing(
        ctx, monkeypatch):
    """A parent commit: no ``CausalLM`` record, no kernel event."""
    from mmlspark_tpu.core.logging_utils import SINK

    monkeypatch.setattr(SINK, "events", [])
    for name in ("generate_mfu", "generate_step_roofline",
                 "retention_decode_roofline", "retention_prefill_roofline",
                 "generate_pad_share", "generate_stack_ms"):
        spec = load_json("layers", name + ".json")
        assert load_module("readers", spec["reader"]).read(
            ctx, spec.get("params", {})) is None, name
