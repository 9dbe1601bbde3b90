"""``opcount_hc_lm`` against numbers worked by hand (ISSUE 39's
arithmetic) and against the program's own parameter shapes, and the
readers the cell ``xing4_0_29b_a4b.extract`` adds on a synthetic trace
and canned records."""

import types

import pytest

from benchmark import opcount
from benchmark import opcount_hc_lm as O
from benchmark import scope_time
from benchmark import trace_reduce as tr
from benchmark.lookup import load_json, load_module

FILE = load_json("configs", "xing4_0_29b_a4b.json")
CFG = {k: FILE[k] for k in FILE["model_keys"]}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_sizes_at_the_published_widths_by_hand():
    assert O.kinds(CFG) == (1, 5) and O.held(CFG) == 64
    sizes = O.sizes(CFG)
    # 3584 x 768, 768 x 32 x 192, 3584 x 576, 512 x 32 x 256 and the
    # output 4096 x 3584 (the issue's 28,411,136 counts the mixer's two
    # norms, 768 + 512, which stand under vector_params here)
    assert sizes["latent_params"] == (
        2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064
    ) == 28_409_856
    assert sizes["expert_params"] == 3 * 3584 * 1024 == 11_010_048
    assert sizes["dense_params"] == 3 * 3584 * 9216 == 99_090_432
    assert sizes["router_params"] == 3584 * 64
    assert sizes["head_params"] == 3584 * 131072 == 469_762_048
    # the path: phi 14,336 x 24, alpha 3, b_pre 4, b_post 4, b_res 16
    assert sizes["hc_params"] == 344_064 + 27 == 344_091
    # two norms a layer, the mixer's two, five selection biases, the
    # final norm
    assert sizes["vector_params"] == (6 * (2 * 3584 + 768 + 512)
                                      + 5 * 64 + 3584) == 54_592
    # the mixer, the router, the shared expert and all 64 routed
    assert sizes["expert_layer_params"] == (
        28_409_856 + 229_376 + 65 * 11_010_048) == 744_292_352
    # one dense and five expert layers, embedding and head whole,
    # twelve hyper-connections: 4,792.7M parameters, 9.59 GB (the
    # issue's 4,792.8M counts a learned scale of 14,336 on each
    # stream norm, which this reading of the paper does not have)
    assert sizes["parameters"] == (
        28_409_856 + 99_090_432 + 5 * 744_292_352 + 2 * 469_762_048
        + 54_592 + 12 * 344_091) == 4_792_669_828
    assert 9.58e9 < 2 * sizes["parameters"] < 9.59e9
    # 64 rows of 2,080 positions, 1,152 bytes a layer: 0.92 GB
    assert sizes["cache_position_bytes"] == 6 * 1152
    assert 64 * 2080 * sizes["cache_position_bytes"] == 920_125_440


def test_the_counts_agree_with_the_programs_own_parameters():
    """At a small size: every leaf of the model is counted once."""
    import jax

    from mmlspark_tpu.dl.backbones import lm_param_shapes

    small = load_json("rehearsal", "configs", "tiny_xing.json")
    small = {k: small[k] for k in small["model_keys"]}
    leaves = jax.tree_util.tree_leaves(lm_param_shapes(small))
    assert O.parameters(small) == sum(x.size for x in leaves)


def test_the_paths_operations_and_bytes_a_token_by_hand():
    flops, nbytes = O.hc_sublayer(CFG)
    # the stream 4 x 3584 float32 = 57,344 bytes: read once for norm,
    # projection and read, read and written once for the write-back
    assert nbytes == 3 * 4 * 3584 * 4 == 172_032
    # projection 2 x 14,336 x 24; the norm's sum and the read 2 x 14,336
    # each; 20 rounds of 2 x (16 adds + 16 divides); the write-back
    # 2 x 4 x 14,336 + 2 x 14,336
    assert flops == (688_128 + 28_672 + 1_280 + 28_672 + 114_688
                     + 28_672) == 890_112
    # 5 FLOP a byte against the chip's 240: the floor is the bytes',
    # 0.21 us a token and sub-layer, 2.5 us a token over 12 sub-layers
    seconds, bound = opcount.least_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and 0.209e-6 < seconds < 0.211e-6
    assert O.hc_path(CFG, 1000.0) == (1000 * flops, 1000 * nbytes)
    # beside one expert layer's 168 MFLOP a token (0.85 us at the peak):
    # the mixer, the shared expert and 4 routed ones, twice
    layer = 2.0 * (28_409_856 + 229_376 + 5 * 11_010_048)
    assert 167e6 < layer < 168e6
    # both sub-layers' floor is half the layer's time at the peak: a
    # third of the two together, and less as the layer runs under it
    assert 0.49 < 2 * seconds / (layer / 197e12) < 0.50


def test_the_models_counts_are_the_layers_and_the_paths():
    from benchmark import opcount_latent_lm as L

    passages = 14 * 12
    assert O.model_flops(CFG, 10, 6, 2, 7, passages) == (
        L.model_flops(CFG, 10, 6, 2, 7) + passages * 890_112)
    flops, nbytes = O.prefill(CFG, 1000, 4, 4.0)
    base = L.prefill(CFG, 1000, 4, 4.0)
    assert flops == base[0] + 1000 * 12 * 890_112
    assert nbytes == base[1] + 1000 * 12 * 172_032 + 12 * 344_091 * 2
    flops, nbytes = O.decode_step(CFG, 64, 1100.0, 4.0)
    base = L.decode_step(CFG, 64, 1100.0, 4.0)
    assert flops == base[0] + 64 * 12 * 890_112
    assert nbytes == base[1] + 64 * 12 * 172_032 + 12 * 344_091 * 2
    # a decode step of 64 rows touches 63 of a layer's 64 experts (256
    # pairs): 8.5 GB of weights (the embedding is gathered, not read),
    # 0.49 GB of cache, and the path's 0.13 GB
    assert 9.1e9 < nbytes < 9.2e9 and 64 * 12 * 172_032 < 0.14e9


# -- the readers ---------------------------------------------------------

ROWS, PROMPT, NEW = 2, 100, 6           # a call: 2 rows, 5 decode steps
THROUGH = PROMPT + ROWS * (NEW - 1)
PASSAGES = 12 * THROUGH


def _record(start):
    return {"className": "CausalLM", "method": "transform", "uid": "u",
            "start_s": start, "end_s": start + 1.0,
            "counts": {"new_tokens": ROWS * NEW, "length_rung": 128,
                       "state_bytes": 1, "cache_bytes": 2,
                       "expert_pairs": 480, "expert_pairs_max": 9,
                       "dropped_pairs": 0, "cache_positions": 6 * THROUGH,
                       "cache_capacity": 6 * ROWS * (128 + NEW),
                       "hc_streams": 4, "hc_sublayer_tokens": PASSAGES},
            "spans": [{"name": "lm.stack", "start_s": start,
                       "end_s": start + 0.25, "parent": "CausalLM.transform",
                       "counts": {"rows": ROWS, "prompt_tokens": PROMPT,
                                  "padded_tokens": 256}}]}


@pytest.fixture
def ctx(monkeypatch):
    from mmlspark_tpu.core.logging_utils import SINK

    calls = [types.SimpleNamespace(
        name="transform_call", start=99.9, end=101.1, in_window=True,
        work={"rows": ROWS, "new_tokens": ROWS * NEW,
              "prompt_tokens": PROMPT}, phases={})]
    monkeypatch.setattr(SINK, "events", [_record(100.0)])
    ops = [("%fusion.1 = f32[2] fusion()", 10.0, 10.10),
           ("%fusion.2 = f32[2] fusion()", 10.10, 10.16),
           ("%fusion.1 = f32[2] fusion()", 10.4, 10.7),
           ("%fusion.2 = f32[2] fusion()", 10.7, 10.72)]
    modules = [("jit_lm_prefill(1)", 10.0, 10.3),
               ("jit_lm_generate(2)", 10.4, 10.8)]
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=ops, modules=modules)
    context = types.SimpleNamespace(
        trace=tr.Trace(devices=[dev], annotations=[]), traced_calls=calls,
        counters={"lm_shape": dict(CFG, prefill_chunk=256)}, config=FILE,
        device_kind="TPU v5 lite", window_calls=lambda: calls,
        emit=lambda **facts: None)
    # fusion.2 stands under the path's scope in both programs
    table = {"fusion.1": "lm.moe.experts", "fusion.2": "lm.hc.mix"}
    monkeypatch.setattr(scope_time, "_tables", lambda: (
        {"jit_lm_prefill": [table], "jit_lm_generate": [table]}, 0.0))
    monkeypatch.setattr(scope_time, "_memo", {})
    return context


def _read(ctx, metric):
    spec = load_json("layers", metric + ".json")
    return load_module("readers", spec["reader"]).read(
        ctx, spec.get("params", {}))


def test_the_paths_share_its_roofline_and_its_milliseconds(ctx):
    peak = opcount.peaks("TPU v5 lite")
    # 0.06 s of the prefill's 0.16 busy; 0.02 s of the decode's 5 steps
    assert _read(ctx, "prefill_hc_share") == pytest.approx(100 * 0.06 / 0.16)
    assert _read(ctx, "decode_hc_ms_per_token") == pytest.approx(
        1000 * 0.02 / 5)
    floor = PASSAGES * 172_032 / peak["hbm_bytes_per_s"]
    assert _read(ctx, "hc_mix_roofline") == pytest.approx(
        100 * floor / 0.08)


def test_shares_of_the_peak_over_busy_time(ctx):
    peak = opcount.peaks("TPU v5 lite")
    busy = ctx.trace.device(0).busy_s()
    assert busy == pytest.approx(0.48)
    flops = O.model_flops(CFG, PROMPT, ROWS * NEW, ROWS, 480, PASSAGES)
    assert _read(ctx, "xing_generate_mfu") == pytest.approx(
        100.0 * flops / (peak["bf16_flops_per_s"] * busy))
    per_token = 480 / (THROUGH * 5)
    floor = (opcount.least_seconds(
        *O.prefill(CFG, PROMPT, ROWS, per_token), peak)[0]
        + 5 * opcount.least_seconds(
            *O.decode_step(CFG, ROWS, 50.0 + 3.0, per_token), peak)[0])
    assert _read(ctx, "xing_step_roofline") == pytest.approx(
        100.0 * floor / busy)
    # 9 pairs on the busiest of 5 x 64 (layer, expert) slots over 480
    assert _read(ctx, "xing_moe_load_imbalance") == pytest.approx(
        9 * 320 / 480)


def test_a_program_without_the_counts_gives_the_readers_nothing(
        ctx, monkeypatch):
    """A parent commit, or another model: no record with
    ``hc_sublayer_tokens``; no scope ``lm.hc`` in any table."""
    from mmlspark_tpu.core.logging_utils import SINK

    parent = _record(100.0)
    del parent["counts"]["hc_sublayer_tokens"], parent["counts"]["hc_streams"]
    for events in ([], [parent]):
        monkeypatch.setattr(SINK, "events", events)
        for name in ("xing_generate_mfu", "xing_step_roofline",
                     "hc_mix_roofline"):
            assert _read(ctx, name) is None, name
    monkeypatch.setattr(SINK, "events", [_record(100.0)])
    monkeypatch.setattr(scope_time, "_tables", lambda: (None, 0.0))
    monkeypatch.setattr(scope_time, "_memo", {})
    for name in ("hc_mix_roofline", "prefill_hc_share",
                 "decode_hc_ms_per_token"):
        assert _read(ctx, name) is None, name
