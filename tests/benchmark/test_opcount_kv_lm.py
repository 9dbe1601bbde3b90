"""``opcount_kv_lm`` against hand counts for one full and one window
layer at a small size, in prefill and in decode; the published cut's
figures; and ``readers/kv_lm_roofline.py`` on a synthetic trace: a
kernel's share from its events, nothing where the trace has no such
kernel or the root no ``window_positions``."""

import types

import pytest

from benchmark import opcount, opcount_kv_lm as O
from benchmark import trace_reduce as tr
from benchmark.lookup import load_json, load_module
from tests.benchmark.test_program_spans import _call, _record

# one full layer (dense) and one window layer (experts): 8 query heads of
# 24 (+16 of value) over 2 key heads full, 4 window; 2 of 8 experts held
SMALL = {"hidden_size": 64, "vocab_size": 256, "num_hidden_layers": 2,
         "published_layers": [0, 1], "hybrid_layer_pattern": [0, 1],
         "moe_layer_freq": [0, 1], "intermediate_size": 128,
         "moe_intermediate_size": 32, "n_routed_experts": 2,
         "experts_held": [0, 2], "router_experts": 8,
         "num_attention_heads": 8, "num_key_value_heads": 2,
         "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16,
         "sliding_window": 4, "add_swa_attention_sink_bias": True,
         "add_full_attention_sink_bias": False}


def test_the_small_models_sizes_by_hand():
    h = 64
    full = h * (8 * 24 + 2 * 40) + 8 * 16 * h          # q, k, v and W_o
    window = h * (8 * 24 + 4 * 40) + 8 * 16 * h
    assert O.attention_params(SMALL, False) == full
    assert O.attention_params(SMALL, True) == window
    assert O.counts(SMALL) == (1, 1, 1, 1)
    assert O.parameters(SMALL) == (
        full + window + 8                     # the window layer's sinks
        + 3 * h * 128 + h * 8 + 8 + 2 * 3 * h * 32   # dense; router; 2
        + 2 * 2 * h + 2 * 256 * h + h)        # norms, embedding, head
    assert O.pair_flops(SMALL, False) == O.pair_flops(SMALL, True) == (
        8 * 2 * (24 + 16))
    assert O.entry_bytes(SMALL, False) == 2 * 2 * 40
    assert O.entry_bytes(SMALL, True) == 2 * 4 * 40


def test_prefill_by_hand():
    """Two rows of 10 prompt tokens: the full layer's queries read 1 ..
    10 positions each (55 pairs a row), the window layer's 1, 2, 3, 4,
    4, ... (34 pairs a row: counted by the program, given here)."""
    pairs = 8 * 2 * 40
    flops, nbytes = O.kv_prefill(SMALL, 20, 2)
    assert flops == 2 * 55 * pairs
    assert nbytes == 20 * (2 * 2 * 40 + 8 * (2 * 24 + 4 * 16))
    window = 2 * 34
    assert O.model_flops(SMALL, 20, 2, 2, 0, window) == pytest.approx(
        20 * 2 * (O.attention_params(SMALL, False)
                  + O.attention_params(SMALL, True) + 3 * 64 * 128
                  + 64 * 8)
        + 2 * 55 * pairs + window * pairs + 2 * 2 * 64 * 256)


def test_decode_by_hand():
    """Two rows of 10 prompt tokens, 3 decode steps: the full layer reads
    11, 12 and 13 positions a row, the window layer 4 a step and row."""
    full = O.decode_positions(20, 2, 3)
    assert full == 2 * (11 + 12 + 13)
    window = O.decode_window_positions(SMALL, 2, 3)
    assert window == 2 * 3 * 4
    flops, nbytes = O.kv_decode(SMALL, full, window, launches=2 * 2 * 3)
    assert flops == (full + window) * 8 * 2 * 40
    assert nbytes == (full * 2 * 2 * 40 + window * 2 * 4 * 40
                      + 12 * 8 * (2 * 24 + 4 * 16))
    # one step of 2 rows with 11 positions in the cache, a pair a token
    # to an expert: both held experts touched 1 - (1/2)^2 of the time
    flops, nbytes = O.decode_step(SMALL, 2, 11, 1.0)
    resident = (O.attention_params(SMALL, False)
                + O.attention_params(SMALL, True) + 3 * 64 * 128 + 64 * 8
                + 64 * 256)
    assert nbytes == pytest.approx(
        2 * (resident + 3 * 64 * 32 * 2 * 0.75)
        + 2 * (11 * 2 * 2 * 40 + 4 * 2 * 4 * 40))
    assert flops == pytest.approx(
        2 * 2 * resident + 2 * 2 * 3 * 64 * 32
        + 2 * (11 + 4) * 8 * 2 * 40)


def test_the_published_cuts_figures():
    cfg = load_json("configs", "mimo_v2_5.json")
    sizes = O.sizes(cfg)
    assert sizes["full_attention_params"] == 89_128_960
    assert sizes["window_attention_params"] == 94_371_840
    assert sizes["expert_params"] == 25_165_824
    assert sizes["layers"] == {"full": 2, "window": 5, "dense": 1,
                               "expert": 6}
    assert sizes["full_entry_bytes"] == 2_560
    assert sizes["parameters"] == 3_429_955_392


CELL = load_json("configs", "mimo_v2_5.json")


def _trace(kernel):
    """0.4 s of the named kernel and 0.6 s of a fusion in one second."""
    ops = [(kernel, 10.0, 10.4), ("fusion.7", 10.4, 11.0)]
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=ops,
                         modules=[("jit_lm_generate(3)", 10.0, 11.0)])
    return tr.Trace(devices=[dev], annotations=[])


def _root(counts):
    rec = _record(100.5, [("lm.stack", 100.6, 100.7, None)],
                  cls="CausalLM", end=103.0)
    rec["spans"][0]["counts"] = {"rows": 16, "prompt_tokens": 16 * 18_000}
    rec["counts"] = dict({"new_tokens": 16 * 512, "expert_pairs": 100},
                         **counts)
    return rec


@pytest.fixture
def sink():
    from mmlspark_tpu.core.logging_utils import SINK
    kept = SINK.drain()
    yield SINK
    SINK.drain()
    SINK.events.extend(kept)


def _read(metric, kernel, sink, counts):
    sink.events.append(_root(counts))
    facts = []
    ctx = types.SimpleNamespace(
        trace=_trace(kernel), traced_calls=[_call("transform_call", 100.0,
                                                  104.0)],
        counters={"lm_shape": {k: CELL[k] for k in CELL["model_keys"]}},
        device_kind="TPU v5 lite", emit=lambda **f: facts.append(f))
    got = load_module("readers", "kv_lm_roofline").read(
        ctx, load_json("layers", metric + ".json")["params"])
    return got, facts


def test_the_decode_share_is_its_floor_over_its_events(sink):
    window = 5 * 16 * 511 * 128 + 1
    got, facts = _read("kv_decode_roofline", "kv_decode.3", sink,
                       {"window_positions": window})
    full = 2 * O.decode_positions(16 * 18_000, 16, 511)
    floor, bound = opcount.least_seconds(*O.kv_decode(
        CELL, full, 5 * 16 * 511 * 128, 7 * 16 * 511),
        opcount.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert got == pytest.approx(100.0 * floor / 0.4)
    assert facts[0]["events"] == 1


def test_nothing_without_the_kernel_or_without_window_positions(sink):
    got, _ = _read("kv_decode_roofline", "fusion.3", sink,
                   {"window_positions": 1})
    assert got is None                      # no kv_decode in the trace
    sink.drain()
    got, _ = _read("kv_prefill_roofline", "kv_decode.3", sink,
                   {"window_positions": 1})
    assert got is None                      # no kv_prefill in the trace
    sink.drain()
    got, _ = _read("mimo_generate_mfu", "kv_decode.3", sink, {})
    assert got is None                      # another model's root
    sink.drain()
    got, _ = _read("mimo_generate_mfu", "kv_decode.3", sink,
                   {"window_positions": 1})
    assert got > 0
