"""``readers/full_attention_roofline.py`` on a synthetic trace: its
floor is ``opcount_gdla_lm``'s full-layer prompt pairs at the cell's
shapes, its time the device seconds under ``lm.mla.full`` in
``lm_prefill``, and it reads nothing for a program without the scope
(a parent commit) or a root without ``window_positions`` (another
model)."""

import types

import pytest

from benchmark import opcount, opcount_gdla_lm, scope_time
from benchmark import trace_reduce as tr
from benchmark.lookup import load_json, load_module
from tests.benchmark.test_program_spans import _call, _record

CELL = load_json("configs", "motif_3_beta.json")
PREFILL = {"fusion.1": "lm.mla.full", "custom-call.2": "lm.mla.full",
           "fusion.3": "lm.mla.diff", "fusion.4": "lm.mla"}


def _trace():
    """``lm_prefill`` 10.0-11.0: 0.2 s of a fusion and 0.5 s of the
    kernel under ``lm.mla.full``, 0.3 s beside them."""
    ops = [(f"%{n} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", 10.0 + s, 10.0 + e)
           for n, s, e in [("fusion.1", 0.0, 0.2), ("custom-call.2", 0.2, 0.7),
                           ("fusion.3", 0.7, 0.9), ("fusion.4", 0.9, 1.0)]]
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=ops,
                         modules=[("jit_lm_prefill(7)", 10.0, 11.0)])
    return tr.Trace(devices=[dev], annotations=[])


def _root(counts):
    rec = _record(100.5, [("lm.stack", 100.6, 100.7, None)],
                  cls="CausalLM", end=103.0)
    rec["spans"][0]["counts"] = {"rows": 16, "prompt_tokens": 143_360}
    rec["counts"] = dict({"new_tokens": 16 * 128, "expert_pairs": 0,
                          "hc_sublayer_tokens": 0}, **counts)
    return rec


@pytest.fixture
def sink():
    from mmlspark_tpu.core.logging_utils import SINK
    kept = SINK.drain()
    yield SINK
    SINK.drain()
    SINK.events.extend(kept)


def _ctx(monkeypatch, tables):
    monkeypatch.setattr(scope_time, "_tables", lambda: (tables, 0.0))
    monkeypatch.setattr(scope_time, "_memo", {})
    facts = []
    return types.SimpleNamespace(
        trace=_trace(), traced_calls=[_call("transform_call", 100.0, 104.0)],
        counters={"lm_shape": {k: CELL[k] for k in CELL["model_keys"]}},
        device_kind="TPU v5 lite", facts=facts,
        emit=lambda **f: facts.append(f))


def _read(ctx):
    return load_module("readers", "full_attention_roofline").read(
        ctx, load_json("layers", "full_attention_roofline.json")["params"])


def test_the_floor_is_the_full_layers_pairs_over_the_scopes_seconds(
        monkeypatch, sink):
    sink.events.append(_root({"window_positions": 1}))
    ctx = _ctx(monkeypatch, {"jit_lm_prefill": [PREFILL]})
    got = _read(ctx)
    # two full layers, 16 rows of 8,960 tokens each, every query head
    # at 2 (192 + 128) operations a pair
    length = 143_360 / 16
    flops = 2 * 16 * length * (length + 1) / 2 * 80 * 2 * (192 + 128)
    assert flops == pytest.approx(
        2 * 16 * opcount_gdla_lm.attended_pairs(length, 1)
        * opcount_gdla_lm.pair_flops(ctx.counters["lm_shape"]))
    (fact,) = [f for f in ctx.facts if "full" in f]
    assert fact["flops"] == pytest.approx(flops)
    assert fact["full_s"] == pytest.approx(0.7)     # the fusion and the kernel
    assert fact["bound"] == "compute"
    peak = opcount.peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert got == pytest.approx(100.0 * flops / peak / 0.7)


def test_nothing_without_the_scope_or_without_window_positions(
        monkeypatch, sink):
    parent = {k: v for k, v in PREFILL.items() if v != "lm.mla.full"}
    sink.events.append(_root({"window_positions": 1}))
    assert _read(_ctx(monkeypatch, {"jit_lm_prefill": [parent]})) is None
    sink.drain()
    sink.events.append(_root({}))                   # another model's root
    assert _read(_ctx(monkeypatch, {"jit_lm_prefill": [PREFILL]})) is None
