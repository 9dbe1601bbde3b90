"""The per-layer metrics that read the program's own spans."""

import json
import math
import types

import pytest

from benchmark import program_spans
from benchmark import trace_reduce as tr
from benchmark.lookup import load_module
from tests.benchmark.test_rehearsal import last_line, run_cell

HOST_SPAN_METRICS = {
    "tiny_gbdt.fit": {
        "fit_extract_share": "%", "fit_bin_transform_share": "%",
        "fit_assembly_share": "%", "fit_unspanned_share": "%"},
    "tiny_resnet.transform": {
        "transform_stack_ms": "ms", "transform_put_ms": "ms",
        "transform_fetch_ms": "ms", "transform_unspanned_share": "%"},
}


@pytest.mark.parametrize("workload", sorted(HOST_SPAN_METRICS))
def test_rehearsal_reports_the_host_span_metrics(workload):
    proc = run_cell(workload, 1)
    result = last_line(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    for name, unit in HOST_SPAN_METRICS[workload].items():
        metric = result["metrics"]["cpu." + name]
        assert metric["unit"] == unit and math.isfinite(metric["value"])
        assert metric["value"] >= 0.0
        if unit == "%":
            assert metric["value"] <= 100.0
    # the device reader finds no device plane on the CPU and says nothing
    assert "cpu.transform_idle_named_share" not in result["metrics"]
    # a call's wall time is its top-level spans and what no span covers
    facts = [json.loads(line) for line in proc.stdout.splitlines()[:-1]
             if line.startswith("{")]
    spans = next(f["program_spans"] for f in facts if "program_spans" in f)
    assert spans["calls"] == result["attempted"]
    assert spans["unspanned_s"] >= 0.0
    assert (sum(spans["top_level_s"].values()) + spans["unspanned_s"]
            == pytest.approx(spans["wall_s"]))
    share = result["metrics"]["cpu." + next(
        n for n in HOST_SPAN_METRICS[workload] if "unspanned" in n)]["value"]
    assert share == pytest.approx(
        100.0 * spans["unspanned_s"] / spans["wall_s"])
    if workload == "tiny_gbdt.fit":
        assert set(spans["top_level_s"]) == {
            "labels", "extract", "binning", "dataPreparation", "training",
            "validation", "treeFetch", "assembly"}
        assert set(spans["nested_s"]) == {
            "binning.fit", "binning.transform", "dataPreparation.transfer"}
    else:
        assert set(spans["top_level_s"]) == {
            "onnx.stack", "onnx.cast", "scorer.pad", "scorer.put",
            "scorer.dispatch", "scorer.fetch", "onnx.columns"}


# -- the readers on synthetic records and a synthetic trace ------------

def _record(start, spans, cls="ONNXModel", method="transform", end=None):
    root = f"{cls}.{method}"
    return {"uid": f"{cls}_1", "className": cls, "method": method,
            "start_s": start, "end_s": end if end is not None else start + 1,
            "spans": [{"name": n, "start_s": s, "end_s": e,
                       "parent": parent or root, "counts": {}}
                      for n, s, e, parent in spans]}


def _call(name, start, end):
    call = types.SimpleNamespace(name=name, start=start, end=end,
                                 in_window=True)
    call.seconds = end - start
    return call


@pytest.fixture
def sink():
    from mmlspark_tpu.core.logging_utils import SINK
    kept = SINK.drain()
    yield SINK
    SINK.drain()
    SINK.events.extend(kept)


def test_an_idle_stretch_goes_to_the_innermost_span_that_covers_it():
    ops = [(2.0, 3.0), (6.0, 6.5)]
    spans = [("outer", 0.5, 5.0), ("outer.inner", 1.0, 1.5),
             ("later", 5.5, 7.0)]
    got = program_spans.idle_by_span(ops, spans, 0.0, 8.0)
    # idle: 0-2 and 3-6 and 6.5-8
    assert got == {
        program_spans.UNNAMED: pytest.approx(0.5 + 0.5 + 1.0),
        "outer": pytest.approx(0.5 + 0.5 + 2.0),
        "outer.inner": pytest.approx(0.5),
        "later": pytest.approx(0.5 + 0.5)}
    assert sum(got.values()) == pytest.approx(8.0 - 1.5)
    assert program_spans.idle_by_span(ops, [], 2.0, 3.0) == {}


def test_idle_reader_maps_the_programs_clock_by_each_calls_offset(sink):
    # trace clock: two calls at 100-101 and 103-104; the host's clock
    # read 10.0 at the first call's start and, drifting, 12.9 at the
    # second's. The device works in the last 0.2 s of each call.
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=[
        ("fusion.1", 100.8, 101.0), ("fusion.1", 103.8, 104.0)])
    trace = tr.Trace(devices=[dev], annotations=[
        ("transform_call", 103.0, 104.0), ("transform_call", 100.0, 101.0),
        ("between_calls", 101.0, 103.0)])
    calls = [_call("transform_call", 10.0, 11.0),
             _call("transform_call", 12.9, 13.9)]
    for t in (10.0, 12.9):
        sink.emit(_record(t + 0.01, [
            ("onnx.stack", t + 0.05, t + 0.45, None),
            ("scorer.put", t + 0.45, t + 0.75, None),
            ("scorer.fetch", t + 0.75, t + 0.95, None)], end=t + 0.99))
    sink.emit({"event": "degradation", "key": "k"})    # not a stage record
    facts = []
    ctx = types.SimpleNamespace(trace=trace, traced_calls=calls,
                                emit=lambda **f: facts.append(f))
    reader = load_module("readers", "idle_named_share")
    value = reader.read(ctx, {})
    # idle a call: 0.8 s, of which 0.05 before the first span
    assert value == pytest.approx(100.0 * 0.75 / 0.8)
    walls, fact = facts
    assert walls == {"traced_call_s": [pytest.approx(1.0)] * 2}
    assert fact["idle_by_span_s"] == {
        "onnx.stack": pytest.approx(0.8), "scorer.put": pytest.approx(0.6),
        "scorer.fetch": pytest.approx(0.1),
        program_spans.UNNAMED: pytest.approx(0.1)}
    # mapped by the first call's offset alone, the second call's spans
    # would lie 0.1 s off: the reader must not do that
    assert list(fact["idle_by_span_s"])[0] == "onnx.stack"

    # a trace that lost an annotation cannot be joined; no trace, nothing
    trace.annotations.pop(0)
    assert reader.read(ctx, {}) is None
    ctx.trace = None
    assert reader.read(ctx, {}) is None


def test_span_readers_say_nothing_of_a_program_that_records_no_spans(sink):
    # the parent commit's record: no start_s, no spans
    sink.emit({"uid": "ONNXModel_1", "className": "ONNXModel",
               "method": "transform", "seconds": 0.9, "numRows": 8})
    calls = [_call("transform_call", 10.0, 11.0)]
    dev = tr.DeviceTrace(plane="/device:TPU:0",
                         ops=[("fusion.1", 100.8, 101.0)])
    ctx = types.SimpleNamespace(
        window_calls=lambda: calls, traced_calls=calls, emit=lambda **f: None,
        trace=tr.Trace(devices=[dev],
                       annotations=[("transform_call", 100.0, 101.0)]))
    assert program_spans.records() == []
    for reader, params in (("span_ms", {"spans": ["onnx.stack"]}),
                           ("unspanned_share", {}),
                           ("idle_named_share", {})):
        assert load_module("readers", reader).read(ctx, params) is None


def test_span_ms_and_unspanned_share_over_the_windows_calls(sink):
    calls = [_call("transform_call", 10.0, 11.0),
             _call("transform_call", 20.0, 22.0),
             _call("transform_call", 30.0, 31.0)]     # no record inside
    sink.emit(_record(10.1, [("onnx.stack", 10.1, 10.5, None),
                             ("scorer.fetch", 10.5, 10.9, None)], end=10.95))
    # a stage inside a stage: read through the outer's spans, not twice
    sink.emit(_record(20.2, [("work", 20.2, 20.6, "Inner.transform")],
                      cls="Inner", end=20.7))
    sink.emit(_record(20.0, [("Inner.transform", 20.2, 20.7, None),
                             ("onnx.stack", 20.8, 21.8, None)], end=21.9))
    sink.emit(_record(5.0, [("onnx.stack", 5.0, 6.0, None)]))  # before any
    facts = []
    ctx = types.SimpleNamespace(window_calls=lambda: calls,
                                emit=lambda **f: facts.append(f))
    span_ms = load_module("readers", "span_ms")
    assert span_ms.read(ctx, {"spans": ["onnx.stack"]}) == pytest.approx(
        1000.0 * (0.4 + 1.0) / 2)
    assert span_ms.read(ctx, {"spans": ["scorer.fetch", "absent"]}) \
        == pytest.approx(1000.0 * 0.4 / 2)
    unspanned = load_module("readers", "unspanned_share")
    named = 0.4 + 0.4 + 0.5 + 1.0
    assert unspanned.read(ctx, {}) == pytest.approx(100.0 * (3.0 - named) / 3)
    (fact,) = facts
    assert fact["program_spans"]["calls"] == 2
    assert fact["program_spans"]["top_level_s"]["onnx.stack"] \
        == pytest.approx(0.7)
    assert fact["program_spans"]["nested_s"] == {}
