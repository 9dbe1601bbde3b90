"""The span recorder (core/timer.py), its root (core/logging_utils.py),
and the names the program gives the host and the device with it."""

import glob
import os
import re
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.logging_utils import SINK, log_stage_method
from mmlspark_tpu.core.timer import InstrumentationMeasures, span

FIT_SPANS = {"labels", "extract", "binning", "binning.fit", "binning.transform",
             "dataPreparation", "dataPreparation.transfer", "training",
             "validation", "treeFetch", "assembly"}
TRANSFORM_SPANS = {"onnx.stack", "onnx.cast", "scorer.pad", "scorer.put",
                   "scorer.dispatch", "scorer.fetch", "onnx.columns"}
BENCHMARKS_OWN = {"fit", "transform_call", "between_calls", "traced_part"}
DEVICE_SCOPES = {"gbdt.sample", "gbdt.grad", "gbdt.hist.feed", "gbdt.hist",
                 "gbdt.split", "gbdt.route", "gbdt.leaf", "gbdt.predict"}


def test_nesting_gives_parent_root_uid_and_order_of_start():
    with span("Stage.fit", uid="Stage_1") as root:
        with span("outer", rows=3) as outer:
            with span("outer.inner") as inner:
                pass
            outer.counts["bytes"] = 24
        with span("second") as second:
            pass
    assert root.parent is None and root.uid == "Stage_1"
    assert outer.parent is root and inner.parent is outer
    assert {s.uid for s in (outer, inner, second)} == {"Stage_1"}
    assert root.spans == [outer, inner, second]
    assert all(s.spans is None for s in (outer, inner, second))
    assert (root.start_s <= outer.start_s <= inner.start_s <= inner.end_s
            <= outer.end_s <= second.start_s <= second.end_s <= root.end_s)
    assert outer.as_record() == {
        "name": "outer", "start_s": outer.start_s, "end_s": outer.end_s,
        "parent": "Stage.fit", "counts": {"rows": 3, "bytes": 24}}
    assert inner.as_record()["parent"] == "outer"


def test_a_span_with_no_root_above_it_is_its_own_root_and_is_kept_nowhere():
    with span("scorer.pad") as alone:
        with span("child") as child:
            pass
    assert alone.parent is None and alone.uid is None
    assert alone.spans == [child] and child.uid is None
    with span("scorer.pad") as again:
        pass
    assert again.spans == []            # nothing of the first one


def test_a_nested_stage_is_a_root_of_its_own_listed_once_above():
    with span("Pipeline.fit", uid="Pipeline_1") as outer:
        with span("Inner.fit", uid="Inner_2") as inner:
            with span("work") as work:
                pass
    assert outer.spans == [inner] and inner.spans == [work]
    assert work.uid == "Inner_2" and inner.parent is outer
    assert inner.as_record()["uid"] == "Inner_2"
    assert "uid" not in work.as_record()


def test_sums_by_name_are_what_phase_gave():
    m = InstrumentationMeasures()
    with m.phase("binning") as whole:
        with span("binning.fit") as first:
            time.sleep(0.002)
        with span("binning.transform") as second:
            time.sleep(0.002)
    for _ in range(3):
        with m.phase("training"):
            pass
    # a nested span stands under its own dotted name and adds nothing
    # to its parent's figure, which is the parent's own interval
    assert m.seconds("binning") == pytest.approx(whole.seconds)
    assert m.seconds("binning.fit") == pytest.approx(first.seconds)
    assert m.seconds("binning.transform") == pytest.approx(second.seconds)
    assert m.seconds("binning") >= (m.seconds("binning.fit")
                                    + m.seconds("binning.transform"))
    assert m.count("training") == 3 and m.count("binning") == 1
    assert m.seconds("never") == 0.0 and m.count("never") == 0
    assert list(m.as_dict()) == ["binning.fit", "binning.transform",
                                 "binning", "training"]
    assert m.as_dict()["training"] == m.seconds("training")
    # a span opened outside any phase of this object adds nothing to it
    with span("elsewhere"):
        pass
    assert "elsewhere" not in m.as_dict()


def test_a_raising_body_still_closes_and_records():
    m = InstrumentationMeasures()
    with pytest.raises(KeyError):
        with span("Stage.transform", uid="u") as root:
            with m.phase("doomed") as doomed:
                raise KeyError("boom")
    assert doomed.end_s >= doomed.start_s and root.end_s >= doomed.end_s
    assert root.spans == [doomed] and m.count("doomed") == 1
    with span("after") as after:        # the stack was unwound
        pass
    assert after.parent is None


def test_the_stage_record_carries_the_spans_even_when_the_body_raises():
    SINK.drain()
    with pytest.raises(ValueError):
        with log_stage_method("Stage_9", "Stage", "transform",
                              {"numRows": 2}):
            with span("part", rows=2):
                raise ValueError("bad row")
    (record,) = [e for e in SINK.drain() if e.get("uid") == "Stage_9"]
    assert record["error"].startswith("ValueError")
    assert record["seconds"] == pytest.approx(
        record["end_s"] - record["start_s"])
    (part,) = record["spans"]
    assert part["name"] == "part" and part["parent"] == "Stage.transform"
    assert record["start_s"] <= part["start_s"] <= part["end_s"] \
        <= record["end_s"]


def test_two_threads_never_adopt_each_others_spans():
    barrier = threading.Barrier(2, timeout=10)
    roots, failures = {}, []

    def serve(tag):
        try:
            with span(f"Model_{tag}.transform", uid=tag) as root:
                barrier.wait()          # both roots are open now
                for i in range(200):
                    with span(f"{tag}.work") as s:
                        if s.parent is not root or s.uid != tag:
                            failures.append((tag, i))
                barrier.wait()
            roots[tag] = root
        except Exception as e:  # noqa: BLE001 — reported below
            failures.append((tag, repr(e)))

    threads = [threading.Thread(target=serve, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads) and not failures
    for tag in "ab":
        assert len(roots[tag].spans) == 200
        assert {s.name for s in roots[tag].spans} == {f"{tag}.work"}


def test_a_span_with_no_profiler_session_costs_microseconds():
    """Spans ride every transform call and every tree of a fit with
    nothing to switch them off: with no profiler session a span is a
    dozen calls, all of them in core/timer.py or on a context variable,
    the clock, a list or a dict (no session opened, no I/O, no lock),
    and it leaves behind nothing but its record under its root. What a
    span does is counted, not timed: a host clock under the suite's
    other workers says nothing a CPU run could assert."""
    import gc
    import sys
    import tracemalloc

    m = InstrumentationMeasures()

    def alone(reps):
        for _ in range(reps):
            with span("x"):
                pass

    def under_root(reps):               # a fit's worth under each root
        for _ in range(reps // 100):
            with span("Stage.fit", uid="u"):
                for _ in range(100):
                    with m.phase("x", rows=1):
                        pass

    alone(100)                           # imports, caches, dict sizes
    under_root(100)
    here = os.path.abspath(__file__)
    calls, foreign = [0], set()

    def profile(frame, event, arg):
        if event == "call":
            path = frame.f_code.co_filename
            if path != here:
                calls[0] += 1
                if not path.endswith(os.path.join("core", "timer.py")):
                    foreign.add(f"{path}:{frame.f_code.co_name}")
        elif event == "c_call":
            owner = getattr(arg, "__self__", None)
            if owner is sys:             # counted() taking the hook off
                return
            calls[0] += 1
            if not (owner is time or isinstance(owner, (list, dict))
                    or type(owner).__name__ == "ContextVar"):
                foreign.add(repr(arg))

    def counted(run, reps):
        calls[0] = 0
        before = sys.getprofile()
        sys.setprofile(profile)
        try:
            run(reps)
        finally:
            sys.setprofile(before)
        return calls[0] / reps

    def kept_bytes(run, reps):
        was_tracing = tracemalloc.is_tracing()
        tracemalloc.start()
        try:
            gc.collect()
            start = tracemalloc.get_traced_memory()[0]
            run(reps)
            gc.collect()         # a root and its spans point at each other
            return (tracemalloc.get_traced_memory()[0] - start) / reps
        finally:
            if not was_tracing:
                tracemalloc.stop()

    reps = 2000
    alone_calls = counted(alone, reps)
    root_calls = counted(under_root, reps)
    assert not foreign, foreign
    # 8 and 13 today (the root's own span adds a hundredth); a lock, a
    # log line or a session check would each add more than the slack
    assert alone_calls <= 10 and root_calls <= 16, (alone_calls,
                                                    root_calls)
    # no root: nothing keeps the span; under a root: its record (about
    # 520 bytes), gone with the root
    assert kept_bytes(alone, reps) < 64
    assert kept_bytes(under_root, reps) < 64


# -- the names, where the work happens ---------------------------------

def _tiny_onnx_model():
    from tests.onnx.test_onnx import _mlp_model
    from mmlspark_tpu.onnx import ONNXModel

    data, _ = _mlp_model(np.random.default_rng(0))
    return ONNXModel(modelPayload=data, miniBatchSize=8)


def _tiny_frames():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(400, 4))
    fit_df = DataFrame({"features": x,
                        "label": (x[:, 0] > 0).astype(np.float64)})
    column = np.empty(6, dtype=object)
    for i in range(6):
        column[i] = rng.normal(size=4).astype(np.float32)
    return fit_df, DataFrame({"features": column})


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def test_span_names_stand_in_the_profilers_host_plane_and_in_the_record(
        tmp_path):
    import jax

    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier

    fit_df, onnx_df = _tiny_frames()
    onnx_model = _tiny_onnx_model()
    estimator = LightGBMClassifier(numIterations=3, numLeaves=4, maxBin=16)
    onnx_model.transform(onnx_df)                     # compiles
    estimator.fit(fit_df)
    SINK.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        onnx_model.transform(onnx_df)
        model = estimator.fit(fit_df)
    finally:
        jax.profiler.stop_trace()
    records = {(e["className"], e["method"]): e for e in SINK.drain()
               if "spans" in e}

    in_trace = _host_event_names(str(tmp_path))
    assert FIT_SPANS | TRANSFORM_SPANS <= in_trace
    assert {"ONNXModel.transform", "LightGBMClassifier.fit"} <= in_trace

    transform = records[("ONNXModel", "transform")]
    assert transform["uid"] == onnx_model.uid
    # the object column is checked by the front end and laid out by the
    # engine (PR 32): an ``onnx.stack`` each, the first with the counts
    assert [s["name"] for s in transform["spans"]] == [
        "onnx.stack", "onnx.cast", "scorer.pad", "onnx.stack", "scorer.put",
        "scorer.dispatch", "scorer.fetch", "onnx.columns"]
    assert {s["parent"] for s in transform["spans"]} == {
        "ONNXModel.transform"}
    assert transform["spans"][0]["counts"] == {"rows": 6, "bytes": 6 * 4 * 4}
    by_name = {s["name"]: s for s in transform["spans"]}
    assert by_name["scorer.put"]["counts"] == {"bytes": 8 * 4 * 4,
                                               "chunks": 1}
    assert by_name["scorer.fetch"]["counts"]["bytes"] == 6 * 3 * 4

    fit = records[("LightGBMClassifier", "fit")]
    assert fit["uid"] == estimator.uid
    parents = {s["name"]: s["parent"] for s in fit["spans"]}
    assert set(parents) == FIT_SPANS
    assert parents["binning.fit"] == parents["binning.transform"] == "binning"
    assert parents["dataPreparation.transfer"] == "dataPreparation"
    assert {parents[n] for n in FIT_SPANS if "." not in n} == {
        "LightGBMClassifier.fit"}
    names = [s["name"] for s in fit["spans"]]
    assert names.count("training") >= 3            # one a tree, and the drain
    assert names.index("labels") < names.index("extract") \
        < names.index("binning") \
        < names.index("dataPreparation") < names.index("training") \
        < names.index("treeFetch") < names.index("assembly")
    by_name = {s["name"]: s for s in fit["spans"]}
    assert by_name["labels"]["counts"] == {"rows": 400}
    assert by_name["extract"]["counts"] == {"rows": 400, "bytes": 400 * 4 * 8}
    assert by_name["binning.transform"]["counts"] == {"rows": 400}
    assert by_name["dataPreparation.transfer"]["counts"] == {
        "bytes": 400 * 4, "chunks": 1}
    assert by_name["treeFetch"]["counts"] == {"trees": 3}

    # the sums the model hands out are the same spans, by name
    measures = model.get_all_instrumentation()
    assert set(measures) == FIT_SPANS
    for name in FIT_SPANS:
        spent = sum(s["end_s"] - s["start_s"] for s in fit["spans"]
                    if s["name"] == name)
        assert measures[name] == pytest.approx(spent)
    # no program span takes a name the benchmark's own annotations use
    assert not BENCHMARKS_OWN & (FIT_SPANS | TRANSFORM_SPANS)


def test_the_lowered_step_carries_every_scope_and_the_kernels_name(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_HIST", "1")
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_FORCE_COMPILE", "1")
    from mmlspark_tpu.models.gbdt.hist_pallas import (
        IN_PLACE_MAX_WIDTH, pallas_level_histogram)
    from mmlspark_tpu.models.gbdt.trainer import TrainConfig, aot_lower_step

    cfg = TrainConfig(objective="binary", num_leaves=7, max_depth=3,
                      max_bin=255, min_data_in_leaf=20)
    text = aot_lower_step(cfg, n=2048, num_f=28, platform="tpu",
                          debug_info=True)
    assert set(re.findall(r"gbdt\.[a-z.]+[a-z]", text)) >= DEVICE_SCOPES
    assert "gbdt_level_hist" in text and "tpu_custom_call" in text
    assert "jit(step)" in text          # device_idle_share.fit's anchor
    assert "gbdt." not in aot_lower_step(cfg, n=2048, num_f=28)

    n = 1024
    # hist_kernel_roofline finds the kernel by its result shape,
    # f32[·,·,8,256], on either path: (F, 3·width/8, 8, 256) in place,
    # (width, F, 8, 256) sorted
    wide = 2 * IN_PLACE_MAX_WIDTH
    for width, shape in ((4, "f32[28,2,8,256]"),
                         (wide, f"f32[{wide},28,8,256]")):
        jaxpr = str(jax.make_jaxpr(
            lambda *a: pallas_level_histogram(*a, width, 28, 255))(
                jnp.zeros((n, 28), jnp.uint8), jnp.ones(n), jnp.ones(n),
                jnp.ones(n), jnp.zeros(n, jnp.int32)))
        assert "name=gbdt_level_hist" in jaxpr
        assert shape in jaxpr


@pytest.mark.parametrize("valid_rows", [0, 512])
def test_the_lowered_step_reads_one_bin_a_row_once_a_route_level(
        monkeypatch, valid_rows):
    """The tree is not walked again over the rows it was built on: at
    depth 6 the (N, 28) binned matrix feeds six one-bin-a-row gathers,
    one a route level (it was twelve, with the walk's six); rows no
    builder routed, a validation set's, still take the walk's six."""
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_HIST", "1")
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_FORCE_COMPILE", "1")
    from mmlspark_tpu.models.gbdt.trainer import TrainConfig, aot_lower_step

    cfg = TrainConfig(objective="binary", num_leaves=63, max_depth=6,
                      max_bin=255, min_data_in_leaf=20)
    text = aot_lower_step(cfg, n=2048, num_f=28, platform="tpu",
                          debug_info=True, valid_rows=valid_rows)
    # take_along_axis(binned, feature[:, None], 1), by the rows it reads
    reads = re.findall(r"call @take_along_axis\w*\(.*: \(tensor<(\d+)x28x"
                       r"(?:ui8|i32)>, tensor<\d+x1xi32>\)", text)
    assert reads.count("2048") == 6
    assert reads.count("512") == (6 if valid_rows else 0)
    assert len(reads) == (12 if valid_rows else 6)
    assert "gbdt.predict" in text and "gbdt.route" in text
