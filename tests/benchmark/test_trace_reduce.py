"""The reduction from a trace to busy, idle and per-op time."""

import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps_by_hand():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert tr.union_length(ivs) == pytest.approx(3.0)
    assert tr.gaps(ivs, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert tr.gaps(ivs, 0.5, 3.5) == [(2.0, 3.0)]
    assert tr.union_length([]) == 0.0


def _synthetic():
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=[
        ("fusion.1", 10.0, 10.4), ("hist_kernel", 10.4, 11.0),
        ("fusion.2", 10.5, 10.6),                 # nested in the kernel
        ("hist_kernel", 12.0, 12.5), ("copy.3", 14.0, 14.5)],
        modules=[("jit_step(1)", 10.0, 11.0), ("jit_step(1)", 12.0, 12.5),
                 ("jit_fetch(2)", 14.0, 14.5)])
    notes = [("fit", 9.0, 15.0), ("between_calls", 12.9, 13.9)]
    return tr.Trace(devices=[dev], annotations=notes)


def test_busy_idle_and_per_op_time_on_a_synthetic_trace():
    trace = _synthetic()
    dev = trace.device(0)
    assert dev.span() == (10.0, 14.5)
    assert dev.busy_s() == pytest.approx(2.0)          # nested op not twice
    assert dev.busy_s(10.0, 12.5) == pytest.approx(1.5)
    assert dev.op_seconds()["hist_kernel"] == pytest.approx(1.1)
    assert dev.union_seconds("kernel") == pytest.approx(1.1)
    busy, window = tr.busy_and_window(trace)
    assert (busy, window) == (pytest.approx(2.0), pytest.approx(4.5))
    out = tr.breakdown(trace, ("fit", "between_calls"))
    assert out["device_ops"][0] == ["hist_kernel", pytest.approx(1.1)]
    # the longest gap (12.5-14.0) has its middle under between_calls,
    # the next (11.0-12.0) only under fit
    assert out["idle_gaps"][0] == ["between_calls", pytest.approx(1.5)]
    assert out["idle_gaps"][1] == ["fit", pytest.approx(1.0)]


def test_idle_share_reader_between_programs():
    from benchmark.lookup import load_module

    class Ctx:
        trace = _synthetic()

    reader = load_module("readers", "idle_share")
    assert reader.read(Ctx, {}) == pytest.approx(100 * (1 - 2.0 / 4.5))
    # from the first to the last jit_step program: 10.0-12.5, busy 1.5
    assert reader.read(Ctx, {"between": r"^jit_step"}) == pytest.approx(40.0)
    assert reader.read(Ctx, {"between": "no_such_program"}) is None
    Ctx.trace = None
    assert reader.read(Ctx, {}) is None


def test_recorded_tpu_trace_against_hand_checked_numbers():
    """``data/tiny_tpu.xplane.pb``: three rounds of a 2048x2048 float32
    matmul and an add on one v5e chip with 20 ms of sleep between
    rounds (my chip run, PR 25). Its ``XLA Ops`` line holds 12 events;
    their durations in ns, read off a listing of the file: per round
    copy-start 13, copy-done 22697/22537/22543, the matmul fusion
    91471/91428/91431, the add 50302/50426/50492; first start
    42468493, last end 87347889; no two overlap."""
    trace = tr.load(os.path.join(DATA, "tiny_tpu.xplane.pb"),
                    ("fit", "between_calls"))
    assert len(trace.devices) == 1
    dev = trace.device(0)
    assert dev.plane == "/device:TPU:0" and len(dev.ops) == 12
    assert [m[0].split("(")[0] for m in dev.modules] == [
        "jit_tiny_matmul", "jit_tiny_add"] * 3
    busy_ns = (3 * 13 + 22697 + 22537 + 22543 + 91471 + 91428 + 91431
               + 50302 + 50426 + 50492)
    window_ns = 87347889 - 42468493
    busy, window = tr.busy_and_window(trace)
    assert busy == pytest.approx(busy_ns / 1e9, rel=1e-9)
    assert window == pytest.approx(window_ns / 1e9, rel=1e-9)
    assert 100 * (1 - busy / window) == pytest.approx(98.9007, abs=1e-3)
    assert dev.union_seconds(r"^%fusion = ") == pytest.approx(
        (91471 + 91428 + 91431) / 1e9, rel=1e-9)
    out = tr.breakdown(trace, ("fit", "between_calls"))
    assert out["device_ops"][0][0].startswith("fusion f32[2048,2048]")
    assert out["device_ops"][0][1] == pytest.approx(274330e-9, rel=1e-9)
    # the two long gaps are the sleeps, and the annotations name them
    assert [g[0] for g in out["idle_gaps"][:2]] == ["between_calls"] * 2
    assert all(0.0205 < g[1] < 0.0215 for g in out["idle_gaps"][:2])
    assert all(g[1] >= 1e-6 for g in out["idle_gaps"])


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    """The CPU backend writes host lines only: nothing is read from
    them under a device metric's name."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jnp.ones(8) + 1)
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert path is not None and tr.load(path) is None
