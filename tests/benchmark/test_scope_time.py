"""Device seconds by the program's scopes: ``benchmark/scope_time.py``
and the two readers that came with it, on a synthetic trace."""

import glob
import json
import os
import types

import pytest

from benchmark import scope_time
from benchmark import trace_reduce as tr
from benchmark.lookup import load_module
from tests.benchmark.test_program_spans import _call, _record
from tests.benchmark.test_rehearsal import last_line, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REAL_TABLES = scope_time._tables
NEW = ["decode_mixer_ms_per_token", "decode_experts_ms_per_token",
       "decode_moe_overhead_ms_per_token", "decode_dense_ms_per_token",
       "decode_unscoped_share", "prefill_mixer_share",
       "prefill_group_move_share", "prefill_unscoped_share",
       "generate_prefill_run_share", "fit_step_hist_ms",
       "fit_step_predict_ms", "fit_step_unscoped_share"]

# two programs that both have a fusion.1 and a while.2, each under
# another scope; the chip names an op by its whole HLO line
GENERATE = {"fusion.1": "lm.moe.experts", "while.2": "lm.mla",
            "fusion.3": "lm.mla.decode", "copy.4": None,
            "fusion.5": "lm.moe.route", "fusion.6": "lm.moe.shared",
            "fusion.7": "lm.sample"}
PREFILL = {"fusion.1": "lm.group", "while.2": "lm.gdn", "fusion.3": None}
CHECK = {"fusion.1": "lm.head"}         # another shape of lm_generate


def _ops(base, names):
    return [(f"%{n} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop",
             base + s, base + e) for n, s, e in names]


def _trace():
    """``lm_prefill`` 10.0-11.0 (busy 0.9: a gap of 0.1), then
    ``lm_generate`` 11.0-13.0 with a while of 1.2 s that holds two
    fusions and its own 0.2 s, a second ``lm_generate`` of another
    shape, and an op outside any module."""
    ops = (_ops(10.0, [("fusion.1", 0.0, 0.3), ("while.2", 0.3, 0.8),
                       ("fusion.3", 0.4, 0.6), ("fusion.9", 0.9, 1.0)])
           + _ops(11.0, [("fusion.1", 0.0, 0.5), ("while.2", 0.5, 1.7),
                         ("fusion.3", 0.6, 1.0), ("fusion.5", 1.0, 1.3),
                         ("fusion.6", 1.3, 1.5), ("copy.4", 1.7, 1.8),
                         ("fusion.7", 1.8, 2.0)])
           + _ops(14.0, [("fusion.1", 0.0, 0.25)])
           + _ops(15.0, [("fusion.1", 0.0, 0.5)]))
    dev = tr.DeviceTrace(plane="/device:TPU:0", ops=ops, modules=[
        ("jit_lm_prefill(7)", 10.0, 11.0), ("jit_lm_generate(8)", 11.0, 13.0),
        ("jit_lm_generate(9)", 14.0, 14.25), ("jit_other(3)", 15.0, 15.5)])
    return tr.Trace(devices=[dev], annotations=[])


@pytest.fixture
def ctx(monkeypatch):
    asked = []

    def tables():
        asked.append(1)
        return {"jit_lm_prefill": [PREFILL],
                "jit_lm_generate": [CHECK, GENERATE]}, 0.25

    monkeypatch.setattr(scope_time, "_tables", tables)
    monkeypatch.setattr(scope_time, "_memo", {})
    facts = []
    call = _call("transform_call", 100.0, 104.0)
    call.work = {"rows": 4, "new_tokens": 4 * 11, "trees": 2}
    return types.SimpleNamespace(
        trace=_trace(), traced_calls=[call], asked=asked, facts=facts,
        emit=lambda **f: facts.append(f), window_calls=lambda: [call])


HLO = """HloModule jit_f, entry_computation_layout={()->f32[]}

%fused_a (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/lm.moe/lm.moe.experts/mul"}
}

%body (c: f32[4]) -> f32[4] {
  %c = f32[4]{0} parameter(0)
  %fusion.2 = f32[4]{0} fusion(%c), kind=kLoop, calls=%fused_a
  %copy.3 = f32[4]{0} copy(%fusion.2)
  %exp.12 = f32[4]{0} exponential(%c), metadata={op_name="jit(f)/while/body/lm.moe/lm.moe.route/exp"}
  ROOT %add.4 = f32[4]{0} add(%copy.3, %c), metadata={op_name="jit(f)/while/body/vmap(lm.moe)/add"}
}

%cond (c: f32[4]) -> pred[] {
  %c.1 = f32[4]{0} parameter(0)
  ROOT %lt.5 = pred[] constant(true), metadata={op_name="jit(f)/while/cond/lt"}
}

%left (a: f32[4]) -> f32[4] {
  ROOT %neg.6 = f32[4]{0} negate(%a), metadata={op_name="jit(f)/gbdt.hist/gbdt.hist.feed/neg"}
}

%right (b: f32[4]) -> f32[4] {
  ROOT %abs.7 = f32[4]{0} abs(%b), metadata={op_name="jit(f)/cond/HybridLM.hidden/abs"}
}

%layers (d: f32[4]) -> f32[4] {
  %while.13 = f32[4]{0} while(%d), condition=%cond, body=%body
  %sin.14 = f32[4]{0} sine(%d), metadata={op_name="jit(f)/while/body/lm.gdn/sin"}
  ROOT %cos.15 = f32[4]{0} cosine(%sin.14), metadata={op_name="jit(f)/while/body/lm.mlp/cos"}
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.8 = f32[4]{0} while(%x), condition=%cond, body=%body
  %while.16 = f32[4]{0} while(%x), condition=%cond, body=%layers
  %conditional.9 = f32[4]{0} conditional(%x, %while.8, %while.8), branch_computations={%left, %right}
  %call.10 = f32[4]{0} call(%x), to_apply=%right
  ROOT %custom-call.11 = f32[4]{0} custom-call(%conditional.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jit(main)/lm.mla/lm.mla.decode/latent_decode"}
}
"""


def test_the_table_of_a_written_module():
    pattern = scope_time.scope_pattern(("gbdt", "lm"))
    assert scope_time.scope_table(HLO, pattern) == {
        "p": None, "mul.1": "lm.moe.experts",
        "c": None, "fusion.2": "lm.moe.experts",    # by its body
        "copy.3": None,                             # XLA's own, nothing called
        "exp.12": "lm.moe.route", "add.4": "lm.moe", "c.1": None,
        "lt.5": None,
        "neg.6": "gbdt.hist.feed", "abs.7": None,   # a module's name is no scope
        "sin.14": "lm.gdn", "cos.15": "lm.mlp",
        "x": None,
        # experts (through the fusion in its body), route and lm.moe
        # itself agree on lm.moe
        "while.8": "lm.moe", "while.13": "lm.moe",
        "while.16": None,           # a scan over whole layers: they disagree
        "conditional.9": "gbdt.hist.feed",          # a branch's
        "call.10": None,                            # nothing inside has one
        "custom-call.11": "lm.mla.decode"}          # the innermost, in full
    assert scope_time.scope_of("jit(f)/transpose(jvp(lm.gdn))/mul",
                               pattern) == "lm.gdn"
    assert scope_time.scope_of("jit(f)/realm.x/film.y/mul", pattern) is None
    # a family the layer files do not ask for is no scope
    only_lm = scope_time.scope_pattern(("lm",))
    assert scope_time.scope_table(HLO, only_lm)["conditional.9"] is None


def test_the_scope_a_set_of_scopes_shares():
    shared = scope_time.shared_scope
    assert shared({"lm.mla.decode"}) == "lm.mla.decode"
    assert shared({"lm.mla", "lm.mla.decode", "lm.mla.write"}) == "lm.mla"
    assert shared({"lm.moe.route", "lm.moe.dispatch"}) == "lm.moe"
    assert shared({"lm.mla", "lm.mlp"}) is None     # a family is no scope
    assert shared({"lm.mla", "gbdt.hist"}) is None


def test_the_families_are_the_layer_files_and_the_tables_the_programs(
        monkeypatch):
    from mmlspark_tpu.core import scopes

    assert scope_time.families() == ("gbdt", "lm")
    monkeypatch.setattr(scopes, "hlo_texts",
                        lambda: {"jit_f": [HLO, HLO.replace("lm.", "xy.")]})
    tables, seconds = scope_time._tables()
    first, second = tables["jit_f"]
    assert first["while.8"] == "lm.moe" and second["while.8"] is None
    assert second["neg.6"] == "gbdt.hist.feed" and seconds >= 0.0


def test_exclusive_seconds_partition_the_union():
    ops = [("a", 0.0, 1.0), ("b", 0.2, 0.5), ("c", 0.3, 0.4), ("b", 0.6, 0.7),
           ("d", 2.0, 2.5)]
    spent = scope_time.exclusive_seconds(ops)
    assert spent == pytest.approx({"a": 0.6, "b": 0.3, "c": 0.1, "d": 0.5})
    assert sum(spent.values()) == pytest.approx(
        tr.union_length((s, e) for _, s, e in ops))
    assert scope_time.instruction(
        "%fusion.17 = f32[8]{0} fusion(%p), calls=%f") == "fusion.17"
    assert scope_time.instruction("fusion.2") == "fusion.2"


def test_each_op_goes_to_its_own_modules_table_and_the_scopes_partition(ctx):
    generate = scope_time.by_scope(ctx, "lm_generate")
    prefill = scope_time.by_scope(ctx, "lm_prefill")
    dev = ctx.trace.device(0)
    # the seconds by scope sum to the modules' busy time
    assert generate["device_s"] == pytest.approx(
        dev.busy_s(11.0, 13.0) + dev.busy_s(14.0, 14.25)) == pytest.approx(2.25)
    assert prefill["device_s"] == pytest.approx(dev.busy_s(10.0, 11.0))
    assert sum(generate["scopes"].values()) == pytest.approx(2.25)
    # fusion.1 is the experts' in lm_generate(8), the head's in the other
    # shape (9: the table that knows its ops) and the groups' in lm_prefill
    assert generate["scopes"] == pytest.approx({
        "lm.moe.experts": 0.5, "lm.mla": 0.3, "lm.mla.decode": 0.4,
        "lm.moe.route": 0.3, "lm.moe.shared": 0.2, "lm.sample": 0.2,
        scope_time.UNSCOPED: 0.1, "lm.head": 0.25})
    assert prefill["scopes"] == pytest.approx({
        "lm.group": 0.3, "lm.gdn": 0.3, scope_time.UNSCOPED: 0.2,
        scope_time.UNLISTED: 0.1})
    # the program was asked once, and a fact a distinct module says what
    # was found
    scope_time.by_scope(ctx, "lm_generate")
    assert ctx.asked == [1]
    assert [f["scope_time"] for f in ctx.facts] == [
        "jit_lm_generate(8)", "jit_lm_generate(9)", "jit_lm_prefill(7)"]
    fact = ctx.facts[-1]
    assert fact["not_in_table_share"] == pytest.approx(0.1 / 0.9)
    assert fact["unscoped_ops_s"] == [("fusion.3 f32[8]{0}",
                                       pytest.approx(0.2))]
    assert fact["by_scope_s"][scope_time.UNLISTED] == pytest.approx(0.1)
    assert set(fact) == {"scope_time", "device_s", "by_scope_s",
                         "not_in_table_share", "unscoped_ops_s", "tables_s"}
    assert fact["tables_s"] == 0.25


def test_the_readers_parameters_partition_a_module(ctx):
    read = load_module("readers", "scope_time").read
    step = {"match": "lm_generate", "per": "new_tokens", "a_row": True,
            "less": 1}
    steps = 10                                     # 11 tokens a row less one
    mixer = read(ctx, dict(step, scopes=["lm.retention", "lm.gdn", "lm.mla"]))
    experts = read(ctx, dict(step, scopes=["lm.moe.experts"]))
    overhead = read(ctx, dict(step, scopes=["lm.moe"],
                              exclude=["lm.moe.experts", "lm.moe.shared"]))
    dense = read(ctx, dict(step, scopes=["lm.mlp", "lm.moe.shared", "lm.head",
                                         "lm.sample"]))
    unscoped = read(ctx, {"match": "lm_generate", "scopes": [],
                          "unscoped": True, "per": "share"})
    assert mixer == pytest.approx(1000 * 0.7 / steps)     # .decode included
    assert experts == pytest.approx(1000 * 0.5 / steps)
    assert overhead == pytest.approx(1000 * 0.3 / steps)
    assert dense == pytest.approx(1000 * 0.65 / steps)
    assert unscoped == pytest.approx(100 * 0.1 / 2.25)
    assert (mixer + experts + overhead + dense) * steps / 1000 + 0.1 \
        == pytest.approx(2.25)
    # a prefix covers whole components only
    assert read(ctx, dict(step, scopes=["lm.m"])) == 0.0
    assert read(ctx, {"match": "lm_prefill", "scopes": ["lm.group"],
                      "per": "share"}) == pytest.approx(100 * 0.3 / 0.9)
    # a unit that is not counted a row
    assert read(ctx, {"match": "lm_prefill", "scopes": ["lm.gdn"],
                      "per": "trees"}) == pytest.approx(1000 * 0.3 / 2)


def test_nothing_to_join_gives_none(ctx, monkeypatch):
    read = load_module("readers", "scope_time").read
    params = {"match": "lm_generate", "scopes": ["lm.mla"], "per": "share"}
    assert read(ctx, dict(params, match="no_such_program")) is None
    assert read(ctx, dict(params, match="jit_other")) is None   # no table
    assert read(ctx, params) is not None
    # no program registered
    monkeypatch.setattr(scope_time, "_memo", {})
    monkeypatch.setattr(scope_time, "_tables", lambda: ({}, 0.0))
    assert read(ctx, params) is None
    # no trace (the CPU rehearsal), no traced call
    monkeypatch.setattr(scope_time, "_memo", {})
    assert read(types.SimpleNamespace(trace=None, traced_calls=[1]),
                params) is None
    ctx.traced_calls = []
    assert read(ctx, params) is None


def test_a_program_that_hands_out_no_texts_gives_none(ctx, monkeypatch):
    """A parent commit has no ``core/scopes.py``."""
    import sys

    import mmlspark_tpu.core
    from mmlspark_tpu.core import scopes  # noqa: F401  (so it can go)

    monkeypatch.setattr(scope_time, "_tables", REAL_TABLES)
    monkeypatch.setitem(sys.modules, "mmlspark_tpu.core.scopes", None)
    monkeypatch.delattr(mmlspark_tpu.core, "scopes")
    read = load_module("readers", "scope_time").read
    assert read(ctx, {"match": "lm_generate", "scopes": ["lm.mla"],
                      "per": "share"}) is None
    assert ctx.facts == []


def test_root_count_share_over_the_windows_calls(monkeypatch):
    from mmlspark_tpu.core.logging_utils import SINK

    read = load_module("readers", "root_count_share").read
    params = {"count": "prefill_visits_run", "of": "prefill_visits"}
    records = [_record(0.0, [], "CausalLM"), _record(2.0, [], "CausalLM"),
               _record(4.0, [], "CausalLM")]
    records[0]["counts"] = {"prefill_visits": 64, "prefill_visits_run": 36}
    records[1]["counts"] = {"prefill_visits": 16, "prefill_visits_run": 4}
    records[2]["counts"] = {"prefill_visits": 16, "prefill_visits_run": 16}
    monkeypatch.setattr(SINK, "events", records)
    calls = [_call("transform_call", 0.0, 1.5),
             _call("transform_call", 2.0, 3.5)]
    ctx = types.SimpleNamespace(window_calls=lambda: calls)
    assert read(ctx, params) == pytest.approx(100 * 40 / 80)
    for record in records:
        del record["counts"]["prefill_visits"]      # a program without it
    assert read(ctx, params) is None


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_is_an_entry_a_file_and_a_reader(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["better"] == "lower" and entry["workloads"]
    with open(os.path.join(ROOT, "benchmark", "layers", name + ".json")) as f:
        spec = json.load(f)
    reader = load_module("readers", spec["reader"])
    assert callable(reader.read) and "layer file" in reader.__doc__
    if spec["reader"] == "scope_time":
        assert entry["source"] == "device_trace"
        assert set(spec["params"]) <= {"match", "scopes", "exclude",
                                       "unscoped", "per", "a_row", "less"}
        assert entry["unit"] == ("%" if spec["params"]["per"] == "share"
                                 else "ms")
    else:
        assert entry["source"] == "program_counter"


def test_every_layer_file_is_an_entry_and_every_entry_a_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(NEW) <= {m["name"] for m in bench["per_layer"]}
    files = {os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(ROOT, "benchmark", "layers", "*.json"))}
    assert files == {m["name"] for m in bench["per_layer"]}


@pytest.mark.parametrize("workload", ["tiny_kimi.reason", "tiny_gbdt.fit"])
def test_a_rehearsal_prints_none_of_the_device_keys(workload):
    """On the CPU there is no device plane: the scope reader finds
    nothing and no ``cpu.`` number stands under its metrics' names; the
    root's counts are the program's own and do appear."""
    proc = run_cell(workload, 1)
    result = last_line(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    counted = {"cpu.generate_prefill_run_share"}
    assert not ({"cpu." + n for n in NEW} - counted) & set(result["metrics"])
    assert '"scope_time"' not in proc.stdout
    if workload == "tiny_kimi.reason":
        share = result["metrics"]["cpu.generate_prefill_run_share"]
        assert share["unit"] == "%" and 0.0 < share["value"] <= 100.0
