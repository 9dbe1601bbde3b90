"""The one command, end to end, on the CPU at tiny size.

Each rehearsal is a process of its own (a benchmark run owns its JAX);
they are the slowest tests here, a few seconds each.
"""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
REHEARSALS = sorted(
    os.path.basename(p)[:-len(".json")]
    for p in glob.glob(os.path.join(BENCH, "rehearsal", "cells", "*.json")))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(workload, trace, run_py=None, devices=1, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=ROOT, BENCH_RUN="ignored-by-the-benchmark")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, run_py or os.path.join(BENCH, "run.py"),
         "--workload", workload, "--seed", "3000000019", "--seconds", "1",
         "--trace", str(trace)],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def cell_file(workload, sub="rehearsal/cells"):
    with open(os.path.join(BENCH, sub, workload + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", REHEARSALS)
def test_rehearsal_prints_the_contracts_line(workload, trace):
    cell = cell_file(workload)
    proc = run_cell(workload, trace, devices=cell.get("devices", 1))
    result = last_line(proc)
    assert set(result) - {"breakdown"} == RESULT_KEYS
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # a CPU number never stands under a device metric's name
    assert result["metrics"], "no metric reported"
    assert all(k.startswith("cpu.") for k in result["metrics"])
    assert all(set(v) == {"value", "unit"} for v in result["metrics"].values())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    kind = "per_layer" if trace else "end_to_end"
    allowed = {"cpu." + m["name"] for m in bench[kind]}
    assert set(result["metrics"]) <= allowed
    if not trace:
        assert set(result["metrics"]) == {"cpu." + n
                                          for n in cell["end_to_end"]}
    # check (a) read the platform and the formulation the rehearsal
    # cell's own file expects, and no compile fell into the window
    assert cell["correct"]["platform"] == "cpu"
    facts = [json.loads(line) for line in proc.stdout.splitlines()[:-1]
             if line.startswith("{")]
    window = next(f for f in facts if "compiles_in_window" in f)
    assert window["compiles_in_window"] == 0


def test_a_program_read_back_from_the_cache_is_not_counted_as_a_compile(
        tmp_path):
    """JAX's backend-compile event fires for a cache hit too; the
    benchmark tells the two apart, or a warm run could never report
    ``compiles_in_window`` 0 where the program re-jits in the window."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    totals = []
    for _ in range(2):
        proc = run_cell("tiny_resnet.transform", 0, extra_env=env)
        assert last_line(proc)["correct"] is True
        facts = [json.loads(line) for line in proc.stdout.splitlines()[:-1]
                 if line.startswith("{")]
        totals.append(next(f for f in facts if "compiles_total" in f))
    cold, warm = totals
    assert cold["compiles_total"] >= 1 and cold["cache_reads_total"] == 0
    assert warm["compiles_total"] == 0
    assert warm["cache_reads_total"] == cold["compiles_total"]


def test_a_real_cell_on_the_cpu_exits_nonzero_and_prints_no_result():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)["workloads"][0]["name"]
    proc = run_cell(real, 0)
    assert proc.returncode != 0
    assert "cpu" in proc.stderr and "TPU" in proc.stderr
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    """Copy the benchmark, then ADD a configuration, a cell, a runner, a
    reader and a per-layer metric as new files and new BENCHMARK.json
    entries; no file that was there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "cache"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}

    b = root / "benchmark"
    (b / "rehearsal" / "configs" / "throwaway.json").write_text(json.dumps(
        {"name": "throwaway", "builder": "higgs_gbdt"}))
    (b / "rehearsal" / "cells" / "throwaway.noop.json").write_text(json.dumps(
        {"config": "throwaway", "runner": "noop", "chips": 1,
         "stands_for": "throwaway.noop", "why": "test",
         "end_to_end": ["noop_calls_per_s", "setup_s"], "traffic": {},
         "correct": {}}))
    (b / "runners" / "noop.py").write_text(
        "def run(ctx):\n"
        "    ctx.open_window()\n"
        "    while True:\n"
        "        with ctx.call('noop', calls=1):\n"
        "            ctx.counters['seen'] = ctx.counters.get('seen', 0) + 1\n"
        "        if not ctx.window_open():\n"
        "            break\n"
        "    ctx.close_window()\n")
    (b / "readers" / "counter_value.py").write_text(
        "def read(ctx, params):\n"
        "    return ctx.counters.get(params['counter'])\n")
    (b / "end_to_end" / "noop_calls_per_s.json").write_text(json.dumps(
        {"reader": "call_rate", "params": {"work": "calls"}}))
    (b / "layers" / "noop_seen.json").write_text(json.dumps(
        {"reader": "counter_value", "params": {"counter": "seen"}}))
    bench["end_to_end"].append(
        {"name": "noop_calls_per_s", "unit": "calls/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["throwaway.noop"]})
    bench["per_layer"].append(
        {"name": "noop_seen", "unit": "calls", "better": "higher",
         "source": "program_counter", "layer": "noop",
         "moves": "noop_calls_per_s", "workloads": ["throwaway.noop"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    run_py = str(b / "run.py")
    end = last_line(run_cell("throwaway.noop", 0, run_py=run_py))
    assert end["correct"] is True
    assert set(end["metrics"]) == {"cpu.noop_calls_per_s", "cpu.setup_s"}
    layer = last_line(run_cell("throwaway.noop", 1, run_py=run_py))
    assert layer["metrics"]["cpu.noop_seen"]["value"] == layer["attempted"]
    # per-layer metrics of other cells found nothing to read or do not
    # list this cell: none of them is on the line
    assert set(layer["metrics"]) <= {"cpu.noop_seen", "cpu.setup_compile_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before
