"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, runner or metric is
a file found by name (see README.md); this file holds no table of any
of them. It sets up, opens a measured window of ``--seconds``, lets the
cell's runner drive the system under test through its public entry
points, checks the outputs by value, and prints ONE JSON object as the
last line of stdout: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``). Earlier lines are
JSON facts about the run (compile count in the window among them).

A real cell needs the TPU and the chips it names: anything else exits 2
and prints no result. Cells under ``rehearsal/cells/`` are tiny copies
for a CPU rehearsal; their metric keys carry the platform as a prefix
(``cpu.``) so a CPU number can never stand under a device metric's name.
"""

import time

_T0 = time.perf_counter()  # process start, as near as Python allows

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lookup import HERE, load_json, load_module  # noqa: E402

# host annotations the benchmark itself writes into a trace; an idle gap
# is named by the innermost of these that covers it
ANNOTATIONS = ("fit", "transform_call", "between_calls", "traced_part")


def emit(**facts):
    print(json.dumps(facts, default=str), flush=True)


class Call:
    """One timed call into the system under test."""

    def __init__(self, name, work):
        self.name, self.work = name, dict(work)
        self.start = self.end = None
        self.phases = {}      # host spans the program reported for it
        self.in_window = False

    @property
    def seconds(self):
        return self.end - self.start


class Context:
    """What a runner is given: the cell, its configuration, the seed,
    and the few verbs of a run (window, timed call, traced part, fail).
    """

    def __init__(self, cell_name, cell, config, args, devices,
                 runtime_init_s):
        self.cell_name, self.cell, self.config = cell_name, cell, config
        self.runtime_init_s = runtime_init_s
        self.seed, self.seconds = args.seed, args.seconds
        self.trace_on = bool(args.trace)
        self.devices = devices
        self.platform = devices[0].platform
        self.device_kind = devices[0].device_kind
        self.calls = []            # every Call, window or not
        self.reasons = []          # why correct is false
        self.counters = {}         # what the runner counted
        self.compiles = []         # (perf_counter at end, seconds, hit)
        self.setup_s = None
        self._open_at = self._closed_at = None
        self.trace = None          # trace_reduce.Trace of the traced part
        self.traced_calls = []     # Calls made inside the traced part
        self._tracing, self._trace_dir = False, None

    # -- logging and verdict ------------------------------------------
    emit = staticmethod(emit)

    def fail(self, reason):
        self.reasons.append(reason)
        emit(incorrect=reason)

    def check(self, cond, reason):
        if not cond:
            self.fail(reason)
        return bool(cond)

    # -- the window ---------------------------------------------------
    @staticmethod
    def since_start():
        return time.perf_counter() - _T0

    def open_window(self):
        """Set-up ends here. It began at process start and leaves out
        only the seconds inside the first ``jax.devices()``: the TPU
        runtime's own start-up (printed as ``runtime_init_s``) drifts by
        seconds over one machine's life and no change to the program
        can move it. Imports, data, loading, warm-up and compiles are
        all on the clock."""
        self._open_at = time.perf_counter()
        self.setup_s = self._open_at - _T0 - self.runtime_init_s

    def window_open(self):
        return time.perf_counter() - self._open_at < self.seconds

    def close_window(self):
        self._closed_at = time.perf_counter()

    @contextlib.contextmanager
    def call(self, name, **work):
        """Time one call; the body must end in a host sync."""
        import jax
        call = Call(name, work)
        call.in_window = (self._open_at is not None
                          and self._closed_at is None)
        with jax.profiler.TraceAnnotation(name):
            call.start = time.perf_counter()
            yield call
            call.end = time.perf_counter()
        self.calls.append(call)
        if self._tracing:
            self.traced_calls.append(call)

    @contextlib.contextmanager
    def annotate(self, name):
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield

    def window_calls(self):
        return [c for c in self.calls if c.in_window]

    def compiles_in_window(self, hit=False):
        """Seconds of each backend compile that ended in the window;
        with ``hit``, of each read from the persistent cache instead."""
        return [s for t, s, h in self.compiles
                if h == hit and self._open_at <= t <= self._closed_at]

    def compile_seconds_in_setup(self):
        """Compiling and reading compiled programs back, before the
        window."""
        return sum(s for t, s, _ in self.compiles if t < self._open_at)

    # -- the traced part ----------------------------------------------
    @contextlib.contextmanager
    def traced(self):
        """Profile the body. The trace is reduced after the runner has
        returned (``reduce_trace``), so the body may lie in the window."""
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._trace_dir)
        self._tracing = True
        try:
            with jax.profiler.TraceAnnotation("traced_part"):
                yield
        finally:
            self._tracing = False
            jax.profiler.stop_trace()

    def reduce_trace(self):
        """Read the traced part's ``.xplane.pb`` and remove the (fresh,
        under ``TMPDIR``) directory it was written to."""
        from benchmark import trace_reduce
        if self._trace_dir is None:
            return
        try:
            path = trace_reduce.find_xplane(self._trace_dir)
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if path and keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(path, os.path.join(
                    keep, f"{self.cell_name}.xplane.pb"))
            self.trace = (trace_reduce.load(path, ANNOTATIONS)
                          if path else None)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


def listen_for_compiles(ctx):
    """Record what each backend compile cost, when it ended, and
    whether the persistent cache served it. JAX's duration event wraps
    the cache look-up (``compiler.compile_or_get_cached``), so it fires
    for a program read back as for one compiled; the cache's own hit
    event fires inside it, on the same thread, and tells them apart."""
    import jax.monitoring
    last = threading.local()

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            last.hit = True

    def on_duration(event, duration, **_):
        if event.endswith("backend_compile_duration"):
            ctx.compiles.append((time.perf_counter(), float(duration),
                                 getattr(last, "hit", False)))
            last.hit = False

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def find_cell(name):
    """``(cell, is_rehearsal)``; real cells win over rehearsal ones."""
    for sub, rehearsal in (("cells", False),
                           (os.path.join("rehearsal", "cells"), True)):
        if os.path.exists(os.path.join(HERE, sub, name + ".json")):
            return load_json(sub, name + ".json"), rehearsal
    raise SystemExit(f"benchmark: no cell file for workload {name!r} under "
                     f"{HERE}/cells or {HERE}/rehearsal/cells")


def metric_entries(cell_name, cell, rehearsal):
    """The metrics this cell reports, from BENCHMARK.json: every
    end-to-end metric the cell's file lists, and every per-layer metric
    whose ``workloads`` key is absent or names the cell. A rehearsal
    cell reports what the cell it ``stands_for`` reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    real = cell.get("stands_for", cell_name) if rehearsal else cell_name
    wanted = set(cell["end_to_end"])
    e2e = [m for m in bench["end_to_end"] if m["name"] in wanted]
    missing = wanted - {m["name"] for m in e2e}
    if missing:
        raise SystemExit(f"benchmark: cell {cell_name!r} lists end-to-end "
                         f"metrics BENCHMARK.json lacks: {sorted(missing)}")
    layer = [m for m in bench["per_layer"]
             if "workloads" not in m or real in m["workloads"]]
    return e2e, layer


def read_metric(kind, entry, ctx):
    """Run the metric's reader over the run's records. ``None`` where
    the reader found nothing to read: the metric is then left out."""
    spec = load_json(kind, entry["name"] + ".json")
    reader = load_module("readers", spec["reader"])
    value = reader.read(ctx, spec.get("params", {}))
    return None if value is None else float(value)


def device_memory_peak(device):
    """``(live, reserved)`` peak bytes of the chip's memory: the peak
    of the process's live buffers (``peak_bytes_in_use``) and the peak
    of what the runtime set aside for running programs' scratch (XLA's
    temporaries: ``peak_bytes_reserved``). On the TPU the allocator
    counts the two apart, in regions that do not overlap (PERF.md §6),
    and a program runs only while both are held, so a chip's peak is
    their sum. ``None`` where the backend has no memory statistics
    (the CPU)."""
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return None
    return (int(stats["peak_bytes_in_use"]),
            int(stats.get("peak_bytes_reserved", 0)))


def device_block(ctx):
    peaks = [p for p in map(device_memory_peak, ctx.devices) if p is not None]
    live, reserved = max(peaks, key=sum) if peaks else (None, None)
    block = {"platform": ctx.platform, "kind": ctx.device_kind,
             "count": len(ctx.devices),
             "memory_peak_bytes": live + reserved if peaks else None,
             "memory_live_peak_bytes": live,
             "memory_reserved_peak_bytes": reserved}
    if ctx.trace is not None:
        from benchmark import trace_reduce
        block["busy_s"], block["window_s"] = (
            trace_reduce.busy_and_window(ctx.trace))
    return block


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, rehearsal = find_cell(args.workload)
    config_dir = (os.path.join("rehearsal", "configs") if rehearsal
                  else "configs")
    config = load_json(config_dir, cell["config"] + ".json")
    e2e_entries, layer_entries = metric_entries(args.workload, cell,
                                                rehearsal)

    import jax

    from mmlspark_tpu.core.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    # the program persists only compiles of 0.5 s and more. ops/ingest.py
    # jits a fresh concatenate on every fit, which no warm-up can keep in
    # memory; it compiles in 0.6 s, so whether the window's fit reads it
    # back or compiles it again would hang on that threshold. With every
    # compile persisted it is always read back.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    imported = time.perf_counter()
    devices = jax.devices()
    runtime_init_s = time.perf_counter() - imported
    platform = devices[0].platform
    if not rehearsal and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"benchmark: cell {args.workload!r} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} device(s) of platform "
              f"{platform!r}. Cells under rehearsal/cells/ run anywhere.",
              file=sys.stderr)
        return 2

    ctx = Context(args.workload, cell, config, args, devices, runtime_init_s)
    listen_for_compiles(ctx)
    emit(start=args.workload, import_s=imported - _T0,
         runtime_init_s=runtime_init_s, seed=args.seed,
         seconds=args.seconds, trace=args.trace, rehearsal=rehearsal,
         platform=platform,
         device_kind=ctx.device_kind, device_count=len(devices),
         jax=jax.__version__, compile_cache_dir=cache_dir)

    load_module("runners", cell["runner"]).run(ctx)
    ctx.reduce_trace()

    window = ctx.window_calls()
    in_window = ctx.compiles_in_window()
    read_back = ctx.compiles_in_window(hit=True)
    emit(setup_s=ctx.setup_s, window_calls=len(window),
         window_s=ctx._closed_at - ctx._open_at,
         compiles_total=sum(not h for _, _, h in ctx.compiles),
         cache_reads_total=sum(h for _, _, h in ctx.compiles),
         compile_s_total=sum(s for _, s, _ in ctx.compiles),
         compiles_in_window=len(in_window),
         compile_s_in_window=sum(in_window),
         cache_reads_in_window=len(read_back),
         cache_read_s_in_window=sum(read_back), counters=ctx.counters,
         memory_stats=devices[0].memory_stats())
    # where a run reads far off, these say which call and which phase
    phases = {}
    for c in window:
        for name, seconds in c.phases.items():
            phases[name] = phases.get(name, 0.0) + seconds
    emit(window_call_s=[round(c.seconds, 4) for c in window],
         window_phase_s=phases)
    ctx.check(len(window) >= 1, "no call completed in the window")

    prefix = f"{platform}." if rehearsal else ""
    entries = layer_entries if args.trace else e2e_entries
    kind = "layers" if args.trace else "end_to_end"
    metrics = {}
    for entry in entries:
        value = read_metric(kind, entry, ctx)
        if value is not None:
            metrics[prefix + entry["name"]] = {"value": value,
                                               "unit": entry["unit"]}
    result = {"correct": not ctx.reasons, "attempted": len(window),
              "failed": ctx.counters.get("failed_calls", 0),
              "metrics": metrics, "device": device_block(ctx)}
    if args.trace and ctx.trace is not None:
        from benchmark import trace_reduce
        result["breakdown"] = trace_reduce.breakdown(ctx.trace, ANNOTATIONS)
    if ctx.reasons:
        emit(incorrect_because=ctx.reasons)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
