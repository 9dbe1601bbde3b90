"""Benchmark: HIGGS-scale LightGBM-parity binary classification fit.

Prints one JSON line per metric ({"metric", "value", "unit",
"vs_baseline"}): the fit-throughput row, then a transform-throughput
row for batch scoring through the shard-rules engine (recording the
resolved sharding mode).

Config mirrors the HIGGS-style setup BASELINE.md tracks (28 features,
binary label, 255 bins, 63 leaves / depth 6) at 2M rows x 100 trees.
Throughput unit: million (rows x trees) per second of ``train()`` wall
clock, steady state (second call; compiled executables and the
persistent XLA cache warm, as a fitted production pipeline would be).

``vs_baseline`` divides by a MEASURED comparator: sklearn 1.9
HistGradientBoostingClassifier (the same histogram-GBDT algorithm
family the reference wraps) on this machine's CPU, same data/config:
2M rows x 100 trees in 61.3s = 3.263 Mrow-trees/s (measured 2026-07-29,
single-core container). The previous rounds' invented 2.0 anchor is
retired per the round-2 verdict.
"""

import json
import os
import sys
import time

import numpy as np

BASELINE_MROW_TREES_S = 3.263  # measured: sklearn HistGBDT, this host


def device_stamp():
    """Initialise JAX in THIS process (one process per chip: nothing
    probes the backend from a child first) and name the device every
    row of this run is stamped with. Landing on the CPU is an error
    unless ``JAX_PLATFORMS=cpu`` asked for it — with libtpu installed
    and no chip JAX only warns and carries on, and a CPU number must
    never appear as if a device had produced it."""
    import jax
    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "device_kind": devices[0].device_kind,
             "device_count": len(devices)}
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if stamp["platform"] == "cpu" and not asked_cpu:
        print(f"bench: no accelerator — JAX initialised {stamp}; set "
              "JAX_PLATFORMS=cpu to run a CPU rehearsal on purpose",
              file=sys.stderr, flush=True)
        sys.exit(2)
    print(f"# backend up: {stamp}", file=sys.stderr, flush=True)
    return stamp


def peak_rss_mb():
    """Process-wide peak RSS in MB (ru_maxrss is KB on Linux) — stamped
    into every fit-throughput row so memory regressions are visible in
    the artifact, and the headline number for the --ooc row (whose
    whole process IS the streamed fit)."""
    import resource
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                 / 1024.0, 1)


def _resilience_counters():
    """(stalls, recoveries) observed so far — stamped into fit rows so
    a run that survived a watchdog abort or dp-shrink is attributable."""
    from mmlspark_tpu.parallel import resilience
    return resilience.stall_count(), resilience.recovery_count()


def main():
    stamp = device_stamp()
    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    from mmlspark_tpu.models.gbdt.trainer import TrainConfig, train
    from mmlspark_tpu.ops.binning import BinMapper

    enable_persistent_cache()

    rng = np.random.default_rng(0)
    # BENCH_ROWS: rehearsal/smoke override — the metric NAME changes
    # with it so a small run can never masquerade as the tracked config
    n = int(os.environ.get("BENCH_ROWS", 2_000_000))
    f = 28  # HIGGS-shaped
    num_trees = int(os.environ.get("BENCH_TREES", 100))
    x = rng.normal(size=(n, f)).astype(np.float32)
    logit = (x[:, 0] * 1.2 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
             + 0.3 * np.sin(x[:, 4] * 3))
    y = (logit + rng.normal(size=n) * 0.5 > 0).astype(np.float64)

    mapper = BinMapper.fit(x[:100_000], max_bin=255)
    binned = mapper.transform(x)
    bin_upper = mapper.bin_upper_values(255)
    cfg = TrainConfig(objective="binary", num_iterations=num_trees,
                      num_leaves=63, max_depth=6, min_data_in_leaf=20)

    # warmup/compile at identical shapes (second call reuses the cached
    # compiled step)
    train(binned, y, cfg, bin_upper=bin_upper)

    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    if profile_dir:
        # one profiled steady-state run for op-level attribution
        # (view with tensorboard or xprof; TPU-day triage shortcut)
        import jax
        jax.profiler.start_trace(profile_dir)
    t0 = time.perf_counter()
    result = train(binned, y, cfg, bin_upper=bin_upper)
    dt = time.perf_counter() - t0
    if profile_dir:
        jax.profiler.stop_trace()
        print(f"# trace written to {profile_dir}", file=sys.stderr)

    row_trees_per_s = n * result.booster.num_trees / dt / 1e6
    import jax
    suffix = (f"_rows{n}_trees{num_trees}"
              if n != 2_000_000 or num_trees != 100 else "")
    # kernel attribution (the r4->r5 regression was unattributable from
    # the artifact alone): the subtraction default and whether the
    # native library actually loaded — a throughput swing between
    # rounds must be explainable from these fields without rerunning
    # anything
    from mmlspark_tpu.models.gbdt.trainer import (
        native_histogram_available,
        resolve_subtract,
    )
    # graftsan attribution: whether the sanitizer was live during the
    # timed run (it syncs per boundary, so an accidentally-enabled
    # sanitizer must be visible in the artifact), plus the measured
    # per-call cost of a DISABLED boundary guard — the hook is on the
    # hot path unconditionally, so this number has to stay in the noise
    from mmlspark_tpu.core import sanitizer
    probe = np.zeros(4, np.float32)
    reps = 200_000
    t0 = time.perf_counter()
    for _ in range(reps):
        sanitizer.check_finite("bench.probe", probe)
    san_disabled_ns = ((time.perf_counter() - t0) / reps * 1e9
                       if not sanitizer.enabled() else None)
    # same attribution for the train watchdog: its step hooks sit on
    # the same hot path, so the disabled per-call cost is measured the
    # same way (and any stall/recovery during the timed fit must show)
    from mmlspark_tpu.parallel import resilience
    t0 = time.perf_counter()
    for _ in range(reps):
        resilience.step_start(0)
        resilience.step_end()
    wd_disabled_ns = (time.perf_counter() - t0) / reps * 1e9
    from mmlspark_tpu.core.env import env_float
    watchdog_mult = env_float("MMLSPARK_TPU_WATCHDOG_MULT", 0.0)
    print(json.dumps({
        "metric": "gbdt_fit_throughput_higgs28f_2M" + suffix,
        "value": round(row_trees_per_s, 3),
        "unit": "Mrow-trees/s",
        "vs_baseline": round(row_trees_per_s / BASELINE_MROW_TREES_S, 3),
        "backend": jax.default_backend(),
        **stamp,
        "hist_subtract": resolve_subtract("serial", 255),
        "native_hist_available": native_histogram_available(),
        # formulation/quant/EFB/grow-policy provenance from the timed
        # fit itself (result.hist_stats), not a re-resolution that
        # could disagree
        **{k: result.hist_stats.get(k)
           for k in ("hist_formulation", "tree_mode", "pallas_interpret",
                     "grow_policy", "hist_quant", "hist_shard",
                     "efb_bundles", "efb_bundled_features")},
        "graftsan_enabled": sanitizer.enabled(),
        "graftsan_disabled_overhead_ns": (
            round(san_disabled_ns, 1) if san_disabled_ns is not None
            else None),
        "watchdog_mult": watchdog_mult,
        "watchdog_disabled_overhead_ns": (
            round(wd_disabled_ns, 1) if watchdog_mult <= 0 else None),
        "train_stalls": resilience.stall_count(),
        "train_recoveries": resilience.recovery_count(),
        "peak_rss_mb": peak_rss_mb(),
        **{k: result.hist_stats.get(k) for k in ("ooc", "ooc_reason")},
    }))

    # transform-throughput row: steady-state batch scoring of the
    # fitted booster through the shard-rules engine (the same path
    # every model family's transform now routes through). The engine
    # resolves its placement from the attached mesh — none here, so the
    # row records the serial mode explicitly; a TPU-pod bench with a
    # mesh attached reports "rules" + dp without a code change.
    from mmlspark_tpu.parallel.shard_rules import ShardedScorer
    xs = x[:min(n, 1_000_000)]
    scorer = ShardedScorer(jax.jit(result.booster.predict_fn()), None,
                           family="gbdt", mesh=None, max_batch=65536,
                           label="bench_transform")
    scorer(xs[:65536])  # warm: compiles the rung the timed pass uses
    t0 = time.perf_counter()
    scorer(xs)
    dt_t = time.perf_counter() - t0
    xform_mrow_trees_s = (len(xs) * result.booster.num_trees
                          / dt_t / 1e6)
    print(json.dumps({
        "metric": "gbdt_transform_throughput_higgs28f" + suffix,
        "value": round(xform_mrow_trees_s, 3),
        "unit": "Mrow-trees/s",
        "vs_baseline": None,  # no measured external comparator yet
        "backend": jax.default_backend(),
        **stamp,
        "rows_scored": len(xs),
        "transform_s": round(dt_t, 3),
        **scorer.metadata(),
    }))

    # dl fit-throughput row: steady-state epochs/s of the deep text
    # fit loop — the sharded-training-state (MMLSPARK_TPU_TRAIN_SHARD)
    # + async-input-pipeline data point. The resolved mode, the
    # prefetch state, and the analytic optimizer-memory split ride in
    # the row so an A/B between rounds is attributable without a rerun.
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.dl.text import DeepTextClassifier
    from mmlspark_tpu.parallel.mesh import default_mesh
    dl_rows = int(os.environ.get("BENCH_DL_ROWS", 4096))
    dl_epochs = 2
    words = np.array(["alpha", "beta", "gamma", "delta", "epsilon",
                      "zeta", "eta", "theta", "iota", "kappa"])
    docs = rng.choice(words, size=(dl_rows, 12))
    dl_y = (docs == "alpha").sum(axis=1) > 1
    dl_df = DataFrame({"text": [" ".join(d) for d in docs],
                       "label": dl_y.astype(np.float64)})
    def dl_fit():
        return DeepTextClassifier(
            mesh=default_mesh(), batchSize=256, maxEpochs=dl_epochs,
            labelCol="label", textCol="text", maxLength=16,
            embeddingDim=32, numLayers=1, numHeads=2).fit(dl_df)
    dl_fit()  # warm: identical shapes, compiled step cached
    t0 = time.perf_counter()
    dl_model = dl_fit()
    dt_dl = time.perf_counter() - t0
    dl_meta = dl_model.shard_metadata()
    dl_suffix = f"_rows{dl_rows}" if dl_rows != 4096 else ""
    print(json.dumps({
        "metric": "dl_fit_throughput" + dl_suffix,
        "value": round(dl_rows * dl_epochs / dt_dl, 1),
        "unit": "rows/s",
        "vs_baseline": None,  # no measured external comparator yet
        "backend": jax.default_backend(),
        **stamp,
        "fit_s": round(dt_dl, 3),
        "epochs": dl_epochs,
        **{k: dl_meta.get(k)
           for k in ("train_shard", "train_shard_reason",
                     "train_shard_dp", "prefetch", "prefetch_depth",
                     "opt_state_bytes_per_device",
                     "opt_state_bytes_replicated")},
        "peak_rss_mb": peak_rss_mb(),
    }))


def ooc_main():
    """``python bench.py --ooc``: the out-of-core fit row — a streamed
    fit over rows generated, binned and spilled chunk-by-chunk, so no
    full-N array ever exists in this process. The process-wide
    ``peak_rss_mb`` therefore IS the bounded-memory claim: it must stay
    near the interpreter + jit baseline regardless of BENCH_OOC_ROWS
    (default 4M; scale up on real hardware, down for CI rehearsals)."""
    stamp = device_stamp()
    import tempfile

    import jax

    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    from mmlspark_tpu.models.gbdt.ooc import train_ooc
    from mmlspark_tpu.models.gbdt.trainer import TrainConfig
    from mmlspark_tpu.ops.binning import BinMapper
    from mmlspark_tpu.ops.ingest import ChunkStore, SpillWriter

    enable_persistent_cache()
    n = int(os.environ.get("BENCH_OOC_ROWS", 4_000_000))
    num_trees = int(os.environ.get("BENCH_OOC_TREES", 20))
    f = 28  # HIGGS-shaped, as the in-core row
    from mmlspark_tpu.models.gbdt.trainer import resolve_ooc_chunk_rows
    chunk = resolve_ooc_chunk_rows()

    def gen(i, rows):
        r = np.random.default_rng(1000 + i)
        x = r.normal(size=(rows, f)).astype(np.float32)
        logit = (x[:, 0] * 1.2 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
                 + 0.3 * np.sin(x[:, 4] * 3))
        y = (logit + r.normal(size=rows) * 0.5 > 0).astype(np.float32)
        return x, y

    spans = [(i, s, min(chunk, n - s))
             for i, s in enumerate(range(0, n, chunk))]
    mapper = BinMapper.fit_streaming(
        (gen(i, rows)[0] for i, _, rows in spans), max_bin=63)
    cfg = TrainConfig(objective="binary", num_iterations=num_trees,
                      num_leaves=63, max_depth=6, min_data_in_leaf=20,
                      max_bin=63)
    with tempfile.TemporaryDirectory(prefix="bench-ooc-") as td:
        writer = SpillWriter(os.path.join(td, "binned"), dtype=np.uint8)
        labels = ChunkStore(os.path.join(td, "labels"), "y")
        for i, _, rows in spans:
            x, y = gen(i, rows)
            writer.append(mapper.transform(x))
            labels.put(i, y)
        spill = writer.finalize()
        t0 = time.perf_counter()
        result = train_ooc(spill, labels, cfg,
                           work_dir=os.path.join(td, "state"))
        dt = time.perf_counter() - t0
    suffix = "" if (n == 4_000_000 and num_trees == 20) \
        else f"_rows{n}_trees{num_trees}"
    print(json.dumps({
        "metric": "gbdt_fit_throughput_ooc" + suffix,
        "value": round(n * result.booster.num_trees / dt / 1e6, 3),
        "unit": "Mrow-trees/s",
        "vs_baseline": None,  # the in-core row is the comparator
        "backend": jax.default_backend(),
        **stamp,
        "fit_s": round(dt, 3),
        "peak_rss_mb": peak_rss_mb(),
        **{k: result.hist_stats.get(k)
           for k in ("ooc", "ooc_reason", "chunk_rows", "n_chunks",
                     "hist_quant", "hist_subtract", "spill_verify",
                     "spill_verify_s", "spill_verify_chunks",
                     "spill_repairs")},
    }))


def refresh_latency_main():
    """``python bench.py --refresh-latency``: the streaming-refresh
    row — wall time from fresh-data arrival to the refreshed model
    serving (warm-start refit + atomic hot-swap), with the swap's
    serving downtime recorded separately. Steady state: one warm
    refresh generation first, the second is timed. BENCH_REFRESH_ROWS /
    BENCH_REFRESH_TREES override the window shape for rehearsals."""
    stamp = device_stamp()
    import tempfile

    import jax

    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.io.refresh import RefreshController
    from mmlspark_tpu.io.serving import ServingServer
    from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor

    enable_persistent_cache()
    rng = np.random.default_rng(0)
    n = int(os.environ.get("BENCH_REFRESH_ROWS", 100_000))
    trees = int(os.environ.get("BENCH_REFRESH_TREES", 30))
    f = 28

    def window(shift):
        x = (rng.normal(size=(n, f)) + shift).astype(np.float32)
        y = x[:, 0] - 0.5 * x[:, 1] + 0.25 * x[:, 2] * x[:, 3]
        return x, y

    est = LightGBMRegressor(numIterations=trees, numLeaves=63,
                            maxBin=63, minDataInLeaf=20, seed=0)
    x0, y0 = window(0.0)
    model = est.fit(DataFrame({"features": x0, "label": y0}))

    with tempfile.TemporaryDirectory() as td, \
            ServingServer(model, max_batch_size=64,
                          max_latency_ms=2.0) as server:
        ctrl = RefreshController(est, model, td, server=server,
                                 refresh_interval_s=10_000,
                                 min_refit_rows=n)
        # warm generation: compiles the refit step and the new plane's
        # scoring rung, as a long-lived refresh loop would have
        ctrl.observe(*window(0.5))
        warm = ctrl.refresh()
        if warm.swap_error:
            raise RuntimeError(f"warm swap failed: {warm.swap_error}")
        # timed generation: data arrival -> refreshed model serving
        x1, y1 = window(1.0)
        t0 = time.perf_counter()
        ctrl.observe(x1, y1)
        result = ctrl.refresh()
        wall = time.perf_counter() - t0
        if result.swap_error:
            raise RuntimeError(f"timed swap failed: {result.swap_error}")
        suffix = (f"_rows{n}_trees{trees}"
                  if n != 100_000 or trees != 30 else "")
        print(json.dumps({
            "metric": "refresh_latency" + suffix,
            "value": round(wall, 3),
            "unit": "s",
            "vs_baseline": None,  # no measured external comparator yet
            "backend": jax.default_backend(),
            **stamp,
            "rows": n,
            "new_trees": trees,
            "refit_s": round(result.refit_s, 3),
            "swap_s": round(result.swap["swap_s"], 4),
            "swap_downtime_s": round(result.swap["downtime_s"], 4),
            "generation": result.generation,
            "train_stalls": _resilience_counters()[0],
            "train_recoveries": _resilience_counters()[1],
            "peak_rss_mb": peak_rss_mb(),
        }))
        ctrl.close()


def refresh_under_load_main():
    """``python bench.py --refresh-under-load``: the train-while-serve
    row — serving p50/p99 during a co-located low-priority refit vs
    idle at EQUAL offered load (the refit admission-control claim),
    then a fleet-wide two-phase hot-swap under the same load with the
    per-worker flip downtime and the rejected/timeout deltas across
    the whole run. BENCH_REFRESH_ROWS / BENCH_REFRESH_TREES /
    BENCH_SERVING_CLIENTS / BENCH_SERVING_DURATION_S override the
    shape for rehearsals."""
    stamp = device_stamp()
    import tempfile
    import threading
    import urllib.request as urllib_request

    import jax

    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.io.fleet import FleetSupervisor
    from mmlspark_tpu.io.refresh import RefreshController
    from mmlspark_tpu.io.serving import ServingFleet
    from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor

    enable_persistent_cache()
    rng = np.random.default_rng(0)
    n = int(os.environ.get("BENCH_REFRESH_ROWS", 50_000))
    trees = int(os.environ.get("BENCH_REFRESH_TREES", 20))
    clients = int(os.environ.get("BENCH_SERVING_CLIENTS", 8))
    duration = float(os.environ.get("BENCH_SERVING_DURATION_S", 6))
    f = 28

    def window(shift):
        x = (rng.normal(size=(n, f)) + shift).astype(np.float32)
        y = x[:, 0] - 0.5 * x[:, 1] + 0.25 * x[:, 2] * x[:, 3]
        return x, y

    est = LightGBMRegressor(numIterations=trees, numLeaves=63,
                            maxBin=63, minDataInLeaf=20, seed=0)
    x0, y0 = window(0.0)
    model = est.fit(DataFrame({"features": x0, "label": y0}))
    payload = json.dumps({"features": x0[0].tolist()}).encode()

    def healthz(server):
        with urllib_request.urlopen(
                f"http://{server.host}:{server.port}/healthz",
                timeout=5) as r:
            return json.loads(r.read())

    def offered_load(servers, until):
        """Closed-loop clients round-robined over the workers until
        ``until()`` flips; returns (latencies_ms, client_errors)."""
        lat, errors = [], [0]
        stop = threading.Event()

        def client(i):
            url = servers[i % len(servers)].url
            while not stop.is_set():
                t = time.perf_counter()
                try:
                    req = urllib_request.Request(
                        url, data=payload,
                        headers={"Content-Type": "application/json"})
                    with urllib_request.urlopen(req, timeout=10) as r:
                        r.read()
                    lat.append((time.perf_counter() - t) * 1e3)
                except Exception:
                    errors[0] += 1

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True) for i in range(clients)]
        for t in threads:
            t.start()
        while not until():
            time.sleep(0.02)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        return np.asarray(lat, dtype=np.float64), errors[0]

    def pctls(lat):
        if not len(lat):
            return 0.0, 0.0
        return (float(np.percentile(lat, 50)),
                float(np.percentile(lat, 99)))

    with tempfile.TemporaryDirectory() as td:
        fleet = ServingFleet(model, num_servers=2, max_batch_size=64,
                             max_latency_ms=2.0).start()
        sup = FleetSupervisor(fleet, min_workers=2, max_workers=2)
        servers = list(fleet.servers)
        name = servers[0]._default
        ctrl = RefreshController(est, model, td, server=servers[0],
                                 priority="low",
                                 refresh_interval_s=10_000,
                                 min_refit_rows=n)
        before = [healthz(s) for s in servers]
        try:
            # -- phase 1: idle baseline at the offered load ----------
            t_end = time.perf_counter() + duration
            idle_lat, idle_err = offered_load(
                servers, lambda: time.perf_counter() >= t_end)
            p50_idle, p99_idle = pctls(idle_lat)
            # -- phase 2: same load while the refit runs co-located --
            ctrl.observe(*window(0.5))
            refit_done = threading.Event()
            refit_box = {}

            def refit():
                try:
                    refit_box["result"] = ctrl.refresh(swap=False)
                finally:
                    refit_done.set()

            rt = threading.Thread(target=refit, daemon=True)
            rt.start()
            refit_lat, refit_err = offered_load(
                servers, refit_done.is_set)
            rt.join(timeout=600)
            result = refit_box["result"]
            p50_refit, p99_refit = pctls(refit_lat)
            # -- phase 3: fleet-wide swap under the same load --------
            swap_done = threading.Event()
            swap_box = {}

            def swap():
                try:
                    swap_box["result"] = sup.swap_model_fleet(
                        name, result.model,
                        probe_payload={"features": x0[0].tolist()})
                finally:
                    swap_done.set()

            st = threading.Thread(target=swap, daemon=True)
            st.start()
            _, swap_err = offered_load(servers, swap_done.is_set)
            st.join(timeout=600)
            swap_result = swap_box["result"]
            after = [healthz(s) for s in servers]
        finally:
            ctrl.close()
            fleet.stop()

    suffix = (f"_rows{n}_trees{trees}"
              if n != 50_000 or trees != 20 else "")
    print(json.dumps({
        "metric": "refresh_under_load" + suffix,
        "value": round(p99_refit, 3),
        "unit": "ms",
        "vs_baseline": None,  # no measured external comparator yet
        "backend": jax.default_backend(),
        **stamp,
        "rows": n,
        "new_trees": trees,
        "clients": clients,
        "priority": "low",
        "p50_idle_ms": round(p50_idle, 3),
        "p99_idle_ms": round(p99_idle, 3),
        "p50_refit_ms": round(p50_refit, 3),
        "p99_refit_ms": round(p99_refit, 3),
        "p99_refit_over_idle": round(p99_refit / p99_idle, 3)
        if p99_idle else None,
        "requests_idle": int(len(idle_lat)),
        "requests_refit": int(len(refit_lat)),
        "client_errors": idle_err + refit_err + swap_err,
        "refit_s": round(result.refit_s, 3),
        "refit_yields": ctrl.stats["refit_yields"],
        "refit_yield_s": round(ctrl.stats["refit_yield_s"], 3),
        "fleet_swap_s": round(swap_result["swap_s"], 4),
        "per_worker_downtime_ms": {
            wk: round(t["downtime_s"] * 1e3, 3)
            for wk, t in swap_result["per_worker"].items()},
        "rejected_503_delta": sum(h["rejected"] for h in after)
        - sum(h["rejected"] for h in before),
        "timeout_504_delta": sum(h["timeouts"] for h in after)
        - sum(h["timeouts"] for h in before),
        "train_stalls": _resilience_counters()[0],
        "train_recoveries": _resilience_counters()[1],
        "peak_rss_mb": peak_rss_mb(),
    }))


def serving_elastic_main():
    """``python bench.py --serving-elastic``: the elastic-fleet row —
    sustained fleet load whose offered client count DOUBLES at half
    time while the FleetSupervisor autoscales workers; one
    ``serving_elastic`` JSON row with the worker-count trajectory,
    shed counts, and p99 before/after the doubling
    (tools/bench_serving.py emit_elastic). BENCH_SERVING_CLIENTS /
    BENCH_SERVING_DURATION_S override the load shape for rehearsals."""
    stamp = device_stamp()
    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    from tools.bench_serving import emit_elastic
    emit_elastic(
        clients=int(os.environ.get("BENCH_SERVING_CLIENTS", 16)),
        duration_s=float(os.environ.get("BENCH_SERVING_DURATION_S", 12)),
        extra=stamp)


def serving_gray_main():
    """``python bench.py --serving-gray``: the gray-failure row — a
    3-worker fleet with one seeded 200 ms slow worker under closed-loop
    FleetClient load, hedging+breakers off then on; one ``serving_gray``
    JSON row per arm (p50/p99, hedge/breaker/shed counters, measured
    extra backend load, bitwise reply check) plus the p99-ratio summary
    (tools/bench_serving.py emit_gray). BENCH_SERVING_CLIENTS /
    BENCH_SERVING_DURATION_S override the load shape for rehearsals."""
    stamp = device_stamp()
    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    from tools.bench_serving import emit_gray
    emit_gray(
        clients=int(os.environ.get("BENCH_SERVING_CLIENTS", 8)),
        duration_s=float(os.environ.get("BENCH_SERVING_DURATION_S", 8)),
        extra=stamp)


def serving_sustained_main():
    """``python bench.py --serving-sustained``: the serving-path row —
    64 keep-alive clients for a fixed duration against the generic
    transform arm, the binned bucket-padded data plane, and the binned
    plane under MMLSPARK_TPU_INFER_AUTOCAST=bf16; one JSON row per arm
    plus the QPS-ratio summaries (serving_sustained_speedup and
    serving_bf16_speedup with score_max_abs_delta_vs_f32,
    tools/bench_serving.py emit_sustained). BENCH_SERVING_CLIENTS /
    BENCH_SERVING_DURATION_S override the load shape for rehearsals."""
    stamp = device_stamp()
    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    from tools.bench_serving import emit_sustained
    emit_sustained(
        clients=int(os.environ.get("BENCH_SERVING_CLIENTS", 64)),
        duration_s=float(os.environ.get("BENCH_SERVING_DURATION_S", 10)),
        extra=stamp)


if __name__ == "__main__":
    if "--serving-elastic" in sys.argv:
        serving_elastic_main()
    elif "--serving-sustained" in sys.argv:
        serving_sustained_main()
    elif "--serving-gray" in sys.argv:
        serving_gray_main()
    elif "--refresh-under-load" in sys.argv:
        refresh_under_load_main()
    elif "--refresh-latency" in sys.argv:
        refresh_latency_main()
    elif "--ooc" in sys.argv:
        ooc_main()
    else:
        main()
