"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no subprocesses, no network beyond loopback. Drives the
flagship path once through the entry points a user calls, at the full
width of the tracked HIGGS configuration (BASELINE.md: 2,000,000 x 28
float32, maxBin=255, numLeaves=63, maxDepth=6) with trees cut from 100
to 5 and data generated from a seed:

  fit        LightGBMClassifier.fit() — binning and ingest inside the call
  transform  model.transform() over 1,000,000 rows (the ShardedScorer path)
  serve      ServingServer: sequential single-row requests plus one
             concurrent burst; every reply equals transform's row, bitwise
  kernel     one level histogram at bench dimensions through the
             formulation the fit resolved vs ``per_feature``
  mesh       fit + transform again under ``.set_mesh(create_mesh())``
             when JAX reports more than one device

Every phase failure is fatal: there is no ``except`` around a phase, and
the exit status is 0 only if every assertion held. Nothing here may look
green without the device having done the work — the run fails unless
``jax.devices()[0].platform == "tpu"``, the Pallas kernel was compiled
by Mosaic (not interpreted), the native library built and loaded, and
the server scored every batch on its compiled binned plane.

Prints one JSON line per phase (facts about the run, not metrics) and,
as the last line of stdout, ``{"ok": true, "device": {...}}`` with the
device as JAX reports it.

    python3 chip_smoke.py              # on the chip; anything else exits 2
    python3 chip_smoke.py --rehearse   # tiny sizes on whatever backend JAX
                                       # finds (Pallas interpreted off-TPU);
                                       # never prints the pass line
"""

import argparse
import contextlib
import functools
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

FULL_ROWS, FULL_TRANSFORM_ROWS, FULL_TREES = 2_000_000, 1_000_000, 5
FEATURES, MAX_BIN, HIST_WIDTH = 28, 255, 32
# Accuracy of the 5-tree model on its own training rows. The floors sit
# a little under what the runs gave (see PERF.md, PR 22): a broken
# histogram or a mis-routed scorer lands near 0.5, not near the floor.
ACCURACY_FLOOR = {"full": 0.85, "rehearsal": 0.80}
MESH_ACCURACY_TOLERANCE = 0.005


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(cond: bool, message: str) -> None:
    # not ``assert``: -O must not turn the smoke green
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {message}")


def timed(fn):
    """(result, seconds); ``fn`` must block until the device is done."""
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def make_data(n: int):
    """The HIGGS-shaped generator of the tracked configuration
    (BASELINE.md), seed 0."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, FEATURES)).astype(np.float32)
    logit = (x[:, 0] * 1.2 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
             + 0.3 * np.sin(x[:, 4] * 3))
    y = (logit + rng.normal(size=n) * 0.5 > 0).astype(np.float64)
    return x, y


def peak_bytes():
    """Per-device peak_bytes_in_use, None where the backend has none."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def fit_and_transform(phase: str, estimator, df, x_score, y_score,
                      expect: dict, floor: float):
    """Fit twice and transform twice (first call carries the compiles),
    check the fit's own provenance against ``expect`` and the
    predictions against the labels."""
    from mmlspark_tpu.core.dataframe import DataFrame

    model, fit_first_s = timed(lambda: estimator.fit(df))
    model2, fit_second_s = timed(lambda: estimator.fit(df))
    stats = model.hist_stats
    for key, want in expect.items():
        check(stats.get(key) == want,
              f"{phase}: fit resolved {key}={stats.get(key)!r}, "
              f"expected {want!r}")
    check(model.booster.num_trees == estimator.get("numIterations"),
          f"{phase}: {model.booster.num_trees} trees")

    score_df = DataFrame({"features": x_score})
    out, xf_first_s = timed(lambda: model.transform(score_df))
    out, xf_second_s = timed(lambda: model.transform(score_df))
    pred = np.asarray(out.col("prediction"))
    prob = np.asarray(out.col("probability"))
    check(pred.shape == (len(x_score),) and prob.shape == (len(x_score), 2),
          f"{phase}: transform shapes {pred.shape} {prob.shape}")
    check(bool(np.isfinite(prob).all()), f"{phase}: non-finite probability")
    accuracy = float((pred == y_score).mean())
    check(accuracy >= floor,
          f"{phase}: accuracy {accuracy:.4f} under the floor {floor}")
    emit(phase, rows=int(df.num_rows), trees=model.booster.num_trees,
         fit_first_s=fit_first_s, fit_second_s=fit_second_s,
         refit_identical=(model.get_model_string()
                          == model2.get_model_string()),
         fit_phases_s={k: round(v, 3) for k, v in
                       model2.get_all_instrumentation().items()},
         transform_rows=len(x_score), transform_first_s=xf_first_s,
         transform_second_s=xf_second_s, accuracy=round(accuracy, 5),
         hist_stats=stats, shard=model.shard_metadata(),
         peak_bytes_in_use=peak_bytes())
    return model, out, accuracy


# loopback only: never through a proxy the environment may name
_HTTP = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def _get(url: str) -> dict:
    with _HTTP.open(url, timeout=30) as r:
        return json.loads(r.read())


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with _HTTP.open(req, timeout=60) as r:
        return json.loads(r.read())


SERVE_SEQUENTIAL, SERVE_BURST, SERVE_MAX_BURSTS = 4, 8, 5


def serve(model, rows, expected) -> None:
    """A few sequential requests, then concurrent bursts until one has
    formed a batch larger than one row, against a ServingServer with
    its default 7-rung ladder; every reply must be the transform
    prediction of its row, bitwise (the README's contract), scored on
    the compiled binned plane."""
    from mmlspark_tpu.io.serving import ServingServer

    raw = expected.col("rawPrediction")
    prob = expected.col("probability")
    pred = expected.col("prediction")
    replies = {}

    def ask(i):
        replies[i] = _post(server.url, {"features": rows[i].tolist(),
                                        "__id__": i})

    server = ServingServer(model, max_batch_size=64, max_latency_ms=2.0)
    try:
        # start() builds the plane and compiles every ladder rung
        _, start_s = timed(server.start)
        base = f"http://{server.host}:{server.port}"

        def counters():
            listing = _get(base + "/models")
            return listing["models"][listing["default"]]

        _, seq_s = timed(lambda: [ask(i) for i in range(SERVE_SEQUENTIAL)])
        # whether two requests share a 2 ms batching window is a race
        # the smoke does not control: repeat the burst (fresh rows) until
        # the server reports fewer batches than rows, a few times at most
        sent, bursts, burst_s = SERVE_SEQUENTIAL, 0, []
        while bursts < SERVE_MAX_BURSTS:
            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(sent, sent + SERVE_BURST)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            burst_s.append(round(time.perf_counter() - t0, 3))
            check(not any(t.is_alive() for t in threads),
                  "serve: a burst request never returned")
            sent, bursts = sent + SERVE_BURST, bursts + 1
            if counters()["binned_batches"] < sent:
                break
        health = _get(base + "/healthz")
        stats = counters()
    finally:
        server.stop()

    for i in range(sent):
        reply = replies.get(i)
        check(reply is not None and reply.get("id") == i,
              f"serve: request {i} got {reply!r}")
        # == on floats IS the bitwise contract (json round-trips the
        # repr of a float64 exactly)
        check(reply["prediction"] == float(pred[i])
              and reply["rawPrediction"] == [float(v) for v in raw[i]]
              and reply["probability"] == [float(v) for v in prob[i]],
              f"serve: reply {i} {reply} != transform row "
              f"({pred[i]}, {raw[i]}, {prob[i]})")
    check(health["binned"]["active"] is True,
          f"serve: binned plane inactive: {health['binned']}")
    check(health["buckets"] == [1, 2, 4, 8, 16, 32, 64],
          f"serve: ladder {health['buckets']}")
    check(stats["served"] == sent, f"serve: served {stats['served']} of {sent}")
    check(stats["binned_batches"] > 0 and stats["binned_fallbacks"] == 0
          and stats["generic_batches"] == 0,
          f"serve: batches left the binned plane: {stats}")
    check(stats["binned_batches"] < sent,
          f"serve: {bursts} bursts formed no batch larger than one row "
          f"({stats['binned_batches']} batches for {sent} rows)")
    emit("serve", requests=sent, bursts=bursts, start_first_s=start_s,
         sequential_s=seq_s, burst_s=burst_s, status=health["status"],
         binned=health["binned"], buckets=health["buckets"],
         **{k: stats[k] for k in (
             "served", "errors", "rejected", "timeouts", "binned_batches",
             "binned_fallbacks", "generic_batches", "p50_ms", "p99_ms")},
         replies_bitwise=True)


def kernel_parity(binned, formulation: str) -> None:
    """One level histogram at the fit's dimensions through the
    formulation the fit resolved and through ``per_feature``: counts
    exact, grad and hess to float-sum tolerance — the contract
    tests/gbdt/test_hist_pallas.py pins in interpret mode, here on
    whatever compiled the kernel. The Pallas kernel has two paths told
    apart by the level's width and the feature count
    (hist_pallas.level_feed): one width on each side of the bound is
    checked."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.hist_pallas import (IN_PLACE_MAX_WIDTH,
                                                      level_feed)
    from mmlspark_tpu.models.gbdt.trainer import _level_histogram

    n, f = binned.shape
    rng = np.random.default_rng(1)
    rows = (jnp.asarray(binned),
            jnp.asarray(rng.normal(size=n).astype(np.float32)),
            jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32)),
            jnp.asarray((rng.random(n) < 0.9).astype(np.float32)))
    widths = [HIST_WIDTH]
    if formulation == "pallas":
        widths.append(2 * IN_PLACE_MAX_WIDTH)
    facts = {}
    for width in widths:
        args = rows + (jnp.asarray(rng.integers(0, width, size=n,
                                                dtype=np.int32)),)
        results, seconds = {}, {}
        for name in dict.fromkeys((formulation, "per_feature")):
            fn = jax.jit(functools.partial(
                _level_histogram, width=width, f=f, b=MAX_BIN,
                formulation=name))
            _, first_s = timed(lambda: jax.block_until_ready(fn(*args)))
            out, second_s = timed(lambda: jax.block_until_ready(fn(*args)))
            results[name], seconds[name] = np.asarray(out), (first_s,
                                                             second_s)
        got, ref = results[formulation], results["per_feature"]
        check(got.shape == ref.shape == (width, f, MAX_BIN, 3),
              f"kernel: shapes {got.shape} {ref.shape}")
        check(bool(np.isfinite(got).all()), "kernel: non-finite histogram")
        check(float(ref[..., 2].sum()) > 0, "kernel: empty reference")
        check(np.array_equal(got[..., 2], ref[..., 2]),
              f"kernel: counts differ from per_feature at width {width}")
        err = np.abs(got[..., :2] - ref[..., :2])
        tol = 1e-4 + 1e-5 * np.abs(ref[..., :2])
        check(bool((err <= tol).all()),
              f"kernel: grad/hess off by up to {float(err.max()):.3e} at "
              f"width {width} (rtol 1e-5, atol 1e-4)")
        facts[width] = {
            "feed": (level_feed(width, f) if formulation == "pallas"
                     else None),
            "max_abs_err": float(err.max()),
            **{f"{name}_first_s": s[0] for name, s in seconds.items()},
            **{f"{name}_second_s": s[1] for name, s in seconds.items()}}
    emit("kernel", formulation=formulation, reference="per_feature",
         rows=n, features=f, bins=MAX_BIN, width=HIST_WIDTH,
         counts_exact=True, **facts.pop(HIST_WIDTH),
         other_widths={str(w): v for w, v in facts.items()},
         peak_bytes_in_use=peak_bytes())


def run_phases(device: dict, n: int, n_score: int, trees: int,
               floor: float) -> None:
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier
    from mmlspark_tpu.parallel.mesh import create_mesh

    on_tpu = device["platform"] == "tpu"
    x, y = make_data(n)
    df = DataFrame({"features": x, "label": y})

    def estimator():
        return LightGBMClassifier(
            numIterations=trees, numLeaves=63, maxDepth=6, maxBin=MAX_BIN,
            minDataInLeaf=20)

    # -- no mesh: everything on the first chip; this is the leg that must
    # show the TPU default kernel, compiled
    model, out, accuracy = fit_and_transform(
        "fit_transform", estimator(), df, x[:n_score], y[:n_score],
        expect={"hist_formulation": "pallas", "tree_mode": "serial",
                "pallas_interpret": not on_tpu, "hist_shard": "off",
                "binned_rows_per_device": [n]},
        floor=floor)

    n_serve = SERVE_SEQUENTIAL + SERVE_BURST * SERVE_MAX_BURSTS
    serve(model, x[:n_serve].astype(np.float64), out.head(n_serve))

    binned = model.bin_mapper.transform(x).astype(np.uint8)
    kernel_parity(binned, model.hist_stats["hist_formulation"])

    # -- more than one device: the data-parallel leg
    if device["count"] == 1:
        emit("mesh_fit_transform", skipped="one device")
        return
    dp = device["count"]
    per_device = -(-n // dp)  # train() pads rows to a multiple of dp
    mesh_model, _, mesh_accuracy = fit_and_transform(
        "mesh_fit_transform", estimator().set_mesh(create_mesh()), df,
        x[:n_score], y[:n_score],
        expect={"hist_formulation": "pallas", "tree_mode": "data_sharded",
                "pallas_interpret": not on_tpu, "hist_shard": "on",
                "binned_rows_per_device": [per_device] * dp,
                "raw_rows_per_device": [per_device] * dp},
        floor=floor)
    check(mesh_model.shard_metadata()["shard_rules_dp"] == dp,
          f"mesh: scorer {mesh_model.shard_metadata()}")
    check(abs(mesh_accuracy - accuracy) <= MESH_ACCURACY_TOLERANCE,
          f"mesh: accuracy {mesh_accuracy:.4f} vs one-device "
          f"{accuracy:.4f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never prints the "
                         "pass line")
    ap.add_argument("--rows", type=int, default=20_000,
                    help="rehearsal rows (ignored without --rehearse)")
    ap.add_argument("--trees", type=int, default=2,
                    help="rehearsal trees (ignored without --rehearse)")
    args = ap.parse_args(argv)

    import jax

    from mmlspark_tpu import native
    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    from mmlspark_tpu.core.env import env_override

    cache_dir = enable_persistent_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        # with libtpu installed and no chip JAX only warns and carries
        # on on the CPU, so the check has to be explicit
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{device['platform']!r} ({device}); --rehearse runs a tiny "
              "CPU rehearsal that can never pass", file=sys.stderr)
        return 2
    if args.rehearse:
        n, n_score, trees = args.rows, max(args.rows // 2, 1), args.trees
        floor = ACCURACY_FLOOR["rehearsal"]
    else:
        n, n_score, trees = FULL_ROWS, FULL_TRANSFORM_ROWS, FULL_TREES
        floor = ACCURACY_FLOOR["full"]

    check(native.is_available(),
          "the native data plane did not build or load "
          "(native/data_plane.cpp -> libmmlspark_native.so)")
    emit("start", device=device, jax=jax.__version__, rehearsal=args.rehearse,
         rows=n, trees=trees, compile_cache_dir=cache_dir,
         compile_cache_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         native_available=True)

    # off the TPU the kernel is opt-in and runs interpreted
    kernel_opt_in = (env_override("MMLSPARK_TPU_PALLAS_HIST", "1")
                     if not on_tpu else contextlib.nullcontext())
    with kernel_opt_in:
        run_phases(device, n, n_score, trees, floor)

    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "completed",
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
