"""Serving benches with the REAL flagship GBDT model (HIGGS-shaped
LightGBM classifier: 28 features, 100 trees, 63 leaves).

Two methodologies, selected by flag:

- default (legacy, rounds 3-5 comparable): continuous single-row
  latency behind the HTTP server. JSON adds {"mode", "qps",
  "rejected_503", "timeout_504"} to the legacy fields {"p50_ms",
  "p99_ms" (keep-alive client, TCP_NODELAY), "p50_ms_new_conn" (fresh
  TCP connection per request), "model", "backend", "n_requests"}.
- ``--sustained``: N keep-alive clients (default 64) hammer the
  batched server for a fixed duration, once against the generic
  transform path (MMLSPARK_TPU_SERVE_BINNED=off — the pre-change
  comparator, which recompiles per batch shape) and once against the
  binned bucket-padded data plane (=on). Emits one
  ``serving_sustained`` JSON row per arm {"arm", "qps", "p50_ms",
  "p99_ms", "rejected_503", "timeout_504", "clients", "duration_s",
  "binned_active", "model", "backend"} plus a summary row with the
  binned-vs-generic QPS ratio.

- ``--elastic``: sustained fleet run where offered load DOUBLES at
  half time while a FleetSupervisor autoscales workers inside a
  min/max envelope. Emits one ``serving_elastic`` JSON row with
  per-phase qps + p50/p99, shed counters, and the worker-count
  trajectory.

- ``--hedging``: gray-failure bench — a 3-worker fleet with ONE seeded
  slow worker (200 ms per batch, heartbeats fine) under closed-loop
  FleetClient load, run twice: hedging+breakers OFF (the pre-change
  client) and ON. Emits one ``serving_gray`` row per arm (p50/p99,
  hedge/breaker/shed counters, measured extra backend load =
  hedges_fired/requests, bitwise reply check against the model) plus a
  p99-ratio summary row.

Run: python tools/bench_serving.py [n_requests]
     python tools/bench_serving.py --sustained [--clients N]
                                   [--duration S]
     python tools/bench_serving.py --elastic [--clients N]
                                   [--duration S]
     python tools/bench_serving.py --hedging [--clients N]
                                   [--duration S]
"""

import json
import math
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL_DESC = "LightGBMClassifier 28f x 100 trees x 63 leaves"


def build_model(n=100_000, f=28, num_trees=100):
    import numpy as np

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, f))
    y = (x[:, 0] - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
         + rng.normal(size=n) * 0.5 > 0).astype(np.float64)
    model = LightGBMClassifier(numIterations=num_trees, numLeaves=63,
                               maxBin=255).fit(
        DataFrame({"features": x, "label": y}))
    return model, x


def _san_lock_disabled_overhead_ns():
    """Measured per-acquire cost a DISABLED san_lock with-pass adds
    over a raw threading.Lock — the serving data plane's locks are all
    san_lock-wrapped, so this delta rides every request. Same 200k-rep
    protocol as bench.py's graftsan/watchdog probes; None when the
    sanitizer is live (the guarded path is deliberately not the number
    this field pins)."""
    from mmlspark_tpu.core import sanitizer

    if sanitizer.enabled():
        return None
    raw = threading.Lock()
    wrapped = sanitizer.san_lock("bench.san_lock_probe")
    reps = 200_000

    def probe(lk):
        t0 = time.perf_counter()
        for _ in range(reps):
            with lk:
                pass
        return (time.perf_counter() - t0) / reps * 1e9

    probe(raw), probe(wrapped)  # warm
    return round(probe(wrapped) - probe(raw), 1)


def _san_dtype_disabled_overhead_ns():
    """Measured per-call cost of a DISABLED check_dtype_contract over a
    no-op passthrough — the dtype contract guards the serving score
    path, so this delta rides every scored batch. Same 200k-rep
    protocol as the san_lock probe; None when the sanitizer is live."""
    from mmlspark_tpu.core import sanitizer

    if sanitizer.enabled():
        return None

    def passthrough(boundary, value):
        return value

    reps = 200_000
    payload = {"p": 1.0}

    def probe(fn):
        t0 = time.perf_counter()
        for _ in range(reps):
            fn("bench.dtype_probe", payload)
        return (time.perf_counter() - t0) / reps * 1e9

    probe(passthrough), probe(sanitizer.check_dtype_contract)  # warm
    return round(probe(sanitizer.check_dtype_contract)
                 - probe(passthrough), 1)


def _score_max_abs_delta_vs_f32(model, rows):
    """Max abs difference between the active autocast arm's margins
    and the f32 reference on a fixed probe batch; None when autocast
    is off (the arms would be the same compiled scorer). Expected
    bound for bf16: leaf values round at 2^-8 relative step and sum
    over the trees, so ~num_trees * 2^-8 * mean(|leaf|) — well under
    1e-2 at bench shape."""
    import numpy as np

    from mmlspark_tpu.core.env import INFER_AUTOCAST, env_override
    from mmlspark_tpu.parallel.shard_rules import resolve_infer_autocast

    if resolve_infer_autocast() == "off":
        return None
    try:
        plan = model.serving_binned_plan()
        with env_override(INFER_AUTOCAST, "off"):
            ref = model.serving_binned_plan()
        probe = np.asarray(rows[:64])
        binned = plan.bin_rows(probe)
        got = np.asarray(plan.score(binned), dtype=np.float64)
        want = np.asarray(ref.score(binned), dtype=np.float64)
    except Exception:
        return None   # generic-arm model without a binned plane
    return float(np.max(np.abs(got - want)))


def _percentiles(lat):
    lat = sorted(lat)
    if not lat:
        return None, None
    return (round(lat[len(lat) // 2], 3),
            round(lat[max(0, math.ceil(0.99 * len(lat)) - 1)], 3))


def run_sustained(model, rows, clients=64, duration_s=10.0, binned="auto",
                  max_batch_size=64, max_latency_ms=2.0):
    """Fixed-duration closed-loop load: ``clients`` keep-alive
    connections, each sending single-row requests back-to-back.
    Returns the serving_sustained row (without the backend field —
    the caller labels it)."""
    import http.client

    import numpy as np

    from mmlspark_tpu.core.env import SERVE_BINNED, env_override
    from mmlspark_tpu.io.serving import ServingServer
    from mmlspark_tpu.parallel.shard_rules import resolve_infer_autocast

    with env_override(SERVE_BINNED, binned):
        server = ServingServer(
            model, max_batch_size=max_batch_size,
            max_latency_ms=max_latency_ms, max_queue=4 * max_batch_size,
            request_timeout_s=5.0, max_connections=clients + 8,
            reply_col="prediction").start()
    # pre-encoded request bodies: the bench must measure the server,
    # not per-request rng + json encoding on the client threads
    bodies = [json.dumps({"features": row.tolist()}).encode()
              for row in rows[:256]]
    headers = {"Content-Type": "application/json"}
    barrier = threading.Barrier(clients + 1)
    stop_at = [0.0]
    results = [None] * clients

    def client(idx):
        lat, ok, r503, t504, errs = [], 0, 0, 0, 0
        conn = None
        i = idx
        barrier.wait()
        while time.perf_counter() < stop_at[0]:
            if conn is None:
                conn = http.client.HTTPConnection(
                    server.host, server.port, timeout=10)
                try:
                    conn.connect()
                    conn.sock.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                except OSError:
                    conn = None
                    errs += 1
                    time.sleep(0.01)
                    continue
            t0 = time.perf_counter()
            try:
                conn.request("POST", server.api_path,
                             body=bodies[i % len(bodies)], headers=headers)
                resp = conn.getresponse()
                resp.read()
                status = resp.status
            except Exception:
                conn.close()
                conn = None
                errs += 1
                continue
            i += clients
            if status == 200:
                ok += 1
                lat.append((time.perf_counter() - t0) * 1e3)
            elif status == 503:
                r503 += 1
                time.sleep(0.002)  # honor the shed, then retry
            elif status == 504:
                t504 += 1
            else:
                errs += 1
            if resp.getheader("Connection", "").lower() == "close":
                conn.close()
                conn = None
        if conn is not None:
            conn.close()
        results[idx] = (lat, ok, r503, t504, errs)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t_start = time.perf_counter()
    stop_at[0] = t_start + duration_s
    for t in threads:
        t.join(timeout=duration_s + 30)
    wall = time.perf_counter() - t_start
    health = server._health()
    server.stop()

    lat = [v for r in results if r for v in r[0]]
    ok = sum(r[1] for r in results if r)
    r503 = sum(r[2] for r in results if r)
    t504 = sum(r[3] for r in results if r)
    errs = sum(r[4] for r in results if r)
    p50, p99 = _percentiles(lat)
    return {
        "metric": "serving_sustained", "mode": "sustained",
        "arm": "binned" if health["binned"]["active"] else "generic",
        "binned_active": health["binned"]["active"],
        "binned_mode": binned,
        "clients": clients, "duration_s": round(wall, 2),
        "qps": round(ok / wall, 1), "p50_ms": p50, "p99_ms": p99,
        "rejected_503": r503, "timeout_504": t504, "client_errors": errs,
        "autocast": resolve_infer_autocast(),
        "score_max_abs_delta_vs_f32": _score_max_abs_delta_vs_f32(
            model, rows),
        "san_lock_disabled_overhead_ns": _san_lock_disabled_overhead_ns(),
        "san_dtype_disabled_overhead_ns":
            _san_dtype_disabled_overhead_ns(),
        "model": MODEL_DESC,
    }


def emit_sustained(clients=64, duration_s=10.0, model_rows=None,
                   extra=None):
    """Run three arms (generic comparator, the binned data plane, then
    the binned plane under MMLSPARK_TPU_INFER_AUTOCAST=bf16), print one
    JSON row per arm + ratio summary rows (binned-vs-generic and
    bf16-vs-f32); returns the binned-vs-generic summary. Shared by
    ``--sustained`` here and bench.py's ``--serving-sustained``; every
    row carries ``extra`` (the caller's device stamp)."""
    import jax

    from mmlspark_tpu.core.env import INFER_AUTOCAST, env_override

    model, rows = model_rows if model_rows is not None else build_model()
    backend = jax.default_backend()
    generic = run_sustained(model, rows, clients=clients,
                            duration_s=duration_s, binned="off")
    binned = run_sustained(model, rows, clients=clients,
                           duration_s=duration_s, binned="on")
    with env_override(INFER_AUTOCAST, "bf16"):
        bf16 = run_sustained(model, rows, clients=clients,
                             duration_s=duration_s, binned="on")
    bf16["arm"] = f"{bf16['arm']}_bf16"
    for row in (generic, binned, bf16):
        row["backend"] = backend
        row.update(extra or {})
        print(json.dumps(row), flush=True)
    summary = {
        "metric": "serving_sustained_speedup",
        "value": (round(binned["qps"] / generic["qps"], 2)
                  if generic["qps"] else None),
        "unit": "x_vs_generic_transform",
        "qps_binned": binned["qps"], "qps_generic": generic["qps"],
        "clients": clients, "model": MODEL_DESC, "backend": backend,
        **(extra or {}),
    }
    print(json.dumps(summary), flush=True)
    bf16_summary = {
        "metric": "serving_bf16_speedup",
        "value": (round(bf16["qps"] / binned["qps"], 2)
                  if binned["qps"] else None),
        "unit": "x_vs_f32_binned",
        "qps_bf16": bf16["qps"], "qps_f32": binned["qps"],
        "score_max_abs_delta_vs_f32":
            bf16["score_max_abs_delta_vs_f32"],
        "clients": clients, "model": MODEL_DESC, "backend": backend,
        **(extra or {}),
    }
    print(json.dumps(bf16_summary), flush=True)
    return summary


def run_elastic(model, rows, clients=16, duration_s=12.0,
                min_workers=1, max_workers=4, scale_p99_ms=None,
                max_batch_size=64, max_latency_ms=2.0):
    """Sustained fleet load where OFFERED LOAD DOUBLES mid-run: wave 1
    (``clients`` closed-loop FleetClients) starts at t0, wave 2 (same
    size) joins at half time. A FleetSupervisor on bench timescales
    (fast heartbeat/cooldown) grows the fleet from ``min_workers``
    toward ``max_workers`` as p99/queue pressure builds. Returns the
    ``serving_elastic`` row: per-phase qps + p50/p99, shed counts, and
    the worker-count trajectory (the ROADMAP item-4 deliverable:
    offered load doubles, p99 stays bounded while the fleet grows)."""
    from mmlspark_tpu.io.fleet import FleetSupervisor
    from mmlspark_tpu.io.serving import FleetClient, ServingFleet

    if scale_p99_ms is None:
        scale_p99_ms = float(os.environ.get(
            "BENCH_ELASTIC_SCALE_P99_MS", 25.0))
    fleet = ServingFleet(
        model, num_servers=min_workers, max_batch_size=max_batch_size,
        max_latency_ms=max_latency_ms, max_queue=4 * max_batch_size,
        request_timeout_s=5.0, max_connections=2 * clients + 8,
        reply_col="prediction").start()
    sup = FleetSupervisor(
        fleet, min_workers=min_workers, max_workers=max_workers,
        scale_p99_ms=scale_p99_ms, heartbeat_s=0.25, cooldown_s=1.0,
        scale_streak=2, probe_timeout_s=2.0).start()
    payloads = [{"features": row.tolist()} for row in rows[:256]]
    total = 2 * clients
    stop_at = [0.0]
    wave2 = threading.Event()
    barrier = threading.Barrier(clients + 1)
    results = [None] * total

    def client(idx):
        fc = FleetClient(fleet.registry_url, timeout=10.0,
                         refresh_interval_s=1.0)
        lat, ok, shed, errs = [], 0, 0, 0
        i = idx
        if idx < clients:
            barrier.wait()
        else:
            wave2.wait()
        while time.perf_counter() < stop_at[0]:
            t0 = time.perf_counter()
            try:
                fc.score(dict(payloads[i % len(payloads)]))
            except RuntimeError:
                # every worker shedding (503 rotation exhausted):
                # honor the backpressure, then retry
                shed += 1
                time.sleep(0.002)
                continue
            except Exception:
                errs += 1
                continue
            i += total
            t1 = time.perf_counter()
            ok += 1
            lat.append((t1, (t1 - t0) * 1e3))
        results[idx] = (lat, ok, shed, errs)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(total)]
    for t in threads:
        t.start()
    barrier.wait()
    t_start = time.perf_counter()
    stop_at[0] = t_start + duration_s
    t_half = t_start + duration_s / 2
    time.sleep(max(t_half - time.perf_counter(), 0.0))
    wave2.set()  # offered load doubles HERE
    for t in threads:
        t.join(timeout=duration_s + 60)
    wall = time.perf_counter() - t_start
    # shed/admission counters across the final fleet (workers that
    # died mid-run take their counters with them; supervisor stats
    # record the deaths)
    shed_tenant = shed_priority = rejected = 0
    with fleet._servers_lock:
        servers = list(fleet.servers)
    for s in servers:
        h = s._health()
        shed_tenant += h.get("shed_tenant", 0)
        shed_priority += h.get("shed_priority", 0)
        rejected += h.get("rejected", 0)
    sup_stats = sup.stats()
    history = [(round(t - t_start, 2), n) for t, n in sup.history]
    # compress to change points (first, transitions, last)
    traj = [history[0]] if history else []
    for prev, cur in zip(history, history[1:]):
        if cur[1] != prev[1]:
            traj.append(cur)
    if history and (not traj or traj[-1] != history[-1]):
        traj.append(history[-1])
    sup.stop()
    fleet.stop()

    def phase(pred):
        lat = [ms for r in results if r for t, ms in r[0] if pred(t)]
        p50, p99 = _percentiles(lat)
        span = duration_s / 2
        return {"qps": round(len(lat) / span, 1), "p50_ms": p50,
                "p99_ms": p99}
    before = phase(lambda t: t <= t_half)
    after = phase(lambda t: t > t_half)
    return {
        "metric": "serving_elastic", "mode": "elastic",
        "clients_initial": clients, "clients_peak": total,
        "duration_s": round(wall, 2),
        "qps_before_double": before["qps"],
        "qps_after_double": after["qps"],
        "p50_ms_before": before["p50_ms"], "p99_ms_before": before["p99_ms"],
        "p50_ms_after": after["p50_ms"], "p99_ms_after": after["p99_ms"],
        "workers_min": min_workers, "workers_max": max_workers,
        "workers_end": sup_stats["workers"],
        "worker_trajectory": traj,
        "scale_ups": sup_stats["scale_ups"],
        "scale_downs": sup_stats["scale_downs"],
        "worker_deaths": sup_stats["deaths"],
        "worker_spawns": sup_stats["spawns"],
        "shed_backpressure": sum(r[2] for r in results if r),
        "client_errors": sum(r[3] for r in results if r),
        "shed_tenant": shed_tenant, "shed_priority": shed_priority,
        "rejected": rejected,
        "scale_p99_ms": scale_p99_ms,
        "san_lock_disabled_overhead_ns": _san_lock_disabled_overhead_ns(),
        "san_dtype_disabled_overhead_ns":
            _san_dtype_disabled_overhead_ns(),
        "model": MODEL_DESC,
    }


def emit_elastic(clients=16, duration_s=12.0, model_rows=None,
                 extra=None, **kwargs):
    """Run the elastic-fleet bench and print its JSON row; returns the
    row. Shared by ``--elastic`` here and bench.py's
    ``--serving-elastic`` (the caller's device stamp rides in
    ``extra``)."""
    import jax

    model, rows = model_rows if model_rows is not None else build_model()
    row = run_elastic(model, rows, clients=clients,
                      duration_s=duration_s, **kwargs)
    row["backend"] = jax.default_backend()
    row.update(extra or {})
    print(json.dumps(row), flush=True)
    return row


def run_gray(model, rows, clients=8, duration_s=8.0, hedging=True,
             gray_delay_ms=200.0, num_workers=3, deadline_ms=5000.0,
             max_batch_size=16, max_latency_ms=2.0):
    """One arm of the gray-failure bench: ``num_workers`` fleet with
    ONE seeded slow worker (``gray_delay_ms`` added to every batch it
    scores — slow, not dead: heartbeats keep passing), hammered by
    ``clients`` closed-loop FleetClients with deadline propagation on
    and hedging+breakers per ``hedging``. Every reply is checked
    bitwise against the model's own transform. No supervisor runs: the
    arm measures the CLIENT-side gray tolerance in isolation (the
    supervisor-side recycle is chaosfuzz scenario 6's job)."""
    import numpy as np

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.io.serving import FleetClient, ServingFleet

    fleet = ServingFleet(
        model, num_servers=num_workers, max_batch_size=max_batch_size,
        max_latency_ms=max_latency_ms, max_queue=8 * max_batch_size,
        request_timeout_s=5.0, max_connections=2 * clients + 8,
        reply_col="prediction").start()
    payload_rows = rows[:64]
    payloads = [{"features": row.tolist()} for row in payload_rows]
    reference = [float(v) for v in model.transform(
        DataFrame({"features": np.asarray(payload_rows)})).col(
            "prediction")]
    stop_at = [0.0]
    barrier = threading.Barrier(clients + 1)
    results = [None] * clients
    # ONE client shared by every load thread (the deployment shape: a
    # process-wide client), so the rolling latency map — and with it
    # the slow-worker ejection — learns from the whole run's traffic
    fc = FleetClient(fleet.registry_url, timeout=10.0,
                     refresh_interval_s=1.0, hedging=hedging,
                     deadline_ms=deadline_ms)

    def client(idx):
        lat, ok, shed, errs, mismatches = [], 0, 0, 0, 0
        i = idx
        barrier.wait()
        while time.perf_counter() < stop_at[0]:
            p = i % len(payloads)
            t0 = time.perf_counter()
            try:
                reply = fc.score(dict(payloads[p]))
            except (RuntimeError, TimeoutError):
                # attributed shed (retry budget / deadline / rotation
                # exhausted): honor the backpressure, then retry
                shed += 1
                time.sleep(0.002)
                continue
            except Exception:
                errs += 1
                continue
            i += clients
            ok += 1
            lat.append((time.perf_counter() - t0) * 1e3)
            if float(reply["prediction"]) != reference[p]:
                mismatches += 1
        results[idx] = (lat, ok, shed, errs, mismatches)

    with fleet._servers_lock:
        servers = list(fleet.servers)
    servers[0].gray_delay_ms = gray_delay_ms  # the seeded gray worker
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t_start = time.perf_counter()
    stop_at[0] = t_start + duration_s
    for t in threads:
        t.join(timeout=duration_s + 60)
    wall = time.perf_counter() - t_start
    served = shed_deadline = 0
    for s in servers:
        h = s._health()
        served += h.get("served", 0)
        shed_deadline += h.get("shed_deadline", 0)
    fleet.stop()

    client_stats = dict(fc.stats)
    lat = [v for r in results if r for v in r[0]]
    ok = sum(r[1] for r in results if r)
    p50, p99 = _percentiles(lat)
    return {
        "metric": "serving_gray", "mode": "gray",
        "arm": "hedged" if hedging else "plain",
        "hedging": hedging, "clients": clients,
        "duration_s": round(wall, 2),
        "gray_delay_ms": gray_delay_ms, "workers": num_workers,
        "deadline_ms": deadline_ms,
        "qps": round(ok / wall, 1), "p50_ms": p50, "p99_ms": p99,
        # measured extra backend load the hedges added (the <=5%
        # budget contract), over the CLIENT's own request count
        "extra_load_pct": (round(100.0 * client_stats["hedges_fired"]
                                 / client_stats["requests"], 2)
                           if client_stats["requests"] else 0.0),
        **{k: v for k, v in client_stats.items() if k != "requests"},
        "requests": client_stats["requests"],
        "served": served, "shed_deadline_server": shed_deadline,
        "client_shed": sum(r[2] for r in results if r),
        "client_errors": sum(r[3] for r in results if r),
        "reply_mismatches": sum(r[4] for r in results if r),
        "replies_bitwise": sum(r[4] for r in results if r) == 0,
        "san_lock_disabled_overhead_ns": _san_lock_disabled_overhead_ns(),
        "san_dtype_disabled_overhead_ns":
            _san_dtype_disabled_overhead_ns(),
        "model": MODEL_DESC,
    }


def emit_gray(clients=8, duration_s=8.0, model_rows=None, extra=None,
              **kwargs):
    """Run both gray-bench arms (hedging off first, then on), print one
    JSON row per arm + a p99-ratio summary; returns the summary.
    Shared by ``--hedging`` here and bench.py's ``--serving-gray``."""
    import jax

    model, rows = model_rows if model_rows is not None else build_model()
    backend = jax.default_backend()
    plain = run_gray(model, rows, clients=clients, duration_s=duration_s,
                     hedging=False, **kwargs)
    hedged = run_gray(model, rows, clients=clients,
                      duration_s=duration_s, hedging=True, **kwargs)
    for row in (plain, hedged):
        row["backend"] = backend
        row.update(extra or {})
        print(json.dumps(row), flush=True)
    summary = {
        "metric": "serving_gray_p99_cut",
        "value": (round(plain["p99_ms"] / hedged["p99_ms"], 2)
                  if plain["p99_ms"] and hedged["p99_ms"] else None),
        "unit": "x_vs_hedging_off",
        "p99_ms_plain": plain["p99_ms"], "p99_ms_hedged": hedged["p99_ms"],
        "extra_load_pct": hedged["extra_load_pct"],
        "replies_bitwise": plain["replies_bitwise"]
        and hedged["replies_bitwise"],
        "clients": clients, "model": MODEL_DESC, "backend": backend,
    }
    summary.update(extra or {})
    print(json.dumps(summary), flush=True)
    return summary


def _arg_value(flag, default):
    if flag in sys.argv:
        return type(default)(sys.argv[sys.argv.index(flag) + 1])
    return default


def main():
    n_req = int(next((a for a in sys.argv[1:]
                      if not a.startswith("--")
                      and not sys.argv[sys.argv.index(a) - 1].startswith(
                          ("--clients", "--duration"))), 300))
    from bench import device_stamp
    stamp = device_stamp()

    if "--sustained" in sys.argv:
        emit_sustained(clients=_arg_value("--clients", 64),
                       duration_s=_arg_value("--duration", 10.0),
                       extra=stamp)
        return

    if "--elastic" in sys.argv:
        emit_elastic(clients=_arg_value("--clients", 16),
                     duration_s=_arg_value("--duration", 12.0),
                     extra=stamp)
        return

    if "--hedging" in sys.argv:
        emit_gray(clients=_arg_value("--clients", 8),
                  duration_s=_arg_value("--duration", 8.0),
                  extra=stamp)
        return

    import urllib.request

    import numpy as np

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.io.serving import ContinuousServingServer
    from mmlspark_tpu.core.pipeline import Transformer

    model, _ = build_model()
    f = 28
    rng = np.random.default_rng(1)
    feats = {f"f{i}": 0.0 for i in range(f)}

    # serve the model on a features vector assembled from scalar fields
    class Wrapper(Transformer):
        def _transform(self, df):
            cols = np.stack([np.asarray(df.col(f"f{i}"), np.float64)
                             for i in range(f)], axis=1)
            return model.transform(DataFrame({"features": cols}))

    server = ContinuousServingServer(
        Wrapper(), warmup_payload=feats).start()
    counters = {"rejected_503": 0, "timeout_504": 0}
    try:
        import http.client
        from urllib.parse import urlparse
        u = urlparse(server.url)
        # keep-alive client (realistic serving client; the server talks
        # HTTP/1.1) and fresh-connection client, both measured
        def timed(send, reps):
            out = []
            for _ in range(reps):
                row = {f"f{j}": float(v) for j, v in
                       enumerate(rng.normal(size=f))}
                body = json.dumps(row).encode()
                t0 = time.perf_counter()
                try:
                    send(body)
                except urllib.error.HTTPError as e:
                    key = {503: "rejected_503", 504: "timeout_504"}.get(
                        e.code)
                    if key is None:
                        raise
                    counters[key] += 1
                    continue
                out.append((time.perf_counter() - t0) * 1e3)
            return out

        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=10)
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

        def send_keepalive(body):
            conn.request("POST", u.path, body=body,
                         headers={"Content-Type": "application/json"})
            json.loads(conn.getresponse().read())

        def send_fresh(body):
            req = urllib.request.Request(
                server.url, data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                json.loads(r.read())

        t0 = time.perf_counter()
        lat = timed(send_keepalive, n_req)
        keepalive_wall = time.perf_counter() - t0
        conn.close()
        lat_new = timed(send_fresh, max(1, n_req // 3))
    finally:
        server.stop()
    p50, p99 = _percentiles(lat)
    p50_new, _ = _percentiles(lat_new)
    import jax
    print(json.dumps({
        "mode": "continuous_single",
        "p50_ms": p50,
        "p99_ms": p99,
        "p50_ms_new_conn": p50_new,
        "qps": round(len(lat) / keepalive_wall, 1),
        "rejected_503": counters["rejected_503"],
        "timeout_504": counters["timeout_504"],
        "model": MODEL_DESC,
        "backend": jax.default_backend(),
        **stamp,
        "n_requests": n_req,
    }))


if __name__ == "__main__":
    main()
