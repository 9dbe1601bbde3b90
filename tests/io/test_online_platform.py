"""Train-while-serve platform guardrails: fleet-wide two-phase
hot-swap (commit parity on every worker, attributed rollback that
leaves the old model serving bitwise-unchanged), the serving request
log feeding the refresh loop, refit admission control (a low-priority
co-located refit yields at train-step boundaries instead of starving
the data plane), and a seeded mini chaos campaign over the combined
scenario."""

import json
import threading
import time
import urllib.request as urllib_request

import numpy as np
import pytest

from mmlspark_tpu.core import faults
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.env import env_override
from mmlspark_tpu.io.fleet import FleetSupervisor
from mmlspark_tpu.io.refresh import RefreshController
from mmlspark_tpu.io.serving import ServingFleet, ServingServer, SwapFailed
from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor

from tools import chaosfuzz as cf

pytestmark = pytest.mark.platform_smoke

N, F = 300, 6


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _make_data(seed, n=N, shift=0.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, F)) + shift
    y = x[:, 0] - 0.5 * x[:, 1] + 0.25 * x[:, 2] * x[:, 3] \
        + rng.normal(size=n) * 0.1
    return x, y


def _estimator():
    return LightGBMRegressor(numIterations=4, numLeaves=7, maxBin=15,
                             seed=0)


@pytest.fixture(scope="module")
def base():
    x, y = _make_data(0)
    model = _estimator().fit(DataFrame({"features": x, "label": y}))
    x2, y2 = _make_data(1, shift=0.8)
    new_model = _estimator().fit(DataFrame({"features": x2, "label": y2}))
    return model, new_model, x


def _post(url, payload, timeout=30.0):
    req = urllib_request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib_request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(url, timeout=5.0):
    with urllib_request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _local_pred(model, x_row):
    df = model.transform(DataFrame({"features": x_row[None, :]}))
    return float(df.col("prediction")[0])


# ---------------------------------------------------------------------------
# fleet-wide two-phase swap: commit parity, attributed rollback
# ---------------------------------------------------------------------------

def test_fleet_swap_commits_on_every_worker(base):
    model, new_model, x = base
    with ServingFleet(model, num_servers=2, max_batch_size=8,
                      max_latency_ms=2.0) as fleet:
        sup = FleetSupervisor(fleet, min_workers=2, max_workers=2)
        servers = list(fleet.servers)
        name = servers[0]._default
        want_old = _local_pred(model, x[0])
        want_new = _local_pred(new_model, x[0])
        for server in servers:
            assert _post(server.url,
                         {"features": x[0].tolist()})["prediction"] \
                == want_old
        result = sup.swap_model_fleet(
            name, new_model, probe_payload={"features": x[0].tolist()})
        assert result["workers"] == 2
        assert len(result["per_worker"]) == 2
        for timing in result["per_worker"].values():
            # the flip is the whole downtime window; the fan-out
            # prepare (plane build + probe) is excluded from it
            assert result["swap_s"] >= timing["downtime_s"] >= 0.0
        assert sup.stats()["fleet_swaps"] == 1
        # parity: every worker serves the NEW model, bitwise
        for server in servers:
            assert _post(server.url,
                         {"features": x[0].tolist()})["prediction"] \
                == want_new
            health = _get(f"http://{server.host}:{server.port}/healthz")
            assert health["status"] == "ok"
            assert health["swaps"] == 1


def test_fleet_swap_rolls_back_when_any_worker_fails_prepare(base):
    model, new_model, x = base
    with ServingFleet(model, num_servers=3, max_batch_size=8,
                      max_latency_ms=2.0) as fleet:
        sup = FleetSupervisor(fleet, min_workers=3, max_workers=3)
        servers = list(fleet.servers)
        name = servers[0]._default
        want_old = _local_pred(model, x[0])
        # the THIRD worker's prepare dies: workers 1-2 are already
        # prepared and must abort
        faults.arm("registry.swap_fanout", "raise", nth=3, count=1)
        with pytest.raises(SwapFailed) as ei:
            sup.swap_model_fleet(
                name, new_model,
                probe_payload={"features": x[0].tolist()})
        failing = servers[2]
        assert f"{failing.host}:{failing.port}" in str(ei.value)
        assert "rolled back" in str(ei.value)
        assert sup.stats()["fleet_swap_rollbacks"] == 1
        assert sup.stats()["fleet_swaps"] == 0
        # every worker still serves the OLD model bitwise, no worker
        # is stuck in a swap window, health is clean
        for server in servers:
            assert _post(server.url,
                         {"features": x[0].tolist()})["prediction"] \
                == want_old
            with server._lock:
                assert not server._swapping
            health = _get(f"http://{server.host}:{server.port}/healthz")
            assert health["status"] == "ok"
            assert health["swaps"] == 0


def test_fleet_swap_with_no_workers_is_attributed(base):
    model, new_model, _ = base
    fleet = ServingFleet(model, num_servers=1, max_batch_size=8,
                         max_latency_ms=2.0)
    fleet.start()
    lone = fleet.servers[0]
    try:
        sup = FleetSupervisor(fleet, min_workers=0, max_workers=1)
        name = lone._default
        assert fleet.remove_worker(lone)
        with pytest.raises(SwapFailed, match="no workers"):
            sup.swap_model_fleet(name, new_model)
    finally:
        lone.stop()
        fleet.stop()


# ---------------------------------------------------------------------------
# serving request log -> refresh buffer
# ---------------------------------------------------------------------------

def test_serving_tap_feeds_refresh_buffer(base, tmp_path):
    model, _, x = base
    with ServingServer(model, max_batch_size=8,
                       max_latency_ms=2.0) as server:
        ctrl = RefreshController(_estimator(), model, str(tmp_path),
                                 server=server,
                                 refresh_interval_s=10_000,
                                 min_refit_rows=32)
        labels = {x[i].tobytes(): 10.0 + i for i in range(4)}
        ctrl.tap_serving(label_fn=lambda payload, reply: labels.get(
            np.asarray(payload["features"], dtype=np.float64).tobytes()))
        for i in range(4):
            _post(server.url, {"features": x[i].tolist()})
        assert ctrl.buffer.rows == 4
        assert ctrl.stats["tap_rows"] == 4
        assert server._health()["log_rows"] == 4
        # the tap runs after the reply fan-out on the scoring thread:
        # a dying observer must not touch the data plane
        faults.arm("serving.observe_log", "raise", count=1)
        reply = _post(server.url, {"features": x[4].tolist()})
        assert reply["prediction"] == _local_pred(model, x[4])
        # the reply leaves before the tap runs, so on a loaded host the
        # counter may lag the reply by a moment
        deadline = time.monotonic() + 5.0
        while (server._health()["log_tap_errors"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert server._health()["log_tap_errors"] == 1
        assert ctrl.buffer.rows == 4


# ---------------------------------------------------------------------------
# refit admission control: low priority yields, high does not
# ---------------------------------------------------------------------------

def _refit_under_parked_load(model, tmp_path, priority):
    """Refit while 3 requests sit parked past the queue high-water
    mark; returns (controller stats, parked replies). The batcher's
    latency window is far wider than the whole refit so the parked
    queue deterministically overlaps every train step — the refit's
    throttle, not scheduling luck, decides whether serving waits."""
    with ServingServer(model, max_batch_size=8, max_latency_ms=4000.0,
                       queue_high_water=1) as server:
        ctrl = RefreshController(_estimator(), model, str(tmp_path),
                                 server=server, priority=priority,
                                 refresh_interval_s=10_000,
                                 min_refit_rows=32)
        x1, y1 = _make_data(2, shift=0.5)
        ctrl.observe(x1, y1)
        results = [None] * 3

        def call(i):
            try:
                results[i] = _post(server.url,
                                   {"features": x1[i].tolist()})
            except Exception as e:  # pragma: no cover - failure detail
                results[i] = e

        threads = [threading.Thread(target=call, args=(i,), daemon=True)
                   for i in range(3)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with server._lock:
                if sum(len(m.queue) for m in server._models.values()) \
                        >= 2:
                    break
            time.sleep(0.002)
        with env_override("MMLSPARK_TPU_REFRESH_YIELD_S", "0.05"):
            result = ctrl.refresh(swap=False)
        assert result.generation == 1
        for t in threads:
            t.join(timeout=10)
        return ctrl.stats, results


def test_low_priority_refit_yields_to_serving(base, tmp_path):
    model, _, _ = base
    stats, results = _refit_under_parked_load(
        model, tmp_path / "low", priority="low")
    # the refit saw the queue past high water and yielded compute at
    # train-step boundaries — and every parked request got its reply
    assert stats["refit_yields"] > 0
    assert stats["refit_yield_s"] > 0.0
    for out in results:
        assert isinstance(out, dict) and "prediction" in out, \
            f"request starved by co-located refit: {out!r}"


def test_high_priority_refit_never_yields(base, tmp_path):
    model, _, _ = base
    stats, results = _refit_under_parked_load(
        model, tmp_path / "high", priority="high")
    assert stats["refit_yields"] == 0
    assert stats["refit_yield_s"] == 0.0
    for out in results:
        assert isinstance(out, dict) and "prediction" in out


# ---------------------------------------------------------------------------
# combined scenario: seeded mini campaign, zero violations
# ---------------------------------------------------------------------------

def test_scenario5_mini_campaign_zero_violations():
    report = cf.run_campaign([5], 2, budget_s=120,
                             scenario_names=["train_while_serve"])
    assert report["total_schedules"] == 2
    assert report["violations"] == []
    assert set(report["outcomes"]) <= {"clean", "resumed",
                                       "failed-attributed"}
