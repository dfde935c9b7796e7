"""Prediction server tests over live HTTP.

Covers the serve chain, feedback loop into a live event server, /reload
hot-swap, /stop, plugins, micro-batching — the behaviors of
`core/.../workflow/CreateServer.scala` exercised end-to-end the way the
reference's integration suite does.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core import CoreWorkflow, EngineParams, RuntimeContext
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.eventserver import EventServer, EventServerConfig
from predictionio_tpu.data.storage import AccessKey, App
from predictionio_tpu.models import recommendation as rec
from predictionio_tpu.serving import (
    EngineServerPlugin, OUTPUT_BLOCKER, PredictionServer, ServerConfig,
)
from predictionio_tpu.serving.server import to_jsonable
from predictionio_tpu.utils.wire import BIN_CONTENT_TYPE, encode_bin_query


def call(port, method, path, body=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req) as resp:
            raw = resp.read().decode()
            ct = resp.headers.get("Content-Type", "")
            return resp.status, (json.loads(raw) if "json" in ct else raw)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


@pytest.fixture()
def trained(mem_registry):
    """Registry with a trained recommendation instance."""
    apps = mem_registry.get_meta_data_apps()
    app_id = apps.insert(App(0, "servapp"))
    mem_registry.get_meta_data_access_keys().insert(
        AccessKey("SKEY", app_id, ()))
    events = mem_registry.get_events()
    events.init(app_id)
    rng = np.random.RandomState(0)
    for u in range(20):
        for i in range(15):
            if rng.rand() > 0.5:
                continue
            r = 5.0 if i % 3 == u % 3 else 1.0
            events.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": r})), app_id)
    ctx = RuntimeContext(registry=mem_registry)
    engine = rec.engine()
    params = EngineParams(
        data_source_params=("", rec.DataSourceParams(app_name="servapp")),
        algorithm_params_list=(
            ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=4, seed=1)),))
    row = CoreWorkflow.run_train(engine, params, ctx)
    return mem_registry, engine, row, app_id


def start_server(registry, engine, **cfg):
    config = ServerConfig(ip="127.0.0.1", port=0, **cfg)
    srv = PredictionServer(config, registry=registry, engine=engine)
    srv.start()
    return srv


class TestServe:
    def test_queries_json(self, trained):
        registry, engine, _, _ = trained
        srv = start_server(registry, engine)
        try:
            status, body = call(srv.port, "POST", "/queries.json",
                                {"user": "u1", "num": 3})
            assert status == 200
            assert len(body["itemScores"]) == 3
            assert body["itemScores"][0]["score"] >= body["itemScores"][1]["score"]
            # unknown user -> empty itemScores (ALSAlgorithm.scala:96-112)
            status, body = call(srv.port, "POST", "/queries.json",
                                {"user": "ghost", "num": 3})
            assert status == 200 and body["itemScores"] == []
        finally:
            srv.shutdown()

    def test_bad_query_400(self, trained):
        registry, engine, _, _ = trained
        srv = start_server(registry, engine)
        try:
            status, body = call(srv.port, "POST", "/queries.json",
                                {"nope": 1})
            assert status == 400
            status, _ = call(srv.port, "POST", "/queries.json",
                             {"user": "u1", "num": "three"})
            assert status == 400
        finally:
            srv.shutdown()

    def test_status_and_latency_bookkeeping(self, trained):
        registry, engine, row, _ = trained
        srv = start_server(registry, engine)
        try:
            call(srv.port, "POST", "/queries.json", {"user": "u1", "num": 2})
            call(srv.port, "POST", "/queries.json", {"user": "u2", "num": 2})
            status, body = call(srv.port, "GET", "/status.json")
            assert status == 200
            assert body["requestCount"] == 2
            assert body["avgServingSec"] > 0
            assert body["engineInstanceId"] == row.id
            status, html = call(srv.port, "GET", "/")
            assert status == 200 and "Engine server is running" in html
        finally:
            srv.shutdown()

    def test_no_completed_instance_refuses(self, mem_registry):
        with pytest.raises(RuntimeError, match="train"):
            PredictionServer(ServerConfig(ip="127.0.0.1", port=0),
                             registry=mem_registry, engine=rec.engine())


def call_raw(port, path, data, content_type):
    """POST opaque bytes (the binary query frame) and return the raw
    response body — `call` always speaks JSON."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method="POST")
    req.add_header("Content-Type", content_type)
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class TestBinaryQueries:
    def test_binary_query_parity_with_json(self, trained):
        """The application/x-pio-bin frame must serve byte-identical
        readings to the JSON route for the same logical query — the
        response side is the same pre-serialized splice. The fast lane
        needs the micro-batcher (batch_window_ms > 0) — without it the
        generic JSON route is the only parser."""
        registry, engine, _, _ = trained
        srv = start_server(registry, engine, batch_window_ms=2)
        try:
            if srv.wire != "selector":
                pytest.skip("binary framing rides the selector wire")
            for user, num in [("u1", 3), ("ghost", 2), ("u7", 1)]:
                status, json_body = call(srv.port, "POST",
                                         "/queries.json",
                                         {"user": user, "num": num})
                assert status == 200
                status, raw = call_raw(srv.port, "/queries.json",
                                       encode_bin_query(user, num),
                                       BIN_CONTENT_TYPE)
                assert status == 200
                assert json.loads(raw) == json_body
        finally:
            srv.shutdown()

    def test_malformed_binary_frame_400(self, trained):
        registry, engine, _, _ = trained
        srv = start_server(registry, engine, batch_window_ms=2)
        try:
            if srv.wire != "selector":
                pytest.skip("binary framing rides the selector wire")
            status, raw = call_raw(srv.port, "/queries.json",
                                   b"\x82junk-not-a-frame",
                                   BIN_CONTENT_TYPE)
            assert status == 400
            assert b"binary" in raw
        finally:
            srv.shutdown()


class TestShardedServe:
    def test_reactors_env_serves_and_labels_metrics(self, trained,
                                                    monkeypatch):
        """PIO_WIRE_REACTORS=2 puts ShardedWire behind the server: the
        serve chain works unchanged and /metrics carries one series per
        accept shard via the reactor label."""
        monkeypatch.setenv("PIO_WIRE_REACTORS", "2")
        registry, engine, _, _ = trained
        srv = start_server(registry, engine)
        try:
            if srv.wire != "selector":
                pytest.skip("sharding applies to the selector wire")
            for i in range(6):
                status, body = call(srv.port, "POST", "/queries.json",
                                    {"user": f"u{i}", "num": 2})
                assert status == 200
            status, text = call(srv.port, "GET", "/metrics")
            assert status == 200
            assert 'reactor="0"' in text
            assert 'reactor="1"' in text
            assert "pio_wire_egress_flushes_total" in text
        finally:
            srv.shutdown()


class TestReloadStop:
    def test_reload_picks_latest(self, trained):
        registry, engine, row1, app_id = trained
        srv = start_server(registry, engine)
        try:
            assert srv._dep.instance.id == row1.id
            # retrain -> new instance; /reload must pick it up
            ctx = RuntimeContext(registry=registry)
            params = EngineParams(
                data_source_params=("", rec.DataSourceParams(
                    app_name="servapp")),
                algorithm_params_list=(
                    ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=2,
                                                   seed=2)),))
            row2 = CoreWorkflow.run_train(engine, params, ctx)
            status, _ = call(srv.port, "POST", "/reload")
            assert status == 200
            assert srv._dep.instance.id == row2.id
        finally:
            srv.shutdown()

    def test_stop_endpoint(self, trained):
        registry, engine, _, _ = trained
        srv = start_server(registry, engine)
        status, body = call(srv.port, "POST", "/stop")
        assert status == 200
        deadline = time.time() + 5
        while srv.is_running() and time.time() < deadline:
            time.sleep(0.05)
        assert not srv.is_running()


class TestFeedback:
    def test_feedback_event_posted(self, trained):
        registry, engine, row, app_id = trained
        es = EventServer(EventServerConfig(ip="127.0.0.1", port=0),
                         registry)
        es.start()
        srv = start_server(
            registry, engine, feedback=True,
            event_server_ip="127.0.0.1", event_server_port=es.port,
            access_key="SKEY")
        try:
            status, _ = call(srv.port, "POST", "/queries.json",
                             {"user": "u1", "num": 2})
            assert status == 200
            deadline = time.time() + 5
            found = []
            while not found and time.time() < deadline:
                found = list(registry.get_events().find(
                    app_id, event_names=["predict"]))
                time.sleep(0.05)
            assert found, "feedback predict event not ingested"
            ev = found[0]
            assert ev.entity_type == "pio_pr"
            assert ev.properties.get("engineInstanceId") == row.id
            assert ev.properties.get("query")["user"] == "u1"
        finally:
            srv.shutdown()
            es.shutdown()


class RewritePlugin(EngineServerPlugin):
    plugin_name = "rewriter"
    plugin_type = OUTPUT_BLOCKER

    def process(self, info, context):
        return {"rewritten": True, "orig": to_jsonable(info.prediction)}


class TestPlugins:
    def test_output_blocker_rewrites(self, trained):
        registry, engine, _, _ = trained
        config = ServerConfig(ip="127.0.0.1", port=0)
        srv = PredictionServer(config, registry=registry, engine=engine,
                               plugins=[RewritePlugin()])
        srv.start()
        try:
            status, body = call(srv.port, "POST", "/queries.json",
                                {"user": "u1", "num": 2})
            assert status == 200 and body["rewritten"] is True
            status, body = call(srv.port, "GET", "/plugins.json")
            assert "rewriter" in body["plugins"]["outputblockers"]
        finally:
            srv.shutdown()


class TestMicroBatch:
    def test_concurrent_queries_batched(self, trained):
        registry, engine, _, _ = trained
        srv = start_server(registry, engine, batch_window_ms=50)
        try:
            results = {}

            def one(u):
                results[u] = call(srv.port, "POST", "/queries.json",
                                  {"user": f"u{u}", "num": 2})

            threads = [threading.Thread(target=one, args=(u,))
                       for u in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert all(r[0] == 200 for r in results.values())
            # batched results must equal the unbatched path
            direct = call(srv.port, "POST", "/queries.json",
                          {"user": "u3", "num": 2})
            assert [s["item"] for s in results[3][1]["itemScores"]] == \
                   [s["item"] for s in direct[1]["itemScores"]]
        finally:
            srv.shutdown()


def _pool(srv):
    """The wire's handler pool as built (every reactor's slice)."""
    return srv._httpd.stats_snapshot()["workers"]


def _pool_of(workers):
    """What a sharded wire makes of a pool: ceil-divided slices."""
    from predictionio_tpu.utils.wire import reactor_count
    n = reactor_count()
    return n * -(-workers // n)


class TestWirePoolCoversTheBatcher:
    """A worker sleeps in the batcher until its batch wakes, so with a
    batcher the pool is what the batcher admits: queue_max + 2 x
    batch_max. Everything else keeps the pool by cores."""

    @pytest.mark.parametrize("cfg,cover", [
        (dict(batch_window_ms=5), 256 + 2 * 64),
        (dict(batch_window_ms=5, queue_max=100, batch_max=32), 164),
        (dict(batch_window_ms=5, max_inflight=200), 200),
        (dict(batch_window_ms=5, max_inflight=1000), 384),
        (dict(batch_window_ms=0), 0),
        (dict(batch_window_ms=0, max_inflight=200), 0),
    ])
    def test_prediction_server(self, trained, monkeypatch, cfg, cover):
        from predictionio_tpu.utils.wire import worker_count
        monkeypatch.delenv("PIO_WIRE_WORKERS", raising=False)
        registry, engine, _, _ = trained
        srv = start_server(registry, engine, **cfg)
        try:
            assert srv._wire_cover() == cover
            assert _pool(srv) == _pool_of(max(cover, worker_count()))
            status, _ = call(srv.port, "POST", "/queries.json",
                             {"user": "u1", "num": 2})
            assert status == 200
        finally:
            srv.shutdown()

    def test_event_server_keeps_the_pool_by_cores(self, mem_registry,
                                                  monkeypatch):
        from predictionio_tpu.utils.wire import worker_count
        monkeypatch.delenv("PIO_WIRE_WORKERS", raising=False)
        es = EventServer(EventServerConfig(ip="127.0.0.1", port=0),
                         mem_registry)
        es.start()
        try:
            assert es._wire_cover() == 0
            assert _pool(es) == _pool_of(worker_count())
            assert worker_count() <= 64
        finally:
            es.shutdown()

    @pytest.mark.parametrize("window_ms", [0, 5])
    def test_env_wins_over_both(self, trained, monkeypatch, window_ms):
        monkeypatch.setenv("PIO_WIRE_WORKERS", "6")
        registry, engine, _, _ = trained
        srv = start_server(registry, engine, batch_window_ms=window_ms)
        try:
            assert _pool(srv) == _pool_of(6)
        finally:
            srv.shutdown()


class TestStagesWithoutABatcher:
    @pytest.mark.parametrize("window_ms", [0, 5],
                             ids=["unbatched", "batched"])
    def test_predict_batch_stages_observed_either_way(self, trained,
                                                      window_ms):
        """With no batcher every query is its own `predict_batch` on a
        wire worker: it observes the stages it passes through a record
        of its own, and is no cycle — `window`, `take`, `encode`,
        `wake`, `cycle` and `host` belong to the drainer alone."""
        from predictionio_tpu.obs import MetricsRegistry
        registry, engine, _, _ = trained
        srv = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0, batch_window_ms=window_ms),
            registry=registry, engine=engine, metrics=MetricsRegistry())
        srv.start()
        try:
            for u in range(3):
                status, _ = call(srv.port, "POST", "/queries.json",
                                 {"user": f"u{u}", "num": 2})
                assert status == 200
            deadline = time.perf_counter() + 5.0
            while srv._batcher is not None and srv._batcher._draining \
                    and time.perf_counter() < deadline:
                time.sleep(0.005)
            counts = {key[0]: child.count
                      for key, child in srv._serve_obs.stage._items()}
            for name in ("supplement", "predict", "lookup", "unpack",
                         "serve"):
                assert counts[name] == 3, (name, counts)
            drainer_only = ("window", "take", "encode", "wake", "cycle",
                            "host")
            for name in drainer_only:
                assert counts[name] == (3 if window_ms else 0), (name, counts)
            assert srv._serve_obs.worker_wait.labels().count == 3
        finally:
            srv.shutdown()


class TestServerKeyAuth:
    """/reload and /stop are key-protected when a server key is
    configured (CreateServer.scala:624-637 authenticate guard)."""

    def test_reload_and_stop_require_key(self, trained):
        registry, engine, _, _ = trained
        srv = start_server(registry, engine, server_key="sekrit")
        try:
            code, _ = call(srv.port, "POST", "/queries.json",
                           {"user": "u1", "num": 2})
            assert code == 200  # queries are NOT key-gated
            code, body = call(srv.port, "POST", "/reload")
            assert code == 401
            code, _ = call(srv.port, "POST", "/reload?accessKey=sekrit")
            assert code == 200
            code, _ = call(srv.port, "POST", "/stop")
            assert code == 401
            code, _ = call(srv.port, "POST", "/stop?accessKey=sekrit")
            assert code == 200
        finally:
            srv.shutdown()

    def test_no_key_configured_stays_open(self, trained):
        registry, engine, _, _ = trained
        srv = start_server(registry, engine)
        try:
            code, _ = call(srv.port, "POST", "/reload")
            assert code == 200
        finally:
            srv.shutdown()


class TestRedeployRecipe:
    def test_reload_server_hits_running_server(self, trained):
        """`pio-tpu redeploy` = train + ops.reload_server — the analog
        of examples/redeploy-script/redeploy.sh's curl to /reload."""
        from predictionio_tpu.cli.ops import reload_server

        registry, engine, _, _ = trained
        srv = start_server(registry, engine)
        try:
            assert reload_server("127.0.0.1", srv.port) is True
        finally:
            srv.shutdown()

    def test_reload_server_no_server(self):
        import socket

        from predictionio_tpu.cli.ops import reload_server

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        assert reload_server("127.0.0.1", port) is False


class TestForeignOccupantNotStopped:
    def test_foreign_service_gets_no_stop_and_bind_fails(self, trained):
        """The auto-undeploy PROBES the occupant first: a non-pio HTTP
        service must never receive an unsolicited POST /stop; the deploy
        fails with EADDRINUSE instead (advisor finding, round 3)."""
        import http.server
        import threading as _threading

        registry, engine, _, _ = trained
        hits = []

        class Foreign(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                hits.append(("GET", self.path))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"hi")

            def do_POST(self):
                hits.append(("POST", self.path))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        httpd = http.server.HTTPServer(("127.0.0.1", 0), Foreign)
        port = httpd.server_address[1]
        t = _threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        srv = PredictionServer(ServerConfig(ip="127.0.0.1", port=port),
                               registry=registry, engine=engine)
        try:
            with pytest.raises(OSError):
                srv.start()
            assert ("POST", "/stop") not in hits
            assert ("GET", "/status.json") in hits   # probed, not stopped
        finally:
            httpd.shutdown()


class TestDeployTwiceOnOnePort:
    def test_second_deploy_undeploys_squatter(self, trained):
        """Deploying on an occupied port first stops the squatting server
        (CreateServer.scala:347-357) and then binds with retry
        (CreateServer.scala:260-285)."""
        import socket

        registry, engine, _, _ = trained
        # grab an ephemeral port number, then release it for the servers
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        srv1 = PredictionServer(ServerConfig(ip="127.0.0.1", port=port),
                                registry=registry, engine=engine)
        srv1.start()
        srv2 = PredictionServer(ServerConfig(ip="127.0.0.1", port=port),
                                registry=registry, engine=engine)
        try:
            srv2.start()
            assert srv2.port == port
            status, body = call(port, "POST", "/queries.json",
                                {"user": "u1", "num": 2})
            assert status == 200 and body["itemScores"]
            deadline = time.time() + 5
            while srv1.is_running() and time.time() < deadline:
                time.sleep(0.05)
            assert not srv1.is_running()
        finally:
            srv2.shutdown()
            if srv1.is_running():
                srv1.shutdown()


class TestConcurrencyHardening:
    def test_request_count_exact_under_hammer(self, trained):
        """Latency counters are locked: N concurrent requests must count
        exactly N (no lost read-modify-write updates)."""
        registry, engine, _, _ = trained
        srv = start_server(registry, engine)
        try:
            per_thread, n_threads = 5, 8

            def hammer():
                for _ in range(per_thread):
                    code, _ = call(srv.port, "POST", "/queries.json",
                                   {"user": "u2", "num": 2})
                    assert code == 200

            threads = [threading.Thread(target=hammer)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert srv.request_count == per_thread * n_threads
            assert srv.avg_serving_sec > 0.0
        finally:
            srv.shutdown()

    def test_microbatch_sequential_requests_never_hang(self, trained):
        """Regression for the flush-scheduling race: a request arriving
        as the previous flush worker exits must still get flushed."""
        registry, engine, _, _ = trained
        srv = start_server(registry, engine, batch_window_ms=20)
        try:
            for _ in range(5):
                code, _ = call(srv.port, "POST", "/queries.json",
                               {"user": "u4", "num": 2})
                assert code == 200
                time.sleep(0.03)  # straddle the window boundary
        finally:
            srv.shutdown()
