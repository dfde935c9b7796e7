"""CLI tests: in-process command functions + a full subprocess quickstart.

The subprocess scenario mirrors the reference integration suite
(`tests/pio_tests/scenarios/quickstart_test.py`): app new -> import events
-> build -> train -> deploy -> HTTP queries -> undeploy, against
zero-config sqlite storage in a temp dir.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.cli import ops
from predictionio_tpu.data.event import DataMap, Event


class TestAppOps:
    def test_app_lifecycle(self, mem_registry):
        info = ops.app_new(mem_registry, "a1", description="d")
        assert info["name"] == "a1" and info["accessKey"]
        with pytest.raises(ValueError, match="already exists"):
            ops.app_new(mem_registry, "a1")
        assert [a["name"] for a in ops.app_list(mem_registry)] == ["a1"]
        shown = ops.app_show(mem_registry, "a1")
        assert shown["description"] == "d"
        with pytest.raises(ValueError, match="force"):
            ops.app_delete(mem_registry, "a1")
        ops.app_delete(mem_registry, "a1", force=True)
        assert ops.app_list(mem_registry) == []

    def test_channels(self, mem_registry):
        ops.app_new(mem_registry, "a2")
        ops.channel_new(mem_registry, "a2", "mobile")
        with pytest.raises(ValueError, match="already exists"):
            ops.channel_new(mem_registry, "a2", "mobile")
        assert ops.app_show(mem_registry, "a2")["channels"][0]["name"] == "mobile"
        ops.channel_delete(mem_registry, "a2", "mobile", force=True)
        assert ops.app_show(mem_registry, "a2")["channels"] == []

    def test_data_delete(self, mem_registry):
        info = ops.app_new(mem_registry, "a3")
        store = mem_registry.get_events()
        store.insert(Event(event="view", entity_type="u", entity_id="1"),
                     info["id"])
        assert len(list(store.find(info["id"]))) == 1
        ops.app_data_delete(mem_registry, "a3", force=True)
        assert len(list(store.find(info["id"]))) == 0

    def test_accesskeys(self, mem_registry):
        ops.app_new(mem_registry, "a4")
        k = ops.accesskey_new(mem_registry, "a4", events=["view"])
        assert k["events"] == ["view"]
        keys = ops.accesskey_list(mem_registry, "a4")
        assert len(keys) == 2  # app new creates one + explicit one
        ops.accesskey_delete(mem_registry, k["accessKey"])
        assert len(ops.accesskey_list(mem_registry, "a4")) == 1
        with pytest.raises(ValueError, match="does not exist"):
            ops.accesskey_delete(mem_registry, "zzz")


class TestImportExport:
    def test_roundtrip(self, mem_registry, tmp_path):
        info = ops.app_new(mem_registry, "a5")
        src = tmp_path / "events.jsonl"
        lines = [json.dumps({
            "event": "rate", "entityType": "user", "entityId": f"u{i}",
            "targetEntityType": "item", "targetEntityId": "i1",
            "properties": {"rating": float(i)},
            "eventTime": "2020-01-01T00:00:00.000Z"}) for i in range(5)]
        src.write_text("\n".join(lines) + "\n")
        n = ops.import_events(mem_registry, app_id=info["id"],
                              input_path=str(src))
        assert n == 5
        out = tmp_path / "export.jsonl"
        n2 = ops.export_events(mem_registry, app_id=info["id"],
                               output_path=str(out))
        assert n2 == 5
        rows = [json.loads(s) for s in out.read_text().splitlines()]
        assert {r["entityId"] for r in rows} == {f"u{i}" for i in range(5)}

    def test_parquet_roundtrip(self, mem_registry, tmp_path):
        """export -> parquet -> import into a second app reproduces the
        events (EventsToFile.scala:40-108 text|parquet parity)."""
        pytest.importorskip("pyarrow")
        info = ops.app_new(mem_registry, "pq1")
        store = mem_registry.get_events()
        for i in range(7):
            store.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{i}",
                target_entity_type="item", target_entity_id="i1",
                properties=DataMap({"rating": float(i), "tags": ["a", "b"]})),
                info["id"])
        out = tmp_path / "events.parquet"
        n = ops.export_events(mem_registry, app_id=info["id"],
                              output_path=str(out), format="parquet")
        assert n == 7
        info2 = ops.app_new(mem_registry, "pq2")
        n2 = ops.import_events(mem_registry, app_id=info2["id"],
                               input_path=str(out), format="parquet")
        assert n2 == 7
        back = sorted(store.find(info2["id"]), key=lambda e: e.entity_id)
        assert [e.entity_id for e in back] == [f"u{i}" for i in range(7)]
        assert back[3].properties.get("rating") == 3.0
        assert back[3].properties.get("tags") == ["a", "b"]
        assert back[3].target_entity_id == "i1"

    def test_unknown_format_rejected(self, mem_registry, tmp_path):
        info = ops.app_new(mem_registry, "pq3")
        with pytest.raises(ValueError, match="Unknown export format"):
            ops.export_events(mem_registry, app_id=info["id"],
                              output_path=str(tmp_path / "x"), format="csv")


class TestStatus:
    def test_status(self, mem_registry):
        info = ops.status(mem_registry)
        assert info["storage"] == "ok"
        # the device is probed from a child: status itself stays off it
        assert info["device"]["platform"] == "cpu"
        assert info["device"]["device_count"] >= 1
        assert info["native"] == {"eventlog": True}
        from predictionio_tpu.utils.device import backend_initialized
        assert isinstance(backend_initialized(), bool)


class TestTrainBatchPredict:
    def test_train_and_batchpredict(self, mem_registry, tmp_path):
        info = ops.app_new(mem_registry, "bp")
        store = mem_registry.get_events()
        rng = np.random.RandomState(0)
        for u in range(15):
            for i in range(10):
                if rng.rand() < 0.6:
                    store.insert(Event(
                        event="rate", entity_type="user", entity_id=f"u{u}",
                        target_entity_type="item", target_entity_id=f"i{i}",
                        properties=DataMap({"rating": float(rng.randint(1, 6))})),
                        info["id"])
        variant = {
            "id": "default", "engineFactory": "recommendation",
            "datasource": {"params": {"app_name": "bp"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "num_iterations": 3, "seed": 1}}],
        }
        ej = tmp_path / "engine.json"
        ej.write_text(json.dumps(variant))
        result = ops.train(mem_registry, engine_json=str(ej))
        assert result["status"] == "COMPLETED"
        assert result["phaseTimings"].keys() >= {"read_s", "prepare_s",
                                                 "train_algo0_s"}
        # status surfaces the latest train's per-phase tracing record
        info = ops.status(mem_registry)
        latest = info["latestTrainedInstance"]
        assert latest["id"] == result["engineInstanceId"]
        assert "train_algo0_s" in latest["phaseTimings"]
        qfile = tmp_path / "queries.jsonl"
        qfile.write_text("\n".join(
            json.dumps({"user": f"u{u}", "num": 3}) for u in range(5)))
        ofile = tmp_path / "out.jsonl"
        res = ops.batchpredict(mem_registry, engine_json=str(ej),
                               input_path=str(qfile),
                               output_path=str(ofile))
        assert res["predictions"] == 5
        rows = [json.loads(s) for s in ofile.read_text().splitlines()]
        assert rows[0]["query"]["user"] == "u0"
        assert len(rows[0]["prediction"]["itemScores"]) == 3


@pytest.mark.slow
class TestQuickstartSubprocess:
    """Full lifecycle through real CLI subprocesses + HTTP, one scenario."""

    def run_cli(self, args, cwd, env, **kw):
        return subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.cli", *args],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
            **kw)

    def test_quickstart(self, tmp_path):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(
            os.environ,
            PYTHONPATH=repo,
            JAX_PLATFORMS="cpu",
            PIO_STORAGE_SOURCES_PIO_TYPE="SQLITE",
            PIO_STORAGE_SOURCES_PIO_PATH=str(tmp_path / "pio.db"),
        )
        cwd = str(tmp_path)

        r = self.run_cli(["app", "new", "quickstart"], cwd, env)
        assert r.returncode == 0, r.stderr
        app = json.loads(r.stdout)

        # import MovieLens-style events through the import command
        rng = np.random.RandomState(0)
        lines = []
        for u in range(20):
            for i in range(15):
                if rng.rand() < 0.5:
                    lines.append(json.dumps({
                        "event": "rate", "entityType": "user",
                        "entityId": f"u{u}",
                        "targetEntityType": "item", "targetEntityId": f"i{i}",
                        "properties": {
                            "rating": 5.0 if i % 3 == u % 3 else 1.0},
                        "eventTime": "2020-01-01T00:00:00.000Z"}))
        (tmp_path / "events.jsonl").write_text("\n".join(lines))
        r = self.run_cli(["import", "--appid", str(app["id"]),
                          "--input", "events.jsonl"], cwd, env)
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout)["imported"] == len(lines)

        (tmp_path / "engine.json").write_text(json.dumps({
            "id": "default", "engineFactory": "recommendation",
            "datasource": {"params": {"app_name": "quickstart"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "num_iterations": 4, "seed": 7}}],
        }))
        r = self.run_cli(["build"], cwd, env)
        assert r.returncode == 0, r.stderr
        r = self.run_cli(["train"], cwd, env)
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout)["status"] == "COMPLETED"

        # deploy on an ephemeral port and query over HTTP
        proc = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.cli", "deploy",
             "--ip", "127.0.0.1", "--port", "18321"],
            cwd=cwd, env=env, stdout=subprocess.PIPE, text=True)
        try:
            deadline = time.time() + 120
            up = False
            while time.time() < deadline:
                try:
                    req = urllib.request.Request(
                        "http://127.0.0.1:18321/queries.json",
                        data=json.dumps({"user": "u1", "num": 3}).encode(),
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=2) as resp:
                        body = json.loads(resp.read().decode())
                        up = True
                        break
                except Exception:
                    time.sleep(0.5)
            assert up, "prediction server did not come up"
            assert len(body["itemScores"]) == 3
            # undeploy via the CLI
            r = self.run_cli(["undeploy", "--port", "18321"], cwd, env)
            assert r.returncode == 0, r.stderr
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()


@pytest.mark.slow
class TestServiceOps:
    """start-all / stop-all / daemon with pidfiles (bin/pio-start-all,
    bin/pio-stop-all, bin/pio-daemon analogs)."""

    def run_cli(self, args, cwd, env):
        return subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.cli", *args],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=120)

    def test_start_all_stop_all(self, tmp_path):
        import socket

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(
            os.environ,
            PYTHONPATH=repo,
            JAX_PLATFORMS="cpu",
            PIO_STORAGE_SOURCES_PIO_TYPE="SQLITE",
            PIO_STORAGE_SOURCES_PIO_PATH=str(tmp_path / "pio.db"),
        )
        cwd = str(tmp_path)
        ports = []
        socks = []
        for _ in range(3):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            socks.append(s)
        for s in socks:
            s.close()
        pid_dir = str(tmp_path / "run")
        try:
            r = self.run_cli(
                ["start-all", "--ip", "127.0.0.1",
                 "--event-server-port", str(ports[0]),
                 "--dashboard-port", str(ports[1]),
                 "--admin-port", str(ports[2]),
                 "--pid-dir", pid_dir,
                 "--log-dir", str(tmp_path / "log")], cwd, env)
            assert r.returncode == 0, r.stderr + r.stdout
            started = json.loads(r.stdout)
            assert {s["name"] for s in started} == {
                "eventserver", "dashboard", "adminserver"}
            assert all(s["status"] == "up" for s in started)
            # pidfiles exist and all three answer HTTP
            assert len(list((tmp_path / "run").glob("pio-*.pid"))) == 3
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{ports[0]}/", timeout=5) as resp:
                assert resp.status == 200
        finally:
            r = self.run_cli(["stop-all", "--pid-dir", pid_dir], cwd, env)
        assert r.returncode == 0, r.stderr
        stopped = json.loads(r.stdout)
        assert {s["name"] for s in stopped} == {
            "eventserver", "dashboard", "adminserver"}
        assert all(s["status"] == "stopped" for s in stopped)
        assert not list((tmp_path / "run").glob("pio-*.pid"))
        # ports released (SO_REUSEADDR: sockets may linger in TIME_WAIT)
        time.sleep(0.2)
        for port in ports:
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", port))
            s.close()
