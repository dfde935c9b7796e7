"""One process per chip (`utils/device.py`): servers that do not compute
never load jax, processes that compute claim the device and say which,
the compile cache is placed from outside, and a warm-up that raises
fails the deploy. Subprocess tests where the property is about a fresh
process (module table, backend state, jax config)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(code: str, **env) -> subprocess.CompletedProcess:
    full = dict(os.environ, PYTHONPATH=str(REPO), **env)
    for key, value in env.items():
        if value is None:
            full.pop(key)
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], env=full,
        capture_output=True, text=True, timeout=120)


class TestServersStayOffTheChip:
    def test_no_jax_after_start_and_scraper_ticks(self, tmp_path):
        """EventServer, a 0-replica FleetServer, IngestService, the
        dashboard and the admin server: after start plus several tsdb
        scraper ticks `jax` is not even imported, so no backend can
        have been initialised."""
        out = _run("""
            import sys, time
            from predictionio_tpu.data.storage import StorageRegistry
            reg = StorageRegistry({
                "PIO_STORAGE_SOURCES_MEM_TYPE": "MEM",
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM"})

            def event():
                from predictionio_tpu.data.eventserver import (
                    EventServer, EventServerConfig)
                return EventServer(
                    EventServerConfig(ip="127.0.0.1", port=0), reg)

            def fleet():
                from predictionio_tpu.serving import (
                    FleetServer, ServerConfig, fleet_config_from_env)
                return FleetServer(
                    ServerConfig(ip="127.0.0.1", port=0,
                                 engine_factory="recommendation"),
                    fleet_config_from_env(reg.config, replicas=0),
                    registry=reg)

            def ingest():
                from predictionio_tpu.ingest.service import (
                    IngestConfig, IngestService)
                return IngestService(
                    IngestConfig(ip="127.0.0.1", port=0), reg)

            def dashboard():
                from predictionio_tpu.tools.dashboard import (
                    Dashboard, DashboardConfig)
                return Dashboard(
                    DashboardConfig(ip="127.0.0.1", port=0), reg)

            def admin():
                from predictionio_tpu.tools.admin import (
                    AdminConfig, AdminServer)
                return AdminServer(
                    AdminConfig(ip="127.0.0.1", port=0), reg)

            for make in (event, fleet, ingest, dashboard, admin):
                server = make()
                server.start()
                scraper = server._scraper
                assert scraper is not None and scraper.running, make
                time.sleep(0.5)          # >= 5 ticks at 0.1 s
                assert server.tsdb.scrapes >= 3, server.tsdb.scrapes
                loaded = sorted(m for m in sys.modules
                                if m == "jax" or m.startswith("jaxlib"))
                print(make.__name__, "jax modules:", loaded)
                assert not loaded, (make.__name__, loaded)
                assert server.metrics.value(
                    "pio_jax_backend_initialized") == 0.0
                server.shutdown()
            print("OFF-CHIP")
            """, PIO_TSDB_INTERVAL_S="0.1", PIO_WATCHDOG="off",
            PIO_FSCK_ON_STARTUP="off")
        assert out.returncode == 0, out.stdout + out.stderr
        assert "OFF-CHIP" in out.stdout

    def test_sampling_never_initialises_a_backend(self):
        """jax imported but no backend yet: device-memory sampling and
        the pressure guard report nothing and leave it that way."""
        out = _run("""
            import jax
            from predictionio_tpu.obs.profiler import sample_device_memory
            from predictionio_tpu.resilience.pressure import (
                device_memory_frac)
            from predictionio_tpu.utils.device import (
                backend_initialized, live_devices)
            assert sample_device_memory() == 0
            assert device_memory_frac() is None
            assert live_devices() == []
            assert not backend_initialized()
            jax.devices()
            assert backend_initialized() and len(live_devices()) >= 1
            print("UNTOUCHED")
            """)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "UNTOUCHED" in out.stdout


class TestClaimDevice:
    PRINT = """
        import json, jax
        from predictionio_tpu.utils.device import claim_device
        info, cache = claim_device()
        print(json.dumps(dict(
            info, compile_cache=cache,
            jax_config=jax.config.jax_compilation_cache_dir)))
        """

    def test_cache_placed_from_outside_is_left_alone(self, tmp_path):
        out = _run(self.PRINT, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
        assert out.returncode == 0, out.stderr
        info = json.loads(out.stdout.strip().splitlines()[-1])
        # JAX read the variable itself; the program set no other path
        assert info["compile_cache"] == str(tmp_path / "cc")
        assert info["jax_config"] == str(tmp_path / "cc")

    def test_cache_defaults_to_the_checkout(self):
        out = _run(self.PRINT, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=None)
        assert out.returncode == 0, out.stderr
        info = json.loads(out.stdout.strip().splitlines()[-1])
        assert info["compile_cache"] == str(REPO / ".xla_cache")
        assert info["jax_config"] == str(REPO / ".xla_cache")
        assert info["platform"] == "cpu" and info["device_count"] >= 1
        assert info["device_kind"]

    def test_cpu_nobody_asked_for_is_refused(self):
        """No platform named and no accelerator: JAX falls back to the
        CPU without a word; a process that computes must not."""
        out = _run(self.PRINT, JAX_PLATFORMS=None)
        assert out.returncode != 0
        assert "no accelerator" in out.stderr
        assert "JAX_PLATFORMS=cpu" in out.stderr


class TestTrainAndServeNameTheDevice:
    def test_train_result_and_status_json(self, mem_registry, tmp_path):
        import urllib.request

        import numpy as np

        from predictionio_tpu.cli import ops
        from predictionio_tpu.data.event import DataMap, Event
        from predictionio_tpu.serving import PredictionServer, ServerConfig

        info = ops.app_new(mem_registry, "dev")
        store = mem_registry.get_events()
        rng = np.random.RandomState(0)
        for u in range(12):
            for i in range(10):
                if rng.rand() < 0.6:
                    store.insert(Event(
                        event="rate", entity_type="user",
                        entity_id=f"u{u}", target_entity_type="item",
                        target_entity_id=f"i{i}",
                        properties=DataMap({"rating": float(1 + i % 5)})),
                        info["id"])
        ej = tmp_path / "engine.json"
        ej.write_text(json.dumps({
            "id": "default", "engineFactory": "recommendation",
            "datasource": {"params": {"app_name": "dev"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "num_iterations": 2, "seed": 1}}]}))
        result = ops.train(mem_registry, engine_json=str(ej))
        assert result["device"]["platform"] == "cpu"
        assert result["device"]["device_kind"]
        assert result["device"]["device_count"] == 8   # conftest mesh
        assert result["mesh"] == {"data": 8}
        assert set(result["compileCache"]) == {"dir", "hit", "miss"}

        srv = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0,
                         engine_factory="recommendation",
                         batch_window_ms=2, batch_max=4),
            registry=mem_registry)
        srv.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/status.json") as resp:
                status = json.loads(resp.read())
        finally:
            srv.shutdown()
        assert status["device"] == result["device"]
        assert status["compileCache"]
        (plan,) = status["servePlans"]
        assert plan["algorithm"] == "ALSAlgorithm"
        assert plan["plan"] == "BucketedTopK" and plan["shards"] == 1
        assert plan["buckets"] == {"1": "xla", "2": "xla", "4": "xla"}


class TestWarmupFailureFailsTheDeploy:
    def test_raising_warm_serving_fails_prepare_deploy(self, mem_registry):
        from predictionio_tpu.core import (
            CoreWorkflow, EngineParams, RuntimeContext,
        )
        from predictionio_tpu.data.event import DataMap, Event
        from predictionio_tpu.data.storage import App
        from predictionio_tpu.models import recommendation as rec

        app_id = mem_registry.get_meta_data_apps().insert(App(0, "w"))
        events = mem_registry.get_events()
        events.init(app_id)
        for u in range(6):
            for i in range(8):
                events.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": float(1 + (u + i) % 5)})),
                    app_id)
        ctx = RuntimeContext(registry=mem_registry)
        engine = rec.engine()
        params = EngineParams(
            data_source_params=("", rec.DataSourceParams(app_name="w")),
            algorithm_params_list=(
                ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=2,
                                               seed=1)),))
        instance = CoreWorkflow.run_train(engine, params, ctx)

        class Broken(rec.ALSAlgorithm):
            def warm_serving(self, model, buckets, mesh=None):
                raise RuntimeError("kernel did not compile")

        broken = rec.engine()
        broken.algorithm_classes = {"als": Broken, "": Broken}
        with pytest.raises(RuntimeError, match="kernel did not compile"):
            CoreWorkflow.prepare_deploy(broken, instance, ctx,
                                        warm_batch_max=4)
        # the same instance deploys with a warm-up that works
        algos, models, _ = CoreWorkflow.prepare_deploy(
            engine, instance, ctx, warm_batch_max=4)
        assert algos[0]._serve_plan.bucket_kernels() == {
            1: "xla", 2: "xla", 4: "xla"}
