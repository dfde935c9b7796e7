"""Continuous-observatory tests: the always-on sampling profiler
(frame trie bounds, thread-role attribution, /profile.json +
collapsed-stack export), the GC-pause hook, the in-process tsdb ring
(bounds, delta decode, counter-rate math, /tsdb.json?since=
filtering), fleet metrics federation with a member down, the
dashboard sparkline panels, and the `pio-tpu top` terminal view.
"""

import gc
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core import CoreWorkflow, EngineParams, RuntimeContext
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import AccessKey, App
from predictionio_tpu.models import recommendation as rec
from predictionio_tpu.obs import MetricsRegistry
from predictionio_tpu.obs import profiler as prof_mod
from predictionio_tpu.obs import tsdb as tsdb_mod
from predictionio_tpu.obs.profiler import (
    SamplingProfiler, install_gc_callbacks, role_of,
)
from predictionio_tpu.obs.tsdb import TSDB, Scraper, series_key
from predictionio_tpu.serving import (
    FleetConfig, FleetServer, ServerConfig,
)
from predictionio_tpu.tools.admin import run_top, top_view
from predictionio_tpu.tools.dashboard import _fleet_page, _metrics_page
from predictionio_tpu.utils.http import HTTPServerBase, Response

pytestmark = pytest.mark.prof


# -- helpers ----------------------------------------------------------------

def http_get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
        return resp.status, resp.read().decode()


@pytest.fixture()
def obs_server():
    """A bare HTTPServerBase with one route and a fast scraper; the
    process-global profiler is reset afterwards so test order can't
    leak samples between tests."""
    srv = HTTPServerBase(host="127.0.0.1", port=0)

    @srv.router.get("/ping")
    def ping(req):
        return Response.json({"ok": True})

    srv.start()
    yield srv
    srv.shutdown()
    prof_mod._reset_global_for_tests()


@pytest.fixture()
def clean_gc_hooks():
    """Restore gc.callbacks + the per-registry install guard, so a
    test-installed hook can't observe later tests' collections."""
    before = list(gc.callbacks)
    hooked = set(prof_mod._gc_pending)
    yield
    gc.callbacks[:] = before
    for key in set(prof_mod._gc_pending) - hooked:
        del prof_mod._gc_pending[key]


# -- profiler ---------------------------------------------------------------

class TestSamplingProfiler:
    def test_role_of_prefix_table(self):
        assert role_of("wire-reactor-0") == "reactor"
        assert role_of("wire-3") == "worker"
        assert role_of("pio-batch-drain") == "drainer"
        assert role_of("pio-refresher") == "refresher"
        assert role_of("pio-fleet-health") == "heartbeat"
        assert role_of("pio-prof-sampler") == "obs"
        assert role_of("pio-tsdb-scraper") == "obs"
        assert role_of("pio-http-serve-8000") == "http"
        assert role_of("MainThread") == "main"
        assert role_of("Thread-17") == "other"

    def test_hz_zero_never_starts(self):
        prof = SamplingProfiler(hz=0)
        assert prof.start() is False
        assert prof.running is False
        assert prof.snapshot_json()["running"] is False

    def test_trie_bounds_and_role_attribution(self):
        """Deep stacks from named threads under a live sample loop:
        the node budget holds, truncation is counted, and samples land
        on the thread-name-derived role."""
        prof = SamplingProfiler(hz=0, max_nodes=16)
        halt = threading.Event()

        def _deep(n):
            if n > 0:
                return _deep(n - 1)
            halt.wait(10)

        threads = [threading.Thread(target=_deep, args=(40,),
                                    name=f"wire-reactor-{k}", daemon=True)
                   for k in range(3)]
        for t in threads:
            t.start()
        try:
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                prof.sample_once()
                snap = prof.snapshot_json()
                if (snap["roles"].get("reactor", {}).get("samples", 0) >= 3
                        and snap["trie"]["truncated_samples"] > 0):
                    break
                time.sleep(0.01)
        finally:
            halt.set()
            for t in threads:
                t.join(5)
        snap = prof.snapshot_json()
        assert snap["trie"]["nodes"] <= 16
        assert snap["trie"]["truncated_samples"] > 0
        assert snap["roles"]["reactor"]["samples"] >= 3
        assert snap["samples"] >= sum(
            r["samples"] for r in snap["roles"].values()) > 0

    def test_collapsed_stack_format(self):
        from predictionio_tpu.obs.profiler import _ROLE_PREFIXES
        roles = {role for _, role in _ROLE_PREFIXES} | {"other"}
        prof = SamplingProfiler(hz=0, max_nodes=256)
        prof.sample_once()
        out = prof.collapsed()
        assert out.endswith("\n")
        for line in out.strip().splitlines():
            path, _, count = line.rpartition(" ")
            assert int(count) >= 1
            # any role the profiler knows: other tests of this xdist
            # worker may have left a joiner or a supervisor alive
            assert path.split(";")[0] in roles
        # the sampling frame itself must be on some path
        assert "profiler.py:sample_once" in out

    def test_reset_clears_state(self):
        prof = SamplingProfiler(hz=0)
        prof.sample_once()
        assert prof.snapshot_json()["samples"] > 0
        prof.reset()
        snap = prof.snapshot_json()
        assert snap["samples"] == 0 and snap["trie"]["nodes"] == 0
        assert prof.collapsed() == ""


class TestGCPauseHook:
    def test_histogram_fires_on_collect(self, clean_gc_hooks):
        reg = MetricsRegistry()
        assert install_gc_callbacks(reg) is True
        assert install_gc_callbacks(reg) is False   # idempotent
        gc.collect()
        assert prof_mod.flush_gc_pauses(reg) >= 1
        fam = reg.snapshot()["pio_gc_pause_seconds"]
        assert fam["type"] == "histogram"
        assert sum(s["count"] for s in fam["series"]) >= 1
        gens = {s["labels"]["generation"] for s in fam["series"]}
        assert "2" in gens          # gc.collect() is a full collection

    def test_collection_while_the_family_lock_is_held(self, clean_gc_hooks):
        """A collection can start inside a thread that holds the gc
        histogram's own lock (mid-render): the hook must not take it.
        Seen on the chip machine: every `/metrics` of an idle event
        server hung from ~40 s after start."""
        reg = MetricsRegistry()
        install_gc_callbacks(reg)
        hook = gc.callbacks[-1]
        fam = reg.histogram("pio_gc_pause_seconds", "", labels=(
            "generation",), buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01,
                                     0.05, 0.1, 0.5))
        done = threading.Event()

        def collect_under_the_lock():
            with fam._lock:
                hook("start", {})
                hook("stop", {"generation": 2})
            done.set()

        t = threading.Thread(target=collect_under_the_lock, daemon=True,
                             name="pio-test-gc")
        t.start()
        assert done.wait(5), "gc hook blocked on the family lock"
        assert prof_mod.flush_gc_pauses(reg) == 1
        assert reg.render().count("pio_gc_pause_seconds_count") == 1


# -- tsdb ring --------------------------------------------------------------

class TestTSDB:
    def test_series_key_canonical(self):
        assert series_key("m", {}) == "m"
        assert series_key("m", {"b": "2", "a": "1"}) == "m{a=1,b=2}"
        assert series_key("m", {"a": "1"}, "p99") == "m{a=1}:p99"

    def test_ring_bounds_and_delta_decode(self):
        db = TSDB(points=5)
        base = 1_000_000.0
        for k in range(10):
            db.record_value("g", "gauge", base + k, float(k))
        pts = db.to_json(series="g")["series"]["g"]["points"]
        assert len(pts) == 5                       # bounded
        assert [v for _, v in pts] == [5.0, 6.0, 7.0, 8.0, 9.0]
        # delta encoding decodes back to absolute timestamps
        assert [t for t, _ in pts] == pytest.approx(
            [base + k for k in range(5, 10)], abs=0.002)
        assert db.latest("g") == 9.0

    def test_counter_rate_math_and_reset_guard(self):
        db = TSDB(points=10)
        snap = lambda v: {"c_total": {             # noqa: E731
            "type": "counter", "help": "",
            "series": [{"labels": {}, "value": v}]}}
        db.record_snapshot(snap(0.0), now=100.0)   # first sighting: no rate
        assert db.keys() == []
        db.record_snapshot(snap(50.0), now=105.0)
        assert db.latest("c_total:rate") == pytest.approx(10.0)
        # counter reset (restart): no bogus negative spike
        db.record_snapshot(snap(5.0), now=110.0)
        pts = db.to_json(series="c_total")["series"]["c_total:rate"]
        assert len(pts["points"]) == 1
        # and the rate resumes from the reset base
        db.record_snapshot(snap(25.0), now=115.0)
        assert db.latest("c_total:rate") == pytest.approx(4.0)

    def test_histogram_fold_quantiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", "h", buckets=(0.01, 0.1, 1.0))
        for _ in range(100):
            h.observe(0.05)
        db = TSDB(points=10)
        db.record_snapshot(reg.snapshot(), now=100.0)
        assert db.latest("lat_seconds:p50") is not None
        assert db.latest("lat_seconds:p99") is not None

    def test_max_series_cap_counts_drops(self):
        db = TSDB(points=4, max_series=2)
        db.record_value("a", "gauge", 1.0, 1.0)
        db.record_value("b", "gauge", 1.0, 1.0)
        db.record_value("c", "gauge", 1.0, 1.0)
        assert sorted(db.keys()) == ["a", "b"]
        assert db.to_json()["dropped_series"] == 1

    def test_scraper_tick_disable_and_broken_collector(self):
        reg = MetricsRegistry()
        reg.gauge("g_now", "h").set(7.0)
        db = TSDB(points=8)
        calls = []

        def _boom():
            calls.append(1)
            raise RuntimeError("collector down")

        sc = Scraper(db, reg, interval_s=0, collectors=(_boom,))
        assert sc.start() is False          # interval 0: loop never exists
        assert sc.running is False
        sc.tick(now=50.0)                   # broken collector is swallowed,
        assert calls == [1]                 # the scrape still lands
        assert db.latest("g_now") == 7.0


# -- endpoints on every server ----------------------------------------------

class TestObservatoryEndpoints:
    def test_profile_json_and_collapsed(self, obs_server):
        prof = prof_mod.get_profiler()
        for _ in range(3):
            http_get(obs_server.port, "/ping")
            prof.sample_once()
        status, body = http_get(obs_server.port, "/profile.json")
        assert status == 200
        snap = json.loads(body)
        for field in ("hz", "running", "samples", "roles", "top_self",
                      "top_cumulative", "trie"):
            assert field in snap
        assert snap["samples"] > 0
        assert "main" in snap["roles"] or "http" in snap["roles"]
        status, text = http_get(obs_server.port,
                                "/profile.txt?fmt=collapsed")
        assert status == 200
        line = text.strip().splitlines()[0]
        assert int(line.rpartition(" ")[2]) >= 1
        # non-collapsed fmt: the human summary
        status, text = http_get(obs_server.port, "/profile.txt?fmt=top")
        assert status == 200 and "samples" in text

    def test_tsdb_endpoint_series_and_since_filter(self, obs_server):
        db = obs_server.tsdb
        db.record_value("synth_g", "gauge", 1000.0, 1.0)
        db.record_value("synth_g", "gauge", 2000.0, 2.0)
        db.record_value("synth_other", "gauge", 2000.0, 9.0)
        status, body = http_get(obs_server.port,
                                "/tsdb.json?series=synth_g")
        assert status == 200
        out = json.loads(body)
        assert list(out["series"]) == ["synth_g"]
        assert len(out["series"]["synth_g"]["points"]) == 2
        status, body = http_get(
            obs_server.port, "/tsdb.json?series=synth_g&since=1500")
        pts = json.loads(body)["series"]["synth_g"]["points"]
        assert pts == [[2000.0, 2.0]]

    def test_live_scrape_captures_host_gauges(self, obs_server):
        """One forced scrape tick lands the /proc gauges in the ring
        without waiting out the default 5 s interval."""
        sc = tsdb_mod.Scraper(obs_server.tsdb, obs_server.metrics,
                              interval_s=0,
                              collectors=obs_server._obs_collectors())
        sc.tick()
        assert (obs_server.tsdb.latest("pio_host_rss_bytes") or 0) > 0
        status, body = http_get(obs_server.port, "/tsdb.json")
        assert status == 200
        assert "pio_host_rss_bytes" in json.loads(body)["series"]

    def test_top_view_renders_and_errors(self, obs_server):
        prof_mod.get_profiler().sample_once()
        tsdb_mod.Scraper(obs_server.tsdb, obs_server.metrics,
                         interval_s=0,
                         collectors=obs_server._obs_collectors()).tick()
        view = top_view("127.0.0.1", obs_server.port)
        assert f"127.0.0.1:{obs_server.port}" in view
        assert "profiler:" in view and "rss" in view
        lines = []
        assert run_top("127.0.0.1", obs_server.port,
                       out=lines.append) == 0
        assert "pio-tpu top" in lines[0]
        # unreachable server: [ERROR] + rc 1, no traceback
        assert run_top("127.0.0.1", 1, out=lines.append) == 1
        assert lines[-1].startswith("[ERROR]")


# -- fleet federation -------------------------------------------------------

@pytest.fixture()
def fleet_trained(mem_registry):
    apps = mem_registry.get_meta_data_apps()
    app_id = apps.insert(App(0, "profapp"))
    mem_registry.get_meta_data_access_keys().insert(
        AccessKey("PKEY", app_id, ()))
    events = mem_registry.get_events()
    events.init(app_id)
    rng = np.random.RandomState(0)
    for u in range(12):
        for i in range(10):
            if rng.rand() > 0.5:
                continue
            events.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": 4.0})), app_id)
    ctx = RuntimeContext(registry=mem_registry)
    engine = rec.engine()
    params = EngineParams(
        data_source_params=("", rec.DataSourceParams(app_name="profapp")),
        algorithm_params_list=(
            ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=2,
                                           seed=1)),))
    CoreWorkflow.run_train(engine, params, ctx)
    return mem_registry, engine


class TestFleetFederation:
    def test_federate_covers_members_and_survives_death(
            self, fleet_trained):
        registry, engine = fleet_trained
        fleet = FleetServer(
            ServerConfig(ip="127.0.0.1", port=0),
            FleetConfig(replicas=3, health_interval_s=0.1,
                        eject_threshold=2, drain_timeout_s=2.0),
            registry=registry, engine=engine)
        fleet.start()
        try:
            fleet._scrape_members()     # forced tick, no interval wait
            members = [rep.key for rep in fleet._replicas]
            status, text = http_get(fleet.port, "/federate")
            assert status == 200
            for key in members:
                assert f'member="{key}"' in text
            # derived per-member gauges land in the router's own ring
            fleet._scrape_members()
            snap = fleet.metrics.snapshot()
            burn_series = snap["pio_fleet_member_burn"]["series"]
            # superset, not equality: the metrics registry is process
            # global and earlier suites (elastic chaos scenarios) leave
            # their own fleets' member series behind
            assert {s["labels"]["member"] for s in burn_series} >= set(
                members)
            ok_before = fleet.metrics.value(
                "pio_fleet_metrics_scrapes_total", outcome="ok")
            assert ok_before >= 3

            # abrupt member death: the scrape fails, suspicion
            # advances, /federate keeps serving last-good text
            victim = fleet._replicas[0]
            victim.server.shutdown()
            fails_before = victim.failures
            fleet._scrape_members()
            assert fleet.metrics.value(
                "pio_fleet_metrics_scrapes_total", outcome="error") >= 1
            assert victim.failures > fails_before
            status, text = http_get(fleet.port, "/federate")
            assert status == 200
            for key in members:         # cached text still covers all
                assert f'member="{key}"' in text
        finally:
            fleet.stop()

    def test_federate_empty_before_first_scrape(self, fleet_trained):
        registry, engine = fleet_trained
        fleet = FleetServer(
            ServerConfig(ip="127.0.0.1", port=0),
            FleetConfig(replicas=1, health_interval_s=0.1),
            registry=registry, engine=engine)
        fleet.start()
        try:
            status, text = http_get(fleet.port, "/federate")
            assert status == 200        # empty, not an error
        finally:
            fleet.stop()


# -- dashboard sparklines ---------------------------------------------------

class TestDashboardHistory:
    def test_metrics_page_sparklines(self):
        reg = MetricsRegistry()
        db = TSDB(points=16)
        for k in range(6):
            db.record_value("pio_host_rss_bytes", "gauge",
                            1000.0 + k, 1e6 + k * 1e5)
        html = _metrics_page(reg, tsdb=db)
        assert "<svg" in html and "polyline" in html
        assert "pio_host_rss_bytes" in html
        assert "/tsdb.json" in html

    def test_metrics_page_without_history(self):
        html = _metrics_page(MetricsRegistry(), tsdb=None)
        assert "<html" in html          # panel absent, page intact

    def test_fleet_page_members_and_history(self):
        db = TSDB(points=16)
        for k in range(4):
            db.record_value("pio_fleet_member_qps{member=127.0.0.1:9}",
                            "gauge", 1000.0 + k, 100.0 + k)
        members = [{"replica": 0, "member": "127.0.0.1:9",
                    "state": "serving", "admitted": True, "remote": False,
                    "failures": 0, "inflight": 0, "model": "m1",
                    "beat_age_s": 0.1, "port": 9}]
        html = _fleet_page(db, members)
        assert "127.0.0.1:9" in html
        assert "<svg" in html and "polyline" in html
        assert "/federate" in html
