"""Flight-recorder suite: span stamping, sampling/keep, fleet-hop
stitching, SLO burn accounting.

Covers the observability PR the way an operator would use it:

  - the X-PIO-Trace codec: round-trip, signed verify, refuse-by-default
    on malformed/forged values
  - keep policy: head sampling, error keep, slow-decile keep, the
    bounded ring under sustained load
  - end-to-end serve traces: one /queries.json call through the live
    server yields a ring entry whose stage spans tile >= 90% of the
    measured wall time, resolvable through /traces.json, with the p99
    exemplar on pio_serve_seconds pointing at a real kept trace
  - fleet stitching: a 3-replica fleet query produces router + replica
    entries under ONE trace id; a standby's 307 redirect carries the
    trace header so the re-dialled request stitches too
  - chaos: replica killed under load at sample=1.0 still costs zero
    failed requests (tracing must never turn into availability)
  - SLO burn math, DAO-backed per-app overrides, /ready detail
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
from predictionio_tpu.data.storage import App, SLOObjective
from predictionio_tpu.models import recommendation as rec
from predictionio_tpu.obs import MetricsRegistry, get_registry
from predictionio_tpu.obs import trace
from predictionio_tpu.obs.slo import SLOTracker, dao_overrides_loader
from predictionio_tpu.serving import (
    FleetConfig, FleetServer, PredictionServer, ServerConfig,
)

pytestmark = pytest.mark.trace


@pytest.fixture(autouse=True)
def _trace_reset():
    """Every test leaves the process recorder back at env defaults
    (sampling off) so foreign suites never inherit a hot recorder."""
    yield
    trace.configure(sample=0.0)


def call(port, method, path, body=None, headers=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req) as resp:
            raw = resp.read().decode()
            ct = resp.headers.get("Content-Type", "")
            return resp.status, (json.loads(raw) if "json" in ct else raw)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


# -- header codec -------------------------------------------------------------

class TestHeaderCodec:
    def test_roundtrip_unsigned(self):
        tid, sid = "ab" * 16, "cd" * 8
        value = trace.encode_header(tid, sid, True)
        assert trace.decode_header(value) == (tid, sid, True)
        value = trace.encode_header(tid, sid, False)
        assert trace.decode_header(value) == (tid, sid, False)

    def test_roundtrip_signed(self):
        tid, sid = "12" * 16, "34" * 8
        value = trace.encode_header(tid, sid, True, key="sekrit")
        assert trace.decode_header(value, key="sekrit") == (tid, sid, True)

    def test_forged_signature_refused(self):
        tid, sid = "12" * 16, "34" * 8
        value = trace.encode_header(tid, sid, True, key="sekrit")
        assert trace.decode_header(value, key="other") is None
        # unsigned value against a keyed decoder: refused too
        bare = trace.encode_header(tid, sid, True)
        assert trace.decode_header(bare, key="sekrit") is None

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "xx-yy-1", "ab" * 16 + "-" + "cd" * 8,
        "zz" * 16 + "-" + "cd" * 8 + "-1",          # non-hex trace id
        "ab" * 16 + "-" + "cd" * 8 + "-2",          # bad flag
        "ab" * 15 + "-" + "cd" * 8 + "-1",          # short trace id
    ])
    def test_malformed_refused(self, bad):
        assert trace.decode_header(bad) is None

    def test_adopt_joins_upstream_trace(self):
        trace.configure(sample=1.0, ring=16)
        p = trace.PendingTrace()
        tid, sid = "ef" * 16, "01" * 8
        trace.adopt(p, trace.encode_header(tid, sid, True))
        assert p.trace_id == tid
        assert p.parent_id == sid
        assert p.sampled is True


# -- keep policy + ring -------------------------------------------------------

def _run_one(rec_, sampled=False, status=200, dur_s=0.001, app=""):
    """Feed one synthetic request through the recorder."""
    p = trace.PendingTrace()
    t0 = time.perf_counter() - dur_s
    p.st[trace.S_WIRE_READ] = t0
    p.st[trace.S_FRAMED] = t0 + dur_s * 0.1
    p.st[trace.S_HANDLER] = t0 + dur_s * 0.2
    p.st[trace.S_EXEC] = t0 + dur_s * 0.8
    p.st[trace.S_SENT] = t0 + dur_s
    p.sampled = sampled
    p.status = status
    p.kind = "serve"
    p.app = app
    rec_.finish(p)
    return p


class TestKeepPolicy:
    def test_head_sample_kept(self):
        rec_ = trace.configure(sample=1.0, ring=32)
        _run_one(rec_, sampled=True)
        snap = rec_.snapshot()
        assert len(snap) == 1 and snap[0]["keep"] == "sampled"

    def test_error_kept_even_unsampled(self):
        rec_ = trace.configure(sample=0.5, ring=32)
        _run_one(rec_, sampled=False, status=500)
        snap = rec_.snapshot()
        assert len(snap) == 1 and snap[0]["keep"] == "error"

    def test_slow_decile_kept_after_warmup(self):
        rec_ = trace.configure(sample=0.5, ring=256)
        for _ in range(100):                      # warm the p90 estimate
            _run_one(rec_, dur_s=0.0005)
        _run_one(rec_, dur_s=0.25)                # a 500x outlier
        snap = rec_.snapshot(min_ms=100.0)
        assert snap and snap[0]["keep"] == "slow"

    def test_ring_bounded_under_sustained_load(self):
        rec_ = trace.configure(sample=1.0, ring=16)
        for _ in range(500):
            _run_one(rec_, sampled=True)
        assert rec_.ring_len() == 16
        assert len(rec_.snapshot()) == 16

    def test_snapshot_filters(self):
        rec_ = trace.configure(sample=1.0, ring=64)
        _run_one(rec_, sampled=True, app="a", dur_s=0.001)
        _run_one(rec_, sampled=True, app="b", dur_s=0.05)
        assert {e["app"] for e in rec_.snapshot()} == {"a", "b"}
        assert all(e["app"] == "a" for e in rec_.snapshot(app="a"))
        assert all(e["duration_ms"] >= 10.0
                   for e in rec_.snapshot(min_ms=10.0))
        tid = rec_.snapshot()[0]["trace_id"]
        assert [e["trace_id"] for e in rec_.snapshot(trace_id=tid)] == [tid]
        assert len(rec_.snapshot(limit=1)) == 1

    def test_spans_tile_the_duration(self):
        rec_ = trace.configure(sample=1.0, ring=8)
        _run_one(rec_, sampled=True, dur_s=0.01)
        entry = rec_.snapshot()[0]
        covered = sum(s["dur_ms"] for s in entry["spans"])
        assert covered >= 0.9 * entry["duration_ms"]

    def test_background_span_lands_in_ring(self):
        rec_ = trace.configure(sample=1.0, ring=8)
        with trace.background("unit_tick"):
            pass
        with pytest.raises(RuntimeError):
            with trace.background("unit_fail"):
                raise RuntimeError("boom")
        names = {(e["name"], e.get("error", ""))
                 for e in rec_.snapshot()}
        assert ("unit_tick", "") in names
        assert ("unit_fail", "RuntimeError") in names
        assert all(e["kind"] == "background" for e in rec_.snapshot())

    def test_disabled_recorder_allocates_nothing(self):
        trace.configure(sample=0.0)
        assert trace.new_stamps(time.perf_counter()) is None


# -- SLO burn math ------------------------------------------------------------

class TestSLO:
    def test_burn_math(self):
        t = SLOTracker(latency_ms=100.0, target=0.999)
        now = 1_000_000.0
        # 999 good + 1 bad in a 0.1% budget -> burn exactly 1.0
        for _ in range(999):
            t.record("app1", 0.01, ok=True, now=now)
        t.record("app1", 0.01, ok=False, now=now)
        snap = t.snapshot(now=now)
        assert snap["app1"]["burn_5m"] == pytest.approx(1.0, rel=1e-6)
        assert snap["app1"]["degraded"] is False

    def test_latency_threshold_counts_as_bad(self):
        t = SLOTracker(latency_ms=50.0, target=0.99)
        now = 2_000_000.0
        t.record("a", 0.2, ok=True, now=now)      # slow: bad
        t.record("a", 0.01, ok=True, now=now)     # fast: good
        snap = t.snapshot(now=now)
        # bad fraction 0.5 over budget 0.01 -> burn 50
        assert snap["a"]["burn_5m"] == pytest.approx(50.0, rel=1e-6)
        assert snap["a"]["degraded"] is True
        assert t.degraded(now=now) is True

    def test_window_expiry(self):
        t = SLOTracker(latency_ms=100.0, target=0.999)
        now = 3_000_000.0
        t.record("a", 0.5, ok=False, now=now)
        assert t.snapshot(now=now)["a"]["burn_5m"] > 0
        # 10 minutes later the 5m window is clean, the 1h one still sees it
        later = now + 600.0
        t.record("a", 0.01, ok=True, now=later)
        snap = t.snapshot(now=later)
        assert snap["a"]["burn_5m"] == 0.0
        assert snap["a"]["burn_1h"] > 0.0

    def test_app_map_bounded(self):
        t = SLOTracker(latency_ms=100.0, target=0.999, max_apps=4)
        now = 4_000_000.0
        for n in range(32):
            t.record(f"app{n}", 0.01, ok=True, now=now)
        assert len(t.snapshot(now=now)) == 4

    def test_dao_overrides(self, mem_registry):
        apps = mem_registry.get_meta_data_apps()
        app_id = apps.insert(App(0, "gold"))
        mem_registry.get_meta_data_slo_objectives().upsert(
            SLOObjective(app_id, latency_ms=10.0, target=0.99))
        loader = dao_overrides_loader(mem_registry)
        assert loader is not None
        assert loader() == {"gold": (10.0, 0.99)}
        t = SLOTracker(latency_ms=250.0, target=0.999, loader=loader,
                       loader_ttl_s=0.0)
        now = 5_000_000.0
        t.record("gold", 0.05, ok=True, now=now)   # 50ms > 10ms override
        snap = t.snapshot(now=now)
        assert snap["gold"]["latency_ms"] == 10.0
        assert snap["gold"]["target"] == 0.99
        assert snap["gold"]["burn_5m"] > 0.0

    def test_loader_failure_degrades_to_defaults(self):
        def _boom():
            raise RuntimeError("store down")
        t = SLOTracker(latency_ms=250.0, target=0.999, loader=_boom,
                       loader_ttl_s=0.0)
        now = 6_000_000.0
        t.record("a", 0.01, ok=True, now=now)
        assert t.snapshot(now=now)["a"]["latency_ms"] == 250.0


# -- live-server traces -------------------------------------------------------

@pytest.fixture()
def trained(mem_registry):
    """Registry with a trained recommendation instance."""
    apps = mem_registry.get_meta_data_apps()
    app_id = apps.insert(App(0, "traceapp"))
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
        data_source_params=("", rec.DataSourceParams(app_name="traceapp")),
        algorithm_params_list=(
            ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=4,
                                           seed=1)),))
    CoreWorkflow.run_train(engine, params, ctx)
    return mem_registry, engine


def _start_server(trained, **cfg):
    registry, engine = trained
    srv = PredictionServer(ServerConfig(ip="127.0.0.1", port=0, **cfg),
                           registry=registry, engine=engine)
    srv.start()
    return srv


def _serve_entries(snap):
    return [e for e in snap if e["kind"] == "serve"]


class TestServerTraces:
    def test_query_trace_covers_wall_time(self, trained):
        trace.configure(sample=1.0, ring=64)
        srv = _start_server(trained)
        try:
            status, _ = call(srv.port, "POST", "/queries.json",
                             {"user": "u1", "num": 3})
            assert status == 200
            deadline = time.perf_counter() + 5.0
            while time.perf_counter() < deadline:
                entries = _serve_entries(trace.get_recorder().snapshot())
                if entries:
                    break
                time.sleep(0.01)
            assert entries, "no serve trace landed in the ring"
            e = entries[0]
            assert e["status"] == 200
            assert e["name"] == "/queries.json"
            covered = sum(s["dur_ms"] for s in e["spans"])
            assert covered >= 0.9 * e["duration_ms"]
            names = {s["name"] for s in e["spans"]}
            assert "wire_write" in names or "respond" in names
        finally:
            srv.shutdown()

    def test_batched_trace_carries_batch_and_dispatch(self, trained):
        trace.configure(sample=1.0, ring=64)
        srv = _start_server(trained, batch_window_ms=2)
        try:
            for n in range(4):
                status, _ = call(srv.port, "POST", "/queries.json",
                                 {"user": f"u{n}", "num": 3})
                assert status == 200
            deadline = time.perf_counter() + 5.0
            entries = []
            while time.perf_counter() < deadline:
                entries = [e for e in
                           _serve_entries(trace.get_recorder().snapshot())
                           if e.get("batch_size")]
                if entries:
                    break
                time.sleep(0.01)
            assert entries, "no batched serve trace in the ring"
            e = entries[0]
            assert e["batch_id"] >= 1 and e["batch_size"] >= 1
            assert e["dispatch"] in ("host", "device", "sharded", "fused")
            names = {s["name"] for s in e["spans"]}
            # the member's segment between drain and splice is named for
            # what it spans: its batch, not the device
            assert "batch" in names and "device_exec" not in names
        finally:
            srv.shutdown()

    def test_traces_json_endpoint_and_exemplar(self, trained):
        trace.configure(sample=1.0, ring=256)
        srv = _start_server(trained)
        try:
            for n in range(40):
                call(srv.port, "POST", "/queries.json",
                     {"user": f"u{n % 20}", "num": 3})
            status, body = call(srv.port, "GET", "/traces.json")
            assert status == 200 and body["enabled"] is True
            assert body["count"] == len(body["traces"]) > 0
            # p99 exemplar on the serve histogram resolves to a kept trace
            hist = get_registry().histogram(
                "pio_serve_seconds", trace.SERVE_SECONDS_HELP,
                labels=("app",), buckets=trace.SERVE_BUCKETS)
            # the series is process-global: earlier suites may have
            # parked the cumulative p99 — and stale exemplars — in
            # buckets this test's requests never reach, so accept any
            # bucket exemplar still resolvable in the live ring
            # (exemplar → trace resolution is what's under test; the
            # p99 link itself is the dashboard's job)
            child = hist.labels(app="")
            deadline = time.perf_counter() + 5.0
            tid = None
            while time.perf_counter() < deadline and tid is None:
                p99 = child.exemplar_for_quantile(0.99)
                cands = [p99] if p99 else []
                cands += sorted((child.exemplars or {}).values(),
                                key=lambda e: -e[2])
                rec_ = trace.get_recorder()
                tid = next((c[0] for c in cands
                            if rec_.snapshot(trace_id=c[0])), None)
                if tid is None:
                    time.sleep(0.01)
            assert tid is not None, "no ring-resolvable exemplar recorded"
            status, body = call(srv.port, "GET",
                                f"/traces.json?trace_id={tid}")
            assert status == 200
            assert [t["trace_id"] for t in body["traces"]].count(tid) >= 1
            # filters pass through
            status, body = call(srv.port, "GET",
                                "/traces.json?min_ms=1e9")
            assert status == 200 and body["count"] == 0
        finally:
            srv.shutdown()

    def test_tracing_off_serves_and_reports_disabled(self, trained):
        trace.configure(sample=0.0)
        srv = _start_server(trained)
        try:
            status, _ = call(srv.port, "POST", "/queries.json",
                             {"user": "u1", "num": 3})
            assert status == 200
            status, body = call(srv.port, "GET", "/traces.json")
            assert status == 200 and body["enabled"] is False
        finally:
            srv.shutdown()

    def test_wire_metrics_exported(self, trained):
        srv = _start_server(trained)
        try:
            call(srv.port, "POST", "/queries.json", {"user": "u1", "num": 3})
            status, text = call(srv.port, "GET", "/metrics")
            assert status == 200
            if srv.wire == "selector":
                assert "pio_wire_requests_total" in text
                assert "pio_wire_connections_open" in text
        finally:
            srv.shutdown()

    def test_ready_surfaces_slo_detail(self, trained):
        trace.configure(sample=1.0, ring=64)
        srv = _start_server(trained)
        try:
            call(srv.port, "POST", "/queries.json", {"user": "u1", "num": 3})
            status, body = call(srv.port, "GET", "/ready")
            assert status == 200
            assert "slo" in body
            assert body["sloDegraded"] is False
            assert "(default)" in body["slo"]
        finally:
            srv.shutdown()


# -- batch cycles: one record a cycle, stages that tile it --------------------

# the stages an ALS cycle passes: a sequence model's own (history and
# the stack's pack / launch / fetch) are tests/test_backbone.py's
_STAGES = tuple(n for n in trace.STAGES if n not in trace.SEQ_STAGES)
_LEAVES = tuple(n for n in _STAGES if n != "predict")
_PREDICT_CHILDREN = ("lookup", "pack", "launch", "fetch", "unpack")


def _metrics_server(trained, **cfg):
    """A server over its own registry, so that a test reads only what
    its own requests observed."""
    registry, engine = trained
    srv = PredictionServer(ServerConfig(ip="127.0.0.1", port=0, **cfg),
                           registry=registry, engine=engine,
                           metrics=MetricsRegistry())
    srv.start()
    return srv


def _hammer(port, callers=6, each=6, banned_every=3):
    """Concurrent callers, every `banned_every`-th query with a
    blackList (the generic route; the others take the wire fast path)."""
    def one(n):
        for j in range(each):
            body = {"user": f"u{(n + j) % 20}", "num": 3}
            if (n + j) % banned_every == 0:
                body["blackList"] = ["i1", "i2"]
            status, _ = call(port, "POST", "/queries.json", body)
            assert status == 200
    threads = [threading.Thread(target=one, args=(n,))
               for n in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return callers * each


def _drained(srv, timeout=5.0):
    """Wait until the drainer has retired: a cycle's stages are
    observed after its waiters are woken."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if not srv._batcher._draining:
            return
        time.sleep(0.005)
    raise AssertionError("the drainer did not retire")


def _stage_totals(srv):
    return {key[0]: (child.count, child.sum)
            for key, child in srv._serve_obs.stage._items()}


class TestBatchCycle:
    def test_leaf_stages_tile_the_cycle(self, trained):
        srv = _metrics_server(trained, batch_window_ms=5, batch_max=8)
        try:
            _hammer(srv.port)
            _drained(srv)
            tot = _stage_totals(srv)
            cycles, cycle_s = tot["cycle"]
            assert cycles >= 1 and cycle_s > 0.0
            leaves = sum(tot[n][1] for n in _LEAVES)
            assert abs(leaves - cycle_s) <= 0.02 * cycle_s
            children = sum(tot[n][1] for n in _PREDICT_CHILDREN)
            assert abs(children - tot["predict"][1]) \
                <= 0.02 * tot["predict"][1]
            assert tot["host"][1] == pytest.approx(
                cycle_s - tot["fetch"][1], rel=1e-9)
            # every stage of every cycle, and nothing for the empty
            # windows in which the drainer retired
            batches = srv._serve_obs.batch_size.labels().count
            for name in _STAGES + ("cycle", "host"):
                assert tot[name][0] == batches == cycles, name
        finally:
            srv.shutdown()

    def test_stages_and_wire_waits_observed_with_recorder_off(self, trained):
        trace.configure(sample=0.0)
        srv = _metrics_server(trained, batch_window_ms=5, batch_max=8)
        try:
            sent = _hammer(srv.port)
            _drained(srv)
            status, text = call(srv.port, "GET", "/metrics")
            assert status == 200
            for name in _STAGES + ("cycle", "host"):
                line = next(ln for ln in text.splitlines() if ln.startswith(
                    f'pio_serve_stage_seconds_count{{stage="{name}"}}'))
                assert float(line.split()[-1]) >= 1, name
            obs = srv._serve_obs
            # both routes, every request: fast path and generic
            assert obs.worker_wait.labels().count == sent
            assert obs.queue_delay.labels().count == sent
            assert srv._ss0.count == sent
            deadline = time.perf_counter() + 5.0
            while obs.reply.labels().count < sent \
                    and time.perf_counter() < deadline:
                time.sleep(0.005)
            assert obs.reply.labels().count == sent
            assert "pio_wire_worker_wait_seconds_count" in text
            assert "pio_wire_reply_seconds_count" in text
            assert trace.get_recorder().ring_len() == 0
        finally:
            srv.shutdown()

    @pytest.mark.parametrize("banned", [False, True],
                             ids=["fast_path", "generic_route"])
    @pytest.mark.parametrize("sample", [0.0, 1.0],
                             ids=["recorder_off", "recorder_on"])
    def test_serve_seconds_is_the_handlers_interval(self, trained, sample,
                                                    banned):
        """One meaning on both routes, recorder on or off: handler
        entry to handler return, inside the client's wall time and
        inside the kept trace's wire-to-wire duration."""
        registry, engine = trained
        m = MetricsRegistry()
        trace.configure(sample=sample, ring=64, metrics=m)
        srv = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0, batch_window_ms=2),
            registry=registry, engine=engine, metrics=m)
        srv.start()
        try:
            body = {"user": "u1", "num": 3}
            if banned:
                body["blackList"] = ["i1"]
            t0 = time.perf_counter()
            status, _ = call(srv.port, "POST", "/queries.json", body)
            wall = time.perf_counter() - t0
            assert status == 200
            child = srv._ss0
            deadline = time.perf_counter() + 5.0
            while child.count < 1 and time.perf_counter() < deadline:
                time.sleep(0.005)
            assert child.count == 1
            assert 0.0 < child.sum < wall
            # the window is inside the handler: lane wait is part of it
            assert child.sum >= 0.002 * 0.5
            lane = srv._serve_obs.queue_delay.labels()
            assert lane.count == 1 and lane.sum <= child.sum
            if sample:
                entry = _serve_entries(trace.get_recorder().snapshot())[0]
                assert child.sum * 1000.0 <= entry["duration_ms"]
                handler_ms = sum(
                    s["dur_ms"] for s in entry["spans"]
                    if s["name"] not in ("wire_frame", "worker_queue",
                                         "wire_write"))
                assert child.sum * 1000.0 <= handler_ms + 0.01
        finally:
            srv.shutdown()

    def test_recorder_observes_the_handed_interval(self):
        m = MetricsRegistry()
        rec_ = trace.TraceRecorder(sample=1.0, ring=8, metrics=m)
        p = trace.PendingTrace()
        t0 = time.perf_counter() - 1.0
        p.st[trace.S_WIRE_READ] = t0
        p.st[trace.S_HANDLER] = t0 + 0.4
        p.st[trace.S_DONE] = t0 + 0.7
        p.st[trace.S_SENT] = t0 + 1.0
        p.kind, p.sampled, p.serve_s = "serve", True, 0.25
        rec_.finish(p)
        child = rec_._serve_hist.labels(app="")
        assert (child.count, child.sum) == (1, 0.25)
        assert rec_.snapshot()[0]["duration_ms"] == pytest.approx(1000.0)
        # a handler that died before it could say: its two stamps
        p.serve_s = 0.0
        rec_.finish(p)
        assert child.sum == pytest.approx(0.25 + 0.3)

    def test_batch_ring_entry_and_members_by_batch_id(self, trained):
        trace.configure(sample=1.0, ring=256)
        srv = _start_server(trained, batch_window_ms=5, batch_max=8)
        try:
            _hammer(srv.port, callers=4, each=3)
            _drained(srv)
            deadline = time.perf_counter() + 5.0
            member = None
            while member is None and time.perf_counter() < deadline:
                member = next(
                    (e for e in _serve_entries(
                        trace.get_recorder().snapshot())
                     if e.get("batch_size")), None)
                time.sleep(0.005)
            assert member is not None
            found = trace.get_recorder().snapshot(
                batch_id=member["batch_id"])
            batch = [e for e in found if e["kind"] == "batch"]
            assert len(batch) == 1 and member in found
            b = batch[0]
            assert b["rows"] >= member["batch_size"] >= 1
            assert b["bucket"] >= b["rows"]
            assert b["dispatch"] == member["dispatch"] != ""
            spans = {s["name"]: s for s in b["spans"]}
            assert set(spans) == set(_STAGES)
            assert spans["window"]["start_ms"] == 0.0
            assert sum(spans[n]["dur_ms"] for n in _LEAVES) \
                == pytest.approx(b["duration_ms"], abs=0.02)
            assert b["host_ms"] == pytest.approx(
                b["duration_ms"] - spans["fetch"]["dur_ms"], abs=0.01)
            status, body = call(
                srv.port, "GET",
                f"/traces.json?batch_id={member['batch_id']}")
            assert status == 200
            kinds = sorted({t["kind"] for t in body["traces"]})
            assert kinds == ["batch", "serve"]
        finally:
            srv.shutdown()

    def test_profile_holds_the_cycle_on_the_drainers_thread(self, trained,
                                                            tmp_path):
        """A short CPU capture of batched queries: the stages are
        `pio:batch.*` events of `/host:CPU`, all of one cycle on one
        line (the drainer's thread), predict's children inside it."""
        import glob

        import jax
        srv = _metrics_server(trained, batch_window_ms=5, batch_max=8)
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                _hammer(srv.port, callers=3, each=2)
                _drained(srv)
            finally:
                jax.profiler.stop_trace()
        finally:
            srv.shutdown()
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[0]
        profile = jax.profiler.ProfileData.from_file(path)
        host = next(p for p in profile.planes if p.name == "/host:CPU")
        lines = []
        for line in host.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("pio:batch.")]
            if evs:
                lines.append(evs)
        assert lines, "no pio:batch.* event in /host:CPU"
        seen, cycles = set(), 0
        for evs in lines:
            seen |= {name for name, _, _ in evs}
            predicts = [(a, b) for name, a, b in evs
                        if name == "pio:batch.predict"]
            for name, a, b in evs:
                if name.split(".", 1)[1] in _PREDICT_CHILDREN:
                    assert any(pa <= a and b <= pb for pa, pb in predicts), \
                        f"{name} outside every predict of its thread"
            # a drainer's line holds whole cycles: as many takes as
            # predicts, and one take more where it retired. The second
            # drainer's may hold that empty window alone (started
            # beside a cycle in flight, its turn came with the lane
            # empty)
            n_take = sum(name == "pio:batch.take" for name, _, _ in evs)
            assert n_take >= len(predicts)
            cycles += len(predicts)
        assert cycles >= 1
        assert seen == {"pio:batch." + n for n in _STAGES}

    def test_stage_outside_a_cycle_observes_no_histogram(self, trained):
        m = MetricsRegistry()
        fam = m.histogram("pio_serve_stage_seconds",
                          trace.STAGE_SECONDS_HELP, labels=("stage",))
        children = trace.stage_children(fam)
        assert trace.current_batch() is None
        with trace.stage("pack"):
            pass
        h = trace.stage_open("launch")
        trace.stage_close(h)
        with trace.stage("not_a_stage_of_the_cycle"):
            pass
        # a template's own call into the model, as eval and batch
        # predict make it: the stages inside are spans only
        before = {key: c.count for key, c in get_registry().histogram(
            "pio_serve_stage_seconds", trace.STAGE_SECONDS_HELP,
            labels=("stage",))._items()}
        srv = _metrics_server(trained)
        try:
            algo, model = srv._dep.algos[0], srv._dep.models[0]
        finally:
            srv.shutdown()
        out = algo.batch_predict(model, [(0, rec.Query(user="u1", num=3))])
        assert len(out) == 1
        after = {key: c.count for key, c in get_registry().histogram(
            "pio_serve_stage_seconds", trace.STAGE_SECONDS_HELP,
            labels=("stage",))._items()}
        assert after == before
        assert all(c.count == 0 for c in children.values())
        # and inside one they are observed, through the record alone
        bt = trace.batch_begin(children)
        with trace.stage("pack"):
            pass
        trace.batch_end(bt)
        assert children["pack"].count == 1
        assert children["cycle"].count == children["host"].count == 1
        assert children["launch"].count == 0
        assert trace.current_batch() is None

    def test_dispatch_path_rides_on_the_cycles_record(self):
        """What `ops/topk.last_dispatch()` was for: the call that
        dispatched says so on the record of the thread's cycle, and
        says nothing where there is none."""
        from predictionio_tpu.ops import topk
        assert not hasattr(topk, "last_dispatch")
        rng = np.random.RandomState(3)
        items = rng.randn(50, 4).astype(np.float32)
        vecs = rng.randn(2, 4).astype(np.float32)
        mask = np.ones((2, 50), bool)
        topk.topk_scores(vecs, items, mask, k=3)       # no cycle: fine
        bt = trace.batch_begin()
        topk.topk_scores(vecs, items, mask, k=3)
        assert bt.path == "host" and bt.bucket == 0
        trace.batch_drop()
        plan = topk.BucketedTopK(items, k=3, buckets=(4,), banned_width=4)
        plan.warm()
        bt = trace.batch_begin()
        plan(vecs, [[1], []])
        assert bt.path in ("device", "fused") and bt.bucket == 4
        for name in ("pack", "launch", "fetch"):
            assert bt.start[trace.STAGES.index(name)] > 0.0
        assert trace.batch_end(bt) == bt.t_last
        # another thread has no record of this one's cycle
        bt = trace.batch_begin()
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(trace.current_batch()))
        t.start()
        t.join()
        trace.batch_drop()
        assert seen == [None]


def test_obs_imports_without_jax():
    """Event servers and routers import obs (and the stage helper with
    it) and must never load jax; the helper finds jax only where the
    process has loaded it itself."""
    import subprocess
    import sys
    from pathlib import Path
    code = (
        "import sys\n"
        "from predictionio_tpu.obs import trace\n"
        "with trace.stage('pack'):\n"
        "    pass\n"
        "bt = trace.batch_begin()\n"
        "with trace.stage('take'):\n"
        "    pass\n"
        "trace.batch_end(bt)\n"
        "assert 'jax' not in sys.modules, 'obs loaded jax'\n"
        "print('ok')\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=dict(
            __import__("os").environ,
            PYTHONPATH=str(Path(__file__).resolve().parent.parent)))
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# -- fleet stitching ----------------------------------------------------------

def _start_fleet(trained, replicas=3, **fleet_kw):
    registry, engine = trained
    fleet_kw.setdefault("health_interval_s", 0.1)
    fleet_kw.setdefault("eject_threshold", 2)
    fleet_kw.setdefault("drain_timeout_s", 2.0)
    srv = FleetServer(ServerConfig(ip="127.0.0.1", port=0),
                      FleetConfig(replicas=replicas, **fleet_kw),
                      registry=registry, engine=engine)
    srv.start()
    return srv


class _Loader:
    """Client hammer recording every response status."""

    def __init__(self, port, threads=2):
        self.port = port
        self.halt = threading.Event()
        self.statuses = []
        self._lock = threading.Lock()
        self._threads = [threading.Thread(target=self._run, daemon=True)
                         for _ in range(threads)]

    def _run(self):
        while not self.halt.is_set():
            try:
                status, _ = call(self.port, "POST", "/queries.json",
                                 {"user": "u1", "num": 2})
            except OSError:
                status = -1
            with self._lock:
                self.statuses.append(status)

    def __enter__(self):
        for t in self._threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self.halt.set()
        for t in self._threads:
            t.join(5)

    @property
    def failures(self):
        with self._lock:
            return [s for s in self.statuses if s != 200]


class TestFleetStitching:
    def test_fleet_hop_stitches_under_one_trace_id(self, trained):
        trace.configure(sample=1.0, ring=256)
        fleet = _start_fleet(trained, replicas=3)
        try:
            status, _ = call(fleet.port, "POST", "/queries.json",
                             {"user": "u1", "num": 2})
            assert status == 200
            deadline = time.perf_counter() + 5.0
            stitched = None
            while time.perf_counter() < deadline and stitched is None:
                snap = trace.get_recorder().snapshot()
                by_tid = {}
                for e in snap:
                    by_tid.setdefault(e["trace_id"], []).append(e)
                for tid, group in by_tid.items():
                    kinds = {e["kind"] for e in group}
                    if {"router", "serve"} <= kinds:
                        stitched = group
                        break
                if stitched is None:
                    time.sleep(0.01)
            assert stitched is not None, \
                "router and replica entries never stitched"
            router = next(e for e in stitched if e["kind"] == "router")
            serve = next(e for e in stitched if e["kind"] == "serve")
            # the replica span is parented under the router's span
            assert serve["parent_id"] == router["span_id"]
            # the router hop recorded its proxy sub-span
            assert any(s["name"].startswith("proxy")
                       for s in router["spans"])
            # stitched coverage: the hop spans tile the router's wall
            # time (>= 90% — the acceptance bar for the fleet trace)
            for e in (router, serve):
                covered = sum(s["dur_ms"] for s in e["spans"])
                assert covered >= 0.9 * e["duration_ms"], e
        finally:
            fleet.stop()

    def test_router_hop_not_double_counted_in_serve_hist(self, trained):
        trace.configure(sample=1.0, ring=256)
        hist = get_registry().histogram(
            "pio_serve_seconds", trace.SERVE_SECONDS_HELP,
            labels=("app",), buckets=trace.SERVE_BUCKETS)
        before = hist.labels(app="").count
        fleet = _start_fleet(trained, replicas=2)
        try:
            for _ in range(4):
                status, _ = call(fleet.port, "POST", "/queries.json",
                                 {"user": "u1", "num": 2})
                assert status == 200
            deadline = time.perf_counter() + 5.0
            after = before
            while time.perf_counter() < deadline:
                after = hist.labels(app="").count
                if after - before >= 4:
                    break
                time.sleep(0.01)
            # exactly one serve observation per client request: the
            # router hop (kind=router) must not observe the histogram
            assert after - before == 4
        finally:
            fleet.stop()

    def test_standby_redirect_carries_trace_header(self, trained):
        trace.configure(sample=1.0, ring=256)
        leader = _start_fleet(trained, replicas=1)
        standby = _start_fleet(trained, replicas=0, standby=True,
                               lease_ttl_s=0.5)
        try:
            deadline = time.perf_counter() + 5.0
            while not leader.is_leader():
                assert time.perf_counter() < deadline
                time.sleep(0.05)
            req = urllib.request.Request(
                f"http://127.0.0.1:{standby.port}/queries.json",
                data=json.dumps({"user": "u1", "num": 2}).encode(),
                method="POST",
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req)
            assert err.value.code == 307
            hdr = err.value.headers.get(trace.TRACE_HEADER)
            assert hdr, "307 redirect did not assert X-PIO-Trace"
            ctx = trace.decode_header(hdr)
            assert ctx is not None
            tid, parent_span, _ = ctx
            # a trace-aware client re-asserts the header at the leader:
            # the leader-side entry adopts the standby's trace id
            status, _ = call(leader.port, "POST", "/queries.json",
                             {"user": "u1", "num": 2},
                             headers={trace.TRACE_HEADER: hdr})
            assert status == 200
            deadline = time.perf_counter() + 5.0
            group = []

            def joined():
                return any(e["parent_id"] == parent_span for e in group)
            # the trace's entries reach the ring one by one (the
            # router's hop first): wait for the one that adopted the
            # standby's span, not for the first of them
            while time.perf_counter() < deadline and not joined():
                group = trace.get_recorder().snapshot(trace_id=tid)
                if not joined():
                    time.sleep(0.01)
            assert group, "redirected request never joined the trace"
            assert joined()
        finally:
            standby.stop()
            leader.stop()

    def test_replica_killed_at_full_sampling_zero_failures(self, trained):
        """Chaos at sample=1.0: tracing every request must not cost a
        single failed client call while a replica dies under load."""
        trace.configure(sample=1.0, ring=512)
        fleet = _start_fleet(trained, replicas=3)
        try:
            victim = fleet._replicas[0]
            with _Loader(fleet.port) as load:
                waiter = threading.Event()
                waiter.wait(0.2)
                victim.server.shutdown()
                waiter.wait(0.3)
            assert len(load.statuses) > 0
            assert load.failures == []
            # the episode is visible in the ring: retried hops recorded
            snap = trace.get_recorder().snapshot()
            assert any(e["kind"] == "router" for e in snap)
        finally:
            fleet.stop()

    def test_fleet_ready_surfaces_replica_slo(self, trained):
        fleet = _start_fleet(trained, replicas=2)
        try:
            for _ in range(3):
                status, _ = call(fleet.port, "POST", "/queries.json",
                                 {"user": "u1", "num": 2})
                assert status == 200
            status, body = call(fleet.port, "GET", "/ready")
            assert status == 200
            assert "slo" in body and body["sloDegraded"] is False
            assert "(default)" in body["slo"]
        finally:
            fleet.stop()

    def test_rolling_reload_records_background_span(self, trained):
        trace.configure(sample=1.0, ring=256)
        fleet = _start_fleet(trained, replicas=2)
        try:
            status, report = call(fleet.port, "POST", "/reload")
            assert status == 200 and report["aborted"] is False
            snap = trace.get_recorder().snapshot()
            rolls = [e for e in snap if e["name"] == "rolling_reload"]
            assert rolls and rolls[0]["kind"] == "background"
            assert rolls[0].get("error", "") == ""
        finally:
            fleet.stop()
