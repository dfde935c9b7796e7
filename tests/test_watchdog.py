"""Self-healing serve plane: thread watchdog, memory-pressure guard,
replica supervisor, chaos scenario runner.

Covers the PR's acceptance gates end to end:

  - Beat mechanics: age math, attach/tick/guard trampoline, the
    `thread.<role>.stall` / `thread.<role>.die` chaos seams, and the
    Superseded protocol that retires stalled threads quietly
  - Watchdog sweep: stall detection with a stack dump, restart with
    jittered backoff, the crash-loop breaker degrading the beat, and
    degraded beats flipping the OWNING server's /ready
  - Memory watermarks: soft = trim bounded state + shed new work
    `503 surface=memory` while inflight completes; hard = /ready fails
    and the graceful drain runs exactly once
  - Supervisor: a SIGKILLed child respawns with backoff; a
    crash-looping child circuit-breaks to given_up
  - SIGTERM under load (install_signal_handlers): accepted requests
    complete through the graceful stop() drain
  - Scenario runner: the ISSUE's four chaos gates as declarative
    scenarios, and a violated invariant is a loud non-ok report
"""

import json
import os
import signal as signal_mod
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core import CoreWorkflow, EngineParams, RuntimeContext
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import AccessKey, App
from predictionio_tpu.models import recommendation as rec
from predictionio_tpu.obs import get_registry
from predictionio_tpu.resilience import FaultError, faults
from predictionio_tpu.resilience.pressure import MemoryGuard
from predictionio_tpu.resilience.watchdog import (
    Beat, Superseded, Watchdog,
)
from predictionio_tpu.serving import PredictionServer, ServerConfig

pytestmark = pytest.mark.watchdog


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults().clear()
    yield
    faults().clear()


def _metric(name, **labels):
    return get_registry().value(name, **labels)


def _wait(pred, timeout=8.0, interval=0.02, msg="condition"):
    end = time.perf_counter() + timeout
    while time.perf_counter() < end:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for: {msg}")


def call(port, method, path, body=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            raw = resp.read().decode()
            ct = resp.headers.get("Content-Type", "")
            return resp.status, (json.loads(raw) if "json" in ct else raw)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


@pytest.fixture()
def trained(mem_registry):
    """Registry with a trained tiny recommendation instance."""
    apps = mem_registry.get_meta_data_apps()
    app_id = apps.insert(App(0, "wdapp"))
    mem_registry.get_meta_data_access_keys().insert(
        AccessKey("WDKEY", app_id, ()))
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
        data_source_params=("", rec.DataSourceParams(app_name="wdapp")),
        algorithm_params_list=(
            ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=4,
                                           seed=1)),))
    CoreWorkflow.run_train(engine, params, ctx)
    return mem_registry, engine


def _start_server(trained, **cfg):
    registry, engine = trained
    srv = PredictionServer(
        ServerConfig(ip="127.0.0.1", port=0, **cfg),
        registry=registry, engine=engine)
    srv.start()
    return srv


# -- Beat mechanics -----------------------------------------------------------

class TestBeat:
    def test_age_math_and_stamping(self):
        beat = Beat("t", budget_s=1.0)
        beat.stamp -= 5.0
        assert beat.age() == pytest.approx(5.0, abs=0.2)
        beat.beat()
        assert beat.age() < 0.2

    def test_attach_binds_thread_and_resets_flags(self):
        beat = Beat("t")
        beat.dead = True
        beat.stalled = True
        beat.attach()
        assert beat.thread_ident == threading.get_ident()
        assert not beat.dead and not beat.stalled

    def test_tick_raises_superseded_for_stale_thread(self):
        beat = Beat("t")
        beat.thread_ident = -1        # some other (vanished) thread
        with pytest.raises(Superseded):
            beat.tick()

    def test_tick_honors_die_seam(self):
        beat = Beat("seamrole")
        beat.attach()
        faults().arm("thread.seamrole.die", error=FaultError, times=1)
        with pytest.raises(FaultError):
            beat.tick()
        beat.tick()                   # rule exhausted: ticks again

    def test_tick_honors_stall_seam(self):
        beat = Beat("stallrole")
        beat.attach()
        faults().arm("thread.stallrole.stall", latency=0.15, times=1)
        t0 = time.perf_counter()
        beat.tick()
        assert time.perf_counter() - t0 >= 0.15

    def test_guard_counts_uncaught_death(self):
        beat = Beat("dier")
        before = _metric("pio_thread_deaths_total", role="dier")

        def body():
            raise RuntimeError("boom")

        beat.guard(body)              # must not raise
        assert beat.dead
        assert _metric("pio_thread_deaths_total",
                       role="dier") == before + 1

    def test_guard_superseded_is_not_a_death(self):
        beat = Beat("oldgen")
        before = _metric("pio_thread_deaths_total", role="oldgen")

        def body():
            raise Superseded("oldgen")

        beat.guard(body)
        assert not beat.dead
        assert _metric("pio_thread_deaths_total", role="oldgen") == before


# -- Watchdog sweep -----------------------------------------------------------

class TestWatchdogSweep:
    def _wd(self, stall_s=0.2):
        # private instance (no sweeper thread): tests drive sweep()
        return Watchdog(stall_s=stall_s, interval_s=999.0)

    def test_stall_detected_once_and_stack_dumped(self):
        wd = self._wd(stall_s=0.2)
        beat = wd.register("wedged", budget_s=0.1)
        release = threading.Event()

        def loop():
            beat.attach()
            release.wait(5)           # lint: ok — bounded test thread

        t = threading.Thread(target=loop, daemon=True,
                             name="pio-test-wedged")
        t.start()
        _wait(lambda: beat.thread_ident is not None, msg="attach")
        before = _metric("pio_watchdog_stalls_total", role="wedged")
        beat.stamp -= 1.0             # simulate a silent second
        wd.sweep()
        assert _metric("pio_watchdog_stalls_total",
                       role="wedged") == before + 1
        # non-restartable: first stall degrades
        assert beat.degraded and "stalled" in beat.reason
        # a second sweep must NOT double-count the same stall
        wd.sweep()
        assert _metric("pio_watchdog_stalls_total",
                       role="wedged") == before + 1
        release.set()

    def test_restart_with_backoff(self):
        wd = self._wd()
        spawned = []
        beat = wd.register("worker", budget_s=0.1,
                           restart=lambda: spawned.append(1))
        beat.attach()
        beat.dead = True              # the guard saw an escape
        before = _metric("pio_thread_restarts_total", role="worker")
        wd.sweep()
        assert beat.next_restart_at is not None   # scheduled, not yet
        assert not spawned
        beat.next_restart_at = time.monotonic() - 0.01
        wd.sweep()
        assert spawned == [1]
        assert beat.restarts == 1
        assert _metric("pio_thread_restarts_total",
                       role="worker") == before + 1

    def test_crash_loop_breaker_degrades(self):
        wd = self._wd()
        beat = wd.register("flappy", budget_s=0.1, restart=lambda: None)
        now = time.monotonic()
        for _ in range(5):            # BREAKER_K rapid deaths
            wd._on_death(beat, now, "died (test)")
        assert beat.degraded
        assert "crash loop" in beat.reason

    def test_vanished_thread_detected(self):
        wd = self._wd()
        beat = wd.register("ghost", budget_s=0.1)
        beat.attach()
        beat.thread_ident = -1        # not an alive ident
        wd.sweep()
        assert beat.degraded and beat.reason == "thread vanished"

    def test_closed_beats_pruned_and_degraded_gauge_cleared(self):
        wd = self._wd()
        beat = wd.register("tempo", budget_s=0.1)
        beat.mark_degraded("test")
        assert _metric("pio_thread_degraded", role="tempo") == 1.0
        beat.close()
        wd.sweep()
        assert beat not in wd.beats()
        assert _metric("pio_thread_degraded", role="tempo") == 0.0


class TestDegradedReadiness:
    def test_degraded_refresher_flips_ready(self, trained):
        srv = _start_server(trained, refresh_interval_s=60.0)
        try:
            ready, _ = srv.readiness()
            assert ready
            srv._refresher.beat.mark_degraded("crash loop (test)")
            ready, detail = srv.readiness()
            assert not ready
            assert "refresher" in detail["degradedLoops"]
        finally:
            srv.stop()


# -- memory-pressure guard ----------------------------------------------------

class TestMemoryPressure:
    def test_soft_trims_and_sheds_while_inflight_succeeds(self, trained):
        srv = _start_server(trained)
        try:
            # seed the tsdb rings so the trim has bytes to release
            if getattr(srv, "_scraper", None) is not None:
                now = time.time()
                for i in range(4):
                    srv._scraper.tick(now=now + i)
            trims_before = _metric("pio_mem_trims_total", target="tsdb")
            shed_before = _metric("pio_shed_total", surface="memory",
                                  app="")
            faults().arm("mem.pressure.soft", times=1)
            assert srv._pressure.check() == "soft"
            # soft: still ready (fleet keeps us), but new work sheds
            ready, _ = srv.readiness()
            assert ready
            status, body = call(srv.port, "POST", "/queries.json",
                                {"user": "u1", "num": 2})
            assert status == 503
            assert _metric("pio_shed_total", surface="memory",
                           app="") > shed_before
            assert _metric("pio_mem_trims_total",
                           target="tsdb") == trims_before + 1
            # seam exhausted: next check recovers and serving resumes
            assert srv._pressure.check() == "ok"
            status, _ = call(srv.port, "POST", "/queries.json",
                             {"user": "u1", "num": 2})
            assert status == 200
        finally:
            srv.stop()

    def test_hard_fires_drain_once_and_fails_ready(self):
        drains = []
        guard = MemoryGuard(limit_bytes=1 << 40)   # real frac ~0
        guard.on_hard(lambda: drains.append(1))
        faults().arm("mem.pressure.hard", times=2)
        assert guard.check() == "hard"
        assert not guard.ready()
        assert guard.check() == "hard"
        assert drains == [1]          # latched: fired exactly once
        assert guard.check() == "ok"  # seam exhausted: recovers
        assert guard.ready()

    def test_hard_watermark_drains_the_server(self, trained):
        srv = _start_server(trained)
        try:
            faults().arm("mem.pressure.hard", times=1)
            assert srv._pressure.check() == "hard"
            ready, detail = srv.readiness()
            assert not ready
            assert detail["memPressure"]["state"] == "hard"
            _wait(lambda: not srv.is_running(), timeout=15,
                  msg="hard watermark drains the server")
        finally:
            if srv.is_running():
                srv.stop()


# -- supervisor ---------------------------------------------------------------

class TestSupervisor:
    def test_child_argv_from_parent_strips_supervision_flags(self):
        from predictionio_tpu.serving.supervisor import (
            child_argv_from_parent,
        )
        argv = child_argv_from_parent(
            ["deploy", "--engine-json", "e.json", "--supervised", "3",
             "--port", "8000", "--standby", "--feedback"],
            "http://127.0.0.1:9999")
        tail = argv[3:]               # skip python -m module
        assert "--supervised" not in tail and "--standby" not in tail
        assert tail[:3] == ["deploy", "--engine-json", "e.json"]
        assert tail[-4:] == ["--join", "http://127.0.0.1:9999",
                             "--port", "0"]
        assert "--feedback" in tail

    def test_sigkilled_child_respawns(self):
        from predictionio_tpu.serving.supervisor import (
            ChildSpec, Supervisor,
        )
        argv = [sys.executable, "-c",
                "import time; time.sleep(60)"]
        sup = Supervisor([ChildSpec("sleeper", argv)],
                         poll_s=0.05, backoff_base_s=0.1, grace_s=2.0)
        sup.start()
        try:
            _wait(lambda: sup.alive_count() == 1, msg="child starts")
            child = sup.find("sleeper")
            pid1 = child.proc.pid
            os.kill(pid1, signal_mod.SIGKILL)
            _wait(lambda: sup.alive_count() == 1
                  and child.proc.pid != pid1, timeout=10,
                  msg="child respawned with a fresh pid")
            assert child.respawns == 1
            assert _metric("pio_supervisor_respawns_total",
                           child="sleeper") >= 1
        finally:
            sup.stop()
        assert sup.alive_count() == 0

    def test_crash_loop_breaker_gives_up(self):
        from predictionio_tpu.serving.supervisor import (
            ChildSpec, Supervisor,
        )
        argv = [sys.executable, "-c", "import sys; sys.exit(3)"]
        sup = Supervisor([ChildSpec("flappy", argv)],
                         poll_s=0.02, backoff_base_s=0.02,
                         breaker_k=3, grace_s=1.0)
        sup.start()
        try:
            _wait(lambda: sup.find("flappy").given_up, timeout=10,
                  msg="crash loop circuit-breaks")
            assert sup.find("flappy").last_rc == 3
        finally:
            sup.stop()


    def test_one_chip_per_child(self, tmp_path):
        """A chip belongs to one process: more children than chips is
        refused at start and on grow, and on a multi-chip host every
        slot is pinned to its own chip (stable across a retire)."""
        from predictionio_tpu.serving.supervisor import (
            ChildSpec, Supervisor,
        )
        from predictionio_tpu.utils.device import chip_env

        def spec(name):
            out = tmp_path / f"{name}.env"
            return ChildSpec(name, [
                sys.executable, "-c",
                "import os, time; open(%r, 'w').write(os.environ.get("
                "'TPU_VISIBLE_CHIPS', '-')); time.sleep(60)" % str(out)])

        with pytest.raises(ValueError, match="this host has 1"):
            Supervisor([spec("a"), spec("b")], chips=1)
        sup = Supervisor([spec("a"), spec("b")], chips=3,
                         poll_s=0.05, grace_s=2.0)
        sup.start()
        try:
            _wait(lambda: all((tmp_path / f"{n}.env").exists()
                              and (tmp_path / f"{n}.env").read_text()
                              for n in "ab"), msg="children started")
            assert (tmp_path / "a.env").read_text() == "0"
            assert (tmp_path / "b.env").read_text() == "1"
            assert [c["chip"] for c in sup.children()] == [0, 1]
            sup.grow(spec("c"))
            assert sup.find("c").chip == 2
            with pytest.raises(ValueError, match="no free chip"):
                sup.grow(spec("d"))
            assert sup.retire("a", grace_s=2.0)
            sup.grow(spec("d"))              # the freed chip is reused
            assert sup.find("d").chip == 0
        finally:
            sup.stop()
        assert chip_env(2)["TPU_VISIBLE_CHIPS"] == "2"
        # one chip (or no accelerator host): nothing to pin
        solo = Supervisor([spec("s")], chips=1)
        assert solo.find("s").chip is None
        assert Supervisor([spec("n")]).find("n").chip is None


# -- SIGTERM drain under load -------------------------------------------------

class TestSignalDrain:
    def test_sigterm_completes_accepted_requests(self, trained):
        from predictionio_tpu.serving import install_signal_handlers
        saved = {sig: signal_mod.getsignal(sig)
                 for sig in (signal_mod.SIGTERM, signal_mod.SIGINT)}
        srv = _start_server(trained)
        statuses = []
        lock = threading.Lock()

        def one_request():
            status, _ = call(srv.port, "POST", "/queries.json",
                             {"user": "u1", "num": 2})
            with lock:
                statuses.append(status)

        try:
            install_signal_handlers(srv)
            # every request rides a 200ms injected predict latency, so
            # all of them are mid-flight when the SIGTERM lands
            faults().arm("serve.predict", latency=0.2)
            threads = [threading.Thread(target=one_request, daemon=True,
                                        name=f"pio-test-load-{i}")
                       for i in range(6)]
            for t in threads:
                t.start()
            time.sleep(0.08)          # connections accepted, in predict
            os.kill(os.getpid(), signal_mod.SIGTERM)
            for t in threads:
                t.join(15)
            _wait(lambda: not srv.is_running(), timeout=15,
                  msg="graceful stop completes")
            assert len(statuses) == 6
            assert all(s == 200 for s in statuses), statuses
        finally:
            for sig, handler in saved.items():
                signal_mod.signal(sig, handler)
            if srv.is_running():
                srv.stop()


# -- scenario runner ----------------------------------------------------------

class TestScenarioRunner:
    def test_violated_invariant_is_loud(self):
        from predictionio_tpu.resilience import scenarios
        sc = scenarios.Scenario(
            name="always-red",
            description="an invariant that always fails",
            duration_s=0.0,
            setup=lambda ctx: None,
            steps=(),
            invariants=(("never true",
                         lambda ctx: "deliberate violation"),),
            load=False)
        report = scenarios.run(sc, trained=(None, None))
        assert not report.ok
        assert any("deliberate violation" in v
                   for v in report.violations)

    def test_step_crash_is_a_violation(self):
        from predictionio_tpu.resilience import scenarios

        def bad_step(ctx):
            raise RuntimeError("scripted explosion")

        sc = scenarios.Scenario(
            name="crashy", description="a step that crashes",
            duration_s=0.0, setup=lambda ctx: None,
            steps=((0.0, "boom", bad_step),), invariants=(),
            load=False)
        report = scenarios.run(sc, trained=(None, None))
        assert not report.ok
        assert any("scripted explosion" in v for v in report.violations)

    def test_cli_rejects_unknown_scenario(self):
        from predictionio_tpu.cli.main import main
        assert main(["chaos", "run", "no-such-scenario"]) == 2

    # -- the ISSUE's four acceptance gates, as declarative scenarios ------

    def test_gate_refresher_stall_recovers(self, trained):
        from predictionio_tpu.resilience import scenarios
        report = scenarios.run("refresher-stall", trained=trained)
        assert report.ok, report.violations

    def test_gate_lease_failover_zero_drops(self, trained):
        from predictionio_tpu.resilience import scenarios
        report = scenarios.run("lease-failover", trained=trained)
        assert report.ok, report.violations

    def test_gate_mem_soft_sheds_and_trims(self, trained):
        from predictionio_tpu.resilience import scenarios
        report = scenarios.run("mem-soft", trained=trained)
        assert report.ok, report.violations

    def test_gate_supervised_replica_kill(self, trained):
        from predictionio_tpu.resilience import scenarios
        report = scenarios.run("replica-kill", trained=trained)
        assert report.ok, report.violations


# -- lint rule extension ------------------------------------------------------

def test_lint_flags_unprefixed_thread_name(tmp_path):
    from predictionio_tpu.tools import lint
    bad = tmp_path / "predictionio_tpu" / "bad_thread.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        '"""doc"""\n'
        "import threading\n"
        "def f():\n"
        "    t = threading.Thread(target=lambda: None, name='worker')\n"
        "    return t\n")
    kinds = "\n".join(lint.run(tmp_path))
    assert "lacks a role prefix" in kinds


def test_lint_accepts_prefixed_thread_name(tmp_path):
    from predictionio_tpu.tools import lint
    ok = tmp_path / "predictionio_tpu" / "ok_thread.py"
    ok.parent.mkdir(parents=True)
    ok.write_text(
        '"""doc"""\n'
        "import threading\n"
        "def f():\n"
        "    t = threading.Thread(target=lambda: None,\n"
        "                         name='pio-worker')\n"
        "    return t\n")
    assert not lint.run(tmp_path)
