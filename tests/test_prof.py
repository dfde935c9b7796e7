"""Continuous-observatory tests: the always-on sampling profiler
(frame trie bounds, thread-role attribution, /profile.json +
collapsed-stack export, and its fold, which reuses the paths of the
chains unchanged since the last tick, against a walk of every frame),
the GC-pause hook, the in-process tsdb ring
(bounds, delta decode, counter-rate math, /tsdb.json?since=
filtering), fleet metrics federation with a member down, the
dashboard sparkline panels, and the `pio-tpu top` terminal view.
"""

import gc
import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request
import weakref

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


# -- the fold against a walk of every frame ----------------------------------

class _EagerFold:
    """The reference: every frame of every thread walked on every tick,
    and the per-frame counts kept per sample, with the trie's budget
    rule; what the sampler's fold must equal."""

    def __init__(self, max_nodes):
        self.max_nodes = max_nodes
        self.reset()

    def reset(self):
        self.roots, self.nodes, self.truncated = {}, 0, 0
        self.self_counts, self.cum_counts, self.role_samples = {}, {}, {}
        self.samples = self.ticks = 0

    def tick(self, frames, names):
        self.ticks += 1
        for ident, frame in frames.items():
            stack, f = [], frame
            while f is not None:
                code = f.f_code
                mod = code.co_filename.rsplit("/", 1)[-1]
                stack.append(f"{mod}:{code.co_name}")
                f = f.f_back
            stack.reverse()
            self._fold(role_of(names.get(ident, "")), stack)
            self.samples += 1

    def _fold(self, role, stack):
        self.role_samples[role] = self.role_samples.get(role, 0) + 1
        node = self.roots.get(role)
        if node is None:
            if self.nodes >= self.max_nodes:
                self.truncated += 1
                return
            node = self.roots[role] = prof_mod._Node()
            self.nodes += 1
        for key in stack:
            child = node.children.get(key)
            if child is None:
                if self.nodes >= self.max_nodes:
                    self.truncated += 1
                    break
                child = node.children[key] = prof_mod._Node()
                self.nodes += 1
            node = child
        node.ended += 1
        self.self_counts[stack[-1]] = self.self_counts.get(stack[-1], 0) + 1
        for key in set(stack):
            self.cum_counts[key] = self.cum_counts.get(key, 0) + 1

    def snapshot(self, top=30):
        denom = float(self.samples) or 1.0

        def frames(counts):
            pairs = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
            return [{"frame": k, "samples": v, "share": round(v / denom, 4)}
                    for k, v in pairs[:top]]

        return {
            "ticks": self.ticks, "samples": self.samples,
            "roles": {r: {"samples": n, "share": round(n / denom, 4)}
                      for r, n in sorted(self.role_samples.items(),
                                         key=lambda kv: -kv[1])},
            "top_self": frames(self.self_counts),
            "top_cumulative": frames(self.cum_counts),
            "trie": {"nodes": self.nodes, "max_nodes": self.max_nodes,
                     "truncated_samples": self.truncated}}

    def collapsed(self):
        lines = []
        for role in sorted(self.roots):
            stack = [(self.roots[role], role)]
            while stack:
                node, path = stack.pop()
                if node.ended:
                    lines.append(f"{path} {node.ended}")
                for key in sorted(node.children):
                    stack.append((node.children[key], f"{path};{key}"))
        return "".join(line + "\n" for line in lines)


def _trie(node):
    return (node.ended,
            {k: _trie(c) for k, c in node.children.items()})


def _assert_same(prof, ref):
    snap = prof.snapshot_json()
    for volatile in ("hz", "running", "since"):
        del snap[volatile]
    assert snap == ref.snapshot()
    assert prof.collapsed() == ref.collapsed()
    assert {r: _trie(n) for r, n in prof._roots.items()} == \
        {r: _trie(n) for r, n in ref.roots.items()}


class _Scripted:
    """A named thread that runs `steps` in turn; each parks once or
    more in `park`, where its Python frames stand still (the two locks'
    methods are C calls: no frame of theirs is on the stack) until the
    test `advance`s it to its next park, or to its end."""

    def __init__(self, name, *steps):
        self.steps, self.halted = steps, False
        self.arrive = threading.Lock()
        self.gate = threading.Lock()
        self.arrive.acquire()
        self.gate.acquire()
        self.thread = threading.Thread(target=self._run, name=name,
                                       daemon=True)

    def park(self):
        if not self.halted:
            self.arrive.release()
            self.gate.acquire()

    def _run(self):
        for step in self.steps:
            step(self)
        if not self.halted:
            self.arrive.release()

    def start(self):
        self.thread.start()
        assert self.arrive.acquire(timeout=10)
        return self

    def advance(self):
        self.gate.release()
        assert self.arrive.acquire(timeout=10)

    def finish(self):
        if self.thread.is_alive():
            self.halted = True
            self.gate.release()
            self.thread.join(10)
        assert not self.thread.is_alive()


def _deep(s, n):
    if n:
        return _deep(s, n - 1)
    s.park()


def _depths(*ns):
    def step(s):
        for n in ns:
            _deep(s, n)
    return step


def _next_job(s):
    s.park()


def _handle(s, n):
    _deep(s, n)


def _worker_loop(s):
    for n in (2, 5, 1):
        _next_job(s)
        _handle(s, n)
    _next_job(s)


class _Yield:
    def __await__(self):
        yield


def _gen_body(s):
    while not s.halted:
        s.arrive.release()      # parked in this very frame
        s.gate.acquire()
        yield


async def _coro_body(s):
    while not s.halted:
        s.arrive.release()
        s.gate.acquire()
        await _Yield()


def _via_a(g):
    g.send(None)


def _via_b(g):
    g.send(None)


def _resumed(body):
    def step(s):
        g = body(s)
        for via in (_via_a, _via_b, _via_b, _via_a):
            via(g)
    return step


def _scene_parked(run):
    run.start(_Scripted("wire-3", _depths(6)))
    run.start(_Scripted("wire-reactor-0", _depths(9)))
    run.start(_Scripted("pio-fleet-health", _depths(0)))
    for _ in range(4):
        run.tick()


def _scene_returned(run):
    worker = run.start(_Scripted("wire-7", _worker_loop))
    run.start(_Scripted("wire-8", _depths(3)))
    for _ in range(6):
        run.tick()
        run.tick()
        worker.advance()
    run.tick()


def _scene_resumed(body):
    def scene(run):
        s = run.start(_Scripted("pio-batch-drain", _resumed(body)))
        for _ in range(3):
            run.tick()
            s.advance()
            run.tick()
    return scene


def _scene_exit_and_reuse(run):
    gone = run.start(_Scripted("wire-1", _depths(4)))
    stays = run.start(_Scripted("wire-2", _depths(2)))
    ended = run.start(_Scripted("wire-3", _depths(3)))
    run.tick()
    ended.advance()                     # returns, and the thread ends
    ended.finish()
    run.tick()
    run.tick({42: gone, 43: stays}, {42: "wire-1", 43: "wire-2"})
    gone.advance()
    gone.finish()
    # the ident comes back at once, on a thread of another role
    heir = run.start(_Scripted("pio-batch-drain", _depths(4)))
    run.tick({42: heir, 43: stays}, {42: "pio-batch-drain", 43: "wire-2"})
    run.tick({42: heir, 43: stays}, {42: "pio-batch-drain", 43: "wire-2"})


def _scene_budget(run):
    walker = run.start(_Scripted("wire-5", _depths(*range(0, 60, 3))))
    parked = run.start(_Scripted("wire-reactor-1", _depths(12)))
    for _ in range(19):
        run.tick()
        walker.advance()
        run.tick()
    run.tick({7: parked, 8: walker}, {7: "wire-reactor-1", 8: "wire-5"})
    # a parked thread renamed into a role the spent budget has no root for
    run.tick({7: parked, 8: walker}, {7: "pio-refresher", 8: "wire-5"})
    run.tick({7: parked, 8: walker}, {7: "pio-refresher", 8: "wire-5"})
    assert run.prof._nodes == run.prof.max_nodes
    assert len(run.prof._paths) == run.prof.max_nodes
    assert run.prof._self_counts        # paths past the table, per frame


def _scene_reset(run):
    walker = run.start(_Scripted("wire-9", _worker_loop))
    run.start(_Scripted("pio-fleet-health", _depths(5)))
    run.tick()
    walker.advance()
    run.tick()
    run.reset()
    run.tick()
    walker.advance()
    run.tick()
    run.reset()
    run.tick()


class _Run:
    """Drives a scene: each tick hands the sampler's fold and the
    reference the same frames of the scene's parked threads, and checks
    that the trie, /profile.json and the collapsed stacks agree."""

    def __init__(self, max_nodes):
        self.prof = SamplingProfiler(hz=0, max_nodes=max_nodes)
        self.ref = _EagerFold(self.prof.max_nodes)
        self.scripts = []

    def start(self, script):
        self.scripts.append(script.start())
        return script

    def tick(self, by_ident=None, names=None):
        if by_ident is None:
            by_ident = {s.thread.ident: s for s in self.scripts
                        if s.thread.is_alive()}
            names = {i: s.thread.name for i, s in by_ident.items()}
        now = sys._current_frames()
        frames = {i: now[s.thread.ident] for i, s in by_ident.items()}
        del now
        self.prof._sample(frames, names, None)
        self.ref.tick(frames, names)
        _assert_same(self.prof, self.ref)

    def reset(self):
        self.prof.reset()
        self.ref.reset()


class TestFoldReuse:
    @pytest.mark.parametrize("scene,max_nodes", [
        (_scene_parked, 4096),
        (_scene_returned, 4096),
        (_scene_resumed(_gen_body), 4096),
        (_scene_resumed(_coro_body), 4096),
        (_scene_exit_and_reuse, 4096),
        (_scene_budget, 16),
        (_scene_reset, 4096),
    ], ids=["parked", "returned-and-parked-again", "generator-resumed",
            "coroutine-resumed", "exit-and-ident-reused",
            "trie-at-its-budget", "reset-between-ticks"])
    def test_fold_equals_a_walk_of_every_frame(self, scene, max_nodes):
        run = _Run(max_nodes)
        try:
            scene(run)
        finally:
            for s in run.scripts:
                s.finish()

    @pytest.mark.parametrize("ends", [False, True],
                             ids=["parks-elsewhere", "thread-ends"])
    def test_a_returned_frame_is_not_held(self, ends):
        class Payload:
            pass

        held = []

        def holds(s):
            payload = Payload()
            held.append(weakref.ref(payload))
            _deep(s, 2)

        steps = (holds,) if ends else (holds, _depths(1))
        s = _Scripted("wire-4", *steps).start()
        try:
            prof = SamplingProfiler(hz=0)
            prof.sample_once()
            s.advance()             # `holds` returns
            if ends:
                s.thread.join(10)
            assert held[0]() is None
            prof.sample_once()
        finally:
            s.finish()

    def test_nothing_of_the_sampling_thread_is_held(self):
        class Payload:
            pass

        prof = SamplingProfiler(hz=0)

        def sample_beside_a_payload():
            payload = Payload()
            prof.sample_once()
            return weakref.ref(payload)

        assert sample_beside_a_payload()() is None

    def test_held_frames_go_before_the_interpreter_finalizes(self):
        """A frame the sampler saw at its last tick, returned since, has
        let its locals go before the interpreter finalizes (a sampler
        that held frames let a call's device arrays go during
        finalization, and the process aborted at exit)."""
        script = textwrap.dedent("""
            import sys, threading, time
            from predictionio_tpu.obs.profiler import SamplingProfiler

            class Witness:
                def __del__(self):
                    print("finalizing" if sys.is_finalizing() else "before",
                          flush=True)

            def holds(go):
                witness = Witness()
                go.wait()

            go = threading.Event()
            t = threading.Thread(target=holds, args=(go,), name="wire-1",
                                 daemon=True)
            t.start()
            prof = SamplingProfiler(hz=2)
            prof.start()
            time.sleep(0.7)      # one tick has seen `holds`' frame
            go.set()
            t.join(5)            # returned; the next tick is 0.3 s away
        """)
        out = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=60, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == ["before"]

    def test_reuse_share_is_observed_once_a_tick(self):
        prof_mod._reset_global_for_tests()   # no live sampler beside us
        s = _Scripted("wire-6", _depths(8)).start()
        try:
            prof = SamplingProfiler(hz=0)
            hist = prof._reused
            count, total = hist.count, hist.sum
            frames = {s.thread.ident: sys._current_frames()[s.thread.ident]}
            names = {s.thread.ident: s.thread.name}
            prof._sample(frames, names, None)     # nothing held yet
            assert (hist.count, hist.sum) == (count + 1, total)
            prof._sample(frames, names, None)     # parked: all reused
            assert (hist.count, hist.sum) == (count + 2, total + 1.0)
            for _ in range(3):
                prof.sample_once()
            assert hist.count == count + 5
        finally:
            s.finish()


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
        hook = prof_mod._on_gc
        assert hook in gc.callbacks
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

    def test_a_collection_in_a_cycle_is_in_the_next_read(
            self, clean_gc_hooks):
        """The benchmark's window delta reads the registry through
        `_families_snapshot` and never calls /metrics: every pause that
        ended before a read is in it."""
        from predictionio_tpu.obs import trace
        reg = MetricsRegistry()
        install_gc_callbacks(reg)

        def gen2():
            for fam in reg._families_snapshot():
                if fam.name == "pio_gc_pause_seconds":
                    return {key: (c.count, c.sum)
                            for key, c in fam._items()}.get(("2",), (0, 0))

        before = gen2()
        bt = trace.batch_begin()
        with trace.stage("unpack"):
            gc.collect()
        trace.batch_end(bt)
        count, total = gen2()
        assert count == before[0] + 1 and total > before[1]
        assert prof_mod.flush_gc_pauses(reg) == 0      # nothing left

    def test_collect_under_the_family_lock_then_read(self, clean_gc_hooks):
        """PR 21's case with a real collection: the thread that holds
        the gc family's lock collects; the hook takes no lock, and the
        fold runs at the next read, on a thread that holds none."""
        reg = MetricsRegistry()
        install_gc_callbacks(reg)
        fam = prof_mod._gc_histogram(reg)
        done = threading.Event()

        def collect_under_the_lock():
            with fam._lock:
                gc.collect()
            done.set()

        t = threading.Thread(target=collect_under_the_lock, daemon=True,
                             name="pio-test-gc")
        t.start()
        assert done.wait(10), "a collection blocked on the family lock"
        assert 'pio_gc_pause_seconds_count{generation="2"}' in reg.render()


class _StandIn:
    """A stand-in for jax.profiler.TraceAnnotation: records every enter
    and exit, by thread."""

    events = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _StandIn.events.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        _StandIn.events.append(("exit", self.name, threading.get_ident()))
        return False


def _pairs(events, prefix, thread=None):
    """The spans named `prefix...`, checked to open and close in pairs,
    nested, on each thread; returns the names of those `thread` (any
    where None) closed, in closing order: the live loops of other
    tests may tick beside this one's."""
    stacks, closed = {}, []
    for kind, name, ident in events:
        if not name.startswith(prefix):
            continue
        stack = stacks.setdefault(ident, [])
        if kind == "enter":
            stack.append(name)
        else:
            assert stack and stack[-1] == name, (name, stack)
            stack.pop()
            if thread in (None, ident):
                closed.append(name)
    assert not any(stacks.values()), stacks
    return closed


class TestObserverSpans:
    @pytest.fixture()
    def stand_in(self, monkeypatch):
        from predictionio_tpu.obs import trace
        _StandIn.events = []
        monkeypatch.setattr(trace, "_ANNOTATION", _StandIn)
        return _StandIn

    def test_gc_spans_open_and_close_in_pairs(self, stand_in,
                                              clean_gc_hooks):
        install_gc_callbacks(MetricsRegistry())
        gc.collect()
        gc.collect(0)
        closed = _pairs(stand_in.events, "pio:gc.")
        assert "pio:gc.gen2" in closed and "pio:gc.gen0" in closed

    def test_each_observer_tick_is_a_span_and_an_observation(
            self, stand_in):
        from predictionio_tpu.obs import get_registry
        from predictionio_tpu.resilience.watchdog import Watchdog
        default = get_registry()
        reg = MetricsRegistry()

        def ticks(metrics, loop):
            return prof_mod.obs_tick_seconds(metrics, loop).count

        before = (ticks(default, "sampler"), ticks(reg, "scraper"),
                  ticks(default, "watchdog"))
        SamplingProfiler(hz=0).sample_once()
        Scraper(TSDB(points=4), reg, interval_s=0).tick()
        Watchdog(stall_s=1.0, interval_s=1.0).sweep()
        assert (ticks(default, "sampler"), ticks(reg, "scraper"),
                ticks(default, "watchdog")) == tuple(b + 1 for b in before)
        closed = _pairs(stand_in.events, "pio:obs.",
                        thread=threading.get_ident())
        assert sorted(closed) == ["pio:obs.sampler", "pio:obs.scraper",
                                  "pio:obs.watchdog"]
        assert prof_mod.obs_tick_seconds(reg, "scraper").sum > 0


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
