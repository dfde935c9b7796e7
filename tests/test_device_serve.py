"""Device-resident serve pipeline tests: bucketed AOT plans (padding
correctness, zero-recompile steady state), the amortized dispatch
policy, the concurrent per-algorithm fan-out, and the micro-batcher's
full-batch condition-variable wakeup."""

import threading
import time

import numpy as np
import pytest

from predictionio_tpu.obs import compile_watch, get_registry
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.ops import topk
from predictionio_tpu.serving.server import (
    _Deployment, _MicroBatcher, _ServeInstruments,
)


def _host_reference(vecs, factors, banned_lists, k):
    out_s, out_ix = [], []
    for row in range(vecs.shape[0]):
        sc = vecs[row] @ factors.T
        if banned_lists[row]:
            sc[np.asarray(banned_lists[row], int)] = topk.NEG_INF
        order = np.argsort(-sc, kind="stable")[:k]
        out_ix.append(order)
        out_s.append(sc[order])
    return np.array(out_s), np.array(out_ix)


@pytest.fixture()
def plan_and_factors():
    rng = np.random.default_rng(7)
    # integer-valued factors: host f32 BLAS and device HIGHEST matmul
    # agree bitwise, so parity checks are exact
    factors = rng.integers(-4, 5, size=(200, 8)).astype(np.float32)
    plan = topk.BucketedTopK(factors, k=6, buckets=(1, 2, 4, 8),
                             banned_width=8)
    assert plan.warm() == 4
    return plan, factors


class TestBucketedTopK:
    def test_padded_lanes_never_leak(self, plan_and_factors):
        plan, factors = plan_and_factors
        rng = np.random.default_rng(1)
        # batch 3 pads to bucket 4; batch 5 pads to 8
        for b in (3, 5):
            vecs = rng.integers(-4, 5, size=(b, 8)).astype(np.float32)
            banned = [sorted(rng.choice(200, size=rng.integers(0, 8),
                                        replace=False).tolist())
                      for _ in range(b)]
            s, ix = plan(vecs, banned)
            assert s.shape == (b, 6) and ix.shape == (b, 6)
            ref_s, ref_ix = _host_reference(vecs, factors, banned, 6)
            assert np.array_equal(s, ref_s)
            assert np.array_equal(ix, ref_ix)
            for row in range(b):
                assert not set(ix[row].tolist()) & set(banned[row])

    def test_chunks_past_largest_bucket(self, plan_and_factors):
        plan, factors = plan_and_factors
        rng = np.random.default_rng(2)
        vecs = rng.integers(-4, 5, size=(19, 8)).astype(np.float32)
        banned = [[] for _ in range(19)]
        s, ix = plan(vecs, banned)
        assert s.shape == (19, 6)
        ref_s, ref_ix = _host_reference(vecs, factors, banned, 6)
        assert np.array_equal(s, ref_s) and np.array_equal(ix, ref_ix)

    def test_zero_recompiles_across_every_bucket_size(
            self, plan_and_factors):
        plan, _ = plan_and_factors
        rng = np.random.default_rng(3)
        with compile_watch() as w:
            for _ in range(2):          # every size, twice
                for b in range(1, 9):
                    vecs = rng.integers(-4, 5, size=(b, 8)).astype(
                        np.float32)
                    plan(vecs, [[0]] * b)
        assert w.count == 0

    def test_fits_rejects_oversized_queries(self, plan_and_factors):
        plan, _ = plan_and_factors
        assert plan.fits(max_banned=8, k=6)
        assert not plan.fits(max_banned=9, k=6)     # > banned_width
        assert not plan.fits(max_banned=0, k=7)     # > warmed k
        cold = topk.BucketedTopK(np.ones((10, 4), np.float32), k=3)
        assert not cold.fits(max_banned=0, k=1)     # never warmed
        with pytest.raises(RuntimeError, match="not warmed"):
            cold(np.ones((1, 4), np.float32), [[]])

    def test_warm_is_idempotent(self, plan_and_factors):
        plan, _ = plan_and_factors
        assert plan.warm() == 0


class TestDispatchPolicy:
    def test_cold_start_matches_static_crossover(self):
        p = topk.DispatchPolicy()
        assert p.choose(topk.HOST_CROSSOVER_CELLS) == "device"
        assert p.choose(topk.HOST_CROSSOVER_CELLS - 1) == "host"

    def test_promotion_needs_both_ewmas_and_the_floor(self):
        p = topk.DispatchPolicy()
        cells = max(topk.PROMOTE_FLOOR_CELLS,
                    topk.HOST_CROSSOVER_CELLS // 4)
        p.observe("host", cells, 1.0)        # slow host
        assert p.choose(cells) == "host"     # device EWMA still unknown
        p.observe("device", cells, 1e-4)     # fast device
        assert p.choose(cells) == "device"   # promoted below crossover
        # tiny problems never promote, whatever the EWMAs say
        assert p.choose(topk.PROMOTE_FLOOR_CELLS - 1) == "host"

    def test_slow_device_stays_host(self):
        p = topk.DispatchPolicy()
        cells = max(topk.PROMOTE_FLOOR_CELLS,
                    topk.HOST_CROSSOVER_CELLS // 4)
        p.observe("host", cells, 1e-4)       # fast host
        p.observe("device", cells, 10.0)     # terrible device
        assert p.choose(cells) == "host"

    def test_inflight_coalescing_pulls_toward_device(self):
        p = topk.DispatchPolicy()
        cells = max(topk.PROMOTE_FLOOR_CELLS,
                    topk.HOST_CROSSOVER_CELLS // 4)
        p.observe("host", cells, 5e-4)
        p.observe("device", cells, 1e-3)     # 2x the idle host cost
        assert p.choose(cells) == "host"     # idle host still wins
        p.host_begin()
        p.host_begin()                       # 2 host calls in flight
        assert p.choose(cells) == "device"   # coalescing term flips it
        p.host_end()
        p.host_end()
        assert p.snapshot()["host_inflight"] == 0

    def test_record_dispatch_exports_metric(self):
        reg = get_registry()
        before = reg.value("pio_topk_dispatch_total", path="device")
        counts_before = topk.DISPATCH_COUNTS["device"]
        topk._record_dispatch("device", 100, 0.001)
        assert topk.DISPATCH_COUNTS["device"] == counts_before + 1
        assert reg.value("pio_topk_dispatch_total",
                         path="device") == before + 1


class _EchoAlgo:
    query_class = None
    params = None

    def __init__(self, tag, barrier=None, fail=False):
        self.tag = tag
        self.barrier = barrier
        self.fail = fail

    def batch_predict(self, model, queries):
        if self.barrier is not None:
            # only passes when BOTH algorithms run concurrently
            self.barrier.wait(timeout=5.0)
        if self.fail:
            raise ValueError(f"{self.tag} exploded")
        return [(i, f"{self.tag}:{q}") for i, q in queries]


class _PassthroughServing:
    def supplement(self, query):
        return query

    def serve(self, query, predictions):
        return predictions[0]


def _deployment(algos):
    class _Inst:
        id = "t"
        engine_variant = "default"
    return _Deployment(None, _Inst(), algos,
                       [None] * len(algos), _PassthroughServing())


class TestConcurrentPredict:
    def test_algorithms_run_concurrently(self):
        barrier = threading.Barrier(2)
        dep = _deployment([_EchoAlgo("a", barrier),
                           _EchoAlgo("b", barrier)])
        # sequential execution would deadlock both on the barrier and
        # fail the batch; concurrency is what lets this return
        assert dep.predict_batch(["q1", "q2"]) == ["a:q1", "a:q2"]

    def test_error_isolation_survives_concurrency(self):
        dep = _deployment([_EchoAlgo("bad", fail=True), _EchoAlgo("ok")])
        assert dep.predict_batch(["q"]) == ["ok:q"]

    def test_all_algorithms_failing_raises(self):
        dep = _deployment([_EchoAlgo("x", fail=True),
                           _EchoAlgo("y", fail=True)])
        with pytest.raises(ValueError, match="exploded"):
            dep.predict_batch(["q"])


class _InstantDep:
    query_class = None

    def predict_batch(self, queries):
        return [f"r:{q}" for q in queries]


class TestDrainerWakeup:
    def test_full_batch_ships_before_window_expires(self):
        # window is 5s; a full batch must NOT wait it out
        mb = _MicroBatcher(window_s=5.0, batch_max=4)
        dep = _InstantDep()
        results = {}

        def worker(n):
            results[n] = mb.submit(dep, f"q{n}")

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker, args=(n,))
                   for n in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=4.0)
        elapsed = time.perf_counter() - t0
        assert results == {n: f"r:q{n}" for n in range(4)}
        assert elapsed < 2.0, (
            f"full batch waited {elapsed:.2f}s — condition wakeup broken")

    def test_partial_batch_still_drains_after_window(self):
        mb = _MicroBatcher(window_s=0.02, batch_max=64)
        assert mb.submit(_InstantDep(), "solo") == "r:solo"


class _Crash(BaseException):
    """Passes `_process`'s `except Exception` and kills the drainer."""


class _GatedDep:
    """Stands for the device: every `predict_batch` call waits for its
    own gate, so a test decides which cycle finishes when."""
    query_class = None

    def __init__(self):
        self.lock = threading.Lock()
        self.calls = []               # (queries, gate), in entry order
        self.entered = threading.Semaphore(0)
        self.active = self.max_active = 0

    def predict_batch(self, queries):
        gate = threading.Event()
        with self.lock:
            self.calls.append((list(queries), gate))
            self.active += 1
            self.max_active = max(self.max_active, self.active)
        self.entered.release()
        try:
            assert gate.wait(10.0), "the test never opened this gate"
            if any(q.startswith("crash") for q in queries):
                raise _Crash("drainer killed")
            if any(q.startswith("bad") for q in queries):
                raise ValueError("predict failed")
            return [f"r:{q}" for q in queries]
        finally:
            with self.lock:
                self.active -= 1

    def wait_entered(self, n=1):
        for _ in range(n):
            assert self.entered.acquire(timeout=5.0), \
                "predict_batch was not entered"

    def open(self, i):
        self.calls[i][1].set()


def _wait_until(pred, timeout=5.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if pred():
            return True
        time.sleep(0.002)
    return pred()


class _Overlap:
    """A batcher with two cycles blocked on the fake device: the first
    holds q0 and q1, the second q2 and q3 (a batch goes behind a cycle
    in flight once it has as many rows: `_my_turn_locked`)."""

    def __init__(self, first=("q0", "q1"), second=("q2", "q3")):
        self.reg = MetricsRegistry()
        self.mb = _MicroBatcher(window_s=0.02, batch_max=4,
                                obs=_ServeInstruments(self.reg))
        self.dep = _GatedDep()
        self.results = {}
        self.threads = []
        self.submit(*first)
        self.dep.wait_entered()
        if second:
            self.submit(*second)
            self.dep.wait_entered()

    def submit(self, *queries):
        def worker(q):
            try:
                self.results[q] = self.mb.submit(self.dep, q)
            except BaseException as e:
                self.results[q] = e
        # one lane, one instant: the window batches them together
        ts = [threading.Thread(target=worker, args=(q,), daemon=True)
              for q in queries]
        for t in ts:
            t.start()
        self.threads.extend(ts)
        assert _wait_until(lambda: all(
            any(q in c[0] for c in self.dep.calls) or
            len(self.mb._queue) for q in queries))

    def finish(self, *order):
        for i in order:
            self.dep.open(i)
        for t in self.threads:
            t.join(5.0)
            assert not t.is_alive()

    def hist(self, name):
        return self.reg.histogram(name).labels()


class TestDoubleBuffer:
    """Two cycles in flight, one forming (`_MicroBatcher`)."""

    def test_second_batch_launches_while_first_is_blocked(self):
        o = _Overlap()
        try:
            assert [sorted(c[0]) for c in o.dep.calls] == [
                ["q0", "q1"], ["q2", "q3"]]
            assert o.dep.active == 2          # both inside predict_batch
            assert o.mb._in_flight == 2 and o.mb._draining == 2
            assert len(o.mb.drain_beats()) == 2
        finally:
            o.finish(0, 1)
        assert o.results == {q: f"r:{q}" for q in ("q0", "q1", "q2", "q3")}

    def test_never_a_third_cycle_and_the_lane_keeps_what_arrives(self):
        o = _Overlap()
        try:
            o.submit("q4", "q5")
            time.sleep(5 * o.mb.window_s)
            with o.mb._lock:
                assert (o.mb._in_flight, o.mb._draining) == (2, 2)
                assert not o.mb._forming
                assert len(o.mb._queue) == 2
            assert len(o.dep.calls) == 2 and o.dep.max_active == 2
            o.dep.open(1)                     # the second finishes ...
            o.dep.wait_entered()              # ... and takes the lane
            assert sorted(o.dep.calls[2][0]) == ["q4", "q5"]
        finally:
            o.finish(0, 1, 2)
        assert o.results["q4"] == "r:q4" and o.results["q5"] == "r:q5"
        assert o.dep.max_active == 2

    def test_never_two_windows_open_at_once(self):
        mb = _MicroBatcher(window_s=0.002, batch_max=4,
                           obs=_ServeInstruments(MetricsRegistry()))
        opened, worst, flying = [0], [0], [0]
        wait_for = mb._full.wait_for

        def window(pred, timeout=None):
            # the condition releases the lock while it waits: a second
            # forming drainer would be inside at the same time
            opened[0] += 1
            worst[0] = max(worst[0], opened[0])
            flying[0] = max(flying[0], mb._in_flight, mb._draining)
            try:
                return wait_for(pred, timeout=timeout)
            finally:
                opened[0] -= 1
        mb._full.wait_for = window

        class _Slow:
            query_class = None

            def predict_batch(self, queries):
                time.sleep(0.003)
                return [f"r:{q}" for q in queries]
        dep, out = _Slow(), {}

        def caller(c):
            for i in range(25):
                out[(c, i)] = mb.submit(dep, f"{c}.{i}")
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20.0)
        assert out == {(c, i): f"r:{c}.{i}"
                       for c in range(12) for i in range(25)}
        assert worst[0] == 1
        assert flying[0] <= 2
        assert mb.close(timeout=5.0)

    def test_a_smaller_batch_waits_for_the_cycle_in_flight_to_end(self):
        o = _Overlap(("q0", "q1", "q2"), None)
        o.submit("q3")                        # starts the second drainer
        time.sleep(5 * o.mb.window_s)
        with o.mb._lock:
            assert (o.mb._in_flight, o.mb._draining) == (1, 2)
            assert not o.mb._forming and len(o.mb._queue) == 1
        assert len(o.dep.calls) == 1
        o.dep.open(0)                         # its callers could come back
        o.dep.wait_entered()
        assert o.dep.calls[1][0] == ["q3"]
        o.finish(1)
        assert o.results == {q: f"r:{q}" for q in ("q0", "q1", "q2", "q3")}
        h = o.hist("pio_serve_cycles_in_flight")
        assert (h.count, h.sum) == (2, 2.0)   # serial, both times

    @pytest.mark.parametrize("first,rest", [
        (("q0", "q1"), ("q3",)),
        (("q0", "q1", "q2", "q3", "q4"), ("q6", "q7"))])
    def test_the_held_drainer_ships_once_the_lane_has_as_many_rows(
            self, first, rest):
        # batch_max is 4: five submits make a first cycle of 4 and leave
        # one in the lane; a full batch never waits for more
        o = _Overlap(first[:4], None)
        held = first[4:] + ("q5",)
        o.submit(*held)
        time.sleep(3 * o.mb.window_s)
        assert len(o.dep.calls) == 1          # fewer rows than in flight
        o.submit(*rest)
        o.dep.wait_entered()                  # the first still blocked
        assert sorted(o.dep.calls[1][0]) == sorted(held + rest)
        assert o.mb._in_flight == 2
        o.finish(0, 1)
        assert all(o.results[q] == f"r:{q}" for q in first + held + rest)

    def test_rows_keep_their_results_when_the_second_finishes_first(self):
        o = _Overlap()
        o.dep.open(1)
        assert _wait_until(lambda: len(o.results) == 2)
        # the first still blocked
        assert o.results == {"q2": "r:q2", "q3": "r:q3"}
        assert _wait_until(lambda: o.mb._in_flight == 1)
        o.finish(0)
        assert o.results == {q: f"r:{q}" for q in ("q0", "q1", "q2", "q3")}

    @pytest.mark.parametrize("victim", [0, 1])
    def test_a_failed_predict_fails_its_own_cycle_only(self, victim):
        first, second = ("q0", "q1"), ("q2", "q3")
        if victim == 0:
            first = ("bad0", "bad1")
        else:
            second = ("bad2", "bad3")
        o = _Overlap(first, second)
        o.finish(victim, 1 - victim)
        for q in first + second:
            if q.startswith("bad"):
                assert isinstance(o.results[q], ValueError)
            else:
                assert o.results[q] == f"r:{q}"

    @pytest.mark.parametrize("victim", [0, 1])
    def test_a_dead_drainer_fails_its_batch_and_leaves_the_other(
            self, victim):
        first, second = ("q0", "q1"), ("q2", "q3")
        if victim == 0:
            first = ("crash0", "crash1")
        else:
            second = ("crash2", "crash3")
        o = _Overlap(first, second)
        o.submit("q4")                        # pending while one dies
        o.dep.open(victim)
        dead = first if victim == 0 else second
        assert _wait_until(lambda: all(q in o.results for q in dead))
        for q in dead:
            assert isinstance(o.results[q], _Crash)
        # the other drainer is alive: the lane was not failed with it
        assert "q4" not in o.results
        with o.mb._lock:
            assert (o.mb._in_flight, o.mb._draining) == (1, 1)
            assert o.mb._rows_in_flight == 2
        assert _wait_until(lambda: len(o.mb.drain_beats()) == 1)
        o.dep.open(1 - victim)
        o.dep.wait_entered()                  # q4, by whichever drainer
        o.finish(2)
        for q in (second if victim == 0 else first) + ("q4",):
            assert o.results[q] == f"r:{q}"
        assert o.mb.close(timeout=5.0)

    def test_the_last_drainer_to_die_fails_the_lane(self):
        o = _Overlap(("crash0",), ("crash1",))
        o.submit("q2")
        o.finish(0, 1)
        assert all(isinstance(o.results[q], _Crash)
                   for q in ("crash0", "crash1", "q2"))
        with o.mb._lock:
            assert (o.mb._in_flight, o.mb._draining) == (0, 0)
            assert o.mb._rows_in_flight == 0
            assert not o.mb._forming and not len(o.mb._queue)
        assert _wait_until(lambda: o.mb.drain_beats() == [])
        o.submit("q3")                        # a fresh drainer serves it
        o.dep.wait_entered()
        o.finish(2)
        assert o.results["q3"] == "r:q3"

    def test_close_returns_only_when_both_cycles_are_done(self):
        o = _Overlap()
        assert not o.mb.close(timeout=0.1)
        o.dep.open(1)
        assert _wait_until(lambda: "q2" in o.results)
        assert not o.mb.close(timeout=0.1)    # the first still holds rows
        o.dep.open(0)
        assert o.mb.close(timeout=5.0)
        o.finish()
        assert o.mb._draining == 0 and o.mb._in_flight == 0
        assert o.results == {q: f"r:{q}" for q in ("q0", "q1", "q2", "q3")}

    def test_both_drainers_retire_and_the_next_submit_starts_one(self):
        o = _Overlap()
        o.finish(0, 1)
        assert _wait_until(lambda: o.mb._draining == 0)
        assert _wait_until(lambda: o.mb.drain_beats() == [])
        assert not o.mb._forming
        o.submit("q4")
        o.dep.wait_entered()
        assert o.mb._draining == 1
        o.finish(2)
        assert o.results["q4"] == "r:q4"

    def test_cycles_in_flight_counts_one_serial_and_two_overlapped(self):
        o = _Overlap()
        h = o.hist("pio_serve_cycles_in_flight")
        assert (h.count, h.sum) == (2, 3.0)   # 1 at the first take, then 2
        assert h.bucket_counts == [1, 1, 0]
        o.finish(0, 1)
        assert _wait_until(lambda: o.mb._draining == 0)
        for q in ("s0", "s1", "s2"):          # one at a time: serial
            o.submit(q)
            o.dep.wait_entered()
            o.dep.open(len(o.dep.calls) - 1)
            assert _wait_until(lambda: q in o.results)
        assert (h.count, h.sum) == (5, 6.0)
        assert h.bucket_counts == [4, 1, 0]

    def test_sizes_once_a_cycle_and_queue_delay_once_a_row(self):
        o = _Overlap()
        o.finish(1, 0)
        assert o.mb.size_counts() == {2: 2}
        sizes = o.hist("pio_serve_batch_size")
        assert (sizes.count, sizes.sum) == (2, 4.0)
        assert o.hist("pio_queue_delay_seconds").count == 4

    def test_drain_estimate_is_one_cycle_take_to_wake(self):
        o = _Overlap()
        time.sleep(0.06)
        o.dep.open(1)                         # the second: a short cycle
        assert _wait_until(lambda: o.mb.drain_time_ewma() > 0.0)
        short = o.mb.drain_time_ewma()
        o.finish(0)                           # the first waited longer
        assert _wait_until(lambda: o.mb.drain_time_ewma() > short)
        assert 0.0 < short < o.mb.drain_time_ewma() < 5.0


@pytest.fixture()
def trained_rec(mem_registry):
    """Registry with a trained recommendation instance (the warmup
    integration surface)."""
    from predictionio_tpu.core import (
        CoreWorkflow, EngineParams, RuntimeContext,
    )
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.models import recommendation as rec

    apps = mem_registry.get_meta_data_apps()
    app_id = apps.insert(App(0, "warmapp"))
    events = mem_registry.get_events()
    events.init(app_id)
    rng = np.random.RandomState(0)
    for u in range(12):
        for i in range(15):
            if rng.rand() > 0.6:
                continue
            events.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": float(1 + i % 5)})), app_id)
    ctx = RuntimeContext(registry=mem_registry)
    engine = rec.engine()
    params = EngineParams(
        data_source_params=("", rec.DataSourceParams(app_name="warmapp")),
        algorithm_params_list=(
            ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=3,
                                           seed=1)),))
    CoreWorkflow.run_train(engine, params, ctx)
    return mem_registry, engine


class TestDeployWarmup:
    def _start(self, registry, engine, **cfg):
        from predictionio_tpu.serving import PredictionServer, ServerConfig
        srv = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0, **cfg),
            registry=registry, engine=engine)
        srv.start()
        return srv

    def _query(self, port, user, num=3):
        import json
        import urllib.request
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json",
            data=json.dumps({"user": user, "num": num}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read())

    def test_deploy_builds_plan_and_steady_state_is_recompile_free(
            self, trained_rec):
        registry, engine = trained_rec
        srv = self._start(registry, engine)
        try:
            plan = getattr(srv._dep.algos[0], "_serve_plan", None)
            assert plan is not None, "warm_serving did not run at deploy"
            # batching off -> only the single-query bucket is warmed
            assert tuple(plan._exe) == (1,)
            self._query(srv.port, "u1")     # settle any non-topk lazies
            with compile_watch() as w:
                for q in range(6):
                    res = self._query(srv.port, f"u{q % 12}")
                    assert len(res["itemScores"]) == 3
            assert w.count == 0, (
                f"{w.count} recompiles in steady state — the AOT plan "
                "is not being dispatched")
        finally:
            srv.shutdown()

    def test_batcher_caps_warmed_buckets(self, trained_rec):
        registry, engine = trained_rec
        srv = self._start(registry, engine, batch_window_ms=2,
                          batch_max=8)
        try:
            plan = srv._dep.algos[0]._serve_plan
            assert tuple(plan._exe) == (1, 2, 4, 8)
        finally:
            srv.shutdown()

    def test_warmup_env_off(self, trained_rec, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_WARMUP", "off")
        registry, engine = trained_rec
        srv = self._start(registry, engine)
        try:
            assert getattr(srv._dep.algos[0], "_serve_plan", None) is None
            # the generic dispatch path still serves correctly
            assert len(self._query(srv.port, "u1")["itemScores"]) == 3
        finally:
            srv.shutdown()
