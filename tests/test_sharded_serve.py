"""Mesh-sharded serving tests: `ShardedBucketedTopK` /
`ShardedBucketedSimilar` must be BIT-IDENTICAL (ids and scores, ties
included) to the single-device plans and the stable-argsort host oracle
on the conftest-forced 8-device CPU mesh — across bucket sizes, banned
lists straddling shard boundaries, catalog sizes not divisible by the
shard count, and k above the per-shard candidate count — plus the
mesh-aware plan selection, the sharded dispatch/EWMA bookkeeping, and
the deploy-time warm path end to end."""

import numpy as np
import pytest

from predictionio_tpu.obs import compile_watch, get_registry
from predictionio_tpu.ops import topk, topk_sharded
from predictionio_tpu.ops.topk_sharded import (
    ServeMesh, ShardedBucketedSimilar, ShardedBucketedTopK, serve_plan,
    serve_mesh_from_conf, similar_plan,
)

pytestmark = pytest.mark.sharded


def _mesh(n=None):
    import jax
    from jax.sharding import Mesh
    devices = jax.devices()
    assert len(devices) == 8, "conftest must force 8 CPU devices"
    return Mesh(np.array(devices[:n] if n else devices),
                (topk_sharded.SHARD_AXIS,))


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
def factors_203():
    """203 items (NOT divisible by 8 shards -> per-shard 26, 5 padding
    rows on the tail shard), integer-valued so host f32 BLAS and device
    HIGHEST matmul agree bitwise."""
    rng = np.random.default_rng(11)
    return rng.integers(-4, 5, size=(203, 8)).astype(np.float32)


@pytest.fixture()
def sharded_plan(factors_203):
    plan = ShardedBucketedTopK(factors_203, k=6, buckets=(1, 2, 4, 8),
                               banned_width=16, mesh=_mesh())
    assert plan.n_shards == 8 and plan.per_shard == 26
    assert plan.warm() == 4
    return plan


@pytest.fixture()
def oracle_plan(factors_203):
    plan = topk.BucketedTopK(factors_203, k=6, buckets=(1, 2, 4, 8),
                             banned_width=16)
    plan.warm()
    return plan


class TestShardedTopK:
    def test_bit_identical_across_bucket_sizes(self, factors_203,
                                               sharded_plan, oracle_plan):
        rng = np.random.default_rng(2)
        for b in (1, 2, 3, 5, 8):
            vecs = rng.integers(-4, 5, size=(b, 8)).astype(np.float32)
            banned = [sorted(rng.choice(203, size=rng.integers(0, 12),
                                        replace=False).tolist())
                      for _ in range(b)]
            s, ix = sharded_plan(vecs, banned)
            os_, oix = oracle_plan(vecs, banned)
            assert np.array_equal(ix, oix), f"id mismatch at batch {b}"
            assert np.array_equal(s, os_), f"score mismatch at batch {b}"
            ref_s, ref_ix = _host_reference(vecs, factors_203, banned, 6)
            assert np.array_equal(ix, ref_ix)
            assert np.array_equal(s, ref_s)

    def test_banned_straddles_shard_boundaries(self, factors_203,
                                               sharded_plan, oracle_plan):
        """Banned ids chosen ON the shard boundaries (first/last row of
        every 26-row shard) must be filtered in global id space — an
        off-by-base translation would either leak a banned item or ban
        a neighbor."""
        per = sharded_plan.per_shard
        boundary = sorted(
            {s * per for s in range(8)} |
            {s * per - 1 for s in range(1, 8)} | {202})
        vecs = np.ones((2, 8), np.float32)
        banned = [boundary[:16], boundary[8:16]]
        s, ix = sharded_plan(vecs, banned)
        os_, oix = oracle_plan(vecs, banned)
        assert np.array_equal(ix, oix)
        assert np.array_equal(s, os_)
        for row in range(2):
            assert not set(ix[row].tolist()) & set(banned[row])

    def test_padding_rows_never_leak(self, sharded_plan, factors_203):
        """Catalog 203 pads to 208 sharded rows; the 5 padding ids
        (203..207) must never appear, even when bans push the result
        into low-score territory."""
        rng = np.random.default_rng(3)
        vecs = rng.integers(-4, 5, size=(4, 8)).astype(np.float32)
        banned = [sorted(rng.choice(203, size=12,
                                    replace=False).tolist())
                  for _ in range(4)]
        _, ix = sharded_plan(vecs, banned)
        assert ix.max() < 203

    def test_k_above_per_shard_candidates(self):
        """20 items over 8 shards -> per-shard 3 (pad 24), k=6 > 3: the
        per-shard candidate count clamps and the merge still returns
        the exact global top-6."""
        rng = np.random.default_rng(5)
        factors = rng.integers(-4, 5, size=(20, 8)).astype(np.float32)
        plan = ShardedBucketedTopK(factors, k=6, buckets=(1, 4),
                                   banned_width=8, mesh=_mesh())
        assert plan.per_shard == 3 and plan.k_shard == 3
        plan.warm()
        vecs = rng.integers(-4, 5, size=(3, 8)).astype(np.float32)
        banned = [[2, 3, 17], [0, 19], []]
        s, ix = plan(vecs, banned)
        ref_s, ref_ix = _host_reference(vecs, factors, banned, 6)
        assert np.array_equal(ix, ref_ix)
        assert np.array_equal(s, ref_s)

    def test_all_banned_neg_inf_ties_break_by_global_id(self):
        """Every item banned -> all candidates tie at NEG_INF; the
        deterministic tie-break (lowest global id first) must match the
        full-matrix lax.top_k exactly."""
        rng = np.random.default_rng(6)
        factors = rng.integers(-4, 5, size=(20, 8)).astype(np.float32)
        plan = ShardedBucketedTopK(factors, k=6, buckets=(1,),
                                   banned_width=32, mesh=_mesh())
        plan.warm()
        vecs = rng.integers(-4, 5, size=(1, 8)).astype(np.float32)
        banned = [list(range(20))]
        s, ix = plan(vecs, banned)
        assert np.array_equal(ix[0], np.arange(6))
        assert np.all(s[0] == np.float32(topk.NEG_INF))

    def test_chunks_past_largest_bucket(self, factors_203, sharded_plan,
                                        oracle_plan):
        rng = np.random.default_rng(7)
        vecs = rng.integers(-4, 5, size=(19, 8)).astype(np.float32)
        banned = [[] for _ in range(19)]
        s, ix = sharded_plan(vecs, banned)
        os_, oix = oracle_plan(vecs, banned)
        assert s.shape == (19, 6)
        assert np.array_equal(ix, oix)
        assert np.array_equal(s, os_)

    def test_zero_recompiles_in_steady_state(self, sharded_plan):
        rng = np.random.default_rng(8)
        # one call per bucket first: device_get of a fresh executable
        # may still trigger lazy jit helpers on first touch
        for b in (1, 2, 4, 8):
            sharded_plan(rng.standard_normal((b, 8)).astype(np.float32),
                         [[] for _ in range(b)])
        with compile_watch() as w:
            for b in (1, 3, 8, 2, 5):
                vecs = rng.standard_normal((b, 8)).astype(np.float32)
                sharded_plan(vecs, [[0, 1]] * b)
        assert w.count == 0, (
            f"{w.count} recompiles in sharded steady state")

    def test_unwarmed_bucket_raises(self, factors_203):
        plan = ShardedBucketedTopK(factors_203, k=6, buckets=(1, 2),
                                   banned_width=8, mesh=_mesh())
        with pytest.raises(RuntimeError, match="not warmed"):
            plan(np.ones((1, 8), np.float32), [[]])

    def test_dispatch_counts_and_metric(self, sharded_plan):
        before = topk.DISPATCH_COUNTS["sharded"]
        metric_before = get_registry().value("pio_topk_dispatch_total",
                                             path="sharded")
        sharded_plan(np.ones((2, 8), np.float32), [[], []])
        assert topk.DISPATCH_COUNTS["sharded"] == before + 1
        assert get_registry().value("pio_topk_dispatch_total",
                                    path="sharded") == metric_before + 1

    def test_shard_gauges_published(self, sharded_plan):
        reg = get_registry()
        assert reg.value("pio_serve_shards") == 8.0
        per_bytes = sharded_plan.per_shard * sharded_plan.rank * 4
        for s in range(8):
            assert reg.value("pio_serve_shard_bytes",
                             shard=str(s)) == float(per_bytes)


class TestShardedSimilar:
    def test_bit_identical_to_single_device(self):
        rng = np.random.default_rng(9)
        factors = rng.integers(-4, 5, size=(203, 8)).astype(np.float32)
        sharded = ShardedBucketedSimilar(factors, k=5, buckets=(1, 4),
                                         mesh=_mesh())
        single = topk.BucketedSimilar(factors, k=5, buckets=(1, 4))
        assert sharded.warm() == 2 and single.warm() == 2
        for b in (1, 3, 4):
            vecs = rng.integers(-4, 5, size=(b, 8)).astype(np.float32)
            mask = rng.random((b, 203)) > 0.2
            mask[0, :] = True
            s, ix = sharded(vecs, mask)
            os_, oix = single(vecs, mask)
            assert np.array_equal(ix, oix), f"id mismatch at batch {b}"
            # the normalised factors are irrational, so unlike the
            # integer-valued top-k parity the two matmuls ([b, 203] vs
            # [b, 26] per shard) may round the last bit differently:
            # ids exact, scores within 2 ulp of float32
            np.testing.assert_allclose(
                s, os_, rtol=2 * np.finfo(np.float32).eps, atol=0)
            assert ix.max() < 203   # padding columns never leak

    def test_all_false_mask_row(self):
        rng = np.random.default_rng(10)
        factors = rng.integers(-4, 5, size=(40, 8)).astype(np.float32)
        sharded = ShardedBucketedSimilar(factors, k=4, buckets=(2,),
                                         mesh=_mesh())
        single = topk.BucketedSimilar(factors, k=4, buckets=(2,))
        sharded.warm(), single.warm()
        vecs = rng.integers(-4, 5, size=(2, 8)).astype(np.float32)
        mask = np.ones((2, 40), bool)
        mask[1, :] = False
        s, ix = sharded(vecs, mask)
        os_, oix = single(vecs, mask)
        assert np.array_equal(ix, oix)
        assert np.all(s[1] == np.float32(topk.NEG_INF))


class TestPlanSelection:
    def test_no_mesh_builds_single_device(self, factors_203):
        plan = serve_plan(factors_203, k=6, buckets=(1,), mesh=None)
        assert type(plan) is topk.BucketedTopK

    def test_forced_mesh_builds_sharded(self, factors_203):
        sm = ServeMesh(_mesh(), forced=True)
        plan = serve_plan(factors_203, k=6, buckets=(1,), mesh=sm)
        assert isinstance(plan, ShardedBucketedTopK)
        sim = similar_plan(factors_203, k=6, buckets=(1,), mesh=sm)
        assert isinstance(sim, ShardedBucketedSimilar)

    def test_unforced_mesh_shards_only_past_capacity(self, factors_203,
                                                     monkeypatch):
        sm = ServeMesh(_mesh(), forced=False)
        # capacity unknown (CPU reports nothing) -> single-device
        monkeypatch.delenv("PIO_DEVICE_HBM_BYTES", raising=False)
        plan = serve_plan(factors_203, k=6, buckets=(1,), mesh=sm)
        assert type(plan) is topk.BucketedTopK
        # 203*8*4 = 6496 bytes of factors; a 4 KiB "HBM" overflows
        monkeypatch.setenv("PIO_DEVICE_HBM_BYTES", "4096")
        plan = serve_plan(factors_203, k=6, buckets=(1,), mesh=sm)
        assert isinstance(plan, ShardedBucketedTopK)

    def test_serve_mesh_from_conf(self, monkeypatch):
        monkeypatch.delenv("PIO_SERVE_SHARD", raising=False)
        monkeypatch.delenv("PIO_SERVE_SHARDS", raising=False)
        sm = serve_mesh_from_conf({})
        assert sm is not None and sm.n_shards == 8 and not sm.forced
        # a configured training mesh forces the sharded path
        assert serve_mesh_from_conf({"mesh": "data=8"}).forced
        monkeypatch.setenv("PIO_SERVE_SHARD", "on")
        assert serve_mesh_from_conf({}).forced
        monkeypatch.setenv("PIO_SERVE_SHARD", "off")
        assert serve_mesh_from_conf({"mesh": "data=8"}) is None
        monkeypatch.setenv("PIO_SERVE_SHARD", "auto")
        monkeypatch.setenv("PIO_SERVE_SHARDS", "4")
        assert serve_mesh_from_conf({}).n_shards == 4

    def test_policy_tracks_sharded_ewma(self):
        pol = topk.DispatchPolicy()
        pol.observe("sharded", 1000, 0.02)
        snap = pol.snapshot()
        assert snap["sharded_call_s"] == pytest.approx(0.02)
        assert snap["device_call_s"] is None   # paths don't cross-pollute
        fresh = topk.DispatchPolicy()
        fresh.restore(snap)
        assert fresh.snapshot()["sharded_call_s"] == pytest.approx(0.02)


@pytest.fixture()
def trained_rec(mem_registry):
    """Registry with a trained recommendation instance (mirrors
    test_device_serve.trained_rec; separate copy so the two modules
    stay independently runnable)."""
    from predictionio_tpu.core import (
        CoreWorkflow, EngineParams, RuntimeContext,
    )
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.models import recommendation as rec

    apps = mem_registry.get_meta_data_apps()
    app_id = apps.insert(App(0, "shardapp"))
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
        data_source_params=("", rec.DataSourceParams(app_name="shardapp")),
        algorithm_params_list=(
            ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=3,
                                           seed=1)),))
    CoreWorkflow.run_train(engine, params, ctx)
    return mem_registry, engine


class TestShardedDeployE2E:
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

    def test_env_forced_shard_serves_through_sharded_plan(
            self, trained_rec, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_SHARD", "on")
        registry, engine = trained_rec
        srv = self._start(registry, engine)
        try:
            plan = getattr(srv._dep.algos[0], "_serve_plan", None)
            assert isinstance(plan, ShardedBucketedTopK)
            assert plan.n_shards == 8
            before = topk.DISPATCH_COUNTS["sharded"]
            self._query(srv.port, "u1")     # settle non-topk lazies
            with compile_watch() as w:
                for q in range(6):
                    res = self._query(srv.port, f"u{q % 12}")
                    assert len(res["itemScores"]) == 3
            assert w.count == 0, (
                f"{w.count} recompiles in sharded steady state")
            assert topk.DISPATCH_COUNTS["sharded"] > before
        finally:
            srv.shutdown()

    def test_config_mesh_forces_sharded_plan(self, trained_rec,
                                             monkeypatch):
        monkeypatch.setenv("PIO_SERVE_SHARD", "auto")
        registry, engine = trained_rec
        srv = self._start(registry, engine, mesh="items=8")
        try:
            assert isinstance(srv._dep.algos[0]._serve_plan,
                              ShardedBucketedTopK)
        finally:
            srv.shutdown()

    def test_auto_without_capacity_stays_single_device(
            self, trained_rec, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_SHARD", "auto")
        monkeypatch.delenv("PIO_DEVICE_HBM_BYTES", raising=False)
        registry, engine = trained_rec
        srv = self._start(registry, engine)
        try:
            assert type(srv._dep.algos[0]._serve_plan) \
                is topk.BucketedTopK
        finally:
            srv.shutdown()

    def test_sharded_and_single_device_serve_identically(
            self, trained_rec, monkeypatch):
        """The same trained instance served through both plans returns
        identical items and scores for identical queries."""
        registry, engine = trained_rec
        monkeypatch.setenv("PIO_SERVE_SHARD", "off")
        srv1 = self._start(registry, engine)
        try:
            single = [self._query(srv1.port, f"u{q}") for q in range(6)]
        finally:
            srv1.shutdown()
        monkeypatch.setenv("PIO_SERVE_SHARD", "on")
        srv2 = self._start(registry, engine)
        try:
            assert isinstance(srv2._dep.algos[0]._serve_plan,
                              ShardedBucketedTopK)
            sharded = [self._query(srv2.port, f"u{q}") for q in range(6)]
        finally:
            srv2.shutdown()
        assert sharded == single
