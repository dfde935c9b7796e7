"""The serve plan's contract, once, for every plan class: the bucket
grid, the call cycle and the factor swap live in `ops/topk.BucketedPlan`
and each leaf (`BucketedTopK`, `BucketedSimilar`, `ShardedBucketedTopK`,
`ShardedBucketedSimilar` on the conftest-forced 8-device CPU mesh) and
each wrapper around one (`TieredTopK`, `ShardSliceTopK`) must hold it.
Also `score_banned` / `score_similar`, the one place that decides which
rows of a template's batch go through the plan."""

import numpy as np
import pytest

from predictionio_tpu.obs import (
    MetricsRegistry, compile_watch, get_registry, trace,
)
from predictionio_tpu.ops import topk, topk_sharded
from predictionio_tpu.ops.topk_sharded import (
    ShardedBucketedSimilar, ShardedBucketedTopK, ShardSlice, ShardSliceTopK,
)
from predictionio_tpu.ops.topk_tiered import TieredTopK

N_ITEMS, RANK, K, BUCKETS = 203, 8, 6, (1, 2, 4, 8)
# the slice a `ShardSliceTopK` member owns: shard 1 of 3, ceil-divided
_SLICE = slice(68, 136)


def _mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()), (topk_sharded.SHARD_AXIS,))


def _factors(seed=11):
    """Integer-valued, so host f32 BLAS and the device's HIGHEST matmul
    agree bitwise and parity checks are exact."""
    return np.random.default_rng(seed).integers(
        -4, 5, size=(N_ITEMS, RANK)).astype(np.float32)


class _Kind:
    """One plan class under test: how to build it, what filter a call
    carries, the oracle of a call, and what the cycle should record."""

    def __init__(self, name, build, *, dense=False, path="device",
                 also=None, rows=slice(None)):
        self.name, self.build, self.dense = name, build, dense
        self.path = path        # label the plan's own dispatch counts under
        self.also = also        # a second dispatch a wrapper records after
        self.rows = rows        # catalog rows the plan answers from

    def plan(self, factors, warm=True):
        plan = self.build(factors)
        if warm:
            assert plan.warm() == len(BUCKETS)
        return plan

    def filter(self, rng, b):
        if self.dense:
            return rng.random((b, N_ITEMS)) > 0.3
        return [sorted(rng.choice(N_ITEMS, size=rng.integers(0, 8),
                                  replace=False).tolist())
                for _ in range(b)]

    def oracle(self, vecs, factors, filt):
        """Stable-argsort host reference over the rows the plan owns."""
        if self.dense:
            unit = lambda x: x / (  # noqa: E731
                np.linalg.norm(x, axis=-1, keepdims=True) + 1e-9)
            scores = unit(vecs) @ unit(factors).T
            scores = np.where(filt, scores, np.float32(topk.NEG_INF))
        else:
            scores = vecs @ factors.T
            for row, banned in enumerate(filt):
                scores[row, banned] = topk.NEG_INF
        lo = self.rows.start or 0
        scores = scores[:, self.rows]
        ix = np.argsort(-scores, axis=1, kind="stable")[:, :K]
        return np.take_along_axis(scores, ix, axis=1), ix + lo

    def previous(self, factors):
        """What `swap_factors` hands back for these old factors."""
        return factors[self.rows]


KINDS = [
    _Kind("BucketedTopK", lambda f: topk.BucketedTopK(
        f, k=K, buckets=BUCKETS, banned_width=8)),
    _Kind("BucketedSimilar", lambda f: topk.BucketedSimilar(
        f, k=K, buckets=BUCKETS), dense=True),
    _Kind("ShardedBucketedTopK", lambda f: ShardedBucketedTopK(
        f, k=K, buckets=BUCKETS, banned_width=8, mesh=_mesh()),
        path="sharded"),
    _Kind("ShardedBucketedSimilar", lambda f: ShardedBucketedSimilar(
        f, k=K, buckets=BUCKETS, mesh=_mesh()), dense=True, path="sharded"),
    _Kind("TieredTopK", lambda f: TieredTopK(
        f, k=K, buckets=BUCKETS, banned_width=8, hot_items=50),
        also="host"),
    _Kind("ShardSliceTopK", lambda f: ShardSliceTopK(
        f, k=K, buckets=BUCKETS, banned_width=8,
        slice_spec=ShardSlice(n_shards=3, index=1)), rows=_SLICE),
]


@pytest.fixture(params=KINDS, ids=lambda kind: kind.name)
def kind(request):
    return request.param


def _vecs(rng, b):
    return rng.integers(-4, 5, size=(b, RANK)).astype(np.float32)


def _same(got, want, exact=True):
    """Scores to the bit where the factors are integers; a cosine is
    rounded differently by numpy and by XLA, in the last place."""
    if exact:
        assert np.array_equal(got[0], want[0])
    else:
        np.testing.assert_allclose(got[0], want[0], rtol=0, atol=2e-7)
    assert np.array_equal(got[1], want[1])


def test_warm_twice_compiles_once(kind):
    plan = kind.plan(_factors(), warm=False)
    assert plan.warm() == len(BUCKETS)
    with compile_watch() as w:
        assert plan.warm() == 0
    assert w.count == 0
    assert tuple(plan.buckets) == BUCKETS and plan.max_bucket == 8


def test_batch_past_the_largest_bucket_is_chunked(kind):
    factors, rng = _factors(), np.random.default_rng(2)
    plan = kind.plan(factors)
    vecs, filt = _vecs(rng, 19), kind.filter(rng, 19)
    got = plan(vecs, filt)
    assert got[0].shape == (19, K)
    _same(got, kind.oracle(vecs, factors, filt), not kind.dense)


def test_unwarmed_bucket_raises_the_one_error(kind):
    plan = kind.plan(_factors(), warm=False)
    rng = np.random.default_rng(3)
    with pytest.raises(RuntimeError,
                       match=r"bucket 4 not warmed; call warm\(\)"):
        plan(_vecs(rng, 3), kind.filter(rng, 3))


def test_swap_factors_keeps_the_executables(kind):
    old, new = _factors(), _factors(seed=12)
    plan = kind.plan(old)
    with pytest.raises(ValueError, match="swap_factors shape"):
        plan.swap_factors(np.ones((N_ITEMS + 1, RANK), np.float32))
    rng = np.random.default_rng(4)
    vecs, filt = _vecs(rng, 5), kind.filter(rng, 5)
    with compile_watch() as w:
        prev = plan.swap_factors(new)
        got = plan(vecs, filt)
    assert w.count == 0
    assert np.array_equal(prev, kind.previous(old))
    _same(got, kind.oracle(vecs, new, filt), not kind.dense)
    # the token rolls the swap back
    plan.swap_factors(prev)
    _same(plan(vecs, filt), kind.oracle(vecs, old, filt), not kind.dense)


def test_a_call_is_one_pack_launch_fetch_on_the_cycles_record(kind):
    """The stages are observed once a call and the `BatchTrace` carries
    the bucket and the path, whichever leaf served (at PR 29's parent
    the `Similar` plans recorded neither)."""
    plan = kind.plan(_factors())
    rng = np.random.default_rng(5)
    vecs, filt = _vecs(rng, 3), kind.filter(rng, 3)
    children = trace.stage_children(MetricsRegistry().histogram(
        "pio_serve_stage_seconds", trace.STAGE_SECONDS_HELP,
        labels=("stage",)))
    bt = trace.batch_begin(children)
    try:
        plan(vecs, filt)
    finally:
        trace.batch_end(bt)
    assert bt.bucket == 4
    assert bt.path == (kind.also or kind.path)
    assert [children[s].count for s in ("pack", "launch", "fetch")] == [
        1, 1, 1]


def test_a_call_counts_one_dispatch_under_the_plans_label(kind):
    plan = kind.plan(_factors())
    rng = np.random.default_rng(6)
    vecs, filt = _vecs(rng, 2), kind.filter(rng, 2)
    reg = get_registry()
    labels = [kind.path] + ([kind.also] if kind.also else [])
    before = {p: (topk.DISPATCH_COUNTS[p],
                  reg.value("pio_topk_dispatch_total", path=p))
              for p in topk.DISPATCH_COUNTS}
    plan(vecs, filt)
    for p, (count, metric) in before.items():
        rose = int(p in labels)
        assert topk.DISPATCH_COUNTS[p] - count == rose, p
        assert reg.value("pio_topk_dispatch_total",
                         path=p) - metric == rose, p


# -- which rows go through the plan ----------------------------------------

def _wide_bans(rng, b, heavy_row, heavy):
    banned = [sorted(rng.choice(N_ITEMS, size=3, replace=False).tolist())
              for _ in range(b)]
    banned[heavy_row] = sorted(
        rng.choice(N_ITEMS, size=heavy, replace=False).tolist())
    return banned


@pytest.mark.parametrize("kind", [k for k in KINDS[:4] if not k.dense],
                         ids=lambda kind: kind.name)
def test_score_banned_keeps_fitting_rows_on_the_plan(kind):
    factors, rng = _factors(), np.random.default_rng(7)
    plan = kind.plan(factors)
    vecs, ks = _vecs(rng, 5), [K, 3, K, 2, K]
    banned = _wide_bans(rng, 5, heavy_row=2, heavy=9)    # width is 8
    before = dict(topk.DISPATCH_COUNTS)
    got = topk.score_banned(plan, vecs, factors, banned, ks)
    assert topk.DISPATCH_COUNTS[kind.path] == before[kind.path] + 1
    assert topk.DISPATCH_COUNTS["host"] == before["host"] + 1
    # what the all-generic route returns, in the batch's order
    _same(got, topk.score_banned(None, vecs, factors, banned, ks))
    _same(got, topk.topk_scores_filtered(vecs, factors, banned, k=K))
    # a row asking for more than the plan's k leaves it too, alone
    before = dict(topk.DISPATCH_COUNTS)
    wide = topk.score_banned(plan, vecs, factors, banned[:2], [K + 2, K])
    assert topk.DISPATCH_COUNTS[kind.path] == before[kind.path] + 1
    assert topk.DISPATCH_COUNTS["host"] == before["host"] + 1
    assert wide[0].shape == (2, K + 2) and wide[0][1, K] <= topk.NEG_INF
    want = topk.topk_scores_filtered(vecs[:2], factors, banned[:2], k=K + 2)
    assert np.array_equal(wide[1][0], want[1][0])
    assert np.array_equal(wide[1][1, :K], want[1][1, :K])
    # and a batch that fits is one plan call, returned as it comes
    before = dict(topk.DISPATCH_COUNTS)
    topk.score_banned(plan, vecs[:2], factors, banned[:2], [K, 1])
    assert topk.DISPATCH_COUNTS[kind.path] == before[kind.path] + 1
    assert topk.DISPATCH_COUNTS["host"] == before["host"]


@pytest.mark.parametrize("kind", [k for k in KINDS[:4] if k.dense],
                         ids=lambda kind: kind.name)
def test_score_similar_keeps_fitting_rows_on_the_plan(kind):
    factors, rng = _factors(), np.random.default_rng(8)
    plan = kind.plan(factors)
    vecs, mask = _vecs(rng, 4), kind.filter(rng, 4)
    before = dict(topk.DISPATCH_COUNTS)
    got = topk.score_similar(plan, vecs, factors, mask, [K, K + 3, 2, K])
    assert topk.DISPATCH_COUNTS[kind.path] == before[kind.path] + 1
    assert topk.DISPATCH_COUNTS["host"] == before["host"] + 1
    assert got[0].shape == (4, K + 3)
    # the row that asked for more than the plan's k: the generic route's
    alone = topk.topk_similar(vecs[1:2], factors, mask[1:2], k=K + 3)
    assert np.array_equal(got[0][1], alone[0][0])
    assert np.array_equal(got[1][1], alone[1][0])
    # the rest: the plan's, to the bit, and NEG_INF past its k
    whole = plan(vecs, mask)
    for row in (0, 2, 3):
        assert np.array_equal(got[0][row, :K], whole[0][row])
        assert np.array_equal(got[1][row, :K], whole[1][row])
        assert (got[0][row, K:] <= topk.NEG_INF).all()


def _als(n_users=12):
    from predictionio_tpu.ingest import BiMap
    from predictionio_tpu.ops.als import ALSModel
    rng = np.random.default_rng(9)
    return ALSModel(
        rng.integers(-4, 5, size=(n_users, RANK)).astype(np.float32),
        _factors(), BiMap.from_keys(f"u{i}" for i in range(n_users)),
        BiMap.from_keys(f"i{i}" for i in range(N_ITEMS)))


def test_template_batch_with_one_row_of_65_bans_keeps_the_rest_on_the_plan():
    """`recommendation` gated the whole batch on its longest ban list;
    the rows that fit now stay on the plan, and the answers are those of
    the all-generic route."""
    from predictionio_tpu.models import recommendation as rec
    model = _als()
    rng = np.random.default_rng(10)
    queries = [(i, rec.Query(user=f"u{i}", num=4, blackList=[
        f"i{j}" for j in rng.choice(N_ITEMS, size=65 if i == 3 else 2,
                                    replace=False)])) for i in range(6)]
    generic = rec.ALSAlgorithm().batch_predict(model, queries)
    warmed = rec.ALSAlgorithm()
    assert warmed.warm_serving(model, BUCKETS) == len(BUCKETS)
    assert warmed.serve_plans() == (warmed._serve_plan,)
    assert warmed._serve_plan.banned_width == 64
    before = dict(topk.DISPATCH_COUNTS)
    served = warmed.batch_predict(model, queries)
    assert topk.DISPATCH_COUNTS["device"] == before["device"] + 1
    assert topk.DISPATCH_COUNTS["host"] == before["host"] + 1
    assert sorted(served) == sorted(generic)
    assert all(len(p.itemScores) == 4 for _, p in served)
