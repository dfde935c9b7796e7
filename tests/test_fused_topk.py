"""Fused single-launch serve kernel (`ops/fused_topk.py`): the Pallas
gather->matmul->ban-mask->top-k collapse must be BIT-IDENTICAL — ids
AND scores, ties included — to the XLA-chain oracles it replaces, on
both the single-device `BucketedTopK` plan and the conftest-forced
8-device CPU mesh's `ShardedBucketedTopK`, while preserving the
swap_factors / zero-recompile / fallback contracts. Integer-valued
factors make the matmuls exact so bitwise parity is well-defined.

The kernel reads the catalog in one of two block orientations, chosen
from the rank alone (`fused_topk._items_on_lanes`): items on the lanes
in `(rank, tile)` blocks where a row is not whole 128-lane groups, rows
first in `(tile, rank)` blocks where it is. The parity cases run at
ranks of both kinds; every catalog here ends in a ragged last tile."""

import numpy as np
import pytest

from predictionio_tpu.obs import compile_watch
from predictionio_tpu.ops import fused_topk, topk, topk_sharded
from predictionio_tpu.ops.topk import BucketedTopK
from predictionio_tpu.ops.topk_sharded import ShardedBucketedTopK

pytestmark = pytest.mark.fused

# items on the lanes with the rank's sublanes padded (8 whole, 20 not),
# the benchmark's 64, and rows first (whole lane groups)
_RANKS = (8, 20, 64, 128)


def _mesh():
    import jax
    from jax.sharding import Mesh
    devices = jax.devices()
    assert len(devices) == 8, "conftest must force 8 CPU devices"
    return Mesh(np.array(devices), (topk_sharded.SHARD_AXIS,))


def _int_factors(n, rank, seed=7):
    rng = np.random.default_rng(seed)
    return rng.integers(-4, 5, size=(n, rank)).astype(np.float32)


def _queries(b, rank, seed=13):
    rng = np.random.default_rng(seed)
    return rng.integers(-4, 5, size=(b, rank)).astype(np.float32)


def _ban_cases(n, width, seed=29):
    """Ban-list sweeps: empty, singleton, shard-straddling spans, a
    full-width list, and an everything-banned row (n <= width only)."""
    rng = np.random.default_rng(seed)
    cases = [[], [int(rng.integers(0, n))],
             list(range(0, min(n, width), 2)),
             sorted(rng.choice(n, size=min(n, width), replace=False)
                    .tolist())]
    if n <= width:
        cases.append(list(range(n)))
    return cases


class TestGates:
    def test_mode_parsing(self, monkeypatch):
        for raw, want in [("", "auto"), ("auto", "auto"), ("on", "on"),
                          ("1", "on"), ("true", "on"), ("off", "off"),
                          ("0", "off"), ("no", "off")]:
            monkeypatch.setenv("PIO_SERVE_FUSED", raw)
            assert fused_topk.fused_mode() == want

    def test_auto_stays_off_on_cpu(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_FUSED", "auto")
        assert not fused_topk.fused_wanted()
        plan = BucketedTopK(_int_factors(64, 4), k=5, buckets=(1, 4))
        plan.warm()
        assert plan.fused_buckets == 0

    def test_off_never_builds(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_FUSED", "off")
        assert fused_topk.maybe_build_bucket(
            _int_factors(8, 2), n_items=8, rank=2, k=2, bucket=1,
            banned_width=4) is None
        assert fused_topk.shard_local_candidates(
            8, 2, k=2, bucket=1, banned_width=4,
            axis=topk_sharded.SHARD_AXIS) is None


class TestOrientation:
    def test_follows_the_lanes_a_row_fills(self):
        assert all(fused_topk._items_on_lanes(r)
                   for r in (3, 8, 20, 64, 100, 192))
        assert not any(fused_topk._items_on_lanes(r)
                       for r in (128, 256, 4096))

    @pytest.mark.parametrize("rank", _RANKS)
    def test_blocks_lie_as_the_orientation_says(self, rank, monkeypatch):
        """The catalog operand reaches the kernel as `[rank, n]` with
        `(rank, tile)` blocks, or as `[n, rank]` with `(tile, rank)`
        ones."""
        import jax
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        n = 3000
        fn = fused_topk._pallas_topk(n, rank, k=6, bucket=8,
                                     banned_width=16, n_valid=n)
        jaxpr = jax.make_jaxpr(fn)(
            np.zeros((8, rank), np.float32),
            np.zeros((n, rank), np.float32),
            np.zeros((8, 16), np.int32))
        call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        shape = call.invars[1].aval.shape
        block = call.params["grid_mapping"].block_mappings[1].block_shape
        tile, _ = fused_topk._tile_items(n, 6, rank)
        if fused_topk._items_on_lanes(rank):
            assert shape == (rank, n)
            assert tuple(d.block_size for d in block) == (rank, tile)
        else:
            assert shape == (n, rank)
            assert tuple(d.block_size for d in block) == (tile, rank)


class TestBucketParity:
    @pytest.fixture(params=_RANKS)
    def plans(self, request, monkeypatch):
        """The same 203-row catalog warmed fused and unfused, at a rank
        of each orientation."""
        factors = _int_factors(203, request.param)
        monkeypatch.setenv("PIO_SERVE_FUSED", "off")
        chain = BucketedTopK(factors, k=6, buckets=(1, 2, 4, 8),
                             banned_width=16)
        assert chain.warm() == 4 and chain.fused_buckets == 0
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        fused = BucketedTopK(factors, k=6, buckets=(1, 2, 4, 8),
                             banned_width=16)
        assert fused.warm() == 4
        assert fused.fused_buckets == 4
        return chain, fused

    def test_bit_identical_across_buckets_and_bans(self, plans):
        chain, fused = plans
        for b in (1, 2, 3, 5, 8):
            vecs = _queries(b, fused.rank, seed=b)
            for case in _ban_cases(203, 16):
                bans = [case if r % 2 == 0 else [] for r in range(b)]
                cs, ci = chain(vecs, bans)
                fs, fi = fused(vecs, bans)
                np.testing.assert_array_equal(ci, fi)
                np.testing.assert_array_equal(cs, fs)

    def test_matches_host_stable_argsort_oracle(self, plans):
        _, fused = plans
        factors = fused._host_factors
        vecs = _queries(4, fused.rank, seed=99)
        bans = [[0, 7, 202], [], [5], list(range(0, 16))]
        fs, fi = fused(vecs, bans)
        for row in range(4):
            sc = vecs[row] @ factors.T
            if bans[row]:
                sc[np.asarray(bans[row], int)] = topk.NEG_INF
            order = np.argsort(-sc, kind="stable")[:6]
            np.testing.assert_array_equal(fi[row], order)
            np.testing.assert_array_equal(fs[row], sc[order])

    def test_all_banned_row_matches_oracle(self, monkeypatch):
        """Every item banned: the oracle emits NEG_INF scores with
        ids 0..k-1 (lax.top_k lowest-index ties); the fused scoreboard
        must reproduce that exactly, never a duplicate id."""
        factors = _int_factors(6, 3)
        monkeypatch.setenv("PIO_SERVE_FUSED", "off")
        chain = BucketedTopK(factors, k=4, buckets=(2,), banned_width=8)
        chain.warm()
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        fused = BucketedTopK(factors, k=4, buckets=(2,), banned_width=8)
        fused.warm()
        bans = [list(range(6)), [2]]
        vecs = _queries(2, 3)
        cs, ci = chain(vecs, bans)
        fs, fi = fused(vecs, bans)
        np.testing.assert_array_equal(ci, fi)
        np.testing.assert_array_equal(cs, fs)
        assert len(set(fi[0].tolist())) == 4

    @pytest.mark.parametrize("rank", (4,) + _RANKS)
    def test_swap_factors_zero_recompile(self, rank, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        plan = BucketedTopK(_int_factors(64, rank), k=5, buckets=(1, 4),
                            banned_width=8)
        plan.warm()
        assert plan.fused_buckets == 2
        vecs = _queries(4, rank)
        before, _ = plan(vecs, [[], [], [], []])
        new = _int_factors(64, rank, seed=123)
        with compile_watch() as w:
            plan.swap_factors(new)
            after, ai = plan(vecs, [[], [], [], []])
        assert w.count == 0
        expect = vecs @ new.T
        got = np.take_along_axis(expect, np.asarray(ai), axis=1)
        np.testing.assert_array_equal(after, got)
        assert not np.array_equal(before, after)

    def test_steady_state_zero_recompile(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        plan = BucketedTopK(_int_factors(100, 4), k=3, buckets=(1, 2, 4),
                            banned_width=4)
        plan.warm()
        plan(_queries(4, 4), [[], [1], [], [2, 3]])   # prime every path
        with compile_watch() as w:
            for b in (1, 2, 3, 4):
                plan(_queries(b, 4, seed=b), [[1]] * b)
        assert w.count == 0


class TestShardedParity:
    @pytest.fixture(params=_RANKS)
    def plans(self, request, monkeypatch):
        """203 items over 8 shards (per-shard 26, padded tail), fused
        vs unfused, at a rank of each orientation."""
        factors = _int_factors(203, request.param)
        monkeypatch.setenv("PIO_SERVE_FUSED", "off")
        chain = ShardedBucketedTopK(factors, k=6, buckets=(1, 2, 4, 8),
                                    banned_width=16, mesh=_mesh())
        chain.warm()
        assert chain.fused_buckets == 0
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        fused = ShardedBucketedTopK(factors, k=6, buckets=(1, 2, 4, 8),
                                    banned_width=16, mesh=_mesh())
        fused.warm()
        assert fused.fused_buckets
        return chain, fused

    def test_bit_identical_on_8_device_mesh(self, plans):
        chain, fused = plans
        for b in (1, 3, 8):
            vecs = _queries(b, fused.rank, seed=40 + b)
            for case in _ban_cases(203, 16, seed=41):
                bans = [case if r % 2 == 0 else case[:1]
                        for r in range(b)]
                cs, ci = chain(vecs, bans)
                fs, fi = fused(vecs, bans)
                np.testing.assert_array_equal(ci, fi)
                np.testing.assert_array_equal(cs, fs)

    def test_bans_straddling_shard_boundaries(self, plans):
        """Global ids around every shard boundary (per_shard=26) — the
        local translation must drop out-of-shard ids, not wrap them."""
        chain, fused = plans
        vecs = _queries(2, fused.rank, seed=77)
        edges = [25, 26, 27, 51, 52, 53, 201, 202]
        cs, ci = chain(vecs, [edges, []])
        fs, fi = fused(vecs, [edges, []])
        np.testing.assert_array_equal(ci, fi)
        np.testing.assert_array_equal(cs, fs)
        assert not set(edges) & set(fi[0].tolist())

    def test_matches_single_device_fused_plan(self, plans, monkeypatch):
        _, fused = plans
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        single = BucketedTopK(fused._host_factors, k=6,
                              buckets=(1, 2, 4, 8), banned_width=16)
        single.warm()
        vecs = _queries(5, fused.rank, seed=3)
        bans = [[], [7], [0, 1, 2], [100, 200], [50]]
        ss, si = single(vecs, bans)
        hs, hi = fused(vecs, bans)
        np.testing.assert_array_equal(si, hi)
        np.testing.assert_array_equal(ss, hs)

    def test_sharded_swap_factors_zero_recompile(self, plans):
        _, fused = plans
        vecs = _queries(2, fused.rank, seed=5)
        before, _ = fused(vecs, [[], []])
        with compile_watch() as w:
            fused.swap_factors(_int_factors(203, fused.rank, seed=321))
            after, _ = fused(vecs, [[], []])
        assert w.count == 0
        assert not np.array_equal(before, after)


# --- the threshold gate (PR 26): a sub-block is merged only where one of
# its scores lies strictly above a row's k-th best so far

_GATE_SUB = fused_topk._SUB_ITEMS
_GATE_TILE = 2 * _GATE_SUB      # two sub-blocks a grid step
# two grid steps, the second's last sub-block wholly past the end; and
# a catalog of two sub-blocks that a ban list can cover
_GATE_N = _GATE_TILE + _GATE_SUB // 2 + 40
_GATE_N_SMALL = _GATE_SUB + 176


def _axis_catalog(n, rank, scores):
    """Factors whose score against the first unit vector is `scores`
    (integers, so every product is exact)."""
    factors = np.zeros((n, rank), np.float32)
    factors[:, 0] = scores
    factors[:, 1:] = _int_factors(n, rank - 1, seed=n)
    return factors


def _unit(rank, rows=1, scale=1.0):
    vecs = np.zeros((rows, rank), np.float32)
    vecs[:, 0] = scale
    return vecs


def _gate_case(name, n, rank=8):
    """(factors [n, rank], vecs, bans, k, banned_width) for one of the
    gate's edge cases."""
    k, width = 6, 16
    ids = np.arange(n)
    if name == "descending":        # only the first sub-blocks merge
        return (_axis_catalog(n, rank, n - ids), _unit(rank, 2),
                [[], [3]], k, width)
    if name == "ascending":         # every sub-block merges
        return (_axis_catalog(n, rank, ids), _unit(rank, 2),
                [[], [n - 1]], k, width)
    if name == "tie_in_later_tile":
        # a later tile holds the k-th best's score exactly: the lower
        # id keeps its place
        sc = np.where(ids < k, 5, -ids % 4 - 1)
        sc[n - 7] = 5
        sc[_GATE_TILE + 3] = 5
        return _axis_catalog(n, rank, sc), _unit(rank), [[]], k, width
    if name == "banned_best":
        # the best item sits in a late sub-block and is banned: that
        # sub-block merges for nothing, the answer is the plain one
        sc = n - ids
        sc[n - 40] = 10 * n
        return (_axis_catalog(n, rank, sc), _unit(rank, 2),
                [[n - 40], []], k, width)
    if name == "all_and_nearly_all_banned":
        width = 2 * _GATE_SUB
        return (_int_factors(n, rank), _queries(2, rank),
                [list(range(n)), list(range(3, n))], k, width)
    if name == "zero_rows_beside_live":
        vecs = _queries(5, rank, seed=3)
        vecs[1] = 0.0
        vecs[4] = 0.0
        return (_int_factors(n, rank), vecs,
                [[], [2], [n - 1, 0], [], []], k, width)
    if name == "random":
        return (_int_factors(n, rank), _queries(7, rank, seed=5),
                _ban_cases(n, width)[:4] + [[], [], [1]],
                k, width)
    raise AssertionError(name)


_GATE_CASES = ["descending", "ascending", "tie_in_later_tile",
               "banned_best", "all_and_nearly_all_banned",
               "zero_rows_beside_live", "random"]


def _both(cls, factors, monkeypatch, *, k, bucket, width, **kw):
    monkeypatch.setenv("PIO_FUSED_TILE_ITEMS", str(_GATE_TILE))
    plans = []
    for mode in ("off", "on"):
        monkeypatch.setenv("PIO_SERVE_FUSED", mode)
        plan = cls(factors, k=k, buckets=(bucket,), banned_width=width,
                   **kw)
        plan.warm()
        plans.append(plan)
    return plans


class TestGatedMerge:
    @pytest.mark.parametrize("rank", _RANKS)
    @pytest.mark.parametrize("case", _GATE_CASES)
    def test_single_device_bit_identical(self, case, rank, monkeypatch):
        n = _GATE_N_SMALL if case.startswith("all_") else _GATE_N
        factors, vecs, bans, k, width = _gate_case(case, n, rank)
        chain, fused = _both(BucketedTopK, factors, monkeypatch, k=k,
                             bucket=8, width=width)
        assert fused.fused_buckets == 1
        cs, ci = chain(vecs, bans)
        fs, fi = fused(vecs, bans)
        np.testing.assert_array_equal(ci, fi)
        np.testing.assert_array_equal(cs, fs)

    @pytest.mark.parametrize("rank", _RANKS)
    @pytest.mark.parametrize("case", _GATE_CASES)
    def test_sharded_bit_identical(self, case, rank, monkeypatch):
        # 8 shards, the last one short by five rows
        n = 8 * (_GATE_N_SMALL if case.startswith("all_")
                 else _GATE_N) - 5
        factors, vecs, bans, k, width = _gate_case(case, n, rank)
        if case.startswith("all_"):
            # a ban list wider than the plan holds cannot be served:
            # ban a whole shard's rows and all but three of the next's
            per = -(-n // 8)
            bans = [list(range(per)), list(range(per + 3, 2 * per))]
        chain, fused = _both(ShardedBucketedTopK, factors, monkeypatch,
                             k=k, bucket=8, width=width, mesh=_mesh())
        assert fused.fused_buckets and not chain.fused_buckets
        cs, ci = chain(vecs, bans)
        fs, fi = fused(vecs, bans)
        np.testing.assert_array_equal(ci, fi)
        np.testing.assert_array_equal(cs, fs)

    @pytest.mark.parametrize("rank", _RANKS)
    @pytest.mark.parametrize("poison", [np.nan, np.inf])
    def test_poisoned_tail_past_n_valid_is_never_read(self, poison, rank,
                                                      monkeypatch):
        """`n_items` is not a multiple of the tile and the operand's
        items past it hold NaN / +inf (along the lanes or along the
        rows, as the rank lays the blocks): they neither open the gate
        nor reach the scoreboard."""
        import jax
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        monkeypatch.setenv("PIO_FUSED_TILE_ITEMS", str(_GATE_TILE))
        n, pad_n, k = _GATE_N, 2 * _GATE_TILE, 6
        factors = _int_factors(n, rank)
        padded = np.full((pad_n, rank), poison, np.float32)
        padded[:n] = factors
        vecs = _queries(3, rank, seed=11)
        bans = np.full((8, 16), n, np.int32)
        bans[0, :2] = [n - 1, 4]
        fn = jax.jit(fused_topk._pallas_topk(
            pad_n, rank, k=k, bucket=8, banned_width=16, n_valid=n))
        qs = np.zeros((8, rank), np.float32)
        qs[:3] = vecs
        fs, fi, merged = jax.device_get(fn(qs, padded, bans))
        monkeypatch.setenv("PIO_SERVE_FUSED", "off")
        chain = BucketedTopK(factors, k=k, buckets=(8,), banned_width=16)
        chain.warm()
        cs, ci = chain(vecs, [[n - 1, 4], [], []])
        np.testing.assert_array_equal(ci, fi[:3])
        np.testing.assert_array_equal(cs, fs[:3])
        # the sub-block that lies wholly past n_valid never merged
        assert 1 <= int(merged) <= 3

    @pytest.mark.parametrize("rank", _RANKS)
    def test_worst_case_every_block_merges(self, rank, monkeypatch):
        factors, vecs, bans, k, width = _gate_case("ascending",
                                                   2 * _GATE_TILE, rank)
        _, fused = _both(BucketedTopK, factors, monkeypatch, k=k,
                         bucket=8, width=width)
        before = _merge_observations()
        fused(vecs, bans)
        count, total = _merge_observations()
        assert count == before[0] + 1
        assert total - before[1] == pytest.approx(1.0)


def _merge_observations():
    """(count, sum) of `pio_topk_merge_share` so far."""
    fam = topk._MERGE_SHARE
    if fam is None:
        return 0, 0.0
    child = fam._default()
    return child.count, child.sum


def _count_candidate_blocks(factors, vecs, bans, k, sub):
    """The gate's count by its definition, in NumPy: walk the catalog
    in `sub`-item blocks keeping every row's exact banned top-k so far,
    and count the blocks in which some row's raw score lies strictly
    above that row's k-th best."""
    n = factors.shape[0]
    scores = vecs @ factors.T
    banned = scores.copy()
    for r, bl in enumerate(bans):
        if len(bl):
            banned[r, np.asarray(bl, int)] = topk.NEG_INF
    kept = np.full((vecs.shape[0], k), -np.inf, np.float32)
    merged = 0
    for lo in range(0, n, sub):
        blk = scores[:, lo:lo + sub]
        if not (blk > kept[:, -1:]).any():
            continue
        merged += 1
        both = np.concatenate([kept, banned[:, lo:lo + sub]], axis=1)
        kept = -np.sort(-both, axis=1, kind="stable")[:, :k]
    return merged


class TestMergeCounter:
    def test_counts_blocks_holding_a_candidate_once_per_call(
            self, monkeypatch):
        n, rank, k, width = 20 * _GATE_TILE - 480, 8, 6, 16
        factors = _int_factors(n, rank, seed=17)
        vecs = _queries(5, rank, seed=19)
        bans = [[], [7, n // 5], [], list(range(0, 16)), [n - 1]]
        chain, fused = _both(BucketedTopK, factors, monkeypatch, k=k,
                             bucket=8, width=width)
        blocks = fused_topk.gate_blocks(n, k, rank)
        assert blocks == 40         # twenty grid steps of two sub-blocks
        calls = [(vecs, bans), (vecs[:2], bans[:2]), (vecs[3:], bans[3:])]
        for qs, bl in calls:
            before = _merge_observations()
            fused(qs, bl)
            count, total = _merge_observations()
            assert count == before[0] + 1
            want = _count_candidate_blocks(factors, qs, bl, k, _GATE_SUB)
            assert 1 <= want < blocks
            assert total - before[1] == pytest.approx(want / blocks)
        # a bucket served by the XLA chain observes nothing
        before = _merge_observations()
        chain(vecs, bans)
        assert _merge_observations() == before

    def test_sharded_plan_sums_its_shards(self, monkeypatch):
        n, rank, k, width = 8 * _GATE_N - 5, 8, 6, 16
        factors = _int_factors(n, rank, seed=23)
        vecs = _queries(3, rank, seed=29)
        bans = [[], [5, _GATE_N + 700], [n - 1]]
        _, fused = _both(ShardedBucketedTopK, factors, monkeypatch, k=k,
                         bucket=8, width=width, mesh=_mesh())
        per = fused.per_shard
        want = 0
        for sh in range(8):
            lo, hi = sh * per, min(n, (sh + 1) * per)
            local = [[g - lo for g in bl if lo <= g < hi] for bl in bans]
            want += _count_candidate_blocks(factors[lo:hi], vecs, local,
                                            k, _GATE_SUB)
        blocks = 8 * fused_topk.gate_blocks(per, fused.k_shard, rank)
        before = _merge_observations()
        fused(vecs, bans)
        count, total = _merge_observations()
        assert count == before[0] + 1
        assert total - before[1] == pytest.approx(want / blocks)


class _FakeExe:
    """A compiled bucket as far as the gauge reads it."""

    def __init__(self, temp):
        self._temp = temp

    def memory_analysis(self):
        if self._temp is None:
            raise NotImplementedError("no analysis on this backend")
        import types
        return types.SimpleNamespace(temp_size_in_bytes=self._temp)


def _temp_gauge():
    from predictionio_tpu.obs import get_registry
    return get_registry().value("pio_serve_plan_temp_bytes")


class TestPlanTempGauge:
    def test_reads_the_largest_bucket(self):
        topk._publish_plan_temp_bytes(
            _FakeExe(t) for t in (5, 6_170_564_608, 7))
        assert _temp_gauge() == 6_170_564_608

    def test_no_analysis_or_no_fused_bucket_leaves_it_alone(self):
        topk._publish_plan_temp_bytes([_FakeExe(11)])
        topk._publish_plan_temp_bytes([_FakeExe(3), _FakeExe(None)])
        topk._publish_plan_temp_bytes([])
        assert _temp_gauge() == 11

    @pytest.mark.parametrize("cls", [BucketedTopK, ShardedBucketedTopK])
    def test_warm_publishes_the_fused_buckets_analysis(self, cls,
                                                       monkeypatch):
        kw = {"mesh": _mesh()} if cls is ShardedBucketedTopK else {}
        factors = _int_factors(300, 8)
        topk._publish_plan_temp_bytes([_FakeExe(-1)])
        monkeypatch.setenv("PIO_SERVE_FUSED", "off")
        chain = cls(factors, k=5, buckets=(1, 8), banned_width=8, **kw)
        chain.warm()
        assert set(chain.bucket_kernels().values()) == {"xla"}
        assert _temp_gauge() == -1      # the XLA chain publishes nothing
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        fused = cls(factors, k=5, buckets=(1, 8), banned_width=8, **kw)
        fused.warm()
        assert set(fused.bucket_kernels().values()) == {"fused"}
        want = max(exe.memory_analysis().temp_size_in_bytes
                   for exe in fused._exe.values())
        assert _temp_gauge() == want
