"""Fused single-launch serve kernel (`ops/fused_topk.py`): the Pallas
gather->matmul->ban-mask->top-k collapse must be BIT-IDENTICAL — ids
AND scores, ties included — to the XLA-chain oracles it replaces, on
both the single-device `BucketedTopK` plan and the conftest-forced
8-device CPU mesh's `ShardedBucketedTopK`, while preserving the
swap_factors / zero-recompile / fallback contracts. Integer-valued
factors make the matmuls exact so bitwise parity is well-defined."""

import numpy as np
import pytest

from predictionio_tpu.obs import compile_watch
from predictionio_tpu.ops import fused_topk, topk, topk_sharded
from predictionio_tpu.ops.topk import BucketedTopK
from predictionio_tpu.ops.topk_sharded import ShardedBucketedTopK

pytestmark = pytest.mark.fused


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


class TestBucketParity:
    @pytest.fixture()
    def plans(self, monkeypatch):
        """The same 203x8 catalog warmed fused and unfused."""
        factors = _int_factors(203, 8)
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
            vecs = _queries(b, 8, seed=b)
            for case in _ban_cases(203, 16):
                bans = [case if r % 2 == 0 else [] for r in range(b)]
                cs, ci = chain(vecs, bans)
                fs, fi = fused(vecs, bans)
                np.testing.assert_array_equal(ci, fi)
                np.testing.assert_array_equal(cs, fs)

    def test_matches_host_stable_argsort_oracle(self, plans):
        _, fused = plans
        factors = fused._host_factors
        vecs = _queries(4, 8, seed=99)
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

    def test_swap_factors_zero_recompile(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        plan = BucketedTopK(_int_factors(64, 4), k=5, buckets=(1, 4),
                            banned_width=8)
        plan.warm()
        assert plan.fused_buckets == 2
        vecs = _queries(4, 4)
        before, _ = plan(vecs, [[], [], [], []])
        new = _int_factors(64, 4, seed=123)
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
    @pytest.fixture()
    def plans(self, monkeypatch):
        """203 items over 8 shards (per-shard 26, padded tail), fused
        vs unfused."""
        factors = _int_factors(203, 8)
        monkeypatch.setenv("PIO_SERVE_FUSED", "off")
        chain = ShardedBucketedTopK(factors, k=6, buckets=(1, 2, 4, 8),
                                    banned_width=16, mesh=_mesh())
        chain.warm()
        assert not chain.fused
        monkeypatch.setenv("PIO_SERVE_FUSED", "on")
        fused = ShardedBucketedTopK(factors, k=6, buckets=(1, 2, 4, 8),
                                    banned_width=16, mesh=_mesh())
        fused.warm()
        assert fused.fused
        return chain, fused

    def test_bit_identical_on_8_device_mesh(self, plans):
        chain, fused = plans
        for b in (1, 3, 8):
            vecs = _queries(b, 8, seed=40 + b)
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
        vecs = _queries(2, 8, seed=77)
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
        vecs = _queries(5, 8, seed=3)
        bans = [[], [7], [0, 1, 2], [100, 200], [50]]
        ss, si = single(vecs, bans)
        hs, hi = fused(vecs, bans)
        np.testing.assert_array_equal(si, hi)
        np.testing.assert_array_equal(ss, hs)

    def test_sharded_swap_factors_zero_recompile(self, plans):
        _, fused = plans
        vecs = _queries(2, 8, seed=5)
        before, _ = fused(vecs, [[], []])
        with compile_watch() as w:
            fused.swap_factors(_int_factors(203, 8, seed=321))
            after, _ = fused(vecs, [[], []])
        assert w.count == 0
        assert not np.array_equal(before, after)
