"""Alternating least squares, TPU-first.

Replaces Spark MLlib's `ALS` / `ALS.trainImplicit` used by the reference's
recommendation templates (`examples/scala-parallel-recommendation/
blacklist-items/src/main/scala/ALSAlgorithm.scala:51-93`,
`examples/scala-parallel-similarproduct/.../ALSAlgorithm.scala:120`).

MLlib's ALS is a shuffle-heavy blocked solver over dynamically partitioned
rating blocks. The TPU formulation instead makes every step a dense, static
XLA program:

  1. Ratings arrive as COO triples (`ingest.RatingColumns`). Each side
     (user rows / item rows) is packed ONCE into degree-bucketed padded CSR
     slabs: rows with similar degree share a `[rows_b, cap_b]` slab padded
     to the bucket cap. Buckets mean the heavy tail of prolific users costs
     one big slab instead of padding every user to the global max degree.
  2. One half-iteration gathers the opposite side's factors `Y[idx]`
     (`[rows_b, cap_b, rank]`), forms per-row normal equations, adds
     ALS-WR regularization `lambda * n_row * I` (MLlib's default
     scaling), and solves all rows. The hot path (rank > 16) is
     `_solve_slab_paired`: bf16 gathered operands, consecutive-row
     PAIRING so the Gram einsum produces full 128x128 MXU tiles, f32
     accumulation, and warm-started Jacobi-CG with residual tracking.
     Rank <= 16 uses the exact blocked Cholesky (`ops.linalg.spd_solve`).
     Why (round-4 v5e timings, taken over a remote link that is gone;
     none re-measured on a local chip — ROADMAP Speed 2): the factor
     gather is ROW-RATE-bound (~390M rows/s f32 / ~450M bf16,
     independent of row width <= 128 lanes) and is the hard floor of
     the whole step;
     RxR-batched einsums reach <2 TFLOP/s (each batch element fills only
     a 64x64 corner of the MXU) while the paired form is ~3x faster;
     XLA's batched Cholesky runs at ~0.02 TFLOP/s; and a fixed-32-iter
     CG re-reads every normal matrix from HBM per iteration, while warm
     starting cuts the iterations ~4x at equal final RMSE.
  3. Implicit feedback uses the Hu-Koren-Volinsky trick: A_row =
     Y^T Y + sum_k alpha*r_k * y_k y_k^T (+ reg), b_row = sum_k
     (1 + alpha*r_k) y_k, so cost scales with observed entries only.
  4. Factors live on device across iterations. Under a mesh, BOTH factor
     matrices are block-sharded over the "data" axis (device d owns the
     contiguous row block [d*B, (d+1)*B)) and every slab is partitioned by
     the device that owns the rows it solves, so each half-step is: one
     all-gather of the opposite side's factor shard (transient), a local
     gather+einsum+Cholesky, and a purely LOCAL factor-row write — no
     cross-device scatter. The implicit-mode Gram matrix is a [rank,rank]
     psum of local grams. This is the shard_map analog of MLlib's
     shuffle-based factor exchange.

Memory model (per device, D devices, f32):
  persistent:  |X|/D + |Y|/D factor shards, + slab columns /D
               (idx 4B + val 4B per padded entry, both sides; the mask
               derives from the -1 idx sentinel, never materialized)
  transient :  the all-gathered opposite factor matrix (|Y| or |X|) +
               the gathered slab factors [rows_b, cap_b, rank] per bucket
               (~ratings_on_device * rank * 4B for the largest bucket).
ML-25M at rank 64 on a v5e-16 slice (16 GiB HBM/chip), counting bucket
padding (padded entries <= BASE*n_rows + GROWTH*n_ratings per side):
X = 162541*64*4 = 41.6 MB, Y = 59047*64*4 = 15.1 MB, padded slabs
~= 2*103e6*12 B / 16 * skew2 ~= 305 MB/device, transient slab gather
<= 103e6/16 * 64 * 4 * skew2 ~= 3.3 GB — peak ~3.7 GB, inside budget;
see `hbm_footprint` for the formula and its test.

The returned model is `ALSModel` (factor matrices + BiMaps), the analog of
the template's fork of `MatrixFactorizationModel` (`ALSModel.scala`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Tuple

import jax
import numpy as np

from predictionio_tpu.ingest import BiMap, RatingColumns

# degree-bucket caps grow geometrically; a row of degree d lands in the
# smallest bucket with cap >= d. The x1.5 ladder (rounded up to a
# multiple of 8 for TPU sublane alignment) bounds padding at 1.5x the
# real entry count — the r3 x4 ladder padded ML-25M to ~2x, and the
# gather that reads every padded slot is the measured bottleneck of the
# whole training step (row-rate-bound at ~390-450M rows/s on a v5e; see
# module docstring), so padding is gather wall-clock 1:1.
_BUCKET_BASE = 16
# cap-ladder growth. 1.25 holds ML-25M's padded/real entry ratio to
# ~1.12 (1.5 measured 1.27 — r4 bench roofline), cutting EVERY phase of
# the row-rate-bound step ~11%; the cost is more distinct slab shapes
# (26 vs 15 item-side at ML-25M) in the one compiled program, which the
# persistent XLA compile cache amortizes across runs.
_BUCKET_GROWTH = 1.25

# sentinel row index for slab padding rows (scatter mode="drop" discards
# them; _pack_by_owner maps them to an in-range dropped local slot)
_FILL_ROW = np.int32(2**31 - 1)

# ranks <= this solve via the exact blocked Cholesky (ops.linalg.
# spd_solve): at one 16-wide block it is a short, fully batched program
# and beats CG (this is also what keeps the ML-100k rank-10 path on the
# exact solver — the r3 regression was CG burning 4x the FLOPs there).
_SMALL_RANK = 16

# warm-started CG iteration cap for the rank > _SMALL_RANK path. With
# the previous sweep's factors as x0, 8 iterations reach ~2e-4 max
# relative residual on the ML-25M workload (measured); the residual is
# tracked and surfaced so a badly conditioned problem (tiny reg) is
# flagged instead of silently wrong.
_CG_ITERS = 8


def _cap_ladder(max_count: int) -> np.ndarray:
    """Bucket caps: BASE, then x_BUCKET_GROWTH steps rounded up to a
    multiple of 8, up to max_count."""
    caps = [_BUCKET_BASE]
    while caps[-1] < max_count:
        caps.append(int(math.ceil(caps[-1] * _BUCKET_GROWTH / 8) * 8))
    return np.asarray(caps, np.int64)

# Per-slab transient memory budgets (bytes, f32). A bucket slab of B rows
# x cap K at rank R materializes a [B, K, R] factor gather and [B, R, R]
# normal matrices during its solve; unboundedly large buckets (ML-25M has
# ~150k users in one degree bucket) would blow HBM. Slabs are therefore
# split so that  B*K*R*4 <= _SLAB_GATHER_BUDGET  and
# B*R*R*4 <= _SLAB_NORMAL_BUDGET. At rank 10 the caps are ~53M entries /
# ~1.3M rows (no effect on small problems); at rank 64 they bound the
# gather to 2 GiB and the normal-equation batch to 512 MiB.
_SLAB_GATHER_BUDGET = 2 << 30
_SLAB_NORMAL_BUDGET = 512 << 20


@dataclass
class _SideBuckets:
    """Degree-bucketed CSR for one side (one entry per bucket chunk).

    Entries are stored RAGGED (per-row counts + concatenated idx/val),
    so only real entries ever cross the host->device link (what the
    padding bytes would cost on a local chip: not measured) —
    padded slab forms are materialized ON DEVICE by `_pad_side_device`
    (hot path) or on host by `padded()` (mesh re-partitioner, direct
    solver tests). Slot padding carries idx == -1; the mask is derived
    from it device-side, never stored or transferred."""
    rows: List[np.ndarray]     # [rows_b] row indexes into this side
    counts: List[np.ndarray]   # [rows_b] real entries per row
    idx: List[np.ndarray]      # [entries_b] ragged opposite-side indexes
    val: List[np.ndarray]      # [entries_b] ragged ratings
    caps: List[int]            # bucket cap (padded row width) per chunk
    n_rows: int

    def padded(self, j: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host materialization of chunk j as ([rows_b, cap] idx with -1
        padding, [rows_b, cap] val)."""
        counts, cap = self.counts[j], self.caps[j]
        nb = len(counts)
        member, intra = _group_offsets(counts)
        idx = np.full((nb, cap), -1, np.int32)
        val = np.zeros((nb, cap), np.float32)
        idx[member, intra] = self.idx[j]
        val[member, intra] = self.val[j]
        return idx, val


def _group_offsets(counts: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Destination coordinates for a ragged->padded scatter of items laid
    out in stable group order: `member[j]` is item j's group index,
    `intra[j]` its offset within the group."""
    total = int(counts.sum())
    member = np.repeat(np.arange(len(counts)), counts)
    intra = np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts)
    return member, intra


def _pack_side(row_ix: np.ndarray, col_ix: np.ndarray, val: np.ndarray,
               n_rows: int, rank: Optional[int] = None) -> _SideBuckets:
    """Group COO entries by row, then bucket rows by degree into padded
    slabs. Host-side preprocessing, done once per training run — fully
    vectorized (no per-row Python) so ML-25M-scale packing stays cheap.

    When `rank` is given, oversized buckets are split into row chunks so
    each slab's solve-time transients ([B, cap, rank] gather and
    [B, rank, rank] normal matrices) stay inside the module budgets."""
    order = np.argsort(row_ix, kind="stable")
    r, c, v = row_ix[order], col_ix[order], val[order]
    uniq, starts, counts = np.unique(r, return_index=True, return_counts=True)
    # bucket cap per unique row: smallest ladder cap >= count
    ladder = _cap_ladder(int(counts.max()) if len(counts) else _BUCKET_BASE)
    caps_per_row = ladder[np.searchsorted(ladder, counts)]
    out = _SideBuckets([], [], [], [], [], n_rows)
    for cap in np.unique(caps_per_row):
        sel = caps_per_row == cap
        rows = uniq[sel].astype(np.int32)
        m_starts, m_counts = starts[sel], counts[sel]
        nb = len(rows)
        # ragged entries in row order: flat source index for every entry
        member_of, intra = _group_offsets(m_counts)
        src = np.repeat(m_starts, m_counts) + intra
        ends = np.cumsum(m_counts)
        if rank is None:
            chunk = nb
        else:
            chunk = max(2, min(_SLAB_NORMAL_BUDGET // (rank * rank * 4),
                               _SLAB_GATHER_BUDGET // (int(cap) * rank * 4)))
            chunk -= chunk % 2   # paired solver consumes rows two at a time
        for s in range(0, nb, max(chunk, 1)):
            e = min(s + chunk, nb)
            rws, cnts = rows[s:e], m_counts[s:e].astype(np.int32)
            lo = ends[s - 1] if s else 0
            src_se = src[lo:ends[e - 1]]
            if len(rws) % 2:
                # pad to even rows for the paired solver; the fill row
                # (count 0) is dropped at scatter time (see _FILL_ROW)
                rws = np.concatenate([rws, np.asarray([_FILL_ROW], np.int32)])
                cnts = np.concatenate([cnts, np.zeros(1, np.int32)])
            out.rows.append(rws)
            out.counts.append(cnts)
            out.idx.append(c[src_se].astype(np.int32))
            out.val.append(v[src_se].astype(np.float32))
            out.caps.append(int(cap))
    return out


@partial(jax.jit, static_argnames=("meta",))
def _pad_side_device(rows_c, counts_c, idx_c, val_c, *, meta):
    """Device-side ragged -> padded materialization of a whole side in
    ONE compiled program (a per-chunk program would compile ~40 tiny
    kernels, each paying the runtime's compile round trip — measured
    +440 s cold on the ML-25M pack). Inputs are the side's chunks
    CONCATENATED; `meta` is the static ((rows_j, entries_j, cap_j), ...)
    chunk table. Returns a tuple of (rows, idx, val) per chunk, idx
    carrying -1 slot padding (the mask derives from it downstream)."""
    import jax.numpy as jnp

    out = []
    ro = eo = 0
    for nb, ne, cap in meta:
        rows = jax.lax.slice(rows_c, (ro,), (ro + nb,))
        counts = jax.lax.slice(counts_c, (ro,), (ro + nb,))
        ridx = jax.lax.slice(idx_c, (eo,), (eo + ne,))
        rval = jax.lax.slice(val_c, (eo,), (eo + ne,))
        member = jnp.repeat(jnp.arange(nb, dtype=jnp.int32), counts,
                            total_repeat_length=ne)
        starts = jnp.cumsum(counts) - counts
        intra = jnp.arange(ne, dtype=jnp.int32) - jnp.repeat(
            starts.astype(jnp.int32), counts, total_repeat_length=ne)
        idx = jnp.full((nb, cap), -1, jnp.int32)
        idx = idx.at[member, intra].set(ridx.astype(jnp.int32))
        val = jnp.zeros((nb, cap), rval.dtype)
        val = val.at[member, intra].set(rval)
        out.append((rows, idx, val))
        ro += nb
        eo += ne
    return tuple(out)


def device_slabs(side: _SideBuckets, n_opposite: int,
                 val_dtype=np.float32) -> List[tuple]:
    """Upload one side's slabs as (rows, padded idx, padded val) device
    tuples. Transfer-lean: ragged entries only (no padding, no mask
    plane), indexes narrowed to uint16 when the opposite side fits, and
    `val_dtype` (bfloat16 on the paired hot path) halving value bytes
    (the transfer's share of an ML-25M train on a local chip: not
    measured). Four uploads + one compiled pad program per side
    signature."""
    import jax.numpy as jnp

    idx_t = np.uint16 if n_opposite <= np.iinfo(np.uint16).max else np.int32
    meta = tuple((len(side.counts[j]), len(side.idx[j]), side.caps[j])
                 for j in range(len(side.rows)))
    if not meta:
        return []
    padded = _pad_side_device(
        jnp.asarray(np.concatenate(side.rows)),
        jnp.asarray(np.concatenate(side.counts)),
        jnp.asarray(np.concatenate(side.idx).astype(idx_t)),
        jnp.asarray(np.concatenate(side.val).astype(val_dtype)),
        meta=meta)
    return list(padded)


@dataclass
class PackedRatings:
    """Degree-bucketed padded slabs for both sides of a rating matrix —
    the reusable output of `pack_ratings` (pack once, train many times:
    eval sweeps, repeated benches)."""
    user_side: _SideBuckets
    item_side: _SideBuckets
    n_users: int
    n_items: int
    rank: int


def pack_ratings(u_ix: np.ndarray, i_ix: np.ndarray, val: np.ndarray,
                 n_users: int, n_items: int, rank: int) -> PackedRatings:
    """Host-side packing of COO ratings into solver slabs for both
    alternation sides, with rank-aware memory-budget slab splitting."""
    return PackedRatings(
        user_side=_pack_side(u_ix, i_ix, val, n_users, rank),
        item_side=_pack_side(i_ix, u_ix, val, n_items, rank),
        n_users=n_users, n_items=n_items, rank=rank)


def iteration_flops(packed: PackedRatings,
                    cg_iters: int = _CG_ITERS) -> int:
    """Closed-form FLOPs of ONE full ALS iteration (both half-steps) over
    the PADDED slab shapes — the denominator work for achieved-FLOP/s /
    MFU accounting, counting the work that actually EXECUTES. Convention:
    multiply-add = 2 FLOPs. Per slab of B rows x cap K at rank R:

    rank > _SMALL_RANK (the paired-MXU path, see _solve_slab_paired):
      paired Gram  gkp,gkq->gpq : 2*(B/2)*K*(2R)^2 = 4*B*K*R^2
        (2x the useful 2*B*K*R^2 — the off-diagonal blocks of each
        128-wide pair are junk, the price of full 128x128 MXU tiles)
      rhs einsums               : 2*B*K*R
      warm CG (stays in PAIRED form: dense [2R,2R] matvecs, so per row
      per iteration 4*R^2 mult-adds and 2R-wide vector ops):
      B*cg_iters*(4*R^2 + 16*R) + warm-start/residual matvecs B*8*R^2

    rank <= _SMALL_RANK (exact spd_solve path): Gram 2*B*K*R^2 + rhs +
      Cholesky ~2*(R^3/3 + 2R^2) per row."""
    r = packed.rank
    total = 0
    paired = r > _SMALL_RANK
    for side in (packed.user_side, packed.item_side):
        for rows, k in zip(side.rows, side.caps):
            b = len(rows)
            if paired:
                total += 4 * b * k * r * r + 2 * b * k * r
                total += b * cg_iters * (4 * r * r + 16 * r)
                total += b * 8 * r * r   # warm-start + residual matvecs
            else:
                total += 2 * b * k * r * r + 2 * b * k * r
                total += b * 2 * (r ** 3 // 3 + 2 * r * r)
    return total


@partial(jax.jit, static_argnames=("implicit",))
def _solve_bucket(factors, idx, val, reg, alpha, yty, *, implicit: bool):
    """Solve normal equations for one bucket slab — the exact f32 path.

    factors: [n_opposite, rank] opposite-side factors (replicated)
    idx/val: [rows_b, cap_b]; slot padding carries idx == -1 (the mask
    is derived here — it never crosses the host->device link)
    yty: [rank, rank] Gram matrix of opposite factors (implicit only)
    Returns [rows_b, rank] solutions.

    Solver choice: rank <= _SMALL_RANK uses the exact blocked Cholesky
    (`spd_solve` — one 16-wide block, short batched program, exact
    regardless of conditioning); larger ranks use Jacobi-preconditioned
    CG at a conservative min(32, rank+8) cap. The TPU training hot loop
    uses `_solve_slab_paired` instead; this function is the reference /
    small-rank / CPU path, and the direct API the unit tests drive.
    """
    import jax.numpy as jnp

    from predictionio_tpu.ops.linalg import pcg_solve, spd_solve

    rank = factors.shape[1]
    msk = (idx >= 0).astype(factors.dtype)              # [B, K]
    val = val.astype(factors.dtype)
    yg = factors[jnp.maximum(idx, 0)]                   # [B, K, R] gather
    if implicit:
        # MLlib trainImplicit semantics: confidence c = 1 + alpha*|r|,
        # preference p = 1 iff r > 0 (negative r = confident dislike)
        conf = alpha * jnp.abs(val) * msk               # c - 1
        pref = (val > 0).astype(factors.dtype)
        a = jnp.einsum("bkr,bks,bk->brs", yg, yg, conf) + yty
        b = jnp.einsum("bkr,bk->br", yg, pref * (1.0 + conf) * msk)
    else:
        a = jnp.einsum("bkr,bks,bk->brs", yg, yg, msk)
        b = jnp.einsum("bkr,bk->br", yg, val * msk)
    n_row = msk.sum(axis=1)                             # ALS-WR scaling
    eye = jnp.eye(rank, dtype=factors.dtype)
    a = a + (reg * n_row)[:, None, None] * eye
    # pad rows (n_row == 0) get an identity system -> solution 0
    a = jnp.where((n_row > 0)[:, None, None], a, eye)
    if rank <= _SMALL_RANK:
        x = spd_solve(a, b)
    else:
        x = pcg_solve(a, b, iters=min(32, rank + 8))
    return jnp.where((n_row > 0)[:, None], x, 0.0)


@partial(jax.jit, static_argnames=("implicit", "cg_iters", "cast"))
def _solve_slab_paired(own, opp_cast, rows, idx, val, reg, alpha, yty,
                       *, implicit: bool, cg_iters: int, cast):
    """The TPU hot-loop slab solver: paired-rows Gram on full MXU tiles +
    warm-started CG. Returns ([rows_b, R] solutions, [rows_b] relative
    residuals).

    Why this shape (each choice from round-4 v5e timings against the
    ML-25M workload; not re-measured on a local chip, ROADMAP Speed 2):
      * The factor gather is row-rate-bound (~390M rows/s f32, ~450M
        bf16, independent of row WIDTH up to 128 lanes) — it is the
        step's hard floor, so the gathered operand is cast (`cast`,
        normally bfloat16) and every padded slot counts.

        WHY THE GATHER FLOOR IS PHYSICAL (the r4->r5 Pallas question):
        the measured rate is invariant in row width up to 128 lanes,
        i.e. the cost is per ROW FETCHED, not per byte — the random-row
        fetch issue rate of the memory system, at ~0.4-0.5 rows/cycle.
        A hand-written Pallas kernel has exactly one primitive for the
        same access pattern (a dynamic-slice row copy per index, issued
        from a scalar loop), which bottlenecks on the same issue path;
        a VMEM-resident table is out (the ML-25M user table alone is
        21 MB bf16 > 16 MB VMEM, and splitting it doubles index
        traffic); and a one-hot-matmul "gather on the MXU" pays
        N*R/(2R^2) ~ 460x junk FLOPs at ML-25M shapes. Entry-level
        Zipf reuse can't be cached either: the top-512-item hot set
        covers only ~9% of entries at the catalog's s=0.5 skew. What
        DOES shrink the floor is gathering fewer rows — the cap-ladder
        growth of 1.25 (padding ~1.12x, was 1.27x) is that lever; a
        fused gather+Gram kernel would only relocate, not remove, the
        per-row fetch cost.
      * A batched [K,R]x[K,R] Gram per row runs the MXU at <2 TFLOP/s
        because each batch element only fills a RxR corner of the
        128x128 systolic array. Pairing consecutive rows (lane-concat of
        their gathered factors -> [B/2, K, 2R]) makes the einsum produce
        [2R, 2R] tiles: 2x redundant FLOPs (the cross blocks are junk)
        for ~3x wall-clock at R=64.
      * Masks are {0,1} so m^2 = m: ONE masked gathered copy serves both
        Gram operands (for implicit, sqrt-confidence weights do the same
        trick), with f32 accumulation via preferred_element_type.
      * The whole solve stays in PAIRED form: the junk cross blocks of
        each [2R, 2R] system are zeroed once (fused into the Gram
        epilogue), which block-diagonalizes the pair so CG solves both
        halves independently-but-together in 128-wide matvecs.
        Un-pairing A first was measured SLOWER (a 3.6 GB relayout copy
        plus worse 64-wide matvec shapes).
      * CG warm-starts from the CURRENT factor rows (inexact ALS:
        block-coordinate descent tolerates approximate solves; measured
        RMSE matches the exact solve at cg_iters=8 on ML-25M). The
        returned residuals let `als_train` flag non-convergence
        (low-reg / ill-conditioned systems) instead of going silently
        wrong.
    """
    import jax.numpy as jnp

    from predictionio_tpu.ops.linalg import pcg_solve

    R = own.shape[1]
    B = idx.shape[0]
    G = B // 2
    a2, b2, n2 = _paired_normal_eqs(opp_cast, idx, val, reg, alpha,
                                    yty, implicit=implicit, cast=cast)
    live2 = n2 > 0                                       # [G, 2R]
    r2 = rows.reshape(G, 2)
    safe = jnp.minimum(r2, own.shape[0] - 1)             # _FILL_ROW-safe
    x0 = jnp.where(live2,
                   jnp.concatenate([own[safe[:, 0]], own[safe[:, 1]]],
                                   axis=-1), 0.0)
    # fixed-trip CG (rtol=0): the early-exit while_loop is a fusion
    # barrier that measured ~30% on the whole ML-25M step; the residual
    # still comes back via the extra true-residual matvec. Matvec
    # precision tracks the Gram precision (see pcg_solve note).
    mv_prec = (jax.lax.Precision.DEFAULT if cast == jnp.bfloat16
               else None)
    x2, rel, _ = pcg_solve(a2, b2, iters=cg_iters, x0=x0, rtol=0.0,
                           return_info=True, matvec_precision=mv_prec)
    x2 = jnp.where(live2, x2, 0.0)
    sol = jnp.stack([x2[:, :R], x2[:, R:]], axis=1).reshape(B, R)
    rel_b = jnp.broadcast_to(rel[:, None], (G, 2)).reshape(B)
    return sol, jnp.where(n2.reshape(G, 2, R)[:, :, 0].reshape(B) > 0,
                          rel_b, 0.0)


def _paired_normal_eqs(opp_cast, idx, val, reg, alpha, yty, *,
                       implicit: bool, cast):
    """Build the per-PAIR normal equations (A2 [B/2, 2R, 2R] f32
    block-diagonal, b2 [B/2, 2R] f32, n2 [B/2, 2R] per-lane row counts)
    through the paired-MXU formulation — the measured-hot
    gather+Gram+rhs stage, shared by `_solve_slab_paired` and the bench
    phase breakdown so the roofline numbers measure exactly the
    production code. The junk cross blocks from pairing are zeroed here
    (fused by XLA into the einsum epilogue), so each returned system is
    exactly blockdiag(A_even, A_odd) + ALS-WR diag (identity on empty /
    padding rows)."""
    import jax.numpy as jnp

    R = opp_cast.shape[1]
    B, K = idx.shape
    G = B // 2
    # multiply precision tracks the operand dtype: bf16 operands gain
    # nothing from multi-pass passes; f32 mode pins HIGHEST so
    # precision="f32" really is the exact-normal-equations escape hatch
    prec = (jax.lax.Precision.DEFAULT if cast == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)
    i2 = idx.reshape(G, 2, K)
    # slot padding carries idx == -1; derive the mask on device and
    # clamp for the gather (mask zeroes the garbage row's contribution)
    m2 = (i2 >= 0).astype(jnp.float32)
    i2 = jnp.maximum(i2, 0)
    v2 = val.reshape(G, 2, K).astype(jnp.float32)
    if implicit:
        # eps keeps c==0 observed entries alive through the sqrt trick:
        # their A-weight becomes eps (harmless) and the b-weight below
        # rescales by 1/sqrt(eps), so pref*(1+c)*y is exact even when
        # alpha == 0 (MLlib allows it: all-equal-confidence model)
        _EPS = 1e-12
        conf_e = alpha * jnp.abs(v2[:, 0]) * m2[:, 0] + _EPS * m2[:, 0]
        conf_o = alpha * jnp.abs(v2[:, 1]) * m2[:, 1] + _EPS * m2[:, 1]
        w_e = jnp.sqrt(conf_e).astype(cast)[..., None]
        w_o = jnp.sqrt(conf_o).astype(cast)[..., None]
    else:
        w_e = m2[:, 0].astype(cast)[..., None]
        w_o = m2[:, 1].astype(cast)[..., None]
    ygm = jnp.concatenate([opp_cast[i2[:, 0]] * w_e,
                           opp_cast[i2[:, 1]] * w_o], axis=-1)  # [G,K,2R]
    a2 = jnp.einsum("gkp,gkq->gpq", ygm, ygm, precision=prec,
                    preferred_element_type=jnp.float32)        # [G,2R,2R]
    if implicit:
        # b weights against the sqrt-conf-weighted copy:
        # pref*(1+c) * y = (sqrt(c) * y) * pref*(1+c)/sqrt(c)
        def bw(v, c):   # c >= eps on observed entries, 0 on padding
            return jnp.where(c > 0, (v > 0) * (1.0 + c) *
                             jax.lax.rsqrt(jnp.maximum(c, 1e-30)), 0.0)
        wb_e = bw(v2[:, 0], conf_e)
        wb_o = bw(v2[:, 1], conf_o)
    else:
        wb_e = v2[:, 0] * m2[:, 0]
        wb_o = v2[:, 1] * m2[:, 1]
    be = jnp.einsum("gkr,gk->gr", ygm[..., :R], wb_e.astype(cast),
                    precision=prec, preferred_element_type=jnp.float32)
    bo = jnp.einsum("gkr,gk->gr", ygm[..., R:], wb_o.astype(cast),
                    precision=prec, preferred_element_type=jnp.float32)
    b2 = jnp.concatenate([be, bo], axis=-1)              # [G, 2R]
    blockmask = np.zeros((2 * R, 2 * R), np.float32)
    blockmask[:R, :R] = 1.0
    blockmask[R:, R:] = 1.0
    a2 = a2 * blockmask
    if implicit:
        yty2 = jnp.zeros((2 * R, 2 * R), jnp.float32)
        yty2 = yty2.at[:R, :R].set(yty).at[R:, R:].set(yty)
        a2 = a2 + yty2
    n_e, n_o = m2[:, 0].sum(axis=1), m2[:, 1].sum(axis=1)
    n2 = jnp.concatenate([jnp.repeat(n_e[:, None], R, axis=1),
                          jnp.repeat(n_o[:, None], R, axis=1)], axis=-1)
    d2 = reg * n2 + (n2 == 0).astype(jnp.float32)        # pad rows -> I
    a2 = a2 + d2[:, :, None] * jnp.eye(2 * R, dtype=jnp.float32)
    return a2, b2, n2


def _pack_by_owner(side: _SideBuckets, block: int, n_dev: int):
    """Re-partition each bucket slab by owning device (owner = row //
    block) into [n_dev * rows_b, ...] arrays whose dim 0 shards evenly
    over the mesh: device d's chunk holds only rows it owns, addressed by
    LOCAL index (row - d*block, fill = block -> dropped scatter).
    Host-side, vectorized."""
    packed = []
    for j, rows in enumerate(side.rows):
        idx, vals = side.padded(j)
        real = rows != _FILL_ROW           # _pack_side even-padding rows
        rows, idx, vals = rows[real], idx[real], vals[real]
        owner = rows // block
        counts = np.bincount(owner, minlength=n_dev)
        rb = max(int(counts.max()), 1)
        rb += rb % 2                       # even rows per device (pairing)
        order = np.argsort(owner, kind="stable")
        member, intra = _group_offsets(counts)
        local_rows = np.full((n_dev, rb), block, np.int32)
        # fill slabs keep the -1 idx sentinel (mask derives from it)
        d_idx = np.full((n_dev, rb) + idx.shape[1:], -1, idx.dtype)
        d_val = np.zeros((n_dev, rb) + vals.shape[1:], vals.dtype)
        local_rows[member, intra] = rows[order] - member * block
        d_idx[member, intra] = idx[order]
        d_val[member, intra] = vals[order]
        packed.append((local_rows.reshape(n_dev * rb),
                       d_idx.reshape((n_dev * rb,) + idx.shape[1:]),
                       d_val.reshape((n_dev * rb,) + vals.shape[1:])))
    return packed


@partial(jax.jit,
         static_argnames=("implicit", "rank", "mesh", "cg_iters", "cast"))
def _run_als_sharded(x_sh, y_sh, user_slabs, item_slabs, reg, alpha,
                     n_iter, *, implicit: bool, rank: int, mesh,
                     cg_iters: int = _CG_ITERS, cast=None):
    """Sharded ALS loop: factor shards stay put; each half-step
    all-gathers the opposite shard (transient, cast to `cast` BEFORE the
    all-gather so the ICI bytes are halved in bf16 mode), psums the
    [rank, rank] Gram for implicit mode, and writes solved rows locally.
    Returns (x, y, max relative solver residual)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    paired = rank > _SMALL_RANK

    def body(x_local, y_local, user_slabs, item_slabs):
        def half_step(own_local, opp_local, slabs, res):
            if implicit:
                yty = jax.lax.psum(opp_local.T @ opp_local, "data")
            else:
                yty = jnp.zeros((rank, rank), jnp.float32)
            if paired:
                opp_cast = (opp_local.astype(cast) if cast is not None
                            else opp_local)
                opp_full = jax.lax.all_gather(opp_cast, "data", axis=0,
                                              tiled=True)
                for local_rows, idx, vals in slabs:
                    sol, rel = _solve_slab_paired(
                        own_local, opp_full, local_rows, idx, vals,
                        reg, alpha, yty, implicit=implicit,
                        cg_iters=cg_iters, cast=cast or jnp.float32)
                    own_local = own_local.at[local_rows].set(sol,
                                                             mode="drop")
                    res = jnp.maximum(res, rel.max())
            else:
                opp_full = jax.lax.all_gather(opp_local, "data", axis=0,
                                              tiled=True)
                for local_rows, idx, vals in slabs:
                    sol = _solve_bucket(opp_full, idx, vals, reg,
                                        alpha, yty, implicit=implicit)
                    # fill rows carry local index == block -> dropped
                    own_local = own_local.at[local_rows].set(sol,
                                                             mode="drop")
            return own_local, res

        def zero():
            # per-device residual: mark varying over the mesh axis so
            # the fori carry type is stable (see shard_map scan-vma
            # docs)
            return jax.lax.pcast(jnp.float32(0.0), ("data",),
                                 to="varying")

        def it(_, state):
            # final-iteration residual only (see _run_als note)
            x_local, y_local, _ = state
            x_local, res = half_step(x_local, y_local, user_slabs, zero())
            y_local, res = half_step(y_local, x_local, item_slabs, res)
            return (x_local, y_local, res)

        x_local, y_local, res = jax.lax.fori_loop(
            0, n_iter, it, (x_local, y_local, zero()))
        return x_local, y_local, jax.lax.pmax(res, "data")

    slab_specs_u = [tuple(P("data", *([None] * (a.ndim - 1)))
                          for a in slab) for slab in user_slabs]
    slab_specs_i = [tuple(P("data", *([None] * (a.ndim - 1)))
                          for a in slab) for slab in item_slabs]
    fsharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None), P("data", None),
                  slab_specs_u, slab_specs_i),
        out_specs=(P("data", None), P("data", None), P()))
    return fsharded(x_sh, y_sh, user_slabs, item_slabs)


@partial(jax.jit, static_argnames=("implicit", "rank", "cg_iters", "cast"))
def _run_als(x, y, user_slabs, item_slabs, reg, alpha, n_iter, *,
             implicit: bool, rank: int, cg_iters: int = _CG_ITERS,
             cast=None):
    """The full ALS training loop as one compiled program (module-level
    jit: the cache persists across als_train calls with the same slab
    shapes). Slabs are pytrees of (rows, idx, val) tuples (mask derives
    from the -1 idx sentinel on device). Returns
    (x, y, max relative solver residual — 0.0 on the exact small-rank
    path)."""
    import jax.numpy as jnp

    paired = rank > _SMALL_RANK

    def half_step(own, opposite, slabs, res):
        yty = (opposite.T @ opposite if implicit
               else jnp.zeros((rank, rank), jnp.float32))
        opp_cast = (opposite.astype(cast) if (paired and cast is not None)
                    else opposite)
        for rows_dev, idx, vals in slabs:
            if paired:
                sol, rel = _solve_slab_paired(
                    own, opp_cast, rows_dev, idx, vals, reg, alpha,
                    yty, implicit=implicit, cg_iters=cg_iters,
                    cast=cast or jnp.float32)
                res = jnp.maximum(res, rel.max())
            else:
                sol = _solve_bucket(opposite, idx, vals, reg, alpha,
                                    yty, implicit=implicit)
            # slab-padding rows carry an out-of-bounds row index; 'drop'
            # discards their updates instead of clamping onto row n-1
            own = own.at[rows_dev].set(sol, mode="drop")
        return own, res

    def body(_, state):
        # residual restarts each iteration: the LAST iteration's solves
        # are what determine the returned factors' quality (early
        # iterations legitimately run with cold warm-starts)
        x, y, _ = state
        x, res = half_step(x, y, user_slabs, jnp.float32(0.0))
        y, res = half_step(y, x, item_slabs, res)
        return (x, y, res)

    return jax.lax.fori_loop(0, n_iter, body, (x, y, jnp.float32(0.0)))


def _train_on_mesh(x, y, user_side, item_side, n_users, n_items, mesh, *,
                   reg, alpha, iterations, implicit, rank,
                   cg_iters=_CG_ITERS, cast=None):
    """Shard inputs and run `_run_als_sharded`; returns the still-sharded
    device factor arrays (padded to a multiple of the mesh size) plus
    the replicated max solver residual."""
    import jax.numpy as jnp

    from predictionio_tpu.parallel import batch_sharding, pad_to_multiple

    n_dev = int(mesh.shape["data"])
    dpad_u = pad_to_multiple(n_users, n_dev)
    dpad_i = pad_to_multiple(n_items, n_dev)
    # padding factor rows are zero (they are never solved and must not
    # bias the psum'd implicit Gram matrix)
    x_sh = jax.device_put(
        jnp.pad(x, ((0, dpad_u - x.shape[0]), (0, 0))),
        batch_sharding(mesh, "data", 2))
    y_sh = jax.device_put(
        jnp.pad(y, ((0, dpad_i - y.shape[0]), (0, 0))),
        batch_sharding(mesh, "data", 2))
    dev_sides = []
    for side, block in ((user_side, dpad_u // n_dev),
                        (item_side, dpad_i // n_dev)):
        slabs = []
        for leaves in _pack_by_owner(side, block, n_dev):
            slabs.append(tuple(
                jax.device_put(a, batch_sharding(mesh, "data", a.ndim))
                for a in leaves))
        dev_sides.append(slabs)
    return _run_als_sharded(
        x_sh, y_sh, dev_sides[0], dev_sides[1], jnp.float32(reg),
        jnp.float32(alpha), jnp.int32(iterations),
        implicit=implicit, rank=rank, mesh=mesh, cg_iters=cg_iters,
        cast=cast)


@jax.jit
def _predict_elements(x, y, u_ix, i_ix):
    import jax.numpy as jnp
    return jnp.einsum("nr,nr->n", x[u_ix], y[i_ix])


def init_factors(n_users: int, n_items: int, rank: int, seed: int,
                 user_present: Optional[np.ndarray] = None,
                 item_present: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Starting factors (numpy): MLlib init abs(normal)/sqrt(rank) keeps
    initial predictions O(1). Rows with no ratings are zeroed from the
    start: they are never solved, and a nonzero phantom row would bias
    the implicit-mode Gram matrix Y^T Y (MLlib has no factor row at all
    for such ids). Exposed so the independent numpy oracle
    (`ops.oracle`) can start from identical factors for parity checks."""
    key = jax.random.PRNGKey(seed)
    ku, ki = jax.random.split(key)

    def _rowkeyed(side_key, n_rows):
        # per-row keyed draws: row r depends only on (seed, r), NOT on
        # the matrix height — threefry bit generation pairs counter
        # halves across the whole block, so a single (n, rank) draw
        # gives row r different values at different n. Shape-stable
        # rows mean a catalog padded with never-rated (zeroed) tail
        # rows starts — and therefore trains — identically to one
        # without them (the phantom-item invariance the tests pin).
        rows = np.arange(max(n_rows, 1))
        block = jax.vmap(lambda r: jax.random.normal(
            jax.random.fold_in(side_key, r), (rank,)))(rows)
        return np.abs(np.asarray(block))

    x = _rowkeyed(ku, n_users) / math.sqrt(rank)
    y = _rowkeyed(ki, n_items) / math.sqrt(rank)
    if user_present is not None:
        x = np.where(user_present[:, None], x, 0.0)
    if item_present is not None:
        y = np.where(item_present[:, None], y, 0.0)
    return x.astype(np.float32), y.astype(np.float32)


def als_train(ratings: "RatingColumns | Tuple[np.ndarray, np.ndarray, np.ndarray]",
              n_users: Optional[int] = None,
              n_items: Optional[int] = None, *,
              rank: int = 10,
              iterations: int = 10,
              reg: float = 0.01,
              implicit: bool = False,
              alpha: float = 1.0,
              seed: int = 0,
              mesh=None,
              packed: Optional[PackedRatings] = None,
              timings: Optional[dict] = None,
              precision: str = "bf16",
              cg_iters: int = _CG_ITERS) -> Tuple[np.ndarray, np.ndarray]:
    """Train factor matrices (X [n_users, rank], Y [n_items, rank]).

    Matches MLlib semantics: ALS-WR regularization (lambda scaled by the
    row's rating count), random normalized init, `iterations` full
    alternations. `mesh` shards each slab's row dimension over the "data"
    axis; None runs single-device. `packed` (from `pack_ratings`) skips
    host-side packing; `timings`, if given, is filled with pack_s /
    solve_s / fetch_s wall-clock phases plus `solver_residual` (the max
    relative residual of the inexact solves; 0.0 on the exact path).

    `precision` ("bf16" | "f32") sets the dtype of the GATHERED factor
    operands in the rank > 16 paired path (normal-equation accumulation
    and the CG solve are always f32) — bf16 is the TPU-first default and
    is gated by the bench's RMSE-parity check; rank <= 16 and the
    reference `_solve_bucket` path are exact f32 regardless. Rating
    VALUES additionally cross the link in bf16 on that path, but only
    when every rating round-trips bfloat16 exactly (half-star ratings
    do); otherwise values stay f32, so no rating is ever silently
    rounded. `cg_iters`
    caps the warm-started CG (see _CG_ITERS).

    Conditioning note (MLlib parity): MLlib's CholeskySolver is exact
    for any regParam; the paired path is iterative, so with reg near 0
    AND ill-conditioned data the solve may not converge within
    `cg_iters`. That case is detected (residual > 1e-2) and logged as a
    warning; raise `cg_iters` or use rank <= 16 / `_solve_bucket` for
    exact behavior.
    """
    import time as _time

    import jax.numpy as jnp

    cast = {"bf16": jnp.bfloat16, "f32": None}[precision]
    t0 = _time.perf_counter()
    if packed is not None:
        user_side, item_side = packed.user_side, packed.item_side
        n_users, n_items = packed.n_users, packed.n_items
        assert packed.rank == rank, "packed slabs were split for a different rank"
    else:
        if isinstance(ratings, RatingColumns):
            u_ix, i_ix, val = ratings.user_ix, ratings.item_ix, ratings.rating
            n_users = n_users or len(ratings.users)
            n_items = n_items or len(ratings.items)
        else:
            u_ix, i_ix, val = ratings
            assert n_users is not None and n_items is not None
        user_side = _pack_side(u_ix, i_ix, val, n_users, rank)
        item_side = _pack_side(i_ix, u_ix, val, n_items, rank)
    t_pack = _time.perf_counter()

    def present_mask(side, n_rows):
        present = np.zeros(max(n_rows, 1), bool)
        for rows in side.rows:
            present[rows[rows != _FILL_ROW]] = True
        return present

    x, y = init_factors(n_users, n_items, rank, seed,
                        user_present=present_mask(user_side, n_users),
                        item_present=present_mask(item_side, n_items))
    x, y = jnp.asarray(x), jnp.asarray(y)

    if mesh is not None:
        x_sh, y_sh, res_sh = _train_on_mesh(
            x, y, user_side, item_side, n_users, n_items, mesh,
            reg=reg, alpha=alpha, iterations=iterations,
            implicit=implicit, rank=rank, cg_iters=cg_iters, cast=cast)
        jax.block_until_ready((x_sh, y_sh))
        t_solve = _time.perf_counter()

        def fetch(arr):
            # multi-host mesh: shards on other processes are not
            # addressable here; all-gather across hosts first
            # (Runner.scala's executors ship results to the driver —
            # here every host ends with the full factors)
            if arr.is_fully_addressable:
                return np.asarray(arr)
            from jax.experimental import multihost_utils
            return np.asarray(
                multihost_utils.process_allgather(arr, tiled=True))

        out = (fetch(x_sh)[:n_users], fetch(y_sh)[:n_items])
        _check_residual(float(np.asarray(res_sh)), timings)
        if timings is not None:
            timings.update(pack_s=t_pack - t0, solve_s=t_solve - t_pack,
                           fetch_s=_time.perf_counter() - t_solve)
        return out

    # transfer-lean upload: ragged entries only, uint16 idx when the
    # opposite side fits, bf16 values on the EXPLICIT paired hot path —
    # but ONLY when every rating round-trips bfloat16 exactly (half-star
    # ratings do; arbitrary scores like 4.7 do not, and silently
    # rounding them in the normal equations is a behavior change the
    # caller never asked for). Non-exact values fall back to f32
    # transfer. Implicit mode keeps f32 values: confidences c = alpha*|r|
    # are computed in f32 from the raw ratings, and count-valued ratings
    # above 256 would round in bf16.
    paired = rank > _SMALL_RANK
    val_dt = (jnp.bfloat16
              if (paired and cast is jnp.bfloat16 and not implicit
                  and _bf16_exact(user_side.val))
              else np.float32)
    dev_sides = [device_slabs(user_side, n_items, val_dt),
                 device_slabs(item_side, n_users, val_dt)]
    jax.block_until_ready(dev_sides)
    t_xfer = _time.perf_counter()

    x, y, res = _run_als(x, y, dev_sides[0], dev_sides[1], jnp.float32(reg),
                         jnp.float32(alpha), jnp.int32(iterations),
                         implicit=implicit, rank=rank, cg_iters=cg_iters,
                         cast=cast)
    jax.block_until_ready((x, y))
    t_solve = _time.perf_counter()
    out = (np.asarray(x), np.asarray(y))
    _check_residual(float(np.asarray(res)), timings)
    if timings is not None:
        timings.update(pack_s=t_pack - t0, transfer_s=t_xfer - t_pack,
                       solve_s=t_solve - t_xfer,
                       fetch_s=_time.perf_counter() - t_solve)
    return out


def _bf16_exact(arrays) -> bool:
    """True iff every value in the per-bucket arrays round-trips
    bfloat16 exactly (host-side, chunked: no values-sized temporary).
    Guards the bf16 value transfer in `als_train` — ratings that bf16
    cannot represent (4.7, percentages) must cross in f32."""
    import jax.numpy as jnp
    step = 1 << 22
    for a in arrays:
        a = np.asarray(a)
        for s in range(0, len(a), step):
            c = np.asarray(a[s:s + step], np.float32)
            if not np.array_equal(
                    c, c.astype(jnp.bfloat16).astype(np.float32)):
                return False
    return True


def _check_residual(res: float, timings: Optional[dict]) -> None:
    """Surface the inexact-solver residual (see als_train conditioning
    note): record it, and warn loudly when the warm-CG solve failed to
    converge — the exact-Cholesky reference (MLlib CholeskySolver) has
    no such failure mode, so silence here would be a parity trap."""
    if timings is not None:
        # keep the WORST residual across a run's solves (two-sided
        # similar-product trains solve twice into one phase dict): the
        # bench convergence gate must see any failed solve, not just
        # the last one
        timings["solver_residual"] = max(
            res, timings.get("solver_residual", 0.0))
    if res > 1e-2:
        import logging
        logging.getLogger(__name__).warning(
            "ALS normal-equation solve did not converge (max relative "
            "residual %.2e > 1e-2): the system is ill-conditioned — "
            "likely reg is near zero. Raise cg_iters, raise reg, or use "
            "rank <= %d for the exact solver.", res, _SMALL_RANK)


def rmse(x: np.ndarray, y: np.ndarray, u_ix: np.ndarray, i_ix: np.ndarray,
         val: np.ndarray) -> float:
    """Root mean squared error over the given elements (the parity gate
    metric from BASELINE.md)."""
    import jax.numpy as jnp
    pred = _predict_elements(jnp.asarray(x), jnp.asarray(y),
                             jnp.asarray(u_ix), jnp.asarray(i_ix))
    return float(np.sqrt(np.mean((np.asarray(pred) - val) ** 2)))


def hbm_footprint(n_users: int, n_items: int, n_ratings: int, rank: int,
                  n_devices: int, *, owner_skew: float = 2.0) -> dict:
    """Per-device HBM upper bound (bytes, f32) for the sharded ALS layout
    — the documented memory model (see module docstring).

    Bucket padding is bounded in closed form: a row of degree d lands in
    a slab of cap(d) <= max(BASE, GROWTH*d + 8) (the x1.5 ladder rounds
    caps up to a multiple of 8), so a side's padded entry count is
    <= BASE*n_rows + GROWTH*n_ratings + 8*n_rows. `owner_skew` bounds
    the extra padding from `_pack_by_owner` equalizing per-device row
    counts (contiguous id blocks; ~1 for hashed/uniform ids, worst case
    n_devices for fully skewed ownership). `peak` is persistent + the
    worst transient: all-gathered opposite factors (bf16 in the default
    paired path, counted at f32 here as the conservative bound), plus
    the per-slab solve transients — the [B, cap, rank] gathered+masked
    factor copy (bf16: cap*rank*2B per row, counted via the gather
    budget at 2.75x for the pre-concat halves and cross-slab
    double-buffering) and the paired [B/2, 2R, 2R] f32 normal-equation
    systems that the solve stays in (counted at 9x the normal budget:
    the Gram is 2 budget-units, live twice across slab pipelining, plus
    2R-wide CG state), each capped by the slab-split budgets
    (`_SLAB_GATHER_BUDGET` / `_SLAB_NORMAL_BUDGET`), since `_pack_side`
    splits any bucket whose transients would exceed them and XLA's
    buffer assignment reuses the previous slab's buffers. See the
    multiplier note below for the measured anchor."""
    fb = 4  # f32 / int32 bytes
    pad_side = _BUCKET_BASE + 8
    padded_user = pad_side * n_users + _BUCKET_GROWTH * n_ratings
    padded_item = pad_side * n_items + _BUCKET_GROWTH * n_ratings
    factors_local = (n_users + n_items) * rank * fb / n_devices
    # idx (int32) + val (f32 bound; the bf16 hot path halves it) per
    # PADDED entry, both sides, sharded with skew — the mask plane is
    # derived from the -1 idx sentinel and never materialized
    # persistently
    slabs_local = ((padded_user + padded_item) * 2 * fb / n_devices
                   * owner_skew)
    gathered_opposite = max(n_users, n_items) * rank * fb
    # Multipliers anchored to the compiler's buffer assignment for the
    # ML-25M rank-64 program (memory_analysis peak 10.66 GiB, r4 bench):
    # 2.75x the gather-stage budget (the paired bf16 [G,K,2R] copy, its
    # two pre-concat producer halves, and cross-slab double-buffering)
    # and 9x the normal-equation budget (the paired [G,2R,2R] f32 Gram
    # = 2 budget-units, live twice across slab pipelining, plus CG state
    # vectors in 2R width). The bench asserts compiler-reported peak <=
    # this bound.
    slab_gather = 2.75 * min(
        max(padded_user, padded_item) * rank * fb / n_devices * owner_skew,
        _SLAB_GATHER_BUDGET)
    normal_bufs = 9 * min(
        max(n_users, n_items) * rank * rank * fb / n_devices * owner_skew,
        _SLAB_NORMAL_BUDGET)
    persistent = factors_local + slabs_local
    transient = gathered_opposite + slab_gather + normal_bufs
    return {
        "persistent": persistent,
        "transient": transient,
        "peak": persistent + transient,
    }


@dataclass
class ALSModel:
    """Factor matrices + BiMaps — the serving-side model
    (`examples/.../ALSModel.scala` fork of MatrixFactorizationModel)."""
    user_factors: np.ndarray    # [n_users, rank]
    item_factors: np.ndarray    # [n_items, rank]
    users: BiMap
    items: BiMap
    # items each user has interacted with at train time (for seen-filtering)
    seen: Optional[dict] = None

    def sanity_check(self):
        assert self.user_factors.ndim == 2 and self.item_factors.ndim == 2
        assert np.isfinite(self.user_factors).all(), "non-finite user factors"
        assert np.isfinite(self.item_factors).all(), "non-finite item factors"


# -- streaming fold-in --------------------------------------------------------

# per-row event cap for fold-in (newest kept) — bounds the padded slab
_FOLD_HISTORY_CAP = 8192


def fold_in_rows(opposite: np.ndarray, histories, *, reg: float,
                 implicit: bool = False, alpha: float = 1.0) -> np.ndarray:
    """Closed-form least-squares fold-in: re-solve factor rows against
    FIXED opposite-side factors — one exact ALS half-step, the classic
    trick for projecting new/updated users into a trained space without
    a retrain. `histories` is a sequence of `(opposite_ix, value)`
    array pairs, one per row to solve; returns `[len(histories), rank]`
    f32 rows.

    Exactness: this drives the same `_solve_bucket` program the
    reference training sweep runs, with identical reg/alpha semantics
    (ALS-WR row-count scaling, implicit confidence c = 1 + alpha*|r|),
    so a folded row equals that row's training solve given the same
    opposite factors. Shapes are padded to pow2 buckets so repeated
    refresh ticks hit the jit cache instead of recompiling per tick;
    histories longer than `_FOLD_HISTORY_CAP` keep their newest events
    (a documented approximation — such users converge on the next full
    retrain)."""
    import jax.numpy as jnp

    opp = np.ascontiguousarray(opposite, np.float32)
    rank = opp.shape[1]
    n_rows = len(histories)
    if n_rows == 0:
        return np.zeros((0, rank), np.float32)
    cap = 8
    for ix, _ in histories:
        cap = max(cap, min(len(ix), _FOLD_HISTORY_CAP))
    cap = 1 << (cap - 1).bit_length()
    b_pad = 1 << (max(8, n_rows) - 1).bit_length()
    idx = np.full((b_pad, cap), -1, np.int32)
    val = np.zeros((b_pad, cap), np.float32)
    for r, (ix, v) in enumerate(histories):
        ix = np.asarray(ix, np.int32)[-cap:]
        v = np.asarray(v, np.float32)[-cap:]
        idx[r, :len(ix)] = ix
        val[r, :len(v)] = v
    # YtY only feeds the implicit branch; the explicit trace still
    # wants the operand, so ship zeros there
    yty = opp.T @ opp if implicit else np.zeros((rank, rank), np.float32)
    sol = _solve_bucket(jnp.asarray(opp), jnp.asarray(idx),
                        jnp.asarray(val), jnp.float32(reg),
                        jnp.float32(alpha), jnp.asarray(yty),
                        implicit=implicit)
    # slice on HOST: an on-device sol[:n_rows] bakes n_rows into a
    # dynamic_slice program, recompiling for every novel touched-row
    # count — exactly the per-tick churn the pow2 padding exists to avoid
    return np.asarray(sol)[:n_rows]
