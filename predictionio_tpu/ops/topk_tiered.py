"""Tiered factor storage: a device-resident demand-paged hot set over a
host-RAM master copy, with EXACT top-k.

A catalog that exceeds even the (multi-host) mesh budget cannot be
device-resident. `TieredTopK` keeps the full `[n_items, rank]` factor
matrix in host RAM and pins only a fixed-size HOT slab `[hot_items,
rank]` on device, chosen by EWMA'd per-item access counts folded off
the serve path (serving/paging.PageManager). A serve call is:

  1. DEVICE: the hot slab scores through the inner `BucketedTopK` —
     same AOT bucket executables, banned filter, zero steady-state
     recompiles. Hot slots are kept SORTED ASCENDING BY GLOBAL ID, so
     `lax.top_k`'s lowest-index-first tie-break in slot space IS the
     global-id tie-break.
  2. HOST: cold items score through exact-f32 host BLAS with an O(n)
     argpartition top-k (`_topk_cold`, bit-identical to `_topk_host`'s
     stable tie semantics), the hot columns masked strictly BELOW
     `NEG_INF` so a masked row can never displace a legitimately-banned
     candidate.
  3. MERGE: the ≥k hot+cold candidates re-rank by (-score, global id)
     — bit-identical to the single-device `BucketedTopK` oracle under
     the same bitwise-score caveat as the sharded plans.

Paging swaps the slab through `BucketedTopK.swap_factors` (the factor
operand is positional, so every bucket executable is reused — zero
recompiles by construction); promotions/evictions are batched, run on
the async page thread, and hysteresis-biased toward incumbents so a
near-tie between a hot and a cold item does not thrash the slab.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from predictionio_tpu.ops.topk import (
    NEG_INF, BucketedTopK, DEFAULT_SERVE_BUCKETS, _record_dispatch,
    _topk_host,
)

# Strictly below NEG_INF: marks hot columns in the cold host pass and
# row-padding in the merge pool. Legitimate candidates (including banned
# ones at exactly NEG_INF) always outrank it, so a sentinel reaches the
# final top-k only when the candidate pool is smaller than k — which
# cannot happen while hot+cold tiers together hold >= k items.
_MASKED = np.float32(-np.inf)


def _topk_cold(scores: np.ndarray, k: int):
    """O(n) per-row top-k with `_topk_host`'s exact lowest-index-first
    tie semantics. The cold tier spans the WHOLE master minus the slab
    — a full stable argsort there is O(n log n) per query and dominates
    serve latency on giant catalogs. `argpartition` preselects in O(n);
    every item tied with the k-th score re-enters the pool so the final
    stable (-score, index) cut is bit-identical to the argsort path
    (degenerate all-tied rows fall back to sorting the whole row, which
    is exactly what the argsort would have done)."""
    b, n = scores.shape
    k = min(k, n)
    if k >= n:
        return _topk_host(scores, k)
    out_s = np.empty((b, k), np.float32)
    out_ix = np.empty((b, k), np.int64)
    for row in range(b):
        s = scores[row]
        part = np.argpartition(-s, k - 1)[:k]
        cand = np.flatnonzero(s >= s[part].min())
        order = np.lexsort((cand, -s[cand]))[:k]
        pick = cand[order]
        out_s[row] = s[pick]
        out_ix[row] = pick
    return out_s, out_ix.astype(np.int32)


class TieredTopK:
    """Serving plan for catalogs bigger than the device budget: host
    master + device hot slab + exact hot/cold merge. Satisfies the
    `BucketedTopK` warm/fits/swap_factors/__call__ contract, so the
    templates, the micro-batcher, and the streaming refresher use it
    unchanged."""

    def __init__(self, item_factors, *, k: int,
                 buckets: Sequence[int] = DEFAULT_SERVE_BUCKETS,
                 banned_width: int = 256, hot_items: int = 0,
                 ewma_decay: float = 0.8):
        master = np.ascontiguousarray(item_factors, dtype=np.float32)  # lint: ok — host master copy
        self.n_items, self.rank = master.shape
        self.k = max(1, min(k, self.n_items))
        self.banned_width = banned_width
        self.master = master
        hot = (int(hot_items) if hot_items > 0  # lint: ok — host int
               else max(1, self.n_items // 4))
        self.hot_items = max(1, min(hot, self.n_items))
        # the page swap and the serve read of (slot_gids, slab) must be
        # atomic together — slot ids decoded against a swapped slab
        # would alias wrong global ids
        self._page_lock = threading.Lock()
        self.slot_gids = np.arange(self.hot_items, dtype=np.int64)
        self._hot = BucketedTopK(master[self.slot_gids],
                                 k=min(self.k, self.hot_items),
                                 buckets=buckets,
                                 banned_width=banned_width)
        # access accounting, folded by the pager off the serve path:
        # GIL-atomic list appends of served-gid arrays (bounded by the
        # pager's drain cadence; drain swaps the list wholesale)
        self._access_buf: List[np.ndarray] = []
        self._ewma = np.zeros(self.n_items, np.float64)
        self.ewma_decay = float(ewma_decay)  # lint: ok — host float
        # hit/served tallies for pio_tier_hit_ratio: plain ints under
        # the GIL (worst case one lost increment, never a wrong ratio)
        self.hits = 0
        self.served = 0
        self.promotions_total = 0
        self.page_count = 0
        self.last_page_seconds = 0.0

    # -- plan contract ------------------------------------------------------
    @property
    def factors(self):
        """The device-resident state (the hot slab): what
        `_sample_plan_bytes` reports as pio_plan_resident_bytes."""
        return self._hot.factors

    @property
    def buckets(self):
        return self._hot.buckets

    @property
    def max_bucket(self) -> int:
        return self._hot.max_bucket

    def resident_per_device_bytes(self) -> float:
        # the inner BucketedTopK registered itself; report 0 here so
        # the slab is not double-counted by plan_resident_bytes()
        return 0.0

    def warm(self) -> int:
        return self._hot.warm()

    def bucket_kernels(self) -> dict:
        return self._hot.bucket_kernels()

    def fits(self, *, max_banned: int, k: int) -> bool:
        return (self._hot.fits(max_banned=max_banned, k=self._hot.k)
                and k <= self.k and max_banned <= self.banned_width)

    def swap_factors(self, item_factors) -> np.ndarray:
        """Whole-model hot swap (the streaming refresher / reload
        rollback): replace the host master and rebuild the slab from
        the CURRENT slot assignment — same shapes, so every bucket
        executable is reused, zero recompiles."""
        host = np.ascontiguousarray(item_factors, dtype=np.float32)  # lint: ok — host master copy
        if host.shape != (self.n_items, self.rank):
            raise ValueError(
                f"swap_factors shape {host.shape} != "
                f"{(self.n_items, self.rank)}: catalog changed — re-warm "
                "instead")
        with self._page_lock:
            prev = self.master
            self.master = host
            self._hot.swap_factors(host[self.slot_gids])
        return prev

    def __call__(self, user_vecs, banned_lists: Sequence[Sequence[int]]):
        """Score `[b, rank]` queries against the full catalog; returns
        host (scores [b, k], GLOBAL ids [b, k]) bit-identical to the
        single-device oracle."""
        user_vecs = np.asarray(user_vecs, np.float32)  # lint: ok — host in
        b = user_vecs.shape[0]
        k = self.k
        # -- hot tier: device slab through the AOT bucket machinery ---------
        with self._page_lock:
            gids = self.slot_gids
            master = self.master
            # global banned ids -> slot ids; out-of-slab bans drop here
            # (the cold pass applies them in global id space)
            hot_banned = []
            for bl in banned_lists:
                if len(bl):
                    arr = np.asarray(bl, np.int64)  # lint: ok — host ids
                    pos = np.searchsorted(gids, arr)
                    pos = pos[(pos < gids.shape[0])
                              & (gids[np.minimum(pos, gids.shape[0] - 1)]
                                 == arr)]
                    hot_banned.append(pos.tolist())
                else:
                    hot_banned.append(())
            hot_s, hot_slots = self._hot(user_vecs, hot_banned)
            hot_g = gids[hot_slots.astype(np.int64)]
        # -- cold tier: exact host BLAS over the master ----------------------
        t0 = time.perf_counter()
        cold = user_vecs @ master.T
        for row, bl in enumerate(banned_lists):
            if len(bl):
                cold[row, np.asarray(bl, np.int64)] = NEG_INF  # lint: ok — host ids
        # hot columns mask AFTER bans: a banned hot item must sit at
        # _MASKED (not NEG_INF) here, or it would surface from BOTH
        # tiers and duplicate a gid in the merged tail
        cold[:, gids] = _MASKED
        cold_s, cold_g = _topk_cold(cold, k)
        _record_dispatch("host", b * max(self.n_items - self.hot_items, 1),
                         time.perf_counter() - t0)
        # -- exact merge by (-score, global id) ------------------------------
        cand_s = np.concatenate([hot_s, cold_s], axis=1)
        cand_g = np.concatenate([hot_g, cold_g.astype(np.int64)], axis=1)
        n_hot = hot_s.shape[1]
        out_s = np.empty((b, k), np.float32)
        out_g = np.empty((b, k), np.int64)
        hot_hits = 0
        for row in range(b):
            order = np.lexsort((cand_g[row], -cand_s[row]))[:k]
            out_s[row] = cand_s[row, order]
            out_g[row] = cand_g[row, order]
            hot_hits += int(np.count_nonzero(order < n_hot))
        # access + hit accounting for the pager (GIL-atomic append)
        self._access_buf.append(out_g.ravel())
        self.hits += hot_hits
        self.served += b * k
        return out_s, out_g.astype(np.int32)

    # -- paging (called from the async page thread ONLY) --------------------
    def fold_accesses(self) -> int:
        """Drain the serve-path access buffer into the per-item EWMA;
        returns how many top-k slots were folded."""
        buf, self._access_buf = self._access_buf, []
        if not buf:
            self._ewma *= self.ewma_decay
            return 0
        gids = np.concatenate(buf)
        counts = np.bincount(gids, minlength=self.n_items)
        self._ewma = self._ewma * self.ewma_decay \
            + counts[:self.n_items].astype(np.float64)
        return int(gids.shape[0])  # lint: ok — host shape

    def rebalance(self, hysteresis: float = 0.25,
                  min_swap: int = 1) -> int:
        """One batched promotion/eviction pass: pick the EWMA top
        `hot_items` (incumbents get a `hysteresis` retention bonus so
        near-ties never thrash), rebuild the slab SORTED by global id,
        and swap it in through the reused bucket executables. Returns
        the number of promotions (0 = slab unchanged)."""
        eff = self._ewma.copy()
        eff[self.slot_gids] *= (1.0 + hysteresis)
        # a vanishing id-ordered tie-break: equal EWMAs (fresh start,
        # uniform traffic) must pick the SAME set every pass, or
        # argpartition's arbitrary tie choice thrashes the slab
        eff -= np.arange(self.n_items, dtype=np.float64) * 1e-12
        desired = np.argpartition(-eff, self.hot_items - 1)[:self.hot_items]
        promoted = np.setdiff1d(desired, self.slot_gids,
                                assume_unique=False)
        if promoted.shape[0] < max(1, min_swap):
            return 0
        t0 = time.perf_counter()
        new_gids = np.sort(desired).astype(np.int64)
        with self._page_lock:
            # slab gathers under the lock: a concurrent whole-model
            # swap_factors must not leave slab rows from the OLD master
            self._hot.swap_factors(self.master[new_gids])
            self.slot_gids = new_gids
        self.promotions_total += int(promoted.shape[0])  # lint: ok — host shape
        self.page_count += 1
        self.last_page_seconds = time.perf_counter() - t0
        return int(promoted.shape[0])  # lint: ok — host shape

    def hit_ratio(self) -> float:
        """Fraction of served top-k entries answered by the hot slab."""
        return self.hits / self.served if self.served else 0.0

    def stats(self) -> dict:
        return {"hot_items": self.hot_items, "n_items": self.n_items,
                "hit_ratio": round(self.hit_ratio(), 4),
                "served": self.served,
                "promotions_total": self.promotions_total,
                "pages": self.page_count}


def tier_mode() -> str:
    """PIO_SERVE_TIER: `auto` (tier when the catalog exceeds the
    effective device budget), `on` (always tier), `off`."""
    import os
    mode = (os.environ.get("PIO_SERVE_TIER", "auto") or "auto").lower()
    if mode in ("on", "1", "true"):
        return "on"
    if mode in ("off", "0", "false"):
        return "off"
    return "auto"


def hot_frac() -> Optional[float]:
    """PIO_TIER_HOT_FRAC: fraction of the catalog to pin hot (clamped
    to (0, 1]); unset -> size the slab from the device budget."""
    import os
    raw = (os.environ.get("PIO_TIER_HOT_FRAC", "") or "").strip()
    if not raw:
        return None
    try:
        return min(max(float(raw), 1e-6), 1.0)  # lint: ok — env str
    except ValueError:
        return None
