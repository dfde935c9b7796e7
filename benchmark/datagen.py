"""Inputs made from --seed: ratings for the train cells, factors for the
serve cells. The same seed gives the same inputs; every seed gives the
same SIZES (the degree sequence of the rating matrix is fixed by the
configuration's `pattern_seed` and only relabelled by the run's seed),
so every seed drives the same compiled programs.
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np

# items drawn per block of the on-device factor generator: one compiled
# shape, 268 MB a block at rank 64, so that making 6 GB of factors never
# holds more than a block on the device
FACTOR_BLOCK_ROWS = 1 << 20


def rating_pattern(cfg: Dict) -> Tuple[np.ndarray, np.ndarray]:
    """(user_ix, item_ix) of every rating: users uniform, items
    Zipf(`item_zipf_s`) by popularity rank, as `bench.py:synthetic_ml25m`
    drew them. A function of the configuration alone."""
    n, n_users, n_items = (int(cfg["n_ratings"]), int(cfg["n_users"]),
                           int(cfg["n_items"]))
    rng = np.random.default_rng(int(cfg["assumed"]["pattern_seed"]))
    u = rng.integers(0, n_users, n, dtype=np.int64).astype(np.int32)
    pop = np.arange(1, n_items + 1, dtype=np.float64) ** -float(
        cfg["assumed"]["item_zipf_s"])
    cdf = np.cumsum(pop / pop.sum())
    i = np.searchsorted(cdf, rng.random(n)).astype(np.int32)
    np.clip(i, 0, n_items - 1, out=i)
    return u, i


def ratings(cfg: Dict, seed: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(user_ix, item_ix, rating) for `seed`: the configuration's pattern
    with users and items relabelled by seeded permutations, and ratings
    from a seeded planted low-rank structure quantised to half stars
    0.5..5.0 (so every value is exact in bfloat16)."""
    u0, i0 = rating_pattern(cfg)
    n_users, n_items = int(cfg["n_users"]), int(cfg["n_items"])
    rng = np.random.default_rng(int(seed))
    u = rng.permutation(n_users).astype(np.int32)[u0]
    i = rng.permutation(n_items).astype(np.int32)[i0]
    del u0, i0
    p = int(cfg["assumed"]["planted_rank"])
    xu = rng.standard_normal((n_users, p), np.float32)
    yi = rng.standard_normal((n_items, p), np.float32)
    r = np.empty(len(u), np.float32)
    step = 5_000_000                       # bounds the host temporaries
    for s in range(0, len(u), step):
        e = min(s + step, len(u))
        raw = (xu[u[s:e]] * yi[i[s:e]]).sum(1) / 2.8 + 3.0
        r[s:e] = np.clip(np.round(raw * 2.0) / 2.0, 0.5, 5.0)
    return u, i, r


def factor_blocks(n_rows: int, rank: int, seed: int, side: int
                  ) -> Iterator[Tuple[int, "object"]]:
    """Yield (first_row, device array [<=FACTOR_BLOCK_ROWS, rank] f32) of
    N(0, 1/rank) factors for `side` (0 users, 1 items). Block b depends
    on (seed, side, b) alone, so the harness and the reference draw the
    same rows without either holding the whole matrix on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(key):
        return (jax.random.normal(key, (FACTOR_BLOCK_ROWS, rank), jnp.float32)
                * np.float32(1.0 / np.sqrt(rank)))

    base = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**31 - 1)),
                              side)
    for b, first in enumerate(range(0, n_rows, FACTOR_BLOCK_ROWS)):
        rows = min(FACTOR_BLOCK_ROWS, n_rows - first)
        yield first, block(jax.random.fold_in(base, b))[:rows]


def factors_host(n_rows: int, rank: int, seed: int, side: int) -> np.ndarray:
    """The whole factor matrix as a host array, made block by block on
    the device."""
    out = np.empty((n_rows, rank), np.float32)
    for first, blk in factor_blocks(n_rows, rank, seed, side):
        out[first:first + blk.shape[0]] = np.asarray(blk)
    return out
