"""The plain references that decide `correct`, and their comparisons.

Nothing here imports the program. Both references are straightforward
`jax.numpy` in float32 at `highest` matmul precision, computed in blocks
so that they fit beside nothing else on the chip: they run once the
window has closed, the peak memory has been read and the program's
state is freed.

`gather_bits` / `low_precision` compute the CONTROL: the same
reference in the nearest precision below the one the configuration
states (float8 gathers for bfloat16 ones; three-pass `high` products
for float32 at `highest`), which the comparison has to reject (PERF.md
section 2).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# padded entries per block of the ALS reference (gathered operand
# 2^21 x rank x 4 B = 512 MB at rank 64), and the shortest padded row
_ENTRIES = 1 << 21
_MIN_CAP = 256


# -- explicit ALS-WR --------------------------------------------------------

def als_init(n_users: int, n_items: int, rank: int, seed: int,
             user_present: np.ndarray, item_present: np.ndarray):
    """Starting factors as the configuration states them: row r of a side
    is |N(0, 1)| / sqrt(rank) drawn from fold_in(side_key, r), the side
    keys split from PRNGKey(seed); rows without a rating start (and
    stay) zero."""
    import jax
    ku, ki = jax.random.split(jax.random.PRNGKey(seed))

    def side(key, n_rows, present):
        rows = np.arange(max(n_rows, 1))
        block = jax.vmap(lambda r: jax.random.normal(
            jax.random.fold_in(key, r), (rank,)))(rows)
        x = np.abs(np.asarray(block)) / math.sqrt(rank)
        return np.where(present[:, None], x, 0.0).astype(np.float32)

    return side(ku, n_users, user_present), side(ki, n_items, item_present)


def _layout(row_ix: np.ndarray, col_ix: np.ndarray, val: np.ndarray,
            n_rows: int) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Rows grouped by padded length (256, 512, 1024, ...): per group
    (rows [nb*B] with n_rows as filler, idx [nb, B, L] with -1 as
    filler, val [nb, B, L]), B*L = _ENTRIES."""
    order = np.argsort(row_ix, kind="stable")
    counts = np.bincount(row_ix, minlength=n_rows).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    cols, vals = col_ix[order], val[order]
    level = np.ceil(np.log2(np.maximum(counts, 1) / _MIN_CAP))
    level = np.maximum(level, 0).astype(np.int64)
    groups = []
    for lv in np.unique(level[counts > 0]):
        cap = _MIN_CAP << int(lv)
        rows = np.nonzero((level == lv) & (counts > 0))[0]
        b = max(min(_ENTRIES // cap, -(-len(rows) // 8) * 8), 8)
        c = counts[rows]
        nb = -(-len(rows) // b)
        slot = np.repeat(np.arange(len(rows)), c)
        intra = np.arange(int(c.sum())) - np.repeat(np.cumsum(c) - c, c)
        src = np.repeat(starts[rows], c) + intra
        idx = np.full((nb * b, cap), -1, np.int32)
        v = np.zeros((nb * b, cap), np.float32)
        idx[slot, intra] = cols[src]
        v[slot, intra] = vals[src]
        rows_p = np.full(nb * b, n_rows, np.int32)
        rows_p[:len(rows)] = rows
        groups.append((rows_p, idx.reshape(nb, b, cap),
                       v.reshape(nb, b, cap)))
    return groups


def _spd_solve(a, b):
    """Gauss-Jordan elimination of a batch of SPD systems (no pivoting
    is needed: every A is a Gram matrix plus a positive diagonal)."""
    import jax
    import jax.numpy as jnp
    r = a.shape[-1]
    lanes = jnp.arange(r)

    def step(k, ab):
        a, b = ab
        piv = a[:, k, k]
        row = a[:, k, :] / piv[:, None]
        bk = b[:, k] / piv
        col = jnp.where(lanes == k, 0.0, a[:, :, k])
        a = a - col[:, :, None] * row[:, None, :]
        b = b - col * bk[:, None]
        keep = (lanes == k)
        a = jnp.where(keep[None, :, None], row[:, None, :], a)
        b = jnp.where(keep[None, :], bk[:, None], b)
        return a, b

    _, x = jax.lax.fori_loop(0, r, step, (a, b))
    return x


def round_mantissa(x, bits: int):
    """x rounded to `bits` explicit mantissa bits (7: bfloat16, 3:
    float8_e4m3), by arithmetic. Not by `astype`: on the TPU the
    compiler elided the float32 -> float8 -> float32 pair and the
    control read exactly 0 (my chip run, PR 24)."""
    import jax.numpy as jnp
    m, e = jnp.frexp(x)
    scale = float(1 << (bits + 1))
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def _half_step_fn(reg: float, gather_bits):
    import jax
    import jax.numpy as jnp

    def block(opp, blk):
        idx, val = blk
        msk = (idx >= 0).astype(jnp.float32)
        yg = opp[jnp.maximum(idx, 0)] * msk[..., None]
        hi = jax.lax.Precision.HIGHEST
        a = jnp.einsum("blr,bls->brs", yg, yg, precision=hi)
        b = jnp.einsum("blr,bl->br", yg, val * msk, precision=hi)
        n = msk.sum(axis=1)
        eye = jnp.eye(opp.shape[1], dtype=jnp.float32)
        a = a + (reg * n)[:, None, None] * eye
        a = jnp.where((n > 0)[:, None, None], a, eye)
        return jnp.where((n > 0)[:, None], _spd_solve(a, b), 0.0)

    @jax.jit
    def group(own, opp, rows, idx, val):
        if gather_bits is not None:
            opp = round_mantissa(opp, gather_bits)
        sol = jax.lax.map(lambda blk: block(opp, blk), (idx, val))
        return own.at[rows].set(sol.reshape(-1, sol.shape[-1]),
                                mode="drop")

    return group


def als_reference(u_ix, i_ix, val, n_users: int, n_items: int, *,
                  rank: int, iterations: int, reg: float, seed: int,
                  gather_bits=None, layouts=None, start=None):
    """Explicit ALS with MLlib's ALS-WR regularisation (lambda times the
    row's rating count), exact solves, `iterations` full alternations
    from `als_init`. Returns (X, Y) as host arrays.

    `gather_bits` rounds the gathered factors (the operand the
    configuration states in bfloat16) to that many mantissa bits: 3,
    float8_e4m3, is the control.
    `layouts` / `start` let a caller that runs several variants on one
    data set build the host-side layout and the init once."""
    import jax
    import jax.numpy as jnp
    if layouts is None:
        layouts = (_layout(u_ix, i_ix, val, n_users),
                   _layout(i_ix, u_ix, val, n_items))
    if start is None:
        start = als_init(
            n_users, n_items, rank, seed,
            np.bincount(u_ix, minlength=n_users) > 0,
            np.bincount(i_ix, minlength=n_items) > 0)
    sides = [[tuple(jnp.asarray(a) for a in g) for g in side]
             for side in layouts]
    x, y = jnp.asarray(start[0]), jnp.asarray(start[1])
    group = _half_step_fn(float(reg), gather_bits)
    for _ in range(iterations):
        for g in sides[0]:
            x = group(x, y, *g)
        for g in sides[1]:
            y = group(y, x, *g)
    out = np.asarray(x), np.asarray(y)
    del sides, x, y
    return out


def compare_factors(got: Tuple[np.ndarray, np.ndarray],
                    ref: Tuple[np.ndarray, np.ndarray],
                    u_ix, i_ix, val, seed: int,
                    sample: int = 1_000_000) -> Dict[str, float]:
    """The train cells' compared numbers, each a gap against the
    reference on a seeded sample of the ratings:

    pred_gap   rms(prediction - reference's prediction) over the rms of
               the reference's prediction
    rmse_gap   |train RMSE - reference's| over the reference's
    user_gap / item_gap   Frobenius gap of each factor matrix over the
               reference's norm
    """
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.integers(0, len(val), min(sample, len(val)))
    us, is_, vs = u_ix[pick], i_ix[pick], val[pick]

    def pred(f):
        return np.einsum("nr,nr->n", f[0][us].astype(np.float64),
                         f[1][is_].astype(np.float64))

    p, q = pred(got), pred(ref)
    rmse_p = math.sqrt(float(np.mean((p - vs) ** 2)))
    rmse_q = math.sqrt(float(np.mean((q - vs) ** 2)))

    def fro(a, b):
        return float(np.linalg.norm(a.astype(np.float64) - b)
                     / max(np.linalg.norm(b.astype(np.float64)), 1e-30))

    return {
        "pred_gap": float(np.sqrt(np.mean((p - q) ** 2))
                          / max(np.sqrt(np.mean(q ** 2)), 1e-30)),
        "rmse_gap": abs(rmse_p - rmse_q) / max(rmse_q, 1e-30),
        "user_gap": fro(got[0], ref[0]),
        "item_gap": fro(got[1], ref[1]),
    }


# -- top-k over a catalog ---------------------------------------------------

def topk_reference(blocks, vecs: np.ndarray, served_ids: np.ndarray,
                   k_ref: int, low_precision: Optional[str] = None
                   ) -> Dict[str, np.ndarray]:
    """One pass over the catalog's factor blocks for Q query vectors.

    Returns host arrays: `top_s`, `top_i` [Q, nb*k_ref], every block's
    k_ref best reference scores with their global ids, and `served_ref`
    [Q, k], the reference's score of each served id. With
    `low_precision` ("high": float32 operands in three bfloat16 passes,
    the step below `highest`; "bf16": bfloat16 operands, float32
    accumulation) also `lo_s`, `lo_i`, `ref_of_lo`: every block's best
    by that scoring of the same blocks, and the reference's score of
    those ids: the control."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one(blk, vecs, local):
        s = jnp.einsum("qr,nr->qn", vecs, blk,
                       precision=jax.lax.Precision.HIGHEST)
        inside = (local >= 0) & (local < blk.shape[0])
        served = jnp.where(inside, jnp.take_along_axis(
            s, jnp.clip(local, 0, blk.shape[0] - 1), axis=1), 0.0)
        out = dict(zip(("top_s", "top_i"), jax.lax.top_k(s, k_ref)),
                   served_ref=served)
        if low_precision == "high":
            lo = jnp.einsum("qr,nr->qn", vecs, blk,
                            precision=jax.lax.Precision.HIGH)
        elif low_precision == "bf16":
            lo = jnp.einsum("qr,nr->qn", vecs.astype(jnp.bfloat16),
                            blk.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        if low_precision:
            out["lo_s"], out["lo_i"] = jax.lax.top_k(lo, k_ref)
            out["ref_of_lo"] = jnp.take_along_axis(s, out["lo_i"], axis=1)
        return out

    vd = jnp.asarray(vecs, jnp.float32)
    ids = jnp.asarray(served_ids, jnp.int32)
    parts: Dict[str, list] = {}
    for first, blk in blocks:
        if blk.shape[0] < k_ref:
            raise ValueError("a factor block is smaller than k_ref")
        for name, arr in one(blk, vd, ids - first).items():
            arr = np.asarray(arr)
            parts.setdefault(name, []).append(
                arr + first if name.endswith("_i") else arr)
    out = {name: np.concatenate(p, 1) for name, p in parts.items()
           if name != "served_ref"}
    out["served_ref"] = np.sum(parts["served_ref"], axis=0, dtype=np.float64)
    return out


def compare_replies(replies: Sequence[Dict], top_s, top_i, served_ref,
                    k: int) -> Dict[str, float]:
    """The serve cells' compared numbers over the sampled replies.

    `replies[q]` = {"ids": [...], "scores": [...], "banned": [...]}.
    (top_s, top_i) are the reference's candidates, `served_ref[q]` the
    reference's score of each served id.

    rank_gap    widest gap by which the reference's score of a served
                item lies below the reference's j-th best allowed item,
                over that query's best score
    score_err   widest |served score - reference's score of that item|,
                over that query's best score
    banned_served   served items that the request had banned (exact)
    short_replies   replies without exactly k items (exact)
    """
    rank_gap = score_err = 0.0
    banned_served = short = 0
    for q, rep in enumerate(replies):
        banned = set(rep["banned"])
        banned_served += sum(1 for i in rep["ids"] if i in banned)
        if len(rep["ids"]) != k:
            short += 1
            continue
        keep = np.array([i not in banned for i in top_i[q]])
        best = np.sort(top_s[q][keep])[::-1][:k]
        scale = max(float(best[0]), 1e-30)
        rank_gap = max(rank_gap,
                       float(np.max(best - served_ref[q])) / scale)
        score_err = max(score_err, float(np.max(np.abs(
            np.asarray(rep["scores"], np.float64) - served_ref[q])))
            / scale)
    return {"rank_gap": rank_gap, "score_err": score_err,
            "banned_served": float(banned_served),
            "short_replies": float(short)}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """`correct` and the compared numbers beside their limits. A number
    with no limit in the cell's file is printed and not compared."""
    compared = {name: {"value": float(v), "limit": limits.get(name)}
                for name, v in numbers.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in compared.values() if c["limit"] is not None)
    return ok, compared
