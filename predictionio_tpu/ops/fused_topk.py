"""Single-launch fused serve kernel: matmul -> ban-mask -> top-k.

The AOT serving plans in `ops/topk.py` run the banned-index hot path as
an XLA chain: a full [b, n_items] score matrix is materialized in HBM,
a scatter stamps NEG_INF over the banned columns, and `lax.top_k` sorts
every row. This module collapses the chain into ONE Pallas launch per
batch bucket:

  - the item catalog streams through VMEM in tiles of
    `PIO_FUSED_TILE_ITEMS` items (grid over item tiles; the full score
    matrix never exists in HBM), read from HBM in the layout it lies in
    (below), and a grid step walks its tile in gate sub-blocks of
    `_SUB_ITEMS` items;
  - each sub-block's scores are computed on the MXU
    (`preferred_element_type=f32`, `Precision.HIGHEST` — identical math
    to the XLA chain) and catalog-padding items are masked to NEG_INF;
  - THE GATE: a running (score, id) scoreboard is carried in the output
    blocks, sorted best first, so its column k-1 is each row's k-th
    best score so far. A sub-block can change a row's top-k only if one
    of its scores is STRICTLY greater than that: sub-blocks come in id
    order and the scoreboard holds only lower ids, so an equal score
    loses `lax.top_k`'s lowest-index-first tie-break to every one of
    the k entries already there. The kernel therefore reduces
    `scores > scoreboard[:, k-1]` to one scalar and runs the ban mask
    and the merge only under it; a sub-block that cannot change the
    answer costs one product, one compare and one reduction. The
    threshold starts at the removed-entry sentinel -inf (below), so a
    row merges every sub-block until it holds k entries, banned items
    at NEG_INF included (the oracle emits those too when fewer than k
    items are allowed). The compare reads the scores BEFORE bans: a
    banned item above the threshold only opens a merge that was not
    needed;
  - inside a merge, banned GLOBAL ids are masked by comparison against
    the sub-block's ids (the `n_items` filler never matches a real id)
    — only where some row bans an id inside the sub-block's range, which
    is the same result — and then k selection steps over scoreboard +
    sub-block pick by an explicit (max score, lowest id) key — exactly
    `lax.top_k`'s documented lowest-index-first tie-break, so the fused
    outputs are BIT-IDENTICAL to the `_topk_scores_banned` oracle
    whenever the per-cell dot products are (always true for the
    integer-valued factors the parity tests use; real factors agree to
    the last ulp of the two matmuls). Removed scoreboard entries are
    parked at -inf, strictly below the NEG_INF ban value, so a banned
    item can be emitted (matching the oracle) but never emitted twice;
  - a third output, one int32 in SMEM, counts the sub-blocks whose
    merge ran. The plans fetch it with the results and observe
    `merged / gate_blocks()` into `pio_topk_merge_share`, once a call.

What the gate buys depends on the catalog's order, which the kernel
observes and no option states: with scores independent of the id,
sub-block t of a row opens with probability about k / t, and a batch
of b rows merges about b·k·(1 + ln(n_blocks / (b·k))) sub-blocks (14%
at 45 rows of 12M items). WORST CASE: a catalog whose scores rise with
the id opens every sub-block; the kernel then does all the work it did
before the gate, plus the gate's compare and reduction and the ban
range's in every sub-block.

Everything the kernel touches is laid out for Mosaic's (8 sublane, 128
lane) vector tiles: the batch is padded to a multiple of 8 rows, the
scoreboard is `_round_up(k, 128)` lanes wide (slots past k stay parked
at the sentinels), the sub-block is a multiple of 128, so the
scoreboard/sub-block concatenation is lane-aligned, and the ban block
arrives twice: as [b, W] rows for the range test and as [W, b, 1] so
each banned id is read as a [b, 1] column by a leading-dim index. The
jitted wrapper pads the query and ban blocks (a few KB) and slices
`[bucket, k]` back out; it never moves the catalog.

THE CATALOG'S ORIENTATION is read off its shape (`_items_on_lanes`), in
one place, and no option states it. The TPU compiler keeps a
`[n_items, rank]` float32 array whose rows are not whole 128-lane
groups (rank 64, and every rank of the ALS templates) with the items on
the lanes, `{0,1:T(8,128)}`, so that nothing is padded. Blocks of
`(tile, rank)` rows ask for the other layout, and the compiler then
transposes the whole catalog into rows padded to 128 lanes before every
call: at 12,047,500 x 64 a 6.17 GB temporary written and read for a
3.08 GB catalog, 14.4 ms of a 31.8 ms call, and the reason the
24,095,000-row catalog did not compile on one chip (PERF.md section 6,
PR 28). So for such a rank the kernel takes `(rank, tile)` blocks of
`factors.T` — the same bytes under another shape, a bitcast in the
compiled call — and each sub-block's product is a plain `[b, rank] x
[rank, sub]`. Rows of whole lane groups (a sequence model's head:
19,072 x 4,096) lie rows first, `{1,0:T(8,128)}`; there `(tile, rank)`
blocks are the copy-free form and the transpose would be the copy
(measured: 2.04 ms a call against 2.89). Either way the compiled call
holds no operation that moves the catalog, which the plans publish as
`pio_serve_plan_temp_bytes` and `tests/test_backbone_compile.py` holds
for a described v5e. Everything after the product sees the same
`[b, sub]` scores in both orientations. The last tile's out-of-bounds
part (along the lanes or along the rows) holds whatever the DMA left
there; the padding select covers it.

`PIO_SERVE_FUSED` selects the kernel:

  auto  (default) fuse on TPU backends; CPU/GPU keep the XLA chain;
  on              fuse everywhere; non-TPU backends run the kernel in
                  Pallas interpret mode (traced to plain XLA ops — the
                  parity tests exercise exactly this);
  off             never fuse.

A bucket that is to be fused either compiles or fails the warm-up: no
builder here catches a lowering or compile error. The compiled
executable keeps the exact `(vecs, factors, banned)` positional
signature of the chain it replaces, so `swap_factors` hot-swaps and the
zero-recompile steady state are preserved unchanged. Nothing is
donated: the wrapper pads its inputs, so their buffers cannot back the
outputs.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.topk import NEG_INF

# items per DMA tile in VMEM, and items per gate sub-block inside it:
# what one product, one compare and, where it holds a candidate, one
# merge cover (both rounded up to whole 128-lane groups, the sub-block
# to at least k so every merge sees >= k real candidates). Set from
# chip measurements at 12,047,500 x 64 (PERF.md section 6, PR 26): tile
# 512 -> 4096 is 4% of a call and 8192 no more; sub-block 512 / 1024 /
# 2048 read 37.0 / 33.7 / 33.8 ms at 45 rows
DEFAULT_TILE_ITEMS = 4096
_SUB_ITEMS = 1024
# most bytes of one catalog tile in VMEM (float32 rows; two in flight)
_TILE_BYTES = 4 * 1024 * 1024

_LANES = 128
_SUBLANES = 8

# scoreboard sentinels: removed entries park BELOW the NEG_INF ban
# value so they are never re-picked; filler ids park ABOVE every real
# id so the lowest-id tie-break prefers any real item
_REMOVED = np.float32(-np.inf)
_FILLER_ID = np.int32(2**31 - 1)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def fused_mode() -> str:
    """Normalized PIO_SERVE_FUSED: "auto" | "on" | "off"."""
    raw = (os.environ.get("PIO_SERVE_FUSED", "auto") or "auto").lower()
    if raw in ("off", "0", "false", "no"):
        return "off"
    if raw in ("on", "1", "true", "yes"):
        return "on"
    return "auto"


def fused_wanted() -> bool:
    """Whether serve plans should attempt the fused kernel at warmup."""
    mode = fused_mode()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return jax.default_backend() == "tpu"


def interpreted() -> bool:
    """Pallas interpret mode (kernel traced to plain XLA) everywhere
    except real TPU backends, where Mosaic compiles it natively."""
    return jax.default_backend() != "tpu"


def _tile_items(n_rows: int, k: int, rank: int) -> tuple:
    """(DMA tile, gate sub-block) for a catalog of `n_rows`, both in
    items: the sub-block is whole 128-lane groups and holds at least k
    items, the tile is a whole number of sub-blocks, and a catalog
    smaller than the tile is one grid step of its own sub-blocks. A
    tile is double-buffered in VMEM, so one of wide rows (a sequence
    model's head: rank 4,096) is cut to the whole lane groups that
    fit `_TILE_BYTES` (rank 2,688: 384 items; the 512 above them ran a
    bucket of 32 out of fast memory); at rank 64 the cut is far above
    the default tile and changes nothing."""
    tile = int(os.environ.get("PIO_FUSED_TILE_ITEMS", "0") or 0)
    if tile <= 0:
        tile = DEFAULT_TILE_ITEMS
    tile = min(tile, max(_LANES,
                         _TILE_BYTES // (4 * rank) // _LANES * _LANES))
    tile = _round_up(max(tile, k), _LANES)
    sub = min(tile, _round_up(max(_SUB_ITEMS, k), _LANES))
    return min(_round_up(tile, sub), _round_up(n_rows, sub)), sub


def _items_on_lanes(rank: int) -> bool:
    """Whether the catalog's copy-free blocks are `(rank, tile)` blocks
    of its transpose (rows that are not whole 128-lane groups: the
    compiler keeps the items on the lanes) or `(tile, rank)` blocks of
    the array as it is. Module docstring, THE CATALOG'S ORIENTATION."""
    return rank % _LANES != 0


def _any(mask) -> jax.Array:
    """Scalar: whether any cell of a 2-D boolean block is set."""
    return jnp.max(jnp.where(mask, np.int32(1), np.int32(0))) > 0


def _merge(base, scores, banrow_ref, ban_ref, out_s_ref, out_i_ref, *,
           k: int, n_banned: int) -> None:
    """Merge the [b, sub] scores of the sub-block that starts at item
    `base` into the scoreboard: bans first, then the k-step
    selection."""
    b, sub = scores.shape
    board = out_s_ref.shape[1]
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, (b, sub), 1)

    def ban_body(w, sc):
        # ban_ref is [W, b, 1]: a leading-dim index yields the w-th
        # banned id of every row as a [b, 1] column
        return jnp.where(ban_ref[w] == gidx, np.float32(NEG_INF), sc)

    def ban_all(sc):
        return jax.lax.fori_loop(0, n_banned, ban_body, sc)

    # the W compare-selects over the whole block are two thirds of a
    # merge, and a ban list names a few ids of millions: run them only
    # where some row bans an id inside this sub-block's range (an id
    # outside it equals no cell of `gidx`, so skipping changes nothing)
    ids = banrow_ref[...]
    near = (ids >= base) & (ids < base + sub)
    scores = jax.lax.cond(_any(near), ban_all, lambda sc: sc, scores)

    # k-step selection over scoreboard + sub-block with the explicit
    # (max score, lowest id) key of lax.top_k
    comb_s = jnp.concatenate([out_s_ref[...], scores], axis=1)
    comb_i = jnp.concatenate([out_i_ref[...], gidx], axis=1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (b, board), 1)

    def step(j, carry):
        cs, outs, outi = carry
        m = jnp.max(cs, axis=1, keepdims=True)
        is_m = cs == m
        pick = jnp.min(jnp.where(is_m, comb_i, _FILLER_ID),
                       axis=1, keepdims=True)
        cs = jnp.where(is_m & (comb_i == pick), _REMOVED, cs)
        outs = jnp.where(kcol == j, m, outs)
        outi = jnp.where(kcol == j, pick, outi)
        return cs, outs, outi

    _, outs, outi = jax.lax.fori_loop(
        0, k, step, (comb_s,
                     jnp.full((b, board), _REMOVED, jnp.float32),
                     jnp.full((b, board), _FILLER_ID, jnp.int32)))
    out_s_ref[...] = outs
    out_i_ref[...] = outi


def _merge_body(n_valid, t, vecs_ref, fac_ref, banrow_ref, ban_ref,
                out_s_ref, out_i_ref, cnt_ref, *, k: int, tile: int,
                sub: int, n_banned: int, lanes: bool) -> None:
    """One grid step: score this item tile sub-block by sub-block, and
    merge into the running scoreboard carried by the output blocks the
    sub-blocks that hold a score above some row's k-th best."""
    b, board = out_s_ref.shape

    @pl.when(t == 0)
    def _init():
        out_s_ref[...] = jnp.full((b, board), _REMOVED, jnp.float32)
        out_i_ref[...] = jnp.full((b, board), _FILLER_ID, jnp.int32)
        cnt_ref[0, 0] = np.int32(0)

    vecs = vecs_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, sub), 1)

    def sub_block(s, carry):
        base = t * tile + s * sub
        at = pl.ds(pl.multiple_of(s * sub, sub), sub)
        # [b, sub] scores — same contraction/precision as the chain,
        # over a [rank, sub] block (items on the lanes) or a [sub, rank]
        # one (`_items_on_lanes`)
        fac, over = ((fac_ref[:, at], 0) if lanes
                     else (fac_ref[at, :], 1))
        scores = jax.lax.dot_general(
            vecs, fac, (((1,), (over,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        # items past n_valid are catalog padding or the out-of-bounds
        # part of the last tile (whatever the DMA left there): select,
        # never add
        scores = jnp.where(lane < n_valid - base, scores,
                           np.float32(NEG_INF))

        # the gate: strictly above the row's k-th best, or nothing in
        # this sub-block can enter that row's top-k
        @pl.when(_any(scores > out_s_ref[:, k - 1:k]))
        def _hit():
            cnt_ref[0, 0] += np.int32(1)
            _merge(base, scores, banrow_ref, ban_ref, out_s_ref,
                   out_i_ref, k=k, n_banned=n_banned)

        return carry

    jax.lax.fori_loop(0, tile // sub, sub_block, 0)


def _kernel_static(*refs, n_valid: int, **static) -> None:
    """Single-device form: the valid-row bound is the static catalog
    size baked into the trace."""
    _merge_body(n_valid, pl.program_id(0), *refs, **static)


def _kernel_dynamic(nv_ref, *refs, **static) -> None:
    """Sharded form: each shard's valid-row bound depends on its mesh
    position, so it arrives as a scalar operand (SMEM on TPU)."""
    _merge_body(nv_ref[0], pl.program_id(0), *refs, **static)


def gate_blocks(n_rows: int, k: int, rank: int) -> int:
    """How many sub-blocks the gate judges in one call over `n_rows`
    catalog rows: the base of the merge counter's share."""
    tile, sub = _tile_items(n_rows, k, rank)
    return -(-n_rows // tile) * (tile // sub)


def _pallas_topk(n_rows: int, rank: int, *, k: int, bucket: int,
                 banned_width: int, n_valid: Optional[int],
                 vma=frozenset()):
    """The fused callable for one bucket: `(vecs [bucket, rank], factors
    [n_rows, rank], banned [bucket, W]) -> (scores [bucket, k], ids
    [bucket, k], merged [] i32)`, `merged` the number of sub-blocks
    whose merge ran. With `n_valid` set the bound is static
    (single-device); with `n_valid=None` the callable takes a leading
    [1] int32 bound operand (per-shard form, SMEM on TPU). `vma` names
    the mesh axes the outputs vary over when the call sits inside a
    shard_map."""
    interpret = interpreted()
    tile, sub = _tile_items(n_rows, k, rank)
    lanes = _items_on_lanes(rank)
    nt = -(-n_rows // tile)
    rows = _round_up(bucket, _SUBLANES)
    board = _round_up(k, _LANES)
    smem = pl.BlockSpec(memory_space=None if interpret else pltpu.SMEM)
    specs = [pl.BlockSpec((rows, rank), lambda i: (0, 0)),
             (pl.BlockSpec((rank, tile), lambda i: (0, i)) if lanes
              else pl.BlockSpec((tile, rank), lambda i: (i, 0))),
             pl.BlockSpec((rows, banned_width), lambda i: (0, 0)),
             pl.BlockSpec((banned_width, rows, 1), lambda i: (0, 0, 0))]
    static = dict(k=k, tile=tile, sub=sub, n_banned=banned_width,
                  lanes=lanes)
    if n_valid is None:
        kern = functools.partial(_kernel_dynamic, **static)
        specs = [smem] + specs
    else:
        kern = functools.partial(_kernel_static, n_valid=n_valid, **static)
    call = pl.pallas_call(
        kern,
        grid=(nt,),
        in_specs=specs,
        out_specs=(pl.BlockSpec((rows, board), lambda i: (0, 0)),
                   pl.BlockSpec((rows, board), lambda i: (0, 0)),
                   smem),
        out_shape=(
            jax.ShapeDtypeStruct((rows, board), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((rows, board), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((1, 1), jnp.int32, vma=vma)),
        # the scoreboard is carried from tile to tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)

    def fn(*operands):
        *bound, vecs, factors, banned = operands
        pad = ((0, rows - bucket), (0, 0))
        # padded rows: zero vectors, bans that match no id. The ban
        # block goes in as [W, rows, 1] so the kernel reads one banned
        # id per row with a leading-dim index (no lane slicing)
        ban_rows = jnp.pad(banned, pad, constant_values=-1)
        # the transpose of a catalog that lies items-on-lanes is a
        # bitcast: the compiled call moves nothing
        out_s, out_i, merged = call(*bound, jnp.pad(vecs, pad),
                                    factors.T if lanes else factors,
                                    ban_rows, ban_rows.T[..., None])
        return out_s[:bucket, :k], out_i[:bucket, :k], merged[0, 0]

    return fn


def maybe_build_bucket(factors, *, n_items: int, rank: int, k: int,
                       bucket: int, banned_width: int):
    """AOT-lower/compile the fused executable for one batch bucket
    against the resident `factors`, behind the PIO_SERVE_FUSED gate:
    None when fusion is off for this backend (the caller compiles the
    XLA chain for that bucket); a kernel that is wanted and does not
    lower or compile raises. The compiled signature is `(vecs [bucket,
    rank] f32, factors, banned [bucket, W] i32)` — positionally
    identical to the XLA chain it replaces, so `swap_factors` keeps
    working with zero recompiles."""
    if not fused_wanted():
        return None
    call = _pallas_topk(n_items, rank, k=k, bucket=bucket,
                        banned_width=banned_width, n_valid=n_items)
    vec_spec = jax.ShapeDtypeStruct((bucket, rank), np.float32)
    ban_spec = jax.ShapeDtypeStruct((bucket, banned_width), np.int32)
    return jax.jit(call).lower(vec_spec, factors, ban_spec).compile()


def shard_local_candidates(per_shard: int, rank: int, *, k: int,
                           bucket: int, banned_width: int, axis: str):
    """The per-shard fused local-candidate program for
    `ShardedBucketedTopK`: `(n_valid [1] i32, vecs, factors_local
    [per_shard, rank], banned_local [bucket, W] i32) -> (scores
    [bucket, k], LOCAL ids [bucket, k])`, for use inside a shard_map
    over mesh axis `axis` (ban translation to local ids and the merge
    stay with the caller).
    None when fusion is off; a lowering or compile failure surfaces
    when the enclosing program compiles."""
    if not fused_wanted():
        return None
    return _pallas_topk(per_shard, rank, k=k, bucket=bucket,
                        banned_width=banned_width, n_valid=None,
                        vma=frozenset({axis}))
