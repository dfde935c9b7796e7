"""Single-launch fused serve kernel: matmul -> ban-mask -> top-k.

The AOT serving plans in `ops/topk.py` run the banned-index hot path as
an XLA chain: a full [b, n_items] score matrix is materialized in HBM,
a scatter stamps NEG_INF over the banned columns, and `lax.top_k` sorts
every row. This module collapses the chain into ONE Pallas launch per
batch bucket:

  - the item catalog streams through VMEM in `PIO_FUSED_TILE_ITEMS`-row
    tiles (grid over item tiles; the full score matrix never exists in
    HBM);
  - each tile's scores are computed on the MXU
    (`preferred_element_type=f32`, `Precision.HIGHEST` — identical math
    to the XLA chain), banned GLOBAL ids are masked by comparison
    against the tile's id range (the `n_items` filler never matches a
    real id), catalog-padding rows are masked to NEG_INF;
  - a running (score, id) scoreboard carried in the output blocks
    merges each tile via k selection steps with an explicit
    (max score, lowest id) key — exactly `lax.top_k`'s documented
    lowest-index-first tie-break, so the fused outputs are
    BIT-IDENTICAL to the `_topk_scores_banned` oracle whenever the
    per-cell dot products are (always true for the integer-valued
    factors the parity tests use; real factors agree to the last ulp
    of the two matmuls). Removed scoreboard entries are parked at
    -inf, strictly below the NEG_INF ban value, so a banned item can
    be emitted (matching the oracle) but never emitted twice.

Everything the kernel touches is laid out for Mosaic's (8 sublane, 128
lane) vector tiles: the batch is padded to a multiple of 8 rows, the
scoreboard is `_round_up(k, 128)` lanes wide (slots past k stay parked
at the sentinels), the item tile is a multiple of 128, so the
scoreboard/tile concatenation is lane-aligned, and the ban block
arrives as [W, b, 1] so each banned id is read as a [b, 1] column by a
leading-dim index. The jitted wrapper pads and re-lays the inputs and
slices `[bucket, k]` back out.

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

# items per VMEM tile (rounded up to whole 128-lane groups, and to k so
# every merge sees >= k real candidates and the scoreboard fillers can
# never leak into results)
DEFAULT_TILE_ITEMS = 512

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


def _tile_items(k: int) -> int:
    tile = int(os.environ.get("PIO_FUSED_TILE_ITEMS", "0") or 0)
    if tile <= 0:
        tile = DEFAULT_TILE_ITEMS
    return _round_up(max(tile, k), _LANES)


def _merge_body(n_valid, t, vecs_ref, fac_ref, ban_ref,
                out_s_ref, out_i_ref, *, k: int, tile: int,
                n_banned: int) -> None:
    """One grid step: score this item tile, mask bans/padding, merge
    into the running scoreboard carried by the output blocks."""
    b, board = out_s_ref.shape

    @pl.when(t == 0)
    def _init():
        out_s_ref[...] = jnp.full((b, board), _REMOVED, jnp.float32)
        out_i_ref[...] = jnp.full((b, board), _FILLER_ID, jnp.int32)

    # [b, tile] tile scores — same contraction/precision as the chain
    scores = jax.lax.dot_general(
        vecs_ref[...], fac_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    gidx = t * tile + jax.lax.broadcasted_iota(jnp.int32, (b, tile), 1)
    # rows past n_valid are catalog padding or the out-of-bounds part
    # of the last tile (whatever the DMA left there): select, never add
    scores = jnp.where(gidx < n_valid, scores, np.float32(NEG_INF))

    def ban_body(w, sc):
        # ban_ref is [W, b, 1]: a leading-dim index yields the w-th
        # banned id of every row as a [b, 1] column
        return jnp.where(ban_ref[w] == gidx, np.float32(NEG_INF), sc)

    scores = jax.lax.fori_loop(0, n_banned, ban_body, scores)

    # k-step selection over scoreboard + tile with the explicit
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


def _kernel_static(vecs_ref, fac_ref, ban_ref, out_s_ref, out_i_ref, *,
                   n_valid: int, k: int, tile: int,
                   n_banned: int) -> None:
    """Single-device form: the valid-row bound is the static catalog
    size baked into the trace."""
    _merge_body(n_valid, pl.program_id(0), vecs_ref, fac_ref, ban_ref,
                out_s_ref, out_i_ref, k=k, tile=tile, n_banned=n_banned)


def _kernel_dynamic(nv_ref, vecs_ref, fac_ref, ban_ref, out_s_ref,
                    out_i_ref, *, k: int, tile: int,
                    n_banned: int) -> None:
    """Sharded form: each shard's valid-row bound depends on its mesh
    position, so it arrives as a scalar operand (SMEM on TPU)."""
    _merge_body(nv_ref[0], pl.program_id(0), vecs_ref, fac_ref, ban_ref,
                out_s_ref, out_i_ref, k=k, tile=tile, n_banned=n_banned)


def _pallas_topk(n_rows: int, rank: int, *, k: int, bucket: int,
                 banned_width: int, n_valid: Optional[int],
                 vma=frozenset()):
    """The fused callable for one bucket: `(vecs [bucket, rank], factors
    [n_rows, rank], banned [bucket, W]) -> (scores, ids) [bucket, k]`.
    With `n_valid` set the bound is static (single-device); with
    `n_valid=None` the callable takes a leading [1] int32 bound operand
    (per-shard form, SMEM on TPU). `vma` names the mesh axes the
    outputs vary over when the call sits inside a shard_map."""
    interpret = interpreted()
    tile = _tile_items(k)
    nt = -(-n_rows // tile)
    rows = _round_up(bucket, _SUBLANES)
    board = _round_up(k, _LANES)
    specs = [pl.BlockSpec((rows, rank), lambda i: (0, 0)),
             pl.BlockSpec((tile, rank), lambda i: (i, 0)),
             pl.BlockSpec((banned_width, rows, 1), lambda i: (0, 0, 0))]
    if n_valid is None:
        kern = functools.partial(_kernel_dynamic, k=k, tile=tile,
                                 n_banned=banned_width)
        specs = [pl.BlockSpec(memory_space=None if interpret
                              else pltpu.SMEM)] + specs
    else:
        kern = functools.partial(_kernel_static, n_valid=n_valid, k=k,
                                 tile=tile, n_banned=banned_width)
    call = pl.pallas_call(
        kern,
        grid=(nt,),
        in_specs=specs,
        out_specs=(pl.BlockSpec((rows, board), lambda i: (0, 0)),
                   pl.BlockSpec((rows, board), lambda i: (0, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((rows, board), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((rows, board), jnp.int32, vma=vma)),
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
        ban_cols = jnp.pad(banned, pad, constant_values=-1).T[..., None]
        out_s, out_i = call(*bound, jnp.pad(vecs, pad), factors, ban_cols)
        return out_s[:bucket, :k], out_i[:bucket, :k]

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
