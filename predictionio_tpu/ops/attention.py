"""Blockwise ring attention: sequence-parallel attention over a mesh.

The round mandate makes long-context a first-class capability: sequences
too long for one device's HBM shard over a mesh axis, and attention runs
as a RING — each device computes its local queries against the
circulating key/value block while `ppermute` rotates K/V around the ICI
ring, accumulating the softmax in streaming (flash) form, so the full
[S, S] score matrix never materializes and no device ever holds more
than its 1/p sequence slice of K/V (Liu et al., "Ring Attention with
Blockwise Transformers", 2023 — reimplemented here from the paper's
recurrence, not ported code).

The reference framework has no attention at all (its models are
ALS/MLlib-era); this op backs the sequential recommender
(`models/seqrec.py`), the post-ALS architecture its templates graduate
to, the same way `ops/twotower.py` backs BASELINE config 5.

TPU notes:
  - the per-step einsums are [B*Sq, Dh] x [Dh, Skv] matmuls — MXU work;
    the streaming-softmax rescale fuses into their epilogues.
  - the K/V rotation is one `ppermute` per ring step: p-1 hops of
    S/p-sized blocks over ICI, overlapping compute on real multi-chip
    topologies (XLA schedules the collective ahead of the next block's
    matmul).
  - autodiff works through shard_map + ppermute (the transpose of a
    ring rotation is the reverse rotation), so the same primitive
    serves training.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def attention_reference(q, k, v, *, causal: bool = False, kv_mask=None,
                        window=None, sink=None, segment_ids=None):
    """Plain softmax attention, q [B, S, H, Dq], k [B, S, Hkv, Dq],
    v [B, S, Hkv, Dv] -> [B, S, H, Dv] — the oracle the ring and the
    packed implementations are tested against (and the single-device
    path when no mesh axis shards the sequence). The [S, S] scores are
    formed whole.

    `kv_mask` [B, S] bool marks VALID key positions (False = padding
    slot that must not receive attention). Query head h reads KV head
    h // (H / Hkv). `window` w keeps only keys with 0 <= t - s < w;
    `segment_ids` [B, S] keeps a query to the keys of its own segment
    (several histories packed on one axis); `sink` [H] is one learned
    score a head that joins the softmax's denominator and carries no
    value."""
    H, Hkv = q.shape[2], k.shape[2]
    if H != Hkv:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    S = q.shape[1]
    mask = None

    def both(m, new):
        return new if m is None else (m & new)

    if causal or window is not None:
        gap = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        ok = gap >= 0
        if window is not None:
            ok = ok & (gap < window)
        mask = ok[None, None]
    if kv_mask is not None:
        mask = both(mask, kv_mask[:, None, None, :])
    if segment_ids is not None:
        mask = both(mask, (segment_ids[:, :, None]
                           == segment_ids[:, None, :])[:, None])
    if mask is None and sink is None:
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(s, axis=-1), v)
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m = s.max(axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink[None, :, None, None])
    p = jnp.exp(s - m)
    if mask is not None:
        # a fully-masked row (a padding query with no visible key)
        # would read uniform; zero it with the COMBINED mask so the
        # dead row is exactly 0, matching the streaming path
        p = jnp.where(mask, p, 0.0)
    den = p.sum(axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink[None, :, None, None] - m)
    p = p / jnp.where(den > 0, den, 1.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _stream_block(carry, k_blk, v_blk, kv_ok, q, q_pos, k_pos, scale,
                  causal: bool):
    """One flash-softmax accumulation step against a circulated block.
    carry = (m [B,H,Sq], num [B,Sq,H,Dh], den [B,H,Sq]); kv_ok
    [B, Skv] bool marks valid (non-padding) key slots of the block."""
    m, num, den = carry
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale   # [B,H,Sq,Skv]
    mask = kv_ok[:, None, None, :]                        # [B,1,1,Skv]
    if causal:
        mask = mask & (q_pos[:, None] >= k_pos[None, :])[None, None]
    s = jnp.where(mask, s, _NEG)
    m_blk = s.max(axis=-1)                                # [B,H,Sq]
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    # a fully-masked row would otherwise read exp(_NEG - _NEG) = 1
    p = jnp.where(mask, p, 0.0)
    num = num * alpha.transpose(0, 2, 1)[..., None] \
        + jnp.einsum("bhqk,bkhd->bqhd", p, v_blk)
    den = den * alpha + p.sum(axis=-1)
    return m_new, num, den


def _ring_attention_local(q, k, v, kv_mask, *, causal: bool, axis: str,
                          n_shards: int):
    """shard_map body: local [B, S/p, H, Dh] blocks; K/V (and their
    validity mask) circulate."""
    idx = jax.lax.axis_index(axis)
    s_loc = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    iota = jnp.arange(s_loc)
    q_pos = idx * s_loc + iota
    # accumulators derive from q so they carry q's varying-device type
    # (a plain constant init trips shard_map's scan carry check)
    zero_bhq = q[..., 0].transpose(0, 2, 1) * 0.0        # [B,H,Sq]
    init = (zero_bhq + _NEG, jnp.zeros_like(q), zero_bhq)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def step(carry, srcstep):
        acc, k_blk, v_blk, ok_blk = carry
        kv_owner = (idx - srcstep) % n_shards
        k_pos = kv_owner * s_loc + iota
        acc = _stream_block(acc, k_blk, v_blk, ok_blk, q, q_pos, k_pos,
                            scale, causal)
        # rotate AFTER consuming: device i's block moves to i+1, so next
        # step sees the block of (owner - 1) — one hop per step, p-1
        # total (the last rotation's result is unused but keeps the scan
        # body uniform; XLA drops the dead final permute pair)
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        ok_blk = jax.lax.ppermute(ok_blk, axis, perm)
        return (acc, k_blk, v_blk, ok_blk), None

    (acc, _, _, _), _ = jax.lax.scan(
        step, (init, k, v, kv_mask), jnp.arange(n_shards))
    m, num, den = acc
    # dead rows (a padding query with no visible key) have num = 0 and
    # den = 0: divide by a where'd 1, not max(den, eps) — eps makes the
    # BACKWARD pass scale upstream gradients by 1/eps and the training
    # step NaNs out
    den_safe = jnp.where(den > 0, den, 1.0)
    return num / den_safe.transpose(0, 2, 1)[..., None]


def ring_attention(q, k, v, mesh, *, axis: str = "sp",
                   batch_axis: str = "data", causal: bool = False,
                   kv_mask=None, window=None, sink=None):
    """Sequence-parallel attention: [B, S, H, Dh] inputs whose S
    dimension shards over `mesh` axis `axis` — and whose BATCH shards
    over `batch_axis` when the mesh has one (without it, a dp x sp mesh
    would all-gather the batch and replicate attention across every
    data group). Equivalent (up to float association) to
    `attention_reference`; with a trivial axis (size 1 or absent) it
    falls through to the reference path. `kv_mask` [B, S] bool marks
    valid key positions (False = padding). `window`, `sink`, grouped KV
    heads and a value size of its own are the plain path's only: the
    ring circulates whole blocks of one head count."""
    from jax.sharding import PartitionSpec as P

    if mesh is None or axis not in mesh.shape or mesh.shape[axis] == 1:
        return attention_reference(q, k, v, causal=causal,
                                   kv_mask=kv_mask, window=window,
                                   sink=sink)
    if (window is not None or sink is not None
            or q.shape[2:] != k.shape[2:] or k.shape != v.shape):
        raise NotImplementedError(
            "ring attention carries no window, sink or grouped KV heads")
    n_shards = int(mesh.shape[axis])
    if q.shape[1] % n_shards:
        raise ValueError(
            f"sequence length {q.shape[1]} must divide over "
            f"{n_shards} '{axis}' shards")
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:2], bool)
    body = partial(_ring_attention_local, causal=causal, axis=axis,
                   n_shards=n_shards)
    b = batch_axis if (batch_axis in mesh.shape
                       and q.shape[0] % mesh.shape[batch_axis] == 0) \
        else None
    spec = P(b, axis, None, None)
    mspec = P(b, axis)
    return jax.shard_map(body, mesh=mesh,
                            in_specs=(spec, spec, spec, mspec),
                            out_specs=spec)(q, k, v, kv_mask)


# ---------------------------------------------------------------------------
# Packed attention: several histories on one token axis, blocked.
# ---------------------------------------------------------------------------

def _packed_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, segq_ref, segk_ref,
                   sink_ref, o_ref, m_sc, l_sc, acc_sc, *, tq: int,
                   tk: int, window, scale: float, n_steps: int):
    """One (KV head, query block, key step) of `packed_attention`. The
    query block holds the G query heads of this KV head, head-major:
    row r is head r // tq, token i * tq + r % tq."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    kb = lo_ref[i] + j

    @pl.when(kb <= hi_ref[i])
    def _step():
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        rows = q.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [R, tk]
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 0)
        gap = (i * tq + jax.lax.rem(r, tq)) - (
            kb * tk + jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 1))
        ok = (segq_ref[...] == segk_ref[...]) & (gap >= 0)
        if window is not None:
            ok = ok & (gap < window)
        s = jnp.where(ok, s, _NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == n_steps - 1)
    def _finish():
        # the sink: one more column of the softmax, with no value
        den = l_sc[...] + jnp.exp(sink_ref[...] - m_sc[...])
        o_ref[...] = (acc_sc[...] / den).astype(o_ref.dtype)


def packed_block_sizes(n_tokens: int, group: int, window=None):
    """(query tokens, key tokens) a block: about 2,048 query rows (the
    group's heads times the tokens) against 256 keys, cut to the call.
    Under a window both are at most the window: a query block then
    sees its own key block and the one before it, and no third."""
    tq, tk = 2048 // group, 256
    if window is not None and window >= 8:
        tq, tk = min(tq, window), min(tk, window)
    return max(8, min(n_tokens, tq)), min(n_tokens, tk)


def packed_attention(q, k, v, segment_ids, seg_start, *, window=None,
                     sink=None, max_segment: int, block_q: int = 0,
                     block_k: int = 0):
    """Causal attention over a packed token axis: q [T, H, Dq],
    k [T, Hkv, Dq], v [T, Hkv, Dv] -> [T, H, Dv]. Token t belongs to
    history `segment_ids[t]`, which starts at token `seg_start[t]`
    (histories are contiguous; padding is one more segment at the end)
    and is at most `max_segment` tokens long. A query sees the keys of
    its own history at or before it, within `window` if given; `sink`
    [H] joins the denominator (None: no sink, which is a sink at
    -inf).

    One Pallas kernel, flash form: grid (KV head, query block, key
    step). The first and last key block a query block can see come
    from its first token (the later of its history's start and the
    window's reach) and its last (causal), are prefetched as scalars,
    and choose the key block each step loads; a step past the last
    loads nothing new and computes nothing. So neither the [T, T]
    scores nor a key block that the window or the segments rule out is
    ever computed. Off the TPU the same kernel runs interpreted."""
    T, H, Dq = q.shape
    Hkv, Dv = k.shape[1], v.shape[2]
    G = H // Hkv
    tq, tk = packed_block_sizes(T, G, window)
    tq, tk = block_q or tq, block_k or tk
    if T % tq or T % tk:
        raise ValueError(f"{T} tokens do not divide into blocks "
                         f"{tq} / {tk}")
    nq, nk, rows = T // tq, T // tk, G * tq
    span = (max_segment if window is None
            else min(window, max_segment)) - 1
    n_steps = min(nk, -(-(span + tq) // tk) + 1)
    first = jnp.arange(nq, dtype=jnp.int32) * tq
    hi = (first + tq - 1) // tk
    reach = first - span if window is not None else seg_start[first]
    lo = jnp.maximum(jnp.maximum(reach, seg_start[first]), 0) // tk
    lo = jnp.maximum(lo, hi - (n_steps - 1)).astype(jnp.int32)
    # head-major query blocks: [Hkv, nq, G * tq, Dq]
    qb = (q.reshape(nq, tq, Hkv, G, Dq).transpose(2, 0, 3, 1, 4)
          .reshape(Hkv, nq, rows, Dq))
    segq = jnp.tile(segment_ids.reshape(nq, 1, tq), (1, G, 1)).reshape(
        nq, rows, 1)
    sink_rows = jnp.repeat(
        (jnp.full((H,), -jnp.inf, jnp.float32) if sink is None
         else sink.astype(jnp.float32)).reshape(Hkv, G), tq,
        axis=1)[..., None]

    def kv_block(h, i, j, lo_ref, hi_ref):
        return h, jnp.minimum(lo_ref[i] + j, hi_ref[i]), 0

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(Hkv, nq, n_steps),
        in_specs=[
            pl.BlockSpec((None, None, rows, Dq),
                         lambda h, i, j, lo, hi: (h, i, 0, 0)),
            pl.BlockSpec((None, tk, Dq), kv_block),
            pl.BlockSpec((None, tk, Dv), kv_block),
            pl.BlockSpec((None, rows, 1),
                         lambda h, i, j, lo, hi: (i, 0, 0)),
            pl.BlockSpec((None, 1, tk),
                         lambda h, i, j, lo, hi: (
                             jnp.minimum(lo[i] + j, hi[i]), 0, 0)),
            pl.BlockSpec((None, rows, 1),
                         lambda h, i, j, lo, hi: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rows, Dv),
                               lambda h, i, j, lo, hi: (h, i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, Dv), jnp.float32)])
    out = pl.pallas_call(
        partial(_packed_kernel, tq=tq, tk=tk, window=window,
                scale=1.0 / math.sqrt(Dq), n_steps=n_steps),
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((Hkv, nq, rows, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=jax.default_backend() != "tpu",
        name="packed_attention",
    )(lo, hi, qb, k.transpose(1, 0, 2), v.transpose(1, 0, 2), segq,
      segment_ids.reshape(nk, 1, tk), sink_rows)
    return (out.reshape(Hkv, nq, G, tq, Dv).transpose(1, 3, 0, 2, 4)
            .reshape(T, H, Dv))
