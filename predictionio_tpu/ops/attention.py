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


_NEG = -1e30


def attention_reference(q, k, v, *, causal: bool = False, kv_mask=None):
    """Plain softmax attention, [B, S, H, Dh] -> [B, S, H, Dh] — the
    oracle the ring implementation is tested against (and the
    single-device path when no mesh axis shards the sequence).
    `kv_mask` [B, S] bool marks VALID key positions (False = padding
    slot that must not receive attention)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = None
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
    if kv_mask is not None:
        km = kv_mask[:, None, None, :]
        mask = km if mask is None else (mask & km)
    if mask is None:
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(s, axis=-1), v)
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    # a fully-masked row (a padding query with no visible key) reads
    # uniform from softmax; zero it with the COMBINED mask so the dead
    # row is exactly 0, matching the streaming path
    p = jnp.where(mask, p, 0.0)
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
                   kv_mask=None):
    """Sequence-parallel attention: [B, S, H, Dh] inputs whose S
    dimension shards over `mesh` axis `axis` — and whose BATCH shards
    over `batch_axis` when the mesh has one (without it, a dp x sp mesh
    would all-gather the batch and replicate attention across every
    data group). Equivalent (up to float association) to
    `attention_reference`; with a trivial axis (size 1 or absent) it
    falls through to the reference path. `kv_mask` [B, S] bool marks
    valid key positions (False = padding)."""
    from jax.sharding import PartitionSpec as P

    if mesh is None or axis not in mesh.shape or mesh.shape[axis] == 1:
        return attention_reference(q, k, v, causal=causal,
                                   kv_mask=kv_mask)
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
