"""The degree-2 power-retention mixer over a packed token axis.

Per KV head j (of Hkv, each P wide), with query heads i of its group
(H / Hkv of them), a gate 0 < g_t <= 1 a KV head and token, and G_t the
sum of log g from the history's first event up to t:

    y_{t,i} = sum_s w_ts v_{s,j} / (sum_s w_ts + eps)
    w_ts    = exp(G_t - G_s) (q_{t,i} . k_{s,j})^2

over the s <= t of t's own history (any scale of q is the caller's).
With phi the symmetric degree-2 embedding (`phi`: D = P (P + 1) / 2
dimensions, phi(q) . phi(k) = (q . k)^2) the same quantity is a
recurrence, zero before a history's first event (`first`):

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        S [D, P]
    z_t = g_t z_{t-1} + phi(k_t)              z [D]
    y_{t,i} = S_t^T phi(q_{t,i}) / (z_t . phi(q_{t,i}) + eps)

`power_retention` computes it in chunks of Q tokens. With L_t the sum
of log g from the chunk's first token, or from the first event of t's
history where that lies in the chunk, a chunk's numerator is

    sum over s <= t of the chunk, same history, of
        exp(L_t - L_s) (q_t . k_s)^2 v_s        the masked decay times
                                                (q k^T)^2
  + exp(L_t) S_in^T phi(q_t)   where t's history began before the
                               chunk, else nothing: the cut

and its denominator the same sums with v_s = 1 and z_in in S_in's
place; the closing state is exp(L_last) S_in (on the same condition
for the last token) plus the sum over s of the last token's history of
exp(L_last - L_s) phi(k_s) v_s^T.

One Pallas kernel, `power_retention`, grid (KV head, chunk): the chunk
axis is swept in order and a KV head's S and z stay in fast memory
from chunk to chunk. phi is formed in the kernel and never written
out, by cyclic diagonals: piece r of phi(u) is u * roll(u, r) over the
head's P lanes (the pairs at cyclic distance r), r = 0 .. P / 2, with
weight 1 for r = 0 and sqrt(2) for the cross terms; the last piece
holds its P / 2 pairs on the first P / 2 lanes and zeros on the rest,
so the kernel's S is phi's D rows and P / 2 zero rows, whole lane
groups (8,320 x 128 float32 at P = 128, 4.06 MiB). z is carried as the
symmetric matrix Z = sum w k k^T [P, P] that holds the same D numbers
(z . phi(q) = q^T Z q). The state read is skipped where no token of the
chunk continues an earlier chunk's history, and the state update where
the next chunk reads none. Scores, decays, sums and the state are
float32; the products take the operands' own precision (bfloat16 in a
deployment) and accumulate in float32. Off the TPU the same kernel runs
interpreted.

`scan_steps` is the recurrence itself, event by event, float32, through
`phi`: the form the small training runs differentiate
(`power_retention`'s gradient is its), and what the tests hold the
kernel to.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.ssm import chunk_sums


def phi(u):
    """[..., P] -> [..., P (P + 1) / 2]: the symmetric degree-2
    embedding, phi(q) . phi(k) = (q . k)^2, laid out by cyclic
    diagonals: u * roll(u, r) for r = 0 .. P / 2 - 1 (the cross terms,
    r > 0, times sqrt(2)), then the first P / 2 lanes of
    sqrt(2) u * roll(u, P / 2). P even."""
    P = u.shape[-1]
    return jnp.concatenate(_diagonals(u, u.ndim - 1, jnp.roll),
                           axis=-1)[..., :P * (P + 1) // 2]


def scan_steps(q, k, v, log_g, first, eps: float):
    """q [T, H, P], k and v [T, Hkv, P], log_g [T, Hkv], first [T] bool
    -> y [T, H, P] float32, one event at a time."""
    H, Hkv, P = q.shape[1], k.shape[1], k.shape[2]
    f32 = jnp.float32
    q, k, v, log_g = (x.astype(f32) for x in (q, k, v, log_g))
    D = P * (P + 1) // 2

    def step(carry, ev):
        S, z = carry
        q_t, k_t, v_t, lg_t, first_t = ev
        g = jnp.where(first_t, 0.0, jnp.exp(lg_t))         # the cut
        fk = phi(k_t)                                      # [Hkv, D]
        S = g[:, None, None] * S + fk[:, :, None] * v_t[:, None, :]
        z = g[:, None] * z + fk
        fq = phi(q_t).reshape(Hkv, H // Hkv, D)
        num = jnp.einsum("jhd,jdp->jhp", fq, S)
        den = jnp.einsum("jhd,jd->jh", fq, z)
        return (S, z), (num / (den[..., None] + eps)).reshape(H, P)

    carry = (jnp.zeros((Hkv, D, P), f32), jnp.zeros((Hkv, D), f32))
    return jax.lax.scan(step, carry, (q, k, v, log_g, first))[1]


def _diagonals(u, axis: int, roll):
    """phi's cyclic diagonals of u along `axis` (P long), each the
    shape of u, in phi's order and weights, the last one's second half
    zero: concatenated along `axis` they are phi and P / 2 zeros.
    `roll` is `jnp.roll`, or in a kernel `pltpu.roll`."""
    P = u.shape[axis]
    half = P // 2
    out = [u * u]
    for r in range(1, half + 1):
        d = math.sqrt(2.0) * u * roll(u, r, axis)
        if r == half:
            lane = jax.lax.broadcasted_iota(jnp.int32, u.shape, axis)
            d = jnp.where(lane < half, d, 0.0)
        out.append(d)
    return out


def _retention_kernel(reads_ref, writes_ref, keep_ref, q_ref, k_ref,
                      v_ref, cols_ref, lrow_ref, segq_ref, segk_ref, o_ref,
                      s_sc, z_sc, num_sc, den_sc, *, heads: int,
                      width: int, eps: float):
    """One (KV head, chunk) of `power_retention`. q [Q, heads * width],
    k and v [Q, width], cols [Q, 3] holds L, the decay of the carried
    state into each token (0 where the token's history began in this
    chunk) and the weight of each token in the chunk's closing state,
    lrow [1, Q] L again with the tokens on the lanes; keep, one number
    a (KV head, chunk), what the closing state keeps of the carried
    one."""
    j, i = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when(i == 0)
    def _():
        s_sc[...] = jnp.zeros_like(s_sc)
        z_sc[...] = jnp.zeros_like(z_sc)

    k, v = k_ref[...], v_ref[...]
    Q, dt = k.shape[0], k.dtype

    def embedded(u, axis):
        """phi of u (float32) along `axis`, padded to whole lane groups,
        in the operands' precision."""
        return jnp.concatenate(
            [d.astype(dt) for d in _diagonals(u, axis, pltpu.roll)], axis)

    cols = cols_ref[...]
    d_in, w_out = cols[:, 1:2], cols[:, 2:3]
    ok = (segq_ref[...] == segk_ref[...]) & (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    # s > t would give a positive exponent: held at 0, then masked
    decay = jnp.where(
        ok, jnp.exp(jnp.minimum(cols[:, 0:1] - lrow_ref[...], 0.0)), 0.0)
    for h in range(heads):
        qh = q_ref[:, h * width:(h + 1) * width]
        s = jax.lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)
        a = s * s * decay
        num_sc[h] = jnp.dot(a.astype(dt), v, preferred_element_type=f32)
        den_sc[h] = a.sum(axis=1, keepdims=True)

    @pl.when(reads_ref[i] == 1)
    def _():
        S, Z = s_sc[...].astype(dt), z_sc[...].astype(dt)
        for h in range(heads):
            qh = q_ref[:, h * width:(h + 1) * width]
            qf = qh.astype(f32)
            fq = embedded(qf, 1)                          # [Q, D']
            num_sc[h] += d_in * jnp.dot(fq, S, preferred_element_type=f32)
            den_sc[h] += d_in * jnp.sum(
                jnp.dot(qh, Z, preferred_element_type=f32) * qf, axis=1,
                keepdims=True)

    for h in range(heads):
        o_ref[:, h * width:(h + 1) * width] = (
            num_sc[h] / (den_sc[h] + eps)).astype(o_ref.dtype)

    @pl.when(writes_ref[i] == 1)
    def _():
        keep = keep_ref[j * pl.num_programs(1) + i]
        kt = k.astype(f32).T                                # [P, Q]
        fk = embedded(kt, 0)                                # [D', Q]
        vw = (v.astype(f32) * w_out).astype(dt)
        kw = (k.astype(f32) * w_out).astype(dt)
        s_sc[...] = keep * s_sc[...] + jnp.dot(
            fk, vw, preferred_element_type=f32)
        z_sc[...] = keep * z_sc[...] + jnp.dot(
            kt.astype(dt), kw, preferred_element_type=f32)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def power_retention(q, k, v, log_g, first, live, chunk: int, eps: float):
    """The mixer of the module docstring in chunks of `chunk` tokens:
    q [T, H P] and k, v [T, Hkv P] token-major, in the precision the
    products take; log_g [T, Hkv] float32; first [T] bool; T a multiple
    of `chunk`. `live` [T] bool (None: every token) marks the tokens
    that are served: a chunk whose first token is not is read from no
    state. Returns y [T, H P] in q's dtype."""
    T, Hkv = log_g.shape
    P = k.shape[1] // Hkv
    H, Q, nc = q.shape[1] // P, chunk, T // chunk
    hg = H // Hkv
    if T % Q or H % Hkv or P % 2:
        raise ValueError(f"{T} tokens in chunks of {Q}, {H} heads on "
                         f"{Hkv}, head width {P}")
    f32 = jnp.float32
    seg = jnp.cumsum(first.astype(jnp.int32)).reshape(nc, Q)
    L, cut = chunk_sums(log_g.astype(f32), first, Q)       # [nc, Q, Hkv]
    closing = (seg == seg[:, -1:])[..., None]
    cols = jnp.stack([
        L, jnp.where(cut, 0.0, jnp.exp(L)),
        jnp.where(closing, jnp.exp(L[:, -1:] - L), 0.0)], axis=-1)
    cols = cols.reshape(T, Hkv, 3).transpose(1, 0, 2)       # [Hkv, T, 3]
    # a chunk reads the carried state where its first token continues a
    # history that is served; it writes one where the next chunk reads
    heads_first = first.reshape(nc, Q)[:, 0]
    if live is not None:
        heads_first = heads_first | ~live.reshape(nc, Q)[:, 0]
    reads = (~heads_first).astype(jnp.int32)
    writes = jnp.concatenate([reads[1:], jnp.zeros((1,), jnp.int32)])
    keep = cols[:, Q - 1::Q, 1].reshape(-1)         # [Hkv nc], head-major
    D = P * (P // 2 + 1)                # phi's rows and P / 2 zero rows
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(Hkv, nc),
        in_specs=[
            pl.BlockSpec((Q, hg * P), lambda j, i, *_: (i, j)),
            pl.BlockSpec((Q, P), lambda j, i, *_: (i, j)),
            pl.BlockSpec((Q, P), lambda j, i, *_: (i, j)),
            pl.BlockSpec((None, Q, 3), lambda j, i, *_: (j, i, 0)),
            pl.BlockSpec((None, 1, Q), lambda j, i, *_: (j, 0, i)),
            pl.BlockSpec((None, Q, 1), lambda j, i, *_: (i, 0, 0)),
            pl.BlockSpec((None, 1, Q), lambda j, i, *_: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((Q, hg * P), lambda j, i, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((D, P), f32), pltpu.VMEM((P, P), f32),
                        pltpu.VMEM((hg, Q, P), f32),
                        pltpu.VMEM((hg, Q, 1), f32)])
    return pl.pallas_call(
        partial(_retention_kernel, heads=hg, width=P, eps=float(eps)),
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((T, H * P), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # headroom: at P = 128 the state (4.3 MB), its bfloat16 copy
            # and one head's embedded q or the chunk's embedded k (2.1 MB
            # each, from float32 pieces) come near the default 16 MiB
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=jax.default_backend() != "tpu",
        name="power_retention",
    )(reads, writes, keep, q, k, v, cols, L.reshape(T, Hkv).T[:, None, :],
      seg.reshape(nc, Q, 1), seg.reshape(nc, 1, Q))


def _power_retention_fwd(q, k, v, log_g, first, live, chunk, eps):
    return (power_retention(q, k, v, log_g, first, live, chunk, eps),
            (q, k, v, log_g, first))


def _power_retention_bwd(chunk, eps, res, dy):
    """For the template's small training runs: the recurrence's own
    gradient, event by event, on `scan_steps`' [T, heads, P] views."""
    q, k, v, log_g, first = res
    T, Hkv = log_g.shape
    P = k.shape[1] // Hkv
    _, vjp = jax.vjp(
        lambda q_, k_, v_, g_: scan_steps(
            q_.reshape(T, -1, P), k_.reshape(T, Hkv, P),
            v_.reshape(T, Hkv, P), g_, first, eps).reshape(T, -1),
        q, k, v, log_g)
    return (*vjp(dy.astype(jnp.float32)), None, None)


power_retention.defvjp(_power_retention_fwd, _power_retention_bwd)
