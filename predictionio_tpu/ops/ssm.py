"""The state-space scan of a Mamba-2 mixer over a packed token axis.

Per head h (of H, each P wide) and token t, with B_t, C_t [N] of the
head's group (G groups, H / G heads each), a step size d_t > 0 and a
rate A_h < 0:

    S_t = exp(d_t A_h) S_{t-1} + d_t x_t (x) B_t        S [P, N]
    y_t = S_t C_t

and S = 0 before a history's first event (`first`): several histories
stand end to end on the one axis, and a state that ran on from the
history in front would serve a stranger's events.

`chunk_scan` computes it in chunks of Q tokens. With L_t the sum of
d_s A_h up to t from the chunk's first token, or from the first event
of t's history where that lies in the chunk, a chunk's result is three
products and its state a fourth:

    y_t  = sum over s <= t of the chunk, same history, of
             exp(L_t - L_s) (C_t . B_s) d_s x_s       the masked decay
                                                      matrix times C B^T
         + exp(L_t) C_t S_in      where t's history began before the
                                  chunk, else nothing: the cut
    S_out = exp(L_last) S_in      on the same condition for the last token
         + sum over s of the last token's history of
             exp(L_last - L_s) d_s x_s (x) B_s

One Pallas kernel, `ssm_chunk_scan`, grid (group, chunk): C B^T is
formed once a step and shared by the group's heads, the chunk axis is
swept in order and the state [N, P] a head is carried in fast memory
from step to step, so the carry costs no call of its own. The sums L,
the step sizes, the decays and the state are float32; the four
products take the operands' own precision (bfloat16 in a deployment)
and accumulate in float32; step x is formed in the kernel, rounded to
that precision. x, B, C and y are token-major slabs ([T, H P], [T, G
N]), read and written where they lie wherever a group's columns are
whole lane groups (`in_place`), copied group-major otherwise. Off the
TPU the same kernel runs interpreted.

`scan_steps` is the recurrence itself, event by event, float32: the
form the small training runs differentiate (`chunk_scan`'s gradient is
its), and what the tests hold the kernel to.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def scan_steps(x, dt, a, b, c, first):
    """x [T, H, P], dt [T, H], a [H], b and c [T, G, N], first [T]
    bool -> y [T, H, P] float32, one event at a time."""
    H, G = x.shape[1], b.shape[1]
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))

    def step(S, ev):
        x_t, dt_t, b_t, c_t, first_t = ev
        b_h, c_h = (jnp.repeat(v, H // G, axis=0) for v in (b_t, c_t))
        S = jnp.where(first_t, 0.0, S)
        S = (jnp.exp(dt_t * a.astype(f32))[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, c_h)

    S0 = jnp.zeros((H, x.shape[2], b.shape[2]), f32)
    return jax.lax.scan(step, S0, (x, dt, b, c, first))[1]


def chunk_sums(x, first, chunk: int):
    """x [T, H] float32, first [T] bool -> (L, cut), each [T / chunk,
    chunk, H]: L the sum of x along the token axis from the chunk's
    first token, or from the last first event at or before the token
    where one stands in the chunk, up to the token itself; `cut`
    whether such a first event stands. What stands in front of a first
    event is in no bit of L behind it. The decays of a chunked scan
    over packed histories are differences of L (`chunk_scan`, and
    `ops/retention.power_retention`)."""
    T, H = x.shape
    nc = T // chunk

    def onward(left, right):
        (l_sum, l_cut), (r_sum, r_cut) = left, right
        return jnp.where(r_cut, r_sum, l_sum + r_sum), l_cut | r_cut

    return jax.lax.associative_scan(
        onward, (x.reshape(nc, chunk, H),
                 jnp.broadcast_to(first.reshape(nc, chunk, 1),
                                  (nc, chunk, H))), axis=1)


def in_place(width: int, state: int, groups: int) -> bool:
    """Whether `chunk_scan` reads x, B and C where the convolution
    leaves them, as (chunk, group) blocks of its token-major slab, and
    writes y token-major: where a group's heads (`width` = H / G * P
    columns of x) and its B and C (`state` = N columns) are whole lane
    groups and B begins on a whole block of N. Otherwise each is copied
    group-major first ([G, T, width]) and y back: at nemotron's widths
    those copies took 2.0 ms a block, twice the kernel's own time (my
    chip run, PR 36). As `moe.columns_first` for weights whose columns
    are no whole lane groups."""
    return (width % 128 == 0 and state % 128 == 0
            and groups * width % state == 0)


def _scan_kernel(x_ref, b_ref, c_ref, cols_ref, lrow_ref, keep_ref,
                 segq_ref, segk_ref, o_ref, s_sc, *, heads: int,
                 width: int):
    """One (group, chunk) of `chunk_scan`. x [Q, heads * width], cols
    [Q, 4 heads] holds L, the weight of each token in the chunk's
    closing state, the decay of the carried state into each token (0
    where the token's history began in this chunk) and the step size,
    lrow [heads, Q] is L again with the tokens on the lanes, keep
    [heads, width] what the carried state keeps to the chunk's end, a
    row of one number a head."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_sc[...] = jnp.zeros_like(s_sc)

    B, C = b_ref[...], c_ref[...]
    Q, dt = B.shape[0], B.dtype
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ok = (segq_ref[...] == segk_ref[...]) & (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    cols, lrow = cols_ref[...], lrow_ref[...]
    for h in range(heads):
        L_col = cols[:, h:h + 1]
        w_out = cols[:, heads + h:heads + h + 1]
        d_in = cols[:, 2 * heads + h:2 * heads + h + 1]
        step = cols[:, 3 * heads + h:3 * heads + h + 1]
        # s > t would give a positive exponent: held at 0, then masked
        decay = jnp.where(
            ok, jnp.exp(jnp.minimum(L_col - lrow[h:h + 1, :], 0.0)), 0.0)
        xh = x_ref[:, h * width:(h + 1) * width]
        xh = (xh.astype(jnp.float32) * step).astype(xh.dtype)   # step x
        S = s_sc[h]
        y = jnp.dot((cb * decay).astype(dt), xh,
                    preferred_element_type=jnp.float32)
        y = y + d_in * jnp.dot(C, S.astype(dt),
                               preferred_element_type=jnp.float32)
        o_ref[:, h * width:(h + 1) * width] = y
        s_sc[h] = keep_ref[h:h + 1, :] * S + jax.lax.dot_general(
            B, (xh.astype(jnp.float32) * w_out).astype(dt),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def chunk_scan(xbc, dt, a, first, chunk: int, groups: int, state: int):
    """`scan_steps` in chunks of `chunk` tokens (module docstring), on
    the convolution's token-major slab xbc [T, H P + 2 G N] (x, B and
    C side by side; G = `groups`, N = `state`) in the precision the
    products take, dt [T, H] and a [H] float32, first [T] bool; T a
    multiple of `chunk`. Returns y [T, H P] float32. The kernel reads
    the slab and writes y in place where `in_place` holds."""
    T, H = dt.shape
    G, N = groups, state
    inner = xbc.shape[1] - 2 * G * N
    P, hg, Q, nc = inner // H, H // G, chunk, T // chunk
    if T % Q or H % G:
        raise ValueError(f"{T} tokens in chunks of {Q}, {H} heads in "
                         f"{G} groups")
    f32 = jnp.float32
    dt = dt.astype(f32)
    seg = jnp.cumsum(first.astype(jnp.int32)).reshape(nc, Q)
    L, cut = chunk_sums(dt * a.astype(f32), first, Q)
    # a token whose history began before its chunk (no first event up
    # to it: `cut` is false) reads the carried state; one of the last
    # token's history feeds the closing state
    closing = (seg == seg[:, -1:])[..., None]
    d_in = jnp.where(cut, 0.0, jnp.exp(L))
    cols = jnp.concatenate([
        L, jnp.where(closing, jnp.exp(L[:, -1:] - L), 0.0), d_in,
        dt.reshape(nc, Q, H)], axis=0)                     # [4 nc, Q, H]
    keep = jnp.broadcast_to(
        d_in[:, -1].reshape(nc, G, hg, 1).transpose(1, 0, 2, 3),
        (G, nc, hg, P))

    def by_group(v, n):        # [n * nc, Q, H] -> [G, T, n * hg]
        return (v.reshape(n, T, G, hg).transpose(2, 1, 0, 3)
                .reshape(G, T, n * hg))

    if in_place(hg * P, N, G):   # (chunk, group) blocks where they lie
        operands = (xbc,) * 3
        specs = [pl.BlockSpec((Q, hg * P), lambda g, i: (i, g))] + [
            pl.BlockSpec((Q, N), lambda g, i, at=at: (i, at + g))
            for at in (inner // N, inner // N + G)]
        y_shape = (T, inner)
    else:                          # group-major copies in and out
        operands = tuple(
            v.reshape(T, G, -1).transpose(1, 0, 2)
            for v in jnp.split(xbc, (inner, inner + G * N), axis=1))
        specs = [pl.BlockSpec((None, Q, v.shape[2]),
                              lambda g, i: (g, i, 0)) for v in operands]
        y_shape = (G, T, hg * P)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0, grid=(G, nc),
        in_specs=[
            *specs,
            pl.BlockSpec((None, Q, 4 * hg), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, hg, Q), lambda g, i: (g, 0, i)),
            pl.BlockSpec((None, None, hg, P), lambda g, i: (g, i, 0, 0)),
            pl.BlockSpec((None, Q, 1), lambda g, i: (i, 0, 0)),
            pl.BlockSpec((None, 1, Q), lambda g, i: (i, 0, 0)),
        ],
        out_specs=specs[0],             # y is laid out as x
        scratch_shapes=[pltpu.VMEM((hg, N, P), f32)])
    y = pl.pallas_call(
        partial(_scan_kernel, heads=hg, width=P), grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct(y_shape, f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="ssm_chunk_scan",
    )(*operands, by_group(cols, 4),
      by_group(L, 1).transpose(0, 2, 1), keep,
      seg.reshape(nc, Q, 1), seg.reshape(nc, 1, Q))
    return y if y.ndim == 2 else y.transpose(1, 0, 2).reshape(T, inner)


def _chunk_scan_fwd(xbc, dt, a, first, chunk, groups, state):
    return (chunk_scan(xbc, dt, a, first, chunk, groups, state),
            (xbc, dt, a, first))


def _chunk_scan_bwd(chunk, groups, state, res, dy):
    """For the template's small training runs: the recurrence's own
    gradient, event by event, on `scan_steps`' [T, H, P] and [T, G, N]
    views of the slab."""
    xbc, dt, a, first = res
    T, H = dt.shape
    inner = xbc.shape[1] - 2 * groups * state
    x, b, c = jnp.split(xbc, (inner, inner + groups * state), axis=1)
    _, vjp = jax.vjp(lambda *v: scan_steps(*v, first),
                     x.reshape(T, H, -1), dt, a, b.reshape(T, groups, -1),
                     c.reshape(T, groups, -1))
    dx, ddt, da, db, dc = vjp(dy.astype(jnp.float32).reshape(T, H, -1))
    return (jnp.concatenate([dx.reshape(T, inner), db.reshape(T, -1),
                             dc.reshape(T, -1)], axis=1), ddt, da, None)


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
