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
and accumulate in float32. Off the TPU the same kernel runs
interpreted.

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


def _scan_kernel(xd_ref, b_ref, c_ref, cols_ref, lrow_ref, keep_ref,
                 segq_ref, segk_ref, o_ref, s_sc, *, heads: int,
                 width: int):
    """One (group, chunk) of `chunk_scan`. xd [Q, heads * width] is
    d x, cols [Q, 3 heads] holds L, the weight of each token in the
    chunk's closing state and the decay of the carried state into each
    token (0 where the token's history began in this chunk), lrow
    [heads, Q] is L again with the tokens on the lanes, keep [heads,
    width] what the carried state keeps to the chunk's end, a row of
    one number a head."""
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
        # s > t would give a positive exponent: held at 0, then masked
        decay = jnp.where(
            ok, jnp.exp(jnp.minimum(L_col - lrow[h:h + 1, :], 0.0)), 0.0)
        xh = xd_ref[:, h * width:(h + 1) * width]
        S = s_sc[h]
        y = jnp.dot((cb * decay).astype(dt), xh,
                    preferred_element_type=jnp.float32)
        y = y + d_in * jnp.dot(C, S.astype(dt),
                               preferred_element_type=jnp.float32)
        o_ref[:, h * width:(h + 1) * width] = y
        s_sc[h] = keep_ref[h:h + 1, :] * S + jax.lax.dot_general(
            B, (xh.astype(jnp.float32) * w_out).astype(dt),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def chunk_scan(x, dt, a, b, c, first, chunk: int):
    """`scan_steps` in chunks of `chunk` tokens (module docstring):
    x [T, H, P] and b, c [T, G, N] in the precision the products take,
    dt [T, H] and a [H] float32, first [T] bool; T a multiple of
    `chunk`. Returns y [T, H, P] float32."""
    T, H, P = x.shape
    G, N = b.shape[1:]
    hg, Q, nc = H // G, chunk, T // chunk
    if T % Q or H % G:
        raise ValueError(f"{T} tokens in chunks of {Q}, {H} heads in "
                         f"{G} groups")
    f32 = jnp.float32
    dt = dt.astype(f32)
    seg = jnp.cumsum(first.astype(jnp.int32)).reshape(nc, Q)

    def onward(left, right):
        """A sum that starts again at a history's first event: what
        stands in front of the event is in no bit of L behind it."""
        (l_sum, l_cut), (r_sum, r_cut) = left, right
        return jnp.where(r_cut, r_sum, l_sum + r_sum), l_cut | r_cut

    L, cut = jax.lax.associative_scan(
        onward, ((dt * a.astype(f32)).reshape(nc, Q, H),
                 jnp.broadcast_to(first.reshape(nc, Q, 1), (nc, Q, H))),
        axis=1)
    # a token whose history began before its chunk (no first event up
    # to it: `cut` is false) reads the carried state; one of the last
    # token's history feeds the closing state
    closing = (seg == seg[:, -1:])[..., None]
    d_in = jnp.where(cut, 0.0, jnp.exp(L))
    cols = jnp.concatenate([
        L, jnp.where(closing, jnp.exp(L[:, -1:] - L), 0.0), d_in],
        axis=0)                                            # [3 nc, Q, H]
    keep = jnp.broadcast_to(
        d_in[:, -1].reshape(nc, G, hg, 1).transpose(1, 0, 2, 3),
        (G, nc, hg, P))

    def by_group(v, n):        # [n * nc, Q, H] -> [G, T, n * hg]
        return (v.reshape(n, T, G, hg).transpose(2, 1, 0, 3)
                .reshape(G, T, n * hg))

    xd = (x.astype(f32) * dt[..., None]).astype(x.dtype)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0, grid=(G, nc),
        in_specs=[
            pl.BlockSpec((None, Q, hg * P), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, Q, N), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, Q, N), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, Q, 3 * hg), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, hg, Q), lambda g, i: (g, 0, i)),
            pl.BlockSpec((None, None, hg, P), lambda g, i: (g, i, 0, 0)),
            pl.BlockSpec((None, Q, 1), lambda g, i: (i, 0, 0)),
            pl.BlockSpec((None, 1, Q), lambda g, i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, Q, hg * P), lambda g, i: (g, i, 0)),
        scratch_shapes=[pltpu.VMEM((hg, N, P), f32)])
    y = pl.pallas_call(
        partial(_scan_kernel, heads=hg, width=P), grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((G, T, hg * P), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="ssm_chunk_scan",
    )(xd.reshape(T, G, hg * P).transpose(1, 0, 2),
      b.transpose(1, 0, 2), c.transpose(1, 0, 2), by_group(cols, 3),
      by_group(L, 1).transpose(0, 2, 1), keep,
      seg.reshape(nc, Q, 1), seg.reshape(nc, 1, Q))
    return y.transpose(1, 0, 2).reshape(T, H, P)


def _chunk_scan_fwd(x, dt, a, b, c, first, chunk):
    return chunk_scan(x, dt, a, b, c, first, chunk), (x, dt, a, b, c, first)


def _chunk_scan_bwd(chunk, res, dy):
    """For the template's small training runs: the recurrence's own
    gradient, event by event."""
    x, dt, a, b, c, first = res
    _, vjp = jax.vjp(lambda *v: scan_steps(*v, first), x, dt, a, b, c)
    return (*vjp(dy.astype(jnp.float32)), None)


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
