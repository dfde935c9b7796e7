"""Sparse experts: a router at its published width, and an expert layer
that is told which experts it holds.

`route` scores every token against ALL experts of the model (sigmoid
scores, selection by `s + c`, weights from `s`). `moe_apply` computes
the part of the layer's result that the experts held HERE give:
`sum over selected & held of w_e * Expert_e(u)`. That is what expert
parallelism asks of one chip; the exchange that would bring the other
chips' tokens in and carry the parts out is no part of this module
(one chip exchanges nothing), and a token none of whose experts is
held here gets zero.

No capacity and no dropped token: the (token, expert) pairs that are
selected and held are sorted by expert, each expert's group is padded
up to whole row blocks, and one grouped product a projection runs over
the blocks (`grouped_matmul`, a Pallas kernel: a block's expert comes
from a prefetched table and picks the weight tile; blocks past the
last used one load and compute nothing). One buffer holds twice the
pairs that uniform routing sends to the held experts, and never more
than every pair of the call (`buffer_pairs`): T pairs for T tokens
where a sixteenth of the experts is held and 8 are selected; all k T
pairs, exactly, where every expert is held. A call that routes more
to this chip than one buffer holds fills further buffers, so the bound
is on memory, never on the answer.

An expert is a SwiGLU (gate beside up in one matrix) or, where the
matrix is F wide and not 2 F, two matrices with relu^2 between them.

A buffer is one gather of the tokens' rows into sorted order, two
grouped products, and the way back, which is one of two
(`gather_combine`, the buffer rule's own integer). Where one buffer
holds every pair of the call (every expert held, or any share of at
least half), each (token, choice) pair has exactly one row of the
down product's result and its index is known when the buffer is laid
out: a token's result is its k rows, gathered and weighted in pair
order, `out[t] = sum_j w[t, j] * y[row_of[t, j]]`, float32, no
atomics. Where a buffer holds fewer pairs than the call may bring
(a sixteenth of the experts), the rows are weighted and
scatter-added onto their tokens, buffer after buffer: most tokens
have no row in a given buffer, and a token-side gather would read
k T rows to find them.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class Routing(NamedTuple):
    experts: jax.Array     # [T, k] int32, the selected experts' ids
    weights: jax.Array     # [T, k] float32, normalised over the k


class MoeStats(NamedTuple):
    expert_tokens: jax.Array   # [held] int32, pairs each held expert got
    unrouted: jax.Array        # [] int32, live tokens with no expert here


def expert_share(index: int, count: int, n_experts: int) -> Tuple[int, int]:
    """(first expert id, experts held) of share `index` of `count` equal
    shares of `n_experts`: share 0 of 16 over 256 holds experts 0-15."""
    if n_experts % count or not 0 <= index < count:
        raise ValueError(f"share {index} of {count} over {n_experts}")
    held = n_experts // count
    return index * held, held


def route(u, w_router, bias, *, top_k: int, norm_topk_prob: bool = True,
          scale: float = 1.0, eps: float = 0.0) -> Routing:
    """u [T, D] float32, w_router [D, E], bias [E] (the correction term
    of the aux-loss-free balancing, used for selection only); `eps` is
    added to the sum the selected scores are normalised by. The
    scores are float32: a selection that flips on rounding sends a
    token to another expert. Against bfloat16 weights that is two
    products on the matrix unit, u's leading and next 8 bits of
    mantissa each against the weights, which bfloat16 holds exactly
    (2^-17 of u left out); XLA lowers the one `highest` float32
    product of this shape to multiplies and adds off the matrix unit,
    3 ms a layer at 8,192 tokens (my chip run, PR 27)."""
    if w_router.dtype == jnp.bfloat16:
        hi = u.astype(jnp.bfloat16)
        lo = (u - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        logits = (jnp.matmul(hi, w_router,
                             preferred_element_type=jnp.float32)
                  + jnp.matmul(lo, w_router,
                               preferred_element_type=jnp.float32))
    else:
        logits = jnp.matmul(u, w_router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, experts, axis=1)
    if norm_topk_prob:
        den = w.sum(axis=1, keepdims=True)
        w = w / (den + eps if eps else den)
    return Routing(experts.astype(jnp.int32), w * scale)


def _gmm_kernel(expert_ref, used_ref, x_ref, w_ref, o_ref, *, w_rows: int):
    del expert_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (w_rows,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def col_block(n_cols: int, at_most: int) -> int:
    """Columns of one weight tile: the widest whole number of lane
    groups (128) that divides `n_cols` and is at most `at_most`; every
    column where there is none (1,856 = 14.5 lane groups)."""
    fits = [d for d in range(128, min(at_most, n_cols) + 1, 128)
            if n_cols % d == 0]
    return fits[-1] if fits else n_cols


def columns_first(n_cols: int) -> bool:
    """Whether `grouped_matmul` is handed w [E, K, N] as its transpose
    [E, N, K]: where N is no whole number of lane groups the compiler
    keeps the array with K on the lanes, so the transpose is the same
    bytes, and a kernel that asked for (K, N) tiles would have every
    call copy all the weights first (2.0 ms an expert layer at 64 x
    2,688 x 1,856: my chip run, PR 36). As `fused_topk._items_on_lanes`
    for a catalog of narrow rows."""
    return n_cols % 128 != 0


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def grouped_matmul(x, w, block_expert, n_used, block_rows: int,
                   block_cols: int = 512, out_dtype=None,
                   w_t: bool = False):
    """x [R, K] in row blocks of `block_rows`, block b all of expert
    `block_expert[b]`; w [E, K, N], or with `w_t` its transpose [E, N,
    K]. Returns [R, N]: rows of the first `n_used` blocks are x @
    w[expert], rows of the others are not written."""
    R, K = x.shape
    N = w.shape[1 if w_t else 2]
    tn = col_block(N, block_cols)
    n_blocks = R // block_rows

    def row_block(b, used_ref):
        return jnp.maximum(jnp.minimum(b, used_ref[0] - 1), 0)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(N // tn, n_blocks),
        in_specs=[
            pl.BlockSpec((block_rows, K),
                         lambda n, b, e, u: (row_block(b, u), 0)),
            (pl.BlockSpec((None, tn, K),
                          lambda n, b, e, u: (e[row_block(b, u)], n, 0))
             if w_t else
             pl.BlockSpec((None, K, tn),
                          lambda n, b, e, u: (e[row_block(b, u)], 0, n))),
        ],
        # a skipped step keeps the last used block's index, so that
        # block is written once, whole, when the sweep ends
        out_specs=pl.BlockSpec((block_rows, tn),
                               lambda n, b, e, u: (row_block(b, u), n)))
    return pl.pallas_call(
        partial(_gmm_kernel, w_rows=int(w_t)), grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((R, N), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=jax.default_backend() != "tpu",
        name="moe_grouped_matmul",
    )(block_expert, jnp.reshape(n_used, (1,)).astype(jnp.int32), x, w)


def _gmm_fwd(x, w, block_expert, n_used, block_rows, block_cols,
             out_dtype, w_t):
    return (grouped_matmul(x, w, block_expert, n_used, block_rows,
                           block_cols, out_dtype, w_t),
            (x, w, block_expert, n_used))


def _gmm_bwd(block_rows, block_cols, out_dtype, w_t, res, dy):
    """For the template's small training runs: dx is the same grouped
    product against the transposed weights; dw is formed block by
    block in plain einsums (every block's [K, N] at once, which no
    training at width could hold)."""
    x, w, block_expert, n_used = res
    used = jnp.arange(x.shape[0] // block_rows) < n_used
    dy = jnp.where(jnp.repeat(used, block_rows)[:, None], dy, 0)
    dx = grouped_matmul(dy.astype(x.dtype), w, block_expert, n_used,
                        block_rows, block_cols, x.dtype, not w_t)
    xb = jnp.where(jnp.repeat(used, block_rows)[:, None], x, 0).reshape(
        -1, block_rows, x.shape[1])
    per_block = jnp.einsum("brk,brn->bkn", xb.astype(jnp.float32),
                           dy.reshape(-1, block_rows, dy.shape[1])
                           .astype(jnp.float32))
    dw = jax.ops.segment_sum(per_block, block_expert,
                             num_segments=w.shape[0])
    if w_t:
        dw = dw.swapaxes(1, 2)
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def moe_block_rows(n_tokens: int) -> int:
    """Rows of one block of the grouped product."""
    return 256 if n_tokens >= 2048 else max(8, min(128, n_tokens // 4))


def buffer_pairs(n_tokens: int, top_k: int, held: int,
                 n_experts: int) -> int:
    """(Token, held expert) pairs one buffer of `moe_apply` takes:
    twice what uniform routing sends to `held` of `n_experts`, at most
    every pair of the call, at least one."""
    every = n_tokens * top_k
    return max(1, min(every, -(-2 * every * held // n_experts)))


def gather_combine(n_tokens: int, top_k: int, held: int,
                   n_experts: int) -> bool:
    """Whether `moe_apply` combines the experts' rows by a gather on
    the token side: where one buffer holds every pair of the call, so
    that each pair has exactly one row. Otherwise it scatter-adds."""
    return buffer_pairs(n_tokens, top_k, held, n_experts) \
        == n_tokens * top_k


def _combine_rows(y, row_of, here, weights):
    """out[t] = sum over j of weights[t, j] * y[row_of[t, j]], over
    the pairs that are `here`; y [rows, D] float32, the others [T, k].
    A pair that is not here carries an index past the buffer, and rows
    past the used blocks were never written: masked, not multiplied
    by a zero weight."""
    last = y.shape[0] - 1
    out = 0.0
    for j in range(row_of.shape[1]):
        rows_j = y[jnp.minimum(row_of[:, j], last)]
        out = out + jnp.where(here[:, j, None], rows_j, 0.0) \
            * weights[:, j, None]
    return out


def moe_apply(u, routing: Routing, w_up, w_down, *, first: int,
              n_experts: int, live=None, block_rows: int = 0):
    """The held experts' part of the expert layer for u [T, D].

    w_up [held, D, 2 F] (gate beside up: a SwiGLU, silu(g) * u) or
    [held, D, F] (two matrices an expert: relu(u)^2), w_down [held, F,
    D]; the held experts are ids first .. first + held - 1 of the
    router's `n_experts`, which sizes a buffer (`buffer_pairs`).
    `live` [T] bool leaves padding tokens out (they would load the
    experts for nothing). The rows come back to their tokens by a
    gather where one buffer holds every pair, by a scatter-add a
    buffer where not (`gather_combine`). Returns ([T, D] float32,
    MoeStats)."""
    T, D = u.shape
    held, F = w_down.shape[0], w_down.shape[1]
    k = routing.experts.shape[1]
    tm = block_rows or moe_block_rows(T)
    local = routing.experts - first
    here = (local >= 0) & (local < held)
    if live is not None:
        here = here & live[:, None]
    # every pair, sorted by held expert; the others carry id `held` and
    # sort to the end
    e_flat = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    tok_sorted = (order // k).astype(jnp.int32)
    counts = jnp.bincount(e_flat, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    n_pairs = ends[-1]
    cap = buffer_pairs(T, k, held, n_experts)
    n_blocks = -(-cap // tm) + held        # every group's padding fits
    rows = n_blocks * tm
    ub = u.astype(w_up.dtype)
    u_ext = jnp.concatenate([ub, jnp.zeros((1, D), ub.dtype)])

    def products(c):
        """Buffer `c`: the down product's rows [rows, D] float32 (those
        past the used blocks never written), each row's token (T where
        it has none) and each sorted pair's row (`rows` where the pair
        is not here)."""
        lo = c * cap
        # this buffer's slice of each expert's group, padded to blocks
        g_lo = jnp.clip(starts, lo, lo + cap)
        g_n = jnp.clip(ends, lo, lo + cap) - g_lo
        g_pad = -(-g_n // tm) * tm
        g_end = jnp.cumsum(g_pad)
        g_off = g_end - g_pad
        pos = lo + jnp.arange(cap, dtype=jnp.int32)
        e = jax.lax.dynamic_slice(e_sorted, (lo,), (cap,))
        tok = jax.lax.dynamic_slice(tok_sorted, (lo,), (cap,))
        ec = jnp.minimum(e, held - 1)
        dest = jnp.where(e < held, g_off[ec] + pos - g_lo[ec], rows)
        row_tok = jnp.full((rows,), T, jnp.int32).at[dest].set(
            tok, mode="drop")
        # block b is of the first expert whose padded group ends past it
        block_expert = jnp.minimum(
            (g_end[None, :] // tm <= jnp.arange(n_blocks)[:, None]).sum(1),
            held - 1).astype(jnp.int32)
        n_used = g_end[-1] // tm
        x = u_ext[row_tok]
        up_t = columns_first(w_up.shape[2])
        gu = grouped_matmul(x, w_up.swapaxes(1, 2) if up_t else w_up,
                            block_expert, n_used, tm, 512, None, up_t)
        if w_up.shape[2] == 2 * F:
            h = (jax.nn.silu(gu[:, :F].astype(jnp.float32))
                 * gu[:, F:].astype(jnp.float32)).astype(x.dtype)
        else:
            h = jnp.square(jax.nn.relu(gu.astype(jnp.float32))).astype(
                x.dtype)
        y = grouped_matmul(h, w_down, block_expert, n_used, tm, 512,
                           jnp.float32)
        return y, row_tok, dest

    if gather_combine(T, k, held, n_experts):
        # the one buffer holds every pair: `dest`, carried back from
        # sorted order to pair order, is each pair's row
        y, _, dest = products(0)
        row_of = jnp.zeros((T * k,), jnp.int32).at[order].set(
            dest, unique_indices=True).reshape(T, k)
        out = _combine_rows(y, row_of, here, routing.weights)
    else:
        w_sorted = routing.weights.reshape(-1)[order]

        def scatter_buffer(c, out):
            y, row_tok, dest = products(c)
            wt = jax.lax.dynamic_slice(w_sorted, (c * cap,), (cap,))
            row_w = jnp.zeros((rows,), jnp.float32).at[dest].set(
                wt, mode="drop")
            # rows past the used blocks were never written: they carry
            # row T, which the scatter drops
            return out.at[row_tok].add(y * row_w[:, None], mode="drop")

        # as many buffers as hold every pair of every token; one that
        # starts past the last pair is skipped. A scan over a cond, not
        # a loop to a computed bound, so that the small training runs
        # can differentiate it.
        def step(out, c):
            return jax.lax.cond(c * cap < n_pairs,
                                lambda o: scatter_buffer(c, o),
                                lambda o: o, out), None

        out, _ = jax.lax.scan(step, jnp.zeros((T, D), jnp.float32),
                              jnp.arange(-(-T * k // cap),
                                         dtype=jnp.int32))
    any_here = here.any(axis=1)
    n_live = T if live is None else live.sum()
    return out, MoeStats(counts, (n_live - any_here.sum()).astype(jnp.int32))
