"""Operations and bytes the Brumby backbone REQUIRES, from shapes and
from the traffic's own counts, whatever implements them (as
`seq_opcount.py`, whose function names these keep where the one driver
calls them): padding tokens, pairs a kernel computes and masks, and
state reads a token does not need do not count, so an implementation
that wastes work reads a lower share. Every layer is a retention mixer
and a dense SwiGLU; the norms, the rotary and the gates' sigmoid are
no matrix product and are left out.

The retention is counted as the chunked form does it at the
configuration's `retention_chunk` Q: the pairs (t, s <= t) of one
history inside one chunk; the state read, 2 D P + D a query head
(S^T phi(q) and the normaliser z . phi(q), D = P (P + 1) / 2), for each
token whose history began before its chunk; the state update, 2 D P + D
a KV head, for each token of a chunk's last history where that history
goes on into the next chunk. Which tokens those are hangs on where a
history starts inside a chunk, which the pack decides (histories end to
end in arrival order): each history is counted at the mean over its Q
possible offsets.

All counts take `a = brumby_reference.arch(config file)`.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Dict, Iterable, Tuple


def phi_dims(a: Dict[str, Any]) -> int:
    return a["Dh"] * (a["Dh"] + 1) // 2


def retention_matrix_params(a: Dict[str, Any]) -> int:
    D, H, hkv, Dh = a["D"], a["H"], a["hkv"], a["Dh"]
    return 2 * D * H * Dh + 2 * D * hkv * Dh + D * hkv


def layer_params(a: Dict[str, Any]) -> int:
    """Every parameter of one layer: the mixer's matrices, the gate's
    bias, the SwiGLU, the two block norms and the q/k gains."""
    D = a["D"]
    return (retention_matrix_params(a) + a["hkv"] + 3 * D * a["dense"]
            + 2 * D + 2 * a["Dh"])


def stack_params(a: Dict[str, Any]) -> int:
    """Parameters this chip holds: every layer, the embedding, the head
    and the final gain."""
    return a["layers"] * layer_params(a) + 2 * a["V"] * a["D"] + a["D"]


def token_matmul_flops(a: Dict[str, Any]) -> float:
    """FLOPs every token requires in one layer's products: two a
    parameter of the projections, the gate and the SwiGLU."""
    return 2.0 * (retention_matrix_params(a) + 3 * a["D"] * a["dense"])


def pair_flops(a: Dict[str, Any]) -> float:
    """Intra-chunk FLOPs of a (t, s) pair, every query head: q . k and
    the weight times v."""
    return 2.0 * a["H"] * 2 * a["Dh"]


def read_flops(a: Dict[str, Any]) -> float:
    """A token's state read, every query head: S^T phi(q) and z . phi(q)."""
    return a["H"] * (2.0 * phi_dims(a) * a["Dh"] + phi_dims(a))


def update_flops(a: Dict[str, Any]) -> float:
    """A token's share of the state update, every KV head: phi(k) v^T
    and phi(k) added in."""
    return a["hkv"] * (2.0 * phi_dims(a) * a["Dh"] + phi_dims(a))


@lru_cache(maxsize=None)
def _history(n: int, chunk: int) -> Tuple[float, float, float]:
    """(intra-chunk pairs, tokens that read the state, tokens that
    update it) of one history of n events, the mean over the chunk's
    offsets it may start at."""
    pairs = reads = updates = 0
    for offset in range(chunk):
        first = min(n, chunk - offset)
        rest = n - first
        pieces = [first] + [chunk] * (rest // chunk) + (
            [rest % chunk] if rest % chunk else [])
        pairs += sum(m * (m + 1) // 2 for m in pieces)
        reads += n - first
        updates += n - pieces[-1]
    return pairs / chunk, reads / chunk, updates / chunk


def retention_counts(a: Dict[str, Any], lengths: Iterable[int]
                     ) -> Tuple[float, float, float, int]:
    """(pairs, reading tokens, updating tokens, tokens) over these
    histories, one layer."""
    pairs = reads = updates = tokens = 0
    for n in lengths:
        p, r, u = _history(int(n), a["chunk"])
        pairs, reads, updates = pairs + p, reads + r, updates + u
        tokens += int(n)
    return pairs, reads, updates, tokens


def retention_work(a: Dict[str, Any], lengths: Iterable[int]
                   ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) the retention kernels of all layers require
    for these histories: the pairs, the state reads and updates (module
    docstring); q, k and v read and y written once a layer in the 2
    bytes the configuration states, the gates in 4. The carried state
    never leaves the chip."""
    pairs, reads, updates, tokens = retention_counts(a, lengths)
    flops = (pairs * pair_flops(a) + reads * read_flops(a)
             + updates * update_flops(a))
    per_token = 2.0 * (2 * a["H"] + 2 * a["hkv"]) * a["Dh"] + 4.0 * a["hkv"]
    return a["layers"] * flops, a["layers"] * tokens * per_token


def attention_work(a: Dict[str, Any], lengths: Iterable[int]
                   ) -> Tuple[float, float]:
    """No attention layer: (0, 0)."""
    return 0.0, 0.0


def serve_flops(a: Dict[str, Any], lengths: Iterable[int]) -> float:
    """FLOPs these queries require end to end: every token through the
    projections, the gates and the SwiGLU of every layer, the
    retention (`retention_work`), and each query's row of the head."""
    lengths = [int(n) for n in lengths]
    return (sum(lengths) * a["layers"] * token_matmul_flops(a)
            + retention_work(a, lengths)[0]
            + len(lengths) * 2.0 * a["V"] * a["D"])
