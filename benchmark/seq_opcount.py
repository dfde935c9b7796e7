"""Operations and bytes the sequence backbone REQUIRES, from shapes and
from the traffic's own counts, whatever implements them (as
`opcount.py` for ALS and top-k): padding tokens, key blocks a kernel
computes and masks, and rows of a grouped product that pad a group do
not count, so an implementation that wastes work reads a lower share.

All counts take `a = seq_reference.arch(config file)`.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple


def _attn_params(a: Dict[str, Any], kind: str) -> int:
    D, H, hkv = a["D"], a["H"], a["hkv"][kind]
    return (D * H * a["Dq"] + D * hkv * a["Dq"] + D * hkv * a["Dv"]
            + H * a["Dv"] * D)


def _expert_params(a: Dict[str, Any]) -> int:
    return 3 * a["D"] * a["F"]


def stack_params(a: Dict[str, Any]) -> int:
    """Parameters this chip holds: every layer's attention, the dense
    feed-forward or the router with its held experts, the norm gains,
    sinks and correction biases, the embedding and the head."""
    D = a["D"]
    n = 2 * a["V"] * D + D
    for kind, ffn in a["layers"]:
        n += _attn_params(a, kind) + 2 * D
        if a["sink"][kind]:
            n += a["H"]
        if ffn == "moe":
            n += D * a["E"] + a["E"] + a["held"] * _expert_params(a)
        else:
            n += 3 * D * a["dense"]
    return n


def expert_visits(a: Dict[str, Any]) -> float:
    """(Token, held expert) pairs a token and expert layer under
    uniform routing: top_k x held / all. The reckoning's figure; the
    cells count the pairs their calls computed."""
    return a["top_k"] * a["held"] / a["E"]


def uniform_pairs(a: Dict[str, Any], tokens: float) -> float:
    """Pairs `tokens` tokens make over all expert layers under uniform
    routing."""
    return tokens * expert_visits(a) * n_layers(a)["moe"]


def token_dense_flops(a: Dict[str, Any]) -> float:
    """Matrix-product FLOPs every token requires on this chip whatever
    its routing: two a parameter of the attention projections, the
    dense feed-forward and the routers."""
    f = 0.0
    for kind, ffn in a["layers"]:
        f += 2.0 * _attn_params(a, kind)
        f += (2.0 * a["D"] * a["E"] if ffn == "moe"
              else 2.0 * 3 * a["D"] * a["dense"])
    return f


def pair_expert_flops(a: Dict[str, Any]) -> float:
    """FLOPs of one (token, held expert) pair: the expert's three
    projections."""
    return 2.0 * _expert_params(a)


def token_matmul_flops(a: Dict[str, Any]) -> float:
    """Matrix-product FLOPs one token requires on this chip at its
    expected expert visits under uniform routing (ISSUE.md's 1.87
    GFLOP)."""
    return token_dense_flops(a) + uniform_pairs(a, 1.0) * pair_expert_flops(a)


def pair_flops(a: Dict[str, Any]) -> float:
    """Attention FLOPs a (query, key) pair, every head: scores and
    values."""
    return 2.0 * a["H"] * (a["Dq"] + a["Dv"])


def history_pairs(a: Dict[str, Any], length: int) -> Tuple[int, int]:
    """(pairs in one full layer, pairs in one window layer) for one
    history: query t sees t + 1 keys, or min(t + 1, window)."""
    w = min(a["window"], length)
    return (length * (length + 1) // 2,
            w * (w + 1) // 2 + (length - w) * a["window"])


def n_layers(a: Dict[str, Any]) -> Dict[str, int]:
    return {"full": sum(k == "full" for k, _ in a["layers"]),
            "window": sum(k == "window" for k, _ in a["layers"]),
            "moe": sum(f == "moe" for _, f in a["layers"])}


def attention_work(a: Dict[str, Any], lengths: Iterable[int]
                   ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) the attention of all layers requires for
    these histories: q, k and v read and the output written once a
    layer, in the 2 bytes the configuration states."""
    nl = n_layers(a)
    pf = pw = tokens = 0
    for n in lengths:
        f, w = history_pairs(a, int(n))
        pf, pw, tokens = pf + f, pw + w, tokens + int(n)
    flops = pair_flops(a) * (nl["full"] * pf + nl["window"] * pw)

    def per_token(kind):
        return 2.0 * (a["H"] * (a["Dq"] + a["Dv"])
                      + a["hkv"][kind] * (a["Dq"] + a["Dv"]))

    bytes_ = tokens * (nl["full"] * per_token("full")
                       + nl["window"] * per_token("window"))
    return flops, bytes_


def moe_work(a: Dict[str, Any], pairs: float, calls: float
             ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) the held experts' grouped products require
    for `pairs` (token, held expert) pairs, counted over all expert
    layers, in `calls` calls: each pair's three projections; every held
    expert's weights once a call and layer, each pair's input read and
    output written in 2 bytes."""
    flops = pairs * pair_expert_flops(a)
    bytes_ = (n_layers(a)["moe"] * calls * a["held"] * _expert_params(a)
              * 2.0 + pairs * 2.0 * a["D"] * 2.0)
    return flops, bytes_


def serve_flops(a: Dict[str, Any], lengths: Iterable[int],
                pairs: float) -> float:
    """FLOPs these queries require end to end: every token through the
    attention projections, the dense layer and the routers, the
    `pairs` their tokens sent to held experts, the attention pairs, and
    each query's row of the head."""
    lengths = [int(n) for n in lengths]
    attn, _ = attention_work(a, lengths)
    return (sum(lengths) * token_dense_flops(a)
            + pairs * pair_expert_flops(a) + attn
            + len(lengths) * 2.0 * a["V"] * a["D"])
