"""Operations and bytes the LFM2 backbone REQUIRES, from shapes and
from the traffic's own counts, whatever implements them (as
`seq_opcount.py`, whose function names these keep so that the one
driver counts either stack): padding tokens, key blocks a kernel
computes and masks, and rows of a grouped product that pad a group do
not count, so an implementation that wastes work reads a lower share.
A convolution mixer counts its two projections; its gates and taps
(2 L + 2 multiplies and adds a channel) are no matrix product and are
left out, as the norms and the softmax are.

All counts take `a = lfm2_reference.arch(config file)`.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple


def mixer_params(a: Dict[str, Any], mixer: str) -> int:
    """Matrix parameters of one mixer (no gains, no kernel)."""
    D = a["D"]
    if mixer == "conv":
        return D * 3 * D + D * D
    return 2 * D * a["H"] * a["Dh"] + 2 * D * a["hkv"] * a["Dh"]


def _expert_params(a: Dict[str, Any]) -> int:
    return 3 * a["D"] * a["F"]


def n_layers(a: Dict[str, Any]) -> Dict[str, int]:
    return {"conv": sum(m == "conv" for m, _ in a["layers"]),
            "attn": sum(m == "attn" for m, _ in a["layers"]),
            "moe": sum(f == "moe" for _, f in a["layers"])}


def stack_params(a: Dict[str, Any]) -> int:
    """Parameters this chip holds: every layer's mixer (a convolution's
    kernel, an attention's two gains of Dh), its two norm gains, the
    dense feed-forward or the router with its bias and all experts, the
    tied table and the final gain."""
    D = a["D"]
    n = a["V"] * D + D
    for mixer, ffn in a["layers"]:
        n += mixer_params(a, mixer) + 2 * D
        n += a["L"] * D if mixer == "conv" else 2 * a["Dh"] * a["qk_norm"]
        if ffn == "moe":
            n += D * a["E"] + a["E"] + a["E"] * _expert_params(a)
        else:
            n += 3 * D * a["dense"]
    return n


def uniform_pairs(a: Dict[str, Any], tokens: float) -> float:
    """(Token, expert) pairs `tokens` tokens make over all expert
    layers: every expert is held, so top_k a token and layer whatever
    the routing."""
    return tokens * a["top_k"] * n_layers(a)["moe"]


def token_dense_flops(a: Dict[str, Any]) -> float:
    """Matrix-product FLOPs every token requires whatever its routing:
    two a parameter of the mixers' projections, the dense feed-forward
    and the routers."""
    f = 0.0
    for mixer, ffn in a["layers"]:
        f += 2.0 * mixer_params(a, mixer)
        f += (2.0 * a["D"] * a["E"] if ffn == "moe"
              else 2.0 * 3 * a["D"] * a["dense"])
    return f


def pair_expert_flops(a: Dict[str, Any]) -> float:
    """FLOPs of one (token, expert) pair: the expert's three
    projections."""
    return 2.0 * _expert_params(a)


def token_matmul_flops(a: Dict[str, Any]) -> float:
    """Matrix-product FLOPs one history token requires (ISSUE.md's
    1,423 MFLOP)."""
    return token_dense_flops(a) + uniform_pairs(a, 1.0) * pair_expert_flops(a)


def pair_flops(a: Dict[str, Any]) -> float:
    """Attention FLOPs a (query, key) pair, every head: scores and
    values."""
    return 2.0 * a["H"] * 2 * a["Dh"]


def history_pairs(length: int) -> int:
    """Pairs in one attention layer for one history: query t sees
    t + 1 keys."""
    return length * (length + 1) // 2


def attention_work(a: Dict[str, Any], lengths: Iterable[int]
                   ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) the attention of all attention layers
    requires for these histories: q, k and v read and the output
    written once a layer, in the 2 bytes the configuration states."""
    pairs = tokens = 0
    for n in lengths:
        pairs, tokens = pairs + history_pairs(int(n)), tokens + int(n)
    layers = n_layers(a)["attn"]
    per_token = 2.0 * 2 * a["Dh"] * (a["H"] + a["hkv"])
    return pair_flops(a) * layers * pairs, tokens * layers * per_token


def moe_work(a: Dict[str, Any], pairs: float, calls: float
             ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) the experts' grouped products require for
    `pairs` (token, expert) pairs, counted over all expert layers, in
    `calls` calls: each pair's three projections; every expert's
    weights once a call and layer, each pair's input read and output
    written in 2 bytes."""
    flops = pairs * pair_expert_flops(a)
    bytes_ = (n_layers(a)["moe"] * calls * a["E"] * _expert_params(a)
              * 2.0 + pairs * 2.0 * a["D"] * 2.0)
    return flops, bytes_


def serve_flops(a: Dict[str, Any], lengths: Iterable[int],
                pairs: float) -> float:
    """FLOPs these queries require end to end: every token through the
    mixers' projections, the dense layers and the routers, the `pairs`
    their tokens sent to experts, the attention pairs, and each query's
    row of the head."""
    lengths = [int(n) for n in lengths]
    attn, _ = attention_work(a, lengths)
    return (sum(lengths) * token_dense_flops(a)
            + pairs * pair_expert_flops(a) + attn
            + len(lengths) * 2.0 * a["V"] * a["D"])
