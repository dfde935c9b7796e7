"""Operations and bytes the Nemotron-H backbone REQUIRES, from shapes
and from the traffic's own counts, whatever implements them (as
`seq_opcount.py`, whose function names these keep so that the one
driver counts either stack): padding tokens, key blocks a kernel
computes and masks, rows of a grouped product that pad a group, and
the part of a scan's chunk that a mask throws away do not count, so an
implementation that wastes work reads a lower share. A Mamba-2 mixer
counts its two projections and its scan at the published chunk size;
its convolution (2 K multiplies and adds a channel), its gates and its
norm are no matrix product and are left out, as the norms and the
softmax are. Every layer is one block: its norm is the only gain
outside the block.

All counts take `a = nemotron_reference.arch(config file)`.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

CHUNK = 128          # the published chunk_size the scan is counted at


def mamba_matrix_params(a: Dict[str, Any]) -> int:
    inner = a["mH"] * a["mP"]
    return a["D"] * (2 * inner + 2 * a["mG"] * a["mN"] + a["mH"]) \
        + inner * a["D"]


def attn_params(a: Dict[str, Any]) -> int:
    return 2 * a["D"] * a["H"] * a["Dh"] + 2 * a["D"] * a["hkv"] * a["Dh"]


def _expert_params(a: Dict[str, Any]) -> int:
    return 2 * a["D"] * a["F"]


def _shared_params(a: Dict[str, Any]) -> int:
    return 2 * a["D"] * a["Fs"]


def block_params(a: Dict[str, Any], kind: str) -> int:
    """Every parameter of one block held here, its norm's gain among
    them."""
    D = a["D"]
    if kind == "mamba":
        inner = a["mH"] * a["mP"]
        conv = inner + 2 * a["mG"] * a["mN"]
        return (mamba_matrix_params(a) + (a["K"] + 1) * conv + inner
                + 3 * a["mH"] + D)
    if kind == "attn":
        return attn_params(a) + D
    return (D * a["E"] + a["E"] + a["held"] * _expert_params(a)
            + _shared_params(a) + D)


def n_layers(a: Dict[str, Any]) -> Dict[str, int]:
    return {k: sum(x == k for x in a["layers"])
            for k in ("mamba", "attn", "moe")}


def stack_params(a: Dict[str, Any]) -> int:
    """Parameters this chip holds: every block, the embedding, the
    head and the final gain."""
    return (2 * a["V"] * a["D"] + a["D"]
            + sum(block_params(a, kind) for kind in a["layers"]))


def uniform_pairs(a: Dict[str, Any], tokens: float) -> float:
    """(Token, held expert) pairs `tokens` tokens make over all expert
    layers under uniform routing: top_k x held / all a token and layer.
    The reckoning's figure; the cell counts the pairs its calls
    computed."""
    return tokens * a["top_k"] * a["held"] / a["E"] * n_layers(a)["moe"]


def scan_flops_a_token(a: Dict[str, Any]) -> float:
    """The chunked scan of one Mamba-2 block, a token: C B^T once a
    group (2 Q N G), the masked decay times it applied to step x
    (2 Q P H), the read of the carried state and the chunk's own state
    (2 N P H each)."""
    H, P, G, N = a["mH"], a["mP"], a["mG"], a["mN"]
    return 2.0 * CHUNK * N * G + 2.0 * CHUNK * P * H + 4.0 * N * P * H


def token_dense_flops(a: Dict[str, Any]) -> float:
    """FLOPs every token requires on this chip whatever its routing:
    two a parameter of the Mamba-2 and attention projections, the
    routers and the shared experts, and the scans."""
    nl = n_layers(a)
    return (nl["mamba"] * (2.0 * mamba_matrix_params(a)
                           + scan_flops_a_token(a))
            + nl["attn"] * 2.0 * attn_params(a)
            + nl["moe"] * 2.0 * (a["D"] * a["E"] + _shared_params(a)))


def pair_expert_flops(a: Dict[str, Any]) -> float:
    """FLOPs of one (token, held expert) pair: the expert's two
    projections."""
    return 2.0 * _expert_params(a)


def token_matmul_flops(a: Dict[str, Any]) -> float:
    """FLOPs one history token requires on this chip at its expected
    expert visits under uniform routing, the scans left out (ISSUE.md's
    530M active parameters: 1.06 GFLOP)."""
    return (token_dense_flops(a)
            - n_layers(a)["mamba"] * scan_flops_a_token(a)
            + uniform_pairs(a, 1.0) * pair_expert_flops(a))


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
    """(FLOPs, HBM bytes) the held experts' grouped products require
    for `pairs` (token, held expert) pairs, counted over all expert
    layers, in `calls` calls: each pair's two projections; every held
    expert's weights once a call and layer, each pair's input read and
    output written in 2 bytes. The shared expert is no grouped product:
    `serve_flops` counts it."""
    flops = pairs * pair_expert_flops(a)
    bytes_ = (n_layers(a)["moe"] * calls * a["held"] * _expert_params(a)
              * 2.0 + pairs * 2.0 * a["D"] * 2.0)
    return flops, bytes_


def ssm_work(a: Dict[str, Any], tokens: float, calls: float
             ) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) the scans of all Mamba-2 blocks require for
    `tokens` live tokens in `calls` calls: `scan_flops_a_token`; x, B
    and C read and y written once a block in 2 bytes, the step sizes
    read in 4, and the rates A once a call."""
    H, P, G, N = a["mH"], a["mP"], a["mG"], a["mN"]
    layers = n_layers(a)["mamba"]
    per_token = 2.0 * (H * P + 2 * G * N) + 4.0 * H + 2.0 * H * P
    return (tokens * layers * scan_flops_a_token(a),
            layers * (tokens * per_token + calls * 4.0 * H))


def serve_flops(a: Dict[str, Any], lengths: Iterable[int],
                pairs: float) -> float:
    """FLOPs these queries require end to end: every token through the
    projections, the scans, the routers and the shared experts, the
    `pairs` their tokens sent to held experts, the attention pairs, and
    each query's row of the head."""
    lengths = [int(n) for n in lengths]
    attn, _ = attention_work(a, lengths)
    return (sum(lengths) * token_dense_flops(a)
            + pairs * pair_expert_flops(a) + attn
            + len(lengths) * 2.0 * a["V"] * a["D"])
