"""The sequence recommender's backbone: a layer stack built from data.

A backbone is a `BackboneConfig`: widths, a list of layers each made
of one mixer kind (`attn_full`, `attn_window`, `conv`) and one
feed-forward kind (`ffn_dense`, `ffn_moe`), the norm, the positions,
and, where experts are spread over chips, the share held here. The
item catalog is the vocabulary and a user's event history the context.
Two families of configuration are built here:

  - `sasrec_config`: the template's own small block (LayerNorm, full
    attention with as many KV heads as heads, a ReLU feed-forward of
    twice the width, learned positions, the item table tied to the
    output). The default of `SeqRecParams`.
  - `config_from_json`: a public architecture's language-model stack
    from its own `config.json` keys (window and full attention with
    different KV head counts, a learned sink a head in window layers,
    rotary position on part or all of the head, RMSNorm of each head's
    q and k, gated short-convolution mixers, RMSNorm, a dense SwiGLU
    and sigmoid-routed experts), cut as the file says: `layer_ids`
    picks layers of the published patterns, the experts' count and
    `expert_share` say which experts live here, `vocab_size` how many
    rows of the vocabulary. Two families' keys are read into the one
    `BackboneConfig`, by the keys present: `hybrid_layer_pattern` /
    `moe_layer_freq` / `n_routed_experts` / `layernorm_epsilon`, and
    `layer_types` / `num_dense_layers` / `num_experts` / `norm_eps` /
    `conv_L_cache`.

`init_params` / `forward` are the one stack for all of them. The
parameters are one pytree keyed by layer (`l0`, `l1`, ...), a layer's
mixer under `attn` or `conv`. Attention itself is handed in
(`attend`): training runs padded batches through
`ops.attention.ring_attention`, serving runs packed histories through
`ops.attention.packed_attention`. The convolution mixer reads its
neighbours along the token axis and keeps a tap only where
`positions` (each event's index in its own history, which both
layouts hand to `forward`) says the neighbour is of the same history
(`conv_block`); everything else is position-wise and does not know
which layout it runs in.
"""

from __future__ import annotations

import dataclasses
import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import moe


@dataclass(frozen=True)
class BackboneConfig:
    name: str
    hidden: int
    vocab: int                       # rows of the item table held here
    layers: Tuple[Tuple[str, str], ...]      # (mixer, feed-forward)
    n_heads: int
    kv_heads_full: int
    kv_heads_window: int
    qk_dim: int
    v_dim: int
    norm: str = "rms"                # "rms" | "layer"
    eps: float = 1e-5
    act: str = "silu"                # "silu": gated (SwiGLU); "relu"
    dense_width: int = 0
    window: int = 0
    sink_window: bool = False        # a learned sink a head, window layers
    sink_full: bool = False
    rotary_dim: int = 0              # 0: learned positions (`positions`)
    rope_theta_full: float = 10000.0
    rope_theta_window: float = 10000.0
    value_scale: float = 1.0
    positions: int = 0               # rows of the learned position table
    embed_scale: float = 1.0
    tied: bool = False               # the item table is the output head
    pad_row: bool = False            # one more table row, the PAD item
    expert_width: int = 0
    n_experts: int = 0               # the router's width (all experts)
    top_k: int = 0
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    expert_first: int = 0            # held: first .. first + held - 1
    experts_held: int = 0
    qk_norm: bool = False            # RMSNorm of each head's q and k
    conv_kernel: int = 0             # taps of a `conv` mixer
    route_eps: float = 0.0           # added to the routing normaliser
    # serving: the longest history read, the tokens of one call, and
    # the padded sizes a call is compiled for
    max_history: int = 0
    max_batch_tokens: int = 0
    token_buckets: Tuple[int, ...] = ()

    def kv_heads(self, attn: str) -> int:
        return (self.kv_heads_window if attn == "attn_window"
                else self.kv_heads_full)


def sasrec_config(*, dim: int, n_heads: int, n_layers: int, seq_len: int,
                  n_items: int) -> BackboneConfig:
    """The template's own block, as it has always been."""
    hd = dim // n_heads
    return BackboneConfig(
        name="sasrec", hidden=dim, vocab=n_items,
        layers=(("attn_full", "ffn_dense"),) * n_layers,
        n_heads=n_heads, kv_heads_full=n_heads, kv_heads_window=n_heads,
        qk_dim=hd, v_dim=hd, norm="layer", eps=1e-6, act="relu",
        dense_width=2 * dim, positions=seq_len,
        embed_scale=math.sqrt(dim), tied=True, pad_row=True,
        max_history=seq_len, max_batch_tokens=64 * seq_len,
        token_buckets=_pow2_buckets(seq_len, 64 * seq_len))


def _pow2_buckets(lo: int, hi: int) -> Tuple[int, ...]:
    out, b = [], 1 << max(lo - 1, 0).bit_length()
    while b < hi:
        out.append(b)
        b *= 2
    return tuple(out) + (1 << max(hi - 1, 0).bit_length(),)


_MIXERS = {"full_attention": "attn_full", "conv": "conv"}


def _layers_from_json(doc: Dict[str, Any], ids) -> Tuple:
    """(mixer, feed-forward) of the layers `ids`, from whichever
    family's pattern keys the file has."""
    if "layer_types" in doc:
        unknown = sorted({t for t in doc["layer_types"]
                          if t not in _MIXERS})
        if unknown:
            raise ValueError(f"layer_types names {unknown}: the mixers "
                             f"here are {sorted(_MIXERS)}")
        dense = int(doc.get("num_dense_layers", 0))
        return tuple((_MIXERS[doc["layer_types"][i]],
                      "ffn_dense" if i < dense else "ffn_moe")
                     for i in ids)
    swa, sparse = doc["hybrid_layer_pattern"], doc["moe_layer_freq"]
    return tuple(("attn_window" if swa[i] else "attn_full",
                  "ffn_moe" if sparse[i] else "ffn_dense") for i in ids)


def config_from_json(doc: Dict[str, Any], name: str = "") -> BackboneConfig:
    """A configuration file in the architecture's own `config.json`
    keys, with the cut beside them (module docstring)."""
    ids = list(doc.get("layer_ids")
               or range(int(doc["num_hidden_layers"])))
    if len(ids) != int(doc["num_hidden_layers"]):
        raise ValueError("layer_ids does not list num_hidden_layers ids")
    layers = _layers_from_json(doc, ids)
    if doc.get("scoring_func", "sigmoid") != "sigmoid" \
            or int(doc.get("n_group") or 1) != 1 \
            or not doc.get("use_expert_bias", True):
        raise ValueError("the router here scores by sigmoid, one group, "
                         "and selects by score plus correction bias")
    if doc.get("conv_bias"):
        raise ValueError("the convolution mixer here has no bias")
    share = doc.get("expert_share") or {"index": 0, "count": 1}
    held = int(doc.get("n_routed_experts") or doc.get("num_experts") or 0)
    serving = doc.get("assumed") or {}
    heads = int(doc["num_attention_heads"])
    qk = int(doc.get("head_dim") or int(doc["hidden_size"]) // heads)
    return BackboneConfig(
        name=name or str(doc.get("name", "")),
        hidden=int(doc["hidden_size"]), vocab=int(doc["vocab_size"]),
        layers=layers, n_heads=heads,
        kv_heads_full=int(doc["num_key_value_heads"]),
        kv_heads_window=int(doc.get("swa_num_key_value_heads")
                            or doc["num_key_value_heads"]),
        qk_dim=qk, v_dim=int(doc.get("v_head_dim") or qk),
        eps=float(doc.get("layernorm_epsilon")
                  or doc.get("norm_eps", 1e-5)),
        act=str(doc.get("hidden_act", "silu")),
        dense_width=int(doc["intermediate_size"]),
        window=int(doc.get("sliding_window") or 0),
        sink_window=bool(doc.get("add_swa_attention_sink_bias")),
        sink_full=bool(doc.get("add_full_attention_sink_bias")),
        rotary_dim=2 * (int(qk * float(doc.get("partial_rotary_factor",
                                               1.0))) // 2),
        rope_theta_full=float(doc.get("rope_theta", 10000.0)),
        rope_theta_window=float(doc.get("swa_rope_theta")
                                or doc.get("rope_theta", 10000.0)),
        value_scale=float(doc.get("attention_value_scale") or 1.0),
        tied=bool(doc.get("tie_word_embeddings")),
        expert_width=int(doc.get("moe_intermediate_size") or 0),
        n_experts=held * int(share["count"]),
        top_k=int(doc.get("num_experts_per_tok") or 0),
        norm_topk_prob=bool(doc.get("norm_topk_prob", True)),
        routed_scale=float(doc.get("routed_scaling_factor") or 1.0),
        expert_first=int(share["index"]) * held, experts_held=held,
        qk_norm=bool(doc.get("qk_norm")),
        conv_kernel=int(doc.get("conv_L_cache") or 0),
        route_eps=float(doc.get("route_norm_eps") or 0.0),
        max_history=int(serving.get("max_history", 0)),
        max_batch_tokens=int(serving.get("max_batch_tokens", 0)),
        token_buckets=tuple(int(b) for b in
                            serving.get("token_buckets", ())))


def load_config(path: str) -> BackboneConfig:
    """A configuration file; its name is the file's."""
    if not Path(path).is_file():
        raise ValueError(f"no backbone configuration file {path!r}")
    with open(path, "r", encoding="utf-8") as f:
        return config_from_json(json.load(f), name=Path(path).stem)


def config_dict(cfg: BackboneConfig) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)


def config_of(doc: Dict[str, Any]) -> BackboneConfig:
    doc = dict(doc)
    doc["layers"] = tuple(tuple(x) for x in doc["layers"])
    doc["token_buckets"] = tuple(doc["token_buckets"])
    return BackboneConfig(**doc)


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: BackboneConfig) -> Dict[str, Any]:
    """The pytree's shapes; `init_params` gives each leaf, in the
    tree's own order, one key of the split."""
    D, H = cfg.hidden, cfg.n_heads
    norm = ({"g": (D,), "b": (D,)} if cfg.norm == "layer"
            else {"g": (D,)})
    p: Dict[str, Any] = {"embed": (cfg.vocab + int(cfg.pad_row), D)}
    if cfg.positions:
        p["pos"] = (cfg.positions, D)
    if not cfg.tied:
        p["head"] = (cfg.vocab, D)
    p["norm_f"] = dict(norm)
    for li, (mixer, ffn) in enumerate(cfg.layers):
        if mixer == "conv":
            m = {"w_in": (D, 3 * D), "kernel": (cfg.conv_kernel, D),
                 "w_out": (D, D)}
        else:
            hkv = cfg.kv_heads(mixer)
            m = {"wq": (D, H * cfg.qk_dim), "wk": (D, hkv * cfg.qk_dim),
                 "wv": (D, hkv * cfg.v_dim), "wo": (H * cfg.v_dim, D)}
            if cfg.qk_norm:     # one gain vector, shared by the heads
                m["q_norm"] = {"g": (cfg.qk_dim,)}
                m["k_norm"] = {"g": (cfg.qk_dim,)}
            if (cfg.sink_window if mixer == "attn_window"
                    else cfg.sink_full):
                m["sink"] = (H,)
        if ffn == "ffn_moe":
            E, F = cfg.experts_held, cfg.expert_width
            f = {"router": (D, cfg.n_experts), "bias": (cfg.n_experts,),
                 "w_gate_up": (E, D, 2 * F), "w_down": (E, F, D)}
        elif cfg.act == "relu":
            f = {"w1": (D, cfg.dense_width), "w2": (cfg.dense_width, D)}
        else:
            f = {"w_gate": (D, cfg.dense_width),
                 "w_up": (D, cfg.dense_width),
                 "w_down": (cfg.dense_width, D)}
        p[f"l{li}"] = {"norm1": dict(norm),
                       "conv" if mixer == "conv" else "attn": m,
                       "norm2": dict(norm), "ffn": f}
    return p


def n_params(cfg: BackboneConfig) -> int:
    return int(sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))))


def init_params(key, cfg: BackboneConfig, dtype=jnp.float32):
    """Random parameters: matrices N(0, 1 / fan_in), norm gains 1 and
    biases 0, learned positions N(0, 0.02^2), the router's correction
    bias N(0, 0.01^2) and the sinks N(0, 1) (what a trained model
    carries there is not published; they must not be zero, or a test
    could not tell them from absent). The item table is N(0, 1 /
    hidden): its fan-in is the width it is read into. A convolution's
    kernel [L, D] is N(0, 1 / L) by the same rule: L taps feed each
    output."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = iter(jax.random.split(key, len(flat)))

    def draw(path, shape):
        leaf, k = path[-1].key, next(keys)
        if leaf == "g":
            return jnp.ones(shape, jnp.float32)
        if leaf == "b":
            return jnp.zeros(shape, jnp.float32)
        if leaf in ("sink", "bias"):
            return (jax.random.normal(k, shape, jnp.float32)
                    * (1.0 if leaf == "sink" else 0.01))
        if leaf == "pos":
            return jax.random.normal(k, shape, jnp.float32) * 0.02
        fan = cfg.hidden if leaf in ("embed", "head") else shape[-2]
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan)).astype(dtype)

    return jax.tree_util.tree_unflatten(
        tree, [draw(path, shape) for path, shape in flat])


# -- the blocks ---------------------------------------------------------------

def _mm(x, w):
    """x @ w in the weights' precision, accumulated in float32."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def norm(x, p, cfg: BackboneConfig):
    x = x.astype(jnp.float32)
    if cfg.norm == "layer":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + cfg.eps) * p["g"] + p["b"]
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                             + cfg.eps) * p["g"]


def rotary(x, positions, *, dim: int, theta: float):
    """Rotary position on the first `dim` of the head's dimensions,
    half-split: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin) with x1
    the first dim / 2 and x2 the next. x [..., T, H, Dh], positions
    [..., T]."""
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention_block(p, cfg: BackboneConfig, kind: str, u, positions,
                    attend: Callable):
    """u [..., T, D] (normed) -> [..., T, D]. `attend(q, k, v, window=,
    sink=)` is handed q [..., T, H, Dq], k, v with their own head
    counts."""
    lead, hkv = u.shape[:-1], cfg.kv_heads(kind)
    q = _mm(u, p["wq"]).reshape(*lead, cfg.n_heads, cfg.qk_dim)
    k = _mm(u, p["wk"]).reshape(*lead, hkv, cfg.qk_dim)
    v = _mm(u, p["wv"]).reshape(*lead, hkv, cfg.v_dim)
    if cfg.value_scale != 1.0:
        v = v * cfg.value_scale
    if cfg.qk_norm:
        q, k = norm(q, p["q_norm"], cfg), norm(k, p["k_norm"], cfg)
    if cfg.rotary_dim:
        theta = (cfg.rope_theta_window if kind == "attn_window"
                 else cfg.rope_theta_full)
        q = rotary(q, positions, dim=cfg.rotary_dim, theta=theta)
        k = rotary(k, positions, dim=cfg.rotary_dim, theta=theta)
    dt = p["wq"].dtype
    a = attend(q.astype(dt), k.astype(dt), v.astype(dt),
               window=cfg.window if kind == "attn_window" else None,
               sink=p.get("sink"))
    return _mm(a.reshape(*lead, cfg.n_heads * cfg.v_dim), p["wo"])


def conv_block(p, cfg: BackboneConfig, u, positions):
    """The gated short convolution: u [..., T, D] (normed) -> [..., T,
    D]. [B, C, X] = u W_in; z = B * X; c_t = sum over taps j of
    K[L - 1 - j] * z_{t - j}; the result is (C * c) W_out. Depthwise,
    causal, no bias. The neighbour t - j is read along the token axis,
    and tap j is kept only where the event's index in its own history
    is at least j: before a history's first event stands zero, not
    the end of the history packed in front of it (serving) nor the
    padding a right-aligned row starts with (training)."""
    D, L = cfg.hidden, cfg.conv_kernel
    bcx = _mm(u, p["w_in"])
    z = bcx[..., :D] * bcx[..., 2 * D:]
    kernel = p["kernel"].astype(jnp.float32)
    c = kernel[L - 1] * z
    for j in range(1, L):
        c = c + jnp.where((positions >= j)[..., None],
                          kernel[L - 1 - j] * jnp.roll(z, j, axis=-2), 0.0)
    return _mm(bcx[..., D:2 * D] * c, p["w_out"])


def ffn_dense(p, cfg: BackboneConfig, u):
    if cfg.act == "relu":
        return _mm(jax.nn.relu(_mm(u, p["w1"])), p["w2"])
    return _mm(jax.nn.silu(_mm(u, p["w_gate"])) * _mm(u, p["w_up"]),
               p["w_down"])


def ffn_moe(p, cfg: BackboneConfig, u, live=None):
    """The held experts' part of the expert layer, and its counts."""
    lead = u.shape[:-1]
    flat = u.reshape(-1, cfg.hidden)
    routing = moe.route(flat, p["router"], p["bias"], top_k=cfg.top_k,
                        norm_topk_prob=cfg.norm_topk_prob,
                        scale=cfg.routed_scale, eps=cfg.route_eps)
    y, stats = moe.moe_apply(
        flat, routing, p["w_gate_up"], p["w_down"],
        first=cfg.expert_first, n_experts=cfg.n_experts,
        live=None if live is None else live.reshape(-1))
    return y.reshape(*lead, cfg.hidden), stats


def forward(params, cfg: BackboneConfig, tokens, positions,
            attend: Callable, *, valid=None):
    """tokens [..., T] item ids, positions [..., T] each event's index
    in its own history, `valid` [..., T] bool (False: a PAD slot, which
    reads a zero vector and is left out of the experts). Returns the
    final norm's output [..., T, D] float32 and the expert layers'
    `MoeStats`, stacked over those layers (None without one)."""
    x = params["embed"][jnp.clip(tokens, 0, params["embed"].shape[0] - 1)]
    x = x.astype(jnp.float32) * cfg.embed_scale
    if cfg.positions:
        x = x + params["pos"][jnp.clip(positions, 0, cfg.positions - 1)]
    if valid is not None and not cfg.pad_row:
        x = jnp.where(valid[..., None], x, 0.0)
    stats = []
    # one named scope a block kind, so that a profile groups by them
    for li, (mixer, ffn) in enumerate(cfg.layers):
        lp = params[f"l{li}"]
        u = norm(x, lp["norm1"], cfg)
        if mixer == "conv":
            with jax.named_scope("mixer_conv"):
                x = x + conv_block(lp["conv"], cfg, u, positions)
        else:
            with jax.named_scope("mixer_attn"):
                x = x + attention_block(lp["attn"], cfg, mixer, u,
                                        positions, attend)
        u = norm(x, lp["norm2"], cfg)
        if ffn == "ffn_moe":
            with jax.named_scope("ffn_experts"):
                y, st = ffn_moe(lp["ffn"], cfg, u, live=valid)
            stats.append(st)
        else:
            with jax.named_scope("ffn_dense"):
                y = ffn_dense(lp["ffn"], cfg, u)
        x = x + y
    out = norm(x, params["norm_f"], cfg)
    if not stats:
        return out, None
    return out, moe.MoeStats(jnp.stack([s.expert_tokens for s in stats]),
                             jnp.stack([s.unrouted for s in stats]))


def head_table(params, cfg: BackboneConfig):
    """[vocab, D]: the rows the last position is scored against."""
    return (params["embed"][:cfg.vocab] if cfg.tied else params["head"])
