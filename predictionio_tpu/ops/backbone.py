"""The sequence recommender's backbone: a layer stack built from data.

A backbone is a `BackboneConfig`: widths, a list of layers, the norm,
the positions, and, where experts are spread over chips, the share
held here. A layer is a tuple of block kinds, each block `x + Block(
Norm(x))`: a mixer (`attn_full`, `attn_window`, `conv`, `ssm`,
`retention`) followed by a feed-forward (`ffn_dense`, `ffn_moe`), or
one block alone. The item catalog is the vocabulary and a user's event
history the context. Two families of configuration are built here:

  - `sasrec_config`: the template's own small block (LayerNorm, full
    attention with as many KV heads as heads, a ReLU feed-forward of
    twice the width, learned positions, the item table tied to the
    output). The default of `SeqRecParams`.
  - `config_from_json`: a public architecture's language-model stack
    from its own `config.json` keys (window and full attention with
    different KV head counts, a learned sink a head in window layers,
    rotary position on part or all of the head, RMSNorm of each head's
    q and k, attention with no position at all, gated
    short-convolution mixers, Mamba-2 state-space mixers, degree-2
    power-retention mixers, RMSNorm, a dense SwiGLU, sigmoid-routed
    experts that are SwiGLUs or two-matrix squared-ReLU, and a shared
    expert), cut as the file says: `layer_ids` picks layers of the
    published patterns, the experts' count and `expert_share` say which
    experts live here, `vocab_size` how many rows of the vocabulary.
    Four families' keys are read into the one `BackboneConfig`; the
    key that carries the layer pattern tells the family, and a file
    with none names it by `model_type` (`brumby`: every layer a
    `retention` mixer and a dense SwiGLU); `_FAMILIES` holds each
    one's names.

`init_params` / `forward` are the one stack for all of them. The
parameters are one pytree keyed by layer (`l0`, `l1`, ...): a block's
norm under `norm1` (the layer's first block) or `norm2`, a mixer under
`attn`, `conv`, `ssm` or `retention`, a feed-forward under `ffn`.
Attention itself
is handed in (`attend`): training runs padded batches through
`ops.attention.ring_attention`, serving runs packed histories through
`ops.attention.packed_attention`. The convolution, state-space and
retention mixers read along the token axis, and `positions` (each
event's index in its own history, which both layouts hand to
`forward`) says where a history begins: a tap is kept only where the
neighbour is of the same history (`conv_block`, `ssm_block`), and the
state is zero before an event of index 0 (`ssm_block`,
`retention_block`). Everything else is position-wise and does not know
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

from predictionio_tpu.ops import moe, retention, ssm


@dataclass(frozen=True)
class BackboneConfig:
    name: str
    hidden: int
    vocab: int                       # rows of the item table held here
    layers: Tuple[Tuple[str, ...], ...]  # (mixer, feed-forward) | (block,)
    n_heads: int
    kv_heads_full: int
    kv_heads_window: int
    qk_dim: int
    v_dim: int
    norm: str = "rms"                # "rms" | "layer"
    eps: float = 1e-5
    act: str = "silu"       # "silu": gated (SwiGLU); "relu"; "relu2"
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
    conv_kernel: int = 0             # taps of a `conv` or `ssm` mixer
    route_eps: float = 0.0           # added to the routing normaliser
    conv_bias: bool = False          # the `ssm` mixer's convolution
    ssm_heads: int = 0               # an `ssm` mixer: heads, each of
    ssm_head_dim: int = 0            # this width,
    ssm_groups: int = 0              # in groups that share B and C
    ssm_state: int = 0               # of this many dimensions,
    ssm_chunk: int = 0               # scanned in chunks of this length
    shared_width: int = 0            # the expert layer's shared expert
    retention_chunk: int = 0         # a `retention` mixer's chunk,
    retention_eps: float = 0.0       # and its normaliser's epsilon
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
_PATTERN_BLOCKS = {"M": "ssm", "E": "ffn_moe", "*": "attn_full"}

# Each family's own name for what `BackboneConfig` holds, where the
# families differ; None where a family has no such thing. The key that
# carries the layer pattern tells the family.
_FAMILIES: Dict[str, Dict[str, Any]] = {
    "hybrid_layer_pattern": {           # MiMo-V2: window + full attention
        "eps": "layernorm_epsilon", "experts": "n_routed_experts",
        "act": "hidden_act", "rotary": "partial_rotary_factor",
        "conv_kernel": None, "conv_bias": None},
    "layer_types": {                    # lfm2_moe: gated short convolutions
        "eps": "norm_eps", "experts": "num_experts",
        "act": "hidden_act", "rotary": "partial_rotary_factor",
        "conv_kernel": "conv_L_cache", "conv_bias": "conv_bias"},
    "hybrid_override_pattern": {        # nemotron_h: one block a layer;
        "eps": "layer_norm_epsilon",    # its attention takes no position
        "experts": "n_routed_experts", "act": "mlp_hidden_act",
        "rotary": None, "conv_kernel": "conv_kernel",
        "conv_bias": "use_conv_bias"},
    "brumby": {                         # every layer power retention and
        "eps": "rms_norm_eps",          # a dense SwiGLU: no pattern key,
        "experts": None, "act": "hidden_act",   # its model_type names it
        "rotary": "partial_rotary_factor", "conv_kernel": None,
        "conv_bias": None},
}


def _family(doc: Dict[str, Any]) -> Dict[str, Any]:
    """The family whose pattern key the file carries, or, for a stack
    of one layer kind that has no pattern key, whose name is the file's
    `model_type`."""
    found = [k for k in _FAMILIES
             if k in doc or doc.get("model_type") == k]
    if len(found) != 1:
        raise ValueError(f"a configuration carries one layer pattern of "
                         f"{sorted(_FAMILIES)}; this one has {found}")
    return _FAMILIES[found[0]]


def _layers_from_json(doc: Dict[str, Any], ids) -> Tuple:
    """The blocks of the layers `ids`, from whichever family's pattern
    keys the file has."""
    if doc.get("model_type") == "brumby":
        return tuple(("retention", "ffn_dense") for _ in ids)
    if "hybrid_override_pattern" in doc:
        pattern = doc["hybrid_override_pattern"]
        unknown = sorted(set(pattern) - set(_PATTERN_BLOCKS))
        if unknown:
            raise ValueError(f"hybrid_override_pattern has {unknown}: the "
                             f"blocks here are {sorted(_PATTERN_BLOCKS)}")
        return tuple((_PATTERN_BLOCKS[pattern[i]],) for i in ids)
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
    names = _family(doc)

    def of(field: str, default=None):
        """The family's value for `field`; `default` where it has no
        such key or the file leaves it out or null."""
        key = names[field]
        value = None if key is None else doc.get(key)
        return default if value is None else value

    ids = list(doc.get("layer_ids")
               or range(int(doc["num_hidden_layers"])))
    if len(ids) != int(doc["num_hidden_layers"]):
        raise ValueError("layer_ids does not list num_hidden_layers ids")
    layers = _layers_from_json(doc, ids)
    kinds = {kind for blocks in layers for kind in blocks}
    if doc.get("scoring_func", "sigmoid") != "sigmoid" \
            or int(doc.get("n_group") or 1) != 1 \
            or not doc.get("use_expert_bias", True):
        raise ValueError("the router here scores by sigmoid, one group, "
                         "and selects by score plus correction bias")
    conv_bias = bool(of("conv_bias", False))
    if conv_bias and "conv" in kinds:
        raise ValueError("the convolution mixer here has no bias")
    serving = doc.get("assumed") or {}
    if "retention" in kinds and not serving.get("retention_chunk"):
        raise ValueError("a retention mixer needs assumed.retention_chunk")
    share = doc.get("expert_share") or {"index": 0, "count": 1}
    held = int(of("experts", 0))
    heads = int(doc["num_attention_heads"])
    qk = int(doc.get("head_dim") or int(doc["hidden_size"]) // heads)
    rotary = (0.0 if names["rotary"] is None
              else float(of("rotary", 1.0)))
    return BackboneConfig(
        name=name or str(doc.get("name", "")),
        hidden=int(doc["hidden_size"]), vocab=int(doc["vocab_size"]),
        layers=layers, n_heads=heads,
        kv_heads_full=int(doc["num_key_value_heads"]),
        kv_heads_window=int(doc.get("swa_num_key_value_heads")
                            or doc["num_key_value_heads"]),
        qk_dim=qk, v_dim=int(doc.get("v_head_dim") or qk),
        eps=float(of("eps", 1e-5)),
        act=str(of("act", "silu")),
        dense_width=int(doc["intermediate_size"]),
        window=int(doc.get("sliding_window") or 0),
        sink_window=bool(doc.get("add_swa_attention_sink_bias")),
        sink_full=bool(doc.get("add_full_attention_sink_bias")),
        rotary_dim=2 * (int(qk * rotary) // 2),
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
        conv_kernel=int(of("conv_kernel", 0)),
        route_eps=float(doc.get("route_norm_eps") or 0.0),
        conv_bias=conv_bias,
        ssm_heads=int(doc.get("mamba_num_heads") or 0),
        ssm_head_dim=int(doc.get("mamba_head_dim") or 0),
        ssm_groups=int(doc.get("n_groups") or 0),
        ssm_state=int(doc.get("ssm_state_size") or 0),
        ssm_chunk=int(doc.get("chunk_size") or 0),
        shared_width=int(doc.get("moe_shared_expert_intermediate_size")
                         or 0),
        retention_chunk=int(serving.get("retention_chunk", 0)),
        retention_eps=float(serving.get("retention_eps", 0.0)),
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

# where a block's parameters stand in its layer's dict
_PARAM_KEY = {"attn_full": "attn", "attn_window": "attn", "conv": "conv",
              "ssm": "ssm", "retention": "retention", "ffn_dense": "ffn",
              "ffn_moe": "ffn"}


def _block_shapes(cfg: BackboneConfig, kind: str) -> Dict[str, Any]:
    D, H = cfg.hidden, cfg.n_heads
    if kind == "conv":
        return {"w_in": (D, 3 * D), "kernel": (cfg.conv_kernel, D),
                "w_out": (D, D)}
    if kind == "ssm":
        inner = cfg.ssm_heads * cfg.ssm_head_dim
        conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
        m = {"w_in": (D, inner + conv + cfg.ssm_heads),   # z | x B C | dt
             "kernel": (cfg.conv_kernel, conv),
             "dt_bias": (cfg.ssm_heads,), "a_log": (cfg.ssm_heads,),
             "d": (cfg.ssm_heads,), "norm": {"g": (inner,)},
             "w_out": (inner, D)}
        if cfg.conv_bias:
            m["conv_bias"] = (conv,)
        return m
    if kind == "ffn_moe":
        E, F = cfg.experts_held, cfg.expert_width
        f = {"router": (D, cfg.n_experts), "bias": (cfg.n_experts,),
             "w_down": (E, F, D)}
        if cfg.act == "relu2":      # two matrices an expert, not gated
            f["w_up"] = (E, D, F)
        else:
            f["w_gate_up"] = (E, D, 2 * F)
        if cfg.shared_width:
            f["shared"] = {"w_up": (D, cfg.shared_width),
                           "w_down": (cfg.shared_width, D)}
        return f
    if kind == "ffn_dense":
        if cfg.act == "relu":
            return {"w1": (D, cfg.dense_width), "w2": (cfg.dense_width, D)}
        return {"w_gate": (D, cfg.dense_width), "w_up": (D, cfg.dense_width),
                "w_down": (cfg.dense_width, D)}
    hkv = cfg.kv_heads(kind)
    m = {"wq": (D, H * cfg.qk_dim), "wk": (D, hkv * cfg.qk_dim),
         "wv": (D, hkv * cfg.v_dim), "wo": (H * cfg.v_dim, D)}
    if cfg.qk_norm:     # one gain vector, shared by the heads
        m["q_norm"] = {"g": (cfg.qk_dim,)}
        m["k_norm"] = {"g": (cfg.qk_dim,)}
    if kind == "retention":     # one gate a KV head
        m["w_gate"] = (D, hkv)
        m["b_gate"] = (hkv,)
    if (cfg.sink_window if kind == "attn_window" else cfg.sink_full):
        m["sink"] = (H,)
    return m


def param_shapes(cfg: BackboneConfig) -> Dict[str, Any]:
    """The pytree's shapes; `init_params` gives each leaf, in the
    tree's own order, one key of the split."""
    D = cfg.hidden
    norm = ({"g": (D,), "b": (D,)} if cfg.norm == "layer"
            else {"g": (D,)})
    p: Dict[str, Any] = {"embed": (cfg.vocab + int(cfg.pad_row), D)}
    if cfg.positions:
        p["pos"] = (cfg.positions, D)
    if not cfg.tied:
        p["head"] = (cfg.vocab, D)
    p["norm_f"] = dict(norm)
    for li, blocks in enumerate(cfg.layers):
        layer: Dict[str, Any] = {}
        for j, kind in enumerate(blocks):
            layer[f"norm{j + 1}"] = dict(norm)
            layer[_PARAM_KEY[kind]] = _block_shapes(cfg, kind)
        p[f"l{li}"] = layer
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
    output; its bias, where it has one, N(0, 1 / (3 L)). A state-space
    mixer's rates are A = -exp(a_log) with exp(a_log) uniform in
    [1, 16], its step sizes start at softplus(dt_bias) log-uniform in
    [0.001, 0.1], and its skip d is 1 (the family's published
    initialisation). A retention mixer's gate bias puts each KV head's
    half-life at zero input log-uniform in [64, 4096] tokens, and its
    gate matrix is N(0, 1 / (4 fan_in)): the input moves the gate
    without setting it (a trained model's gates are not published, and
    a short memory would leave the carried state unread)."""
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
        if leaf == "d":
            return jnp.ones(shape, jnp.float32)
        if leaf == "a_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              1.0, 16.0))
        if leaf == "dt_bias":       # the inverse softplus of the step
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        if leaf == "conv_bias":
            return (jax.random.normal(k, shape, jnp.float32)
                    / np.sqrt(3.0 * cfg.conv_kernel))
        if leaf == "b_gate":
            return gate_bias(k, shape)
        if leaf == "w_gate":
            return (jax.random.normal(k, shape, jnp.float32)
                    / np.sqrt(4.0 * shape[-2])).astype(dtype)
        fan = cfg.hidden if leaf in ("embed", "head") else shape[-2]
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan)).astype(dtype)

    return jax.tree_util.tree_unflatten(
        tree, [draw(path, shape) for path, shape in flat])


def gate_bias(key, shape):
    """A retention gate's bias: sigmoid(b) = 2^(-1 / h), the half-life
    h log-uniform in [64, 4096] tokens."""
    half_life = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(64.0), math.log(4096.0)))
    g = jnp.exp2(-1.0 / half_life)
    return jnp.log(g) - jnp.log1p(-g)


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


def _causal_taps(kernel, z, positions):
    """sum over taps j of kernel[L - 1 - j] * z_{t - j} along the token
    axis, depthwise: kernel [L, C], z [..., T, C]. Tap j is kept only
    where the event's index in its own history is at least j: before a
    history's first event stands zero, not the end of the history
    packed in front of it (serving) nor the padding a right-aligned
    row starts with (training)."""
    L = kernel.shape[0]
    kernel = kernel.astype(jnp.float32)
    c = kernel[L - 1] * z
    for j in range(1, L):
        c = c + jnp.where((positions >= j)[..., None],
                          kernel[L - 1 - j] * jnp.roll(z, j, axis=-2), 0.0)
    return c


def conv_block(p, cfg: BackboneConfig, u, positions):
    """The gated short convolution: u [..., T, D] (normed) -> [..., T,
    D]. [B, C, X] = u W_in; z = B * X; c_t = sum over taps j of
    K[L - 1 - j] * z_{t - j}; the result is (C * c) W_out. Depthwise,
    causal, no bias, each tap inside its own history
    (`_causal_taps`)."""
    D = cfg.hidden
    bcx = _mm(u, p["w_in"])
    z = bcx[..., :D] * bcx[..., 2 * D:]
    c = _causal_taps(p["kernel"], z, positions)
    return _mm(bcx[..., D:2 * D] * c, p["w_out"])


def _whole(v, chunk: int, fill=0):
    """v [T, ...] padded along its first axis to whole chunks: each slot
    added is a history of one event, and no one is served from it."""
    return jnp.pad(v, ((0, -v.shape[0] % chunk),) + ((0, 0),) * (v.ndim - 1),
                   constant_values=fill)


def ssm_block(p, cfg: BackboneConfig, u, positions):
    """The Mamba-2 mixer: u [..., T, D] (normed) -> [..., T, D].
    [z | xBC | dt] = u W_in; xBC goes through a depthwise causal
    convolution (each tap inside its own history, `_causal_taps`; with
    a bias where the configuration has one) and a silu; x [H, P], B
    and C [G, N] are its parts and step = softplus(dt + dt_bias). S_t =
    exp(step A) S_{t-1} + step x_t (x) B_t, A = -exp(a_log), is zero
    before an event whose index in its own history is 0, and y_t = S_t
    C_t + d x_t (`ops/ssm.py`, in chunks of `ssm_chunk` along the token
    axis). The result is RMSNorm(y * silu(z)) W_out, the norm over each
    of the G groups of channels separately.

    The leading axes are scanned as one token axis. A padded row's
    first slot has index 0 whether it holds an event or padding, so
    the rows are cut from each other; and the row's first event has
    index 0 too, so the padding in front of it, which the convolution's
    bias makes non-zero, never reaches its state."""
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    inner, lead = H * P, u.shape[:-1]
    # one product a part of W_in: the whole of its 80.5 lane groups as
    # one result lay tokens-minor, and cutting it cost 2 ms a block
    # (my chip run, PR 36)
    w_in = p["w_in"]
    z, xbc, dt = (_mm(u, w_in[:, :inner]), _mm(u, w_in[:, inner:-H]),
                  _mm(u, w_in[:, -H:]))
    taps = _causal_taps(p["kernel"], xbc, positions)
    if cfg.conv_bias:
        taps = taps + p["conv_bias"]
    # x, B and C stay the convolution's token-major slab [T, H P + 2 G
    # N] end to end, which the scan reads in place (`ssm.in_place`): a
    # [T, H, P] view with P = 64 on the lanes is a relayout of its own
    xbc = jax.nn.silu(taps).reshape(-1, inner + 2 * G * N)
    x = xbc[:, :inner]
    step = jax.nn.softplus(dt.reshape(-1, H) + p["dt_bias"])
    T, Q = x.shape[0], cfg.ssm_chunk

    y = ssm.chunk_scan(_whole(xbc.astype(w_in.dtype), Q), _whole(step, Q),
                       -jnp.exp(p["a_log"]),
                       _whole(positions.reshape(-1) == 0, Q, True),
                       Q, G, N)[:T]
    y = (y + jnp.repeat(p["d"], P) * x).reshape(*lead, inner) \
        * jax.nn.silu(z)
    # the norm over each group's columns, as column slices: a [..., G,
    # inner / G] view is laid out anew, twice, with its broadcast back
    w = inner // G
    y = jnp.concatenate(
        [v * jax.lax.rsqrt((v * v).mean(-1, keepdims=True) + cfg.eps)
         for v in (y[..., i * w:(i + 1) * w] for i in range(G))], -1)
    return _mm(y * p["norm"]["g"], p["w_out"])


def retention_block(p, cfg: BackboneConfig, u, positions, live=None):
    """The degree-2 power-retention mixer: u [..., T, D] (normed) ->
    [..., T, D]. q = u Wq [H, P], k = u Wk and v = u Wv [Hkv, P]; q and
    k RMSNormed over each head's P dimensions and rotated as
    `attention_block`'s; a gate a KV head, log g = logsigmoid(u W_gate
    + b_gate). y_{t,i} = sum_s w_ts v_s / (sum_s w_ts + eps) over the
    events s <= t of t's own history, w_ts = exp(G_t - G_s) (q_{t,i} .
    k_s / sqrt(P))^2 with G the sum of log g (`ops/retention.py`, in
    chunks of `retention_chunk` along the token axis, the state zero
    before an event whose index in its own history is 0); the result is
    y Wo. `live` [..., T] bool marks the tokens served (None: all)."""
    H, P = cfg.n_heads, cfg.qk_dim
    hkv, lead = cfg.kv_heads_full, u.shape[:-1]
    q = _mm(u, p["wq"]).reshape(*lead, H, P)
    k = _mm(u, p["wk"]).reshape(*lead, hkv, P)
    v = _mm(u, p["wv"])
    if cfg.qk_norm:
        q, k = norm(q, p["q_norm"], cfg), norm(k, p["k_norm"], cfg)
    if cfg.rotary_dim:
        q = rotary(q, positions, dim=cfg.rotary_dim,
                   theta=cfg.rope_theta_full)
        k = rotary(k, positions, dim=cfg.rotary_dim,
                   theta=cfg.rope_theta_full)
    log_g = jax.nn.log_sigmoid(_mm(u, p["w_gate"]) + p["b_gate"])
    dt, T, Q = p["wq"].dtype, math.prod(lead), cfg.retention_chunk
    y = retention.power_retention(
        _whole((q * P ** -0.5).reshape(T, H * P).astype(dt), Q),
        _whole(k.reshape(T, hkv * P).astype(dt), Q),
        _whole(v.reshape(T, hkv * P).astype(dt), Q),
        _whole(log_g.reshape(T, hkv), Q),
        _whole(positions.reshape(T) == 0, Q, True),
        None if live is None else _whole(live.reshape(T), Q, False),
        Q, cfg.retention_eps)[:T]
    return _mm(y.reshape(*lead, H * P), p["wo"])


def ffn_dense(p, cfg: BackboneConfig, u):
    if cfg.act == "relu":
        return _mm(jax.nn.relu(_mm(u, p["w1"])), p["w2"])
    return _mm(jax.nn.silu(_mm(u, p["w_gate"])) * _mm(u, p["w_up"]),
               p["w_down"])


def ffn_moe(p, cfg: BackboneConfig, u, live=None):
    """The held experts' part of the expert layer, and its counts. The
    experts are SwiGLUs (`w_gate_up`) or, where the configuration's
    activation is `relu2`, two matrices each (`w_up`)."""
    lead = u.shape[:-1]
    flat = u.reshape(-1, cfg.hidden)
    routing = moe.route(flat, p["router"], p["bias"], top_k=cfg.top_k,
                        norm_topk_prob=cfg.norm_topk_prob,
                        scale=cfg.routed_scale, eps=cfg.route_eps)
    y, stats = moe.moe_apply(
        flat, routing, p["w_up" if cfg.act == "relu2" else "w_gate_up"],
        p["w_down"], first=cfg.expert_first, n_experts=cfg.n_experts,
        live=None if live is None else live.reshape(-1))
    return y.reshape(*lead, cfg.hidden), stats


def ffn_shared(p, cfg: BackboneConfig, u):
    """The expert layer's shared expert: every token, unweighted, and
    every chip that shares the layer computes it alike."""
    h = jax.nn.relu(_mm(u, p["w_up"]))
    return _mm(h * h, p["w_down"])


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
    for li, blocks in enumerate(cfg.layers):
        lp = params[f"l{li}"]
        for j, kind in enumerate(blocks):
            u = norm(x, lp[f"norm{j + 1}"], cfg)
            bp = lp[_PARAM_KEY[kind]]
            if kind == "conv":
                with jax.named_scope("mixer_conv"):
                    y = conv_block(bp, cfg, u, positions)
            elif kind == "ssm":
                with jax.named_scope("mixer_ssm"):
                    y = ssm_block(bp, cfg, u, positions)
            elif kind == "retention":
                with jax.named_scope("mixer_retention"):
                    y = retention_block(bp, cfg, u, positions, live=valid)
            elif kind == "ffn_moe":
                with jax.named_scope("ffn_experts"):
                    y, st = ffn_moe(bp, cfg, u, live=valid)
                stats.append(st)
                if cfg.shared_width:
                    with jax.named_scope("ffn_shared"):
                        y = y + ffn_shared(bp["shared"], cfg, u)
            elif kind == "ffn_dense":
                with jax.named_scope("ffn_dense"):
                    y = ffn_dense(bp, cfg, u)
            else:
                with jax.named_scope("mixer_attn"):
                    y = attention_block(bp, cfg, kind, u, positions,
                                        attend)
            x = x + y
    out = norm(x, params["norm_f"], cfg)
    if not stats:
        return out, None
    return out, moe.MoeStats(jnp.stack([s.expert_tokens for s in stats]),
                             jnp.stack([s.unrouted for s in stats]))


def head_table(params, cfg: BackboneConfig):
    """[vocab, D]: the rows the last position is scored against."""
    return (params["embed"][:cfg.vocab] if cfg.tied else params["head"])
