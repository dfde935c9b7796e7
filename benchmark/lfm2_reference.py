"""The plain reference of the LFM2-MoE backbone: the forward pass of an
LFM2-8B-A1B-style stack in straightforward `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`, one history at a time: no
packing, no kernels, no cache, no batching. It imports nothing of
`predictionio_tpu`. The tests import this same file.

The equations (u is the normed input of a block, t a position of the
one history; from the configuration's own `config.json` keys):

  RMSNorm(x) = x / sqrt(mean(x^2) + norm_eps) * g
  layer i: h = x + Mixer_i(RMSNorm_op(x)); y = h + FFN_i(RMSNorm_ffn(h))
  input    x0 = E[item] (no scaling); logits = RMSNorm_f(y_last) . E^T
  Mixer, layer_types[i] == "conv" (L = conv_L_cache taps, no bias):
           [B_t, C_t, X_t] = u_t W_in     (W_in [D, 3 D])
           z_t = B_t * X_t
           c_t = sum_{j=0..L-1} K[L-1-j] * z_{t-j},  z_s = 0 for s < 0
           Mixer = (C_t * c_t) W_out      (K [L, D] depthwise)
  Mixer, "full_attention": q = u Wq -> [T, H, Dh]; k = u Wk and
           v = u Wv -> [T, Hkv, Dh]; q and k RMSNormed over their Dh
           dimensions, each with one gain vector that the heads share;
           rotary position on all Dh dimensions; query head h reads KV
           head h // (H / Hkv); scores q.k / sqrt(Dh), causal; output
           [T, H Dh] Wo. No biases.
  FFN, i < num_dense_layers: Wdown(silu(u Wgate) * (u Wup))
  FFN, else: s = sigmoid(u Wr); the top_k experts by s + c are
           selected; w_e = s_e / (sum over selected of s +
           route_norm_eps) * routed_scaling_factor; result sum over
           selected of w_e Expert_e(u), each a SwiGLU. No shared expert.

Departures from the published model, each because the catalog row's
config does not settle it (the configuration's file lists them under
`assumed`): the table is tied to the output; W_in's three chunks are
B, C, X in this order; Dh = hidden_size / num_attention_heads; the
q/k gains are one vector a projection, shared by the heads; rotary in
the half-split convention; 1e-6 joins the routing normaliser. All are
the family's published modelling code's. Every expert is held
(`expert_share` is one share of one), so the stack is the whole of
its layers.

Parameters are a dict of arrays, layer i under `l<i>` (shapes in
`layer_shapes`); `lfm2_datagen.py` draws them from the seed one layer
at a time, so the reference at width never holds the whole model.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# None: float32 operands as given (the reference). "bf16" | "fp8": both
# operands of every matrix product inside the layers are first rounded
# to bfloat16 / float8_e4m3 (products still accumulate in float32): the
# reference put in the program's place at the stated precision, and the
# control one precision below it (`lfm2_control.py`). The router, the
# norms, the convolution's gates and taps, the softmax and the head stay
# float32, as the program's do.
_OPERANDS: Optional[str] = None

# what `layer` takes to plant a fault, and what each does
FAULTS = {
    "drop_tap": "the oldest tap (j = L - 1) of every convolution is left "
                "out",
    "leak": "the L - 1 rows before a history's first event are not zero "
            "but the last rows of the array the layer was handed (a "
            "stranger's events: the padding's in forward_layerwise): what "
            "a convolution that reads across the pack's boundary serves",
    "drop_qk_norm": "q and k go to the rotary as projected",
    "drop_expert_bias": "the experts are selected by s, not s + c",
}


@contextlib.contextmanager
def operands(kind: Optional[str]):
    global _OPERANDS
    before, _OPERANDS = _OPERANDS, kind
    try:
        yield
    finally:
        _OPERANDS = before


def _r(x):
    if _OPERANDS is None:
        return x
    dt = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[_OPERANDS]
    return x.astype(dt).astype(jnp.float32)


def mm(x, w):
    return _r(x) @ _r(w)


def arch(doc: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the equations need, from a configuration file."""
    ids = list(doc.get("layer_ids") or range(doc["num_hidden_layers"]))
    share = doc.get("expert_share") or {"index": 0, "count": 1}
    if (share["index"], share["count"]) != (0, 1):
        raise ValueError("this reference holds every expert")
    kinds = {"conv": "conv", "full_attention": "attn"}
    H = int(doc["num_attention_heads"])
    return {
        "D": int(doc["hidden_size"]), "H": H,
        "Dh": int(doc["hidden_size"]) // H,
        "hkv": int(doc["num_key_value_heads"]),
        "theta": float(doc["rope_theta"]),
        "qk_norm": bool(doc.get("qk_norm")),
        "L": int(doc["conv_L_cache"]),
        "eps": float(doc["norm_eps"]),
        "dense": int(doc["intermediate_size"]),
        "F": int(doc["moe_intermediate_size"]),
        "E": int(doc["num_experts"]),
        "top_k": int(doc["num_experts_per_tok"]),
        "norm_topk": bool(doc.get("norm_topk_prob", True)),
        "rscale": float(doc.get("routed_scaling_factor") or 1.0),
        "route_eps": float(doc.get("route_norm_eps") or 0.0),
        "V": int(doc["vocab_size"]),
        "layers": [(kinds[doc["layer_types"][i]],
                    "dense" if i < int(doc["num_dense_layers"]) else "moe")
                   for i in ids],
    }


def layer_shapes(a: Dict[str, Any], i: int) -> Dict[str, Tuple[int, ...]]:
    """Shapes of layer i's arrays, in the order they are drawn."""
    mixer, ffn = a["layers"][i]
    D, H, hkv, Dh = a["D"], a["H"], a["hkv"], a["Dh"]
    s: Dict[str, Tuple[int, ...]] = {"norm1": (D,)}
    if mixer == "conv":
        s.update({"w_in": (D, 3 * D), "kernel": (a["L"], D),
                  "w_out": (D, D)})
    else:
        s.update({"wq": (D, H * Dh), "wk": (D, hkv * Dh),
                  "wv": (D, hkv * Dh), "wo": (H * Dh, D)})
        if a["qk_norm"]:
            s.update({"norm_q": (Dh,), "norm_k": (Dh,)})
    s["norm2"] = (D,)
    if ffn == "moe":
        s.update({"router": (D, a["E"]), "bias": (a["E"],),
                  "w_gate_up": (a["E"], D, 2 * a["F"]),
                  "w_down": (a["E"], a["F"], D)})
    else:
        s.update({"w_gate": (D, a["dense"]), "w_up": (D, a["dense"]),
                  "w_down": (a["dense"], D)})
    return s


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(x, pos, theta: float):
    """x [T, heads, Dh]; half-split over all Dh dimensions."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq[None, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def conv_mixer(a, p, u, *, drop_tap=False, leak=False):
    T, D, L = u.shape[0], a["D"], a["L"]
    bcx = mm(u, p["w_in"])
    b, c, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    z = b * x
    acc = jnp.zeros_like(z)
    for j in range(L - 1 if drop_tap else L):
        if leak:
            back = jnp.roll(z, j, axis=0)
        else:
            back = jnp.concatenate([jnp.zeros((j, D), z.dtype),
                                    z[:T - j]])[:T]
        acc = acc + p["kernel"][L - 1 - j] * back
    return mm(c * acc, p["w_out"])


def attention(a, p, u, *, drop_qk_norm=False):
    T = u.shape[0]
    H, hkv, Dh = a["H"], a["hkv"], a["Dh"]
    q = mm(u, p["wq"]).reshape(T, H, Dh)
    k = mm(u, p["wk"]).reshape(T, hkv, Dh)
    v = mm(u, p["wv"]).reshape(T, hkv, Dh)
    if a["qk_norm"] and not drop_qk_norm:
        q = rms_norm(q, p["norm_q"], a["eps"])
        k = rms_norm(k, p["norm_k"], a["eps"])
    pos = jnp.arange(T)
    q, k = rotary(q, pos, a["theta"]), rotary(k, pos, a["theta"])
    k = jnp.repeat(k, H // hkv, axis=1)        # head h reads h // (H/hkv)
    v = jnp.repeat(v, H // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", _r(q), _r(k)) / math.sqrt(Dh)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    e = jnp.exp(s - s.max(axis=-1, keepdims=True))
    w = e / e.sum(axis=-1, keepdims=True)
    out = jnp.einsum("hts,shd->thd", _r(w), _r(v))
    return mm(out.reshape(T, H * Dh), p["wo"])


def dense_ffn(p, u):
    return mm(jax.nn.silu(mm(u, p["w_gate"])) * mm(u, p["w_up"]),
              p["w_down"])


def route(a, p, u, *, drop_expert_bias=False):
    """(selected [T, k], weights [T, k]) over all experts."""
    s = jax.nn.sigmoid(u @ p["router"])
    _, sel = jax.lax.top_k(s if drop_expert_bias else s + p["bias"],
                           a["top_k"])
    w = jnp.take_along_axis(s, sel, axis=1)
    if a["norm_topk"]:
        w = w / (w.sum(axis=1, keepdims=True) + a["route_eps"])
    return sel, w * a["rscale"]


def expert_ffn(a, p, u, *, drop_expert_bias=False):
    """sum over selected of w_e Expert_e(u): every expert over every
    token, weighted 0 where it was not selected."""
    sel, w = route(a, p, u, drop_expert_bias=drop_expert_bias)
    F = a["F"]
    out = jnp.zeros_like(u)
    for e in range(a["E"]):
        w_e = jnp.where(sel == e, w, 0.0).sum(axis=1)      # [T]
        gu = mm(u, p["w_gate_up"][e])
        y = mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], p["w_down"][e])
        out = out + w_e[:, None] * y
    return out


def layer(a, i: int, p, x, *, drop_tap=False, leak=False,
          drop_qk_norm=False, drop_expert_bias=False):
    """One block. The keywords plant the faults `lfm2_control.py`
    reads (`FAULTS`)."""
    mixer, ffn = a["layers"][i]
    u = rms_norm(x, p["norm1"], a["eps"])
    if mixer == "conv":
        h = x + conv_mixer(a, p, u, drop_tap=drop_tap, leak=leak)
    else:
        h = x + attention(a, p, u, drop_qk_norm=drop_qk_norm)
    u = rms_norm(h, p["norm2"], a["eps"])
    if ffn == "moe":
        return h + expert_ffn(a, p, u, drop_expert_bias=drop_expert_bias)
    return h + dense_ffn(p, u)


def logits_of(a, norm_f, head, y_last):
    return rms_norm(y_last, norm_f, a["eps"]) @ head.T


def forward(doc: Dict[str, Any], params: Dict[str, Any],
            history: Sequence[int], **faults) -> np.ndarray:
    """Logits [V] for one history (item ids, oldest first) with the
    whole parameter dict in memory: the tests' form."""
    a = arch(doc)
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], jnp.float32)
        x = embed[np.asarray(history)]
        for i in range(len(a["layers"])):
            p = {k: jnp.asarray(v, jnp.float32)
                 for k, v in params[f"l{i}"].items()}
            x = layer(a, i, p, x, **faults)
        out = logits_of(a, jnp.asarray(params["norm_f"], jnp.float32),
                        embed, x[-1])
    return np.asarray(out)


def forward_layerwise(doc: Dict[str, Any], layer_params: Iterator,
                      histories: List[Sequence[int]],
                      **faults) -> np.ndarray:
    """Logits [n, V] for several histories, layer by layer: the chip's
    form. `layer_params` yields ("embed", array), then ("l<i>", dict)
    for each layer in order, then ("final", {"norm_f", "head"}), the
    head being the tied table again; each is dropped before the next is
    asked for, so one layer's float32 weights stand at a time. Each
    history still runs alone; it is padded AT ITS END to a power of
    two, at least 128 (with item 0), which no position of a causal
    stack can see, so that a handful of lengths compile and not one a
    history."""
    a = arch(doc)
    it = iter(layer_params)
    with jax.default_matmul_precision("highest"):
        name, embed = next(it)
        assert name == "embed"
        embed = jnp.asarray(embed, jnp.float32)
        xs = []
        for h in histories:
            padded = np.zeros(max(_MIN_PAD, 1 << (len(h) - 1).bit_length()),
                              np.int64)
            padded[:len(h)] = np.asarray(h)
            xs.append(embed[padded])
        del embed
        for i in range(len(a["layers"])):
            name, p = next(it)
            assert name == f"l{i}"
            step = _layer_step(doc, i, tuple(sorted(faults.items())))
            xs = [step(p, x) for x in xs]
            del p
        name, fin = next(it)
        assert name == "final"
        last = jnp.stack([x[len(h) - 1] for x, h in zip(xs, histories)])
        return np.asarray(logits_of(a, fin["norm_f"], fin["head"], last))


_MIN_PAD = 128
_STEPS: Dict[Any, Any] = {}


def _layer_step(doc, i: int, faults: Tuple):
    """One jitted layer for each (configuration, kinds of layer i,
    faults, operand rounding): layers of the same kinds share it."""
    a = arch(doc)
    key = (repr(sorted((k, repr(v)) for k, v in a.items())),
           a["layers"][i], faults, _OPERANDS)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda p, x: layer(a, i, p, x, **dict(faults)))
    return _STEPS[key]
