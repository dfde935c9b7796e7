"""The plain reference of the sequence backbone: the forward pass of a
MiMo-V2-style language-model stack in straightforward `jax.numpy`,
float32, under `jax.default_matmul_precision("highest")`, one history at
a time: no packing, no kernels, no cache, no batching. It imports
nothing of `predictionio_tpu`. The tests import this same file.

The equations (from the configuration's own `config.json` keys):

  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g
  block:   h = x + Attn(RMSNorm1(x));  y = h + FFN(RMSNorm2(h))
  input    x0 = E[item] (no scaling);  logits = RMSNormf(y_last) . Head^T
  Attn:    q = u Wq -> [T, H, Dq]; k = u Wk -> [T, Hkv, Dq];
           v = value_scale * (u Wv) -> [T, Hkv, Dv]; query head h reads
           KV head h // (H / Hkv); rotary position on the first
           `rotary_dim` of the Dq dimensions of q and k; scores
           q.k / sqrt(Dq), causal; window layers keep 0 <= t - s < W and
           add one learned scalar b_h a head to the softmax's
           denominator (it carries no value); output [T, H Dv] Wo.
  dense:   Wdown(silu(u Wgate) * (u Wup))
  experts: s = sigmoid(u Wr); the top_k experts by s + c are selected;
           w_e = s_e / sum over selected of s; result sum over selected
           of w_e Expert_e(u), each expert a SwiGLU. With a `share`
           (first, held) only the selected experts first .. first + held
           - 1 are summed: what one chip of an expert-parallel
           deployment computes. None sums all of them.

Departures from the published model, each because the catalog row's
config does not settle it (the configuration's file lists them under
`assumed`): rotary in the half-split convention on the LEADING
rotary_dim dimensions; the window counts the query's own position;
`attention_chunk_size` is taken as the source kernel's tiling and
ignored; the sink is a softmax column without a value; vision and audio
towers and multi-token-prediction layers are absent.

Parameters are a dict of arrays, layer i under `l<i>` (shapes in
`shapes()`); `seq_datagen.py` draws them from the seed one layer at a
time, so the reference at width never holds the whole model.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Share = Optional[Tuple[int, int]]

# None: float32 operands as given (the reference). "bf16" | "fp8": both
# operands of every product inside the layers are first rounded to
# bfloat16 / float8_e4m3 (products still accumulate in float32): the
# reference put in the program's place at the stated precision, and the
# control one precision below it (`seq_control.py`). The router, the
# norms, the softmax and the head stay float32, as the program's do.
_OPERANDS: Optional[str] = None


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
    held = int(doc["n_routed_experts"])
    qk = int(doc["head_dim"])
    return {
        "D": int(doc["hidden_size"]), "H": int(doc["num_attention_heads"]),
        "Dq": qk, "Dv": int(doc.get("v_head_dim") or qk),
        "hkv": {"full": int(doc["num_key_value_heads"]),
                "window": int(doc.get("swa_num_key_value_heads")
                              or doc["num_key_value_heads"])},
        "theta": {"full": float(doc["rope_theta"]),
                  "window": float(doc.get("swa_rope_theta")
                                  or doc["rope_theta"])},
        "sink": {"full": bool(doc.get("add_full_attention_sink_bias")),
                 "window": bool(doc.get("add_swa_attention_sink_bias"))},
        "rot": 2 * (int(qk * float(doc["partial_rotary_factor"])) // 2),
        "window": int(doc["sliding_window"]),
        "vscale": float(doc.get("attention_value_scale") or 1.0),
        "eps": float(doc["layernorm_epsilon"]),
        "dense": int(doc["intermediate_size"]),
        "F": int(doc["moe_intermediate_size"]),
        "E": held * int(share["count"]), "held": held,
        "first": int(share["index"]) * held,
        "top_k": int(doc["num_experts_per_tok"]),
        "norm_topk": bool(doc.get("norm_topk_prob", True)),
        "rscale": float(doc.get("routed_scaling_factor") or 1.0),
        "V": int(doc["vocab_size"]),
        "layers": [("window" if doc["hybrid_layer_pattern"][i] else "full",
                    "moe" if doc["moe_layer_freq"][i] else "dense")
                   for i in ids],
    }


def layer_shapes(a: Dict[str, Any], i: int) -> Dict[str, Tuple[int, ...]]:
    """Shapes of layer i's arrays, in the order they are drawn."""
    kind, ffn = a["layers"][i]
    D, H, hkv = a["D"], a["H"], a["hkv"][kind]
    s = {"norm1": (D,), "wq": (D, H * a["Dq"]), "wk": (D, hkv * a["Dq"]),
         "wv": (D, hkv * a["Dv"]), "wo": (H * a["Dv"], D)}
    if a["sink"][kind]:
        s["sink"] = (H,)
    s["norm2"] = (D,)
    if ffn == "moe":
        s.update({"router": (D, a["E"]), "bias": (a["E"],),
                  "w_gate_up": (a["held"], D, 2 * a["F"]),
                  "w_down": (a["held"], a["F"], D)})
    else:
        s.update({"w_gate": (D, a["dense"]), "w_up": (D, a["dense"]),
                  "w_down": (a["dense"], D)})
    return s


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(x, pos, rot: int, theta: float):
    """x [T, heads, Dh]; half-split on the first `rot` dimensions."""
    half = rot // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, None, None] * freq[None, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., rot:]], axis=-1)


def attention(a, kind: str, p, u, *, ignore_window=False,
              drop_sink=False):
    T = u.shape[0]
    H, hkv, Dq, Dv = a["H"], a["hkv"][kind], a["Dq"], a["Dv"]
    q = mm(u, p["wq"]).reshape(T, H, Dq)
    k = mm(u, p["wk"]).reshape(T, hkv, Dq)
    v = a["vscale"] * mm(u, p["wv"]).reshape(T, hkv, Dv)
    pos = jnp.arange(T)
    q = rotary(q, pos, a["rot"], a["theta"][kind])
    k = rotary(k, pos, a["rot"], a["theta"][kind])
    k = jnp.repeat(k, H // hkv, axis=1)        # head h reads h // (H/hkv)
    v = jnp.repeat(v, H // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", _r(q), _r(k)) / math.sqrt(Dq)
    gap = pos[:, None] - pos[None, :]          # t - s
    ok = gap >= 0
    if kind == "window" and not ignore_window:
        ok = ok & (gap < a["window"])
    s = jnp.where(ok[None], s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    sink = p.get("sink") if not drop_sink else None
    if sink is not None:
        m = jnp.maximum(m, sink[:, None, None])
    e = jnp.exp(s - m)
    den = e.sum(axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink[:, None, None] - m)
    out = jnp.einsum("hts,shd->thd", _r(e / den), _r(v))
    return mm(out.reshape(T, H * Dv), p["wo"])


def dense_ffn(p, u):
    return mm(jax.nn.silu(mm(u, p["w_gate"])) * mm(u, p["w_up"]),
              p["w_down"])


def route(a, p, u):
    """(selected [T, k], weights [T, k]) over ALL experts."""
    s = jax.nn.sigmoid(u @ p["router"])
    _, sel = jax.lax.top_k(s + p["bias"], a["top_k"])
    w = jnp.take_along_axis(s, sel, axis=1)
    if a["norm_topk"]:
        w = w / w.sum(axis=1, keepdims=True)
    return sel, w * a["rscale"]


def expert_ffn(a, p, u, share: Share, *, p_first: Optional[int] = None):
    """sum over selected & in-share of w_e Expert_e(u). `p` holds the
    weights of experts p_first .. (default: the configuration's own
    held experts); the share has to lie inside them."""
    sel, w = route(a, p, u)
    base = a["first"] if p_first is None else p_first
    first, held = (0, a["E"]) if share is None else share
    F = p["w_down"].shape[1]
    out = jnp.zeros_like(u)
    for e in range(first, first + held):
        w_e = jnp.where(sel == e, w, 0.0).sum(axis=1)      # [T]
        gu = mm(u, p["w_gate_up"][e - base])
        y = mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], p["w_down"][e - base])
        out = out + w_e[:, None] * y
    return out


def layer(a, i: int, p, x, share: Share, *, ignore_window=False,
          drop_sink=False, shift_share: int = 0):
    """One block. The three keywords plant the faults `seq_control.py`
    reads: the window ignored, the sink dropped, the share's experts
    shifted (expert e + shift computed with expert e's weights)."""
    kind, ffn = a["layers"][i]
    h = x + attention(a, kind, p, rms_norm(x, p["norm1"], a["eps"]),
                      ignore_window=ignore_window, drop_sink=drop_sink)
    u = rms_norm(h, p["norm2"], a["eps"])
    if ffn != "moe":
        return h + dense_ffn(p, u)
    if shift_share and share is not None:
        return h + expert_ffn(a, p, u, (share[0] + shift_share, share[1]),
                              p_first=a["first"] + shift_share)
    return h + expert_ffn(a, p, u, share)


def logits_of(a, norm_f, head, y_last):
    return rms_norm(y_last, norm_f, a["eps"]) @ head.T


def forward(doc: Dict[str, Any], params: Dict[str, Any],
            history: Sequence[int], share: Share = "own") -> np.ndarray:
    """Logits [V] for one history (item ids, oldest first) with the
    whole parameter dict in memory: the tests' form. `share` "own" is
    the configuration's own held experts."""
    a = arch(doc)
    if share == "own":
        share = (a["first"], a["held"])
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[np.asarray(history)]
        for i in range(len(a["layers"])):
            p = {k: jnp.asarray(v, jnp.float32)
                 for k, v in params[f"l{i}"].items()}
            x = layer(a, i, p, x, share)
        out = logits_of(a, jnp.asarray(params["norm_f"], jnp.float32),
                        jnp.asarray(params["head"], jnp.float32), x[-1])
    return np.asarray(out)


def forward_layerwise(doc: Dict[str, Any], layer_params: Iterator,
                      histories: List[Sequence[int]],
                      **faults) -> np.ndarray:
    """Logits [n, V] for several histories, layer by layer: the chip's
    form. `layer_params` yields ("embed", array), then ("l<i>", dict)
    for each layer in order, then ("final", {"norm_f", "head"}); each
    is dropped before the next is asked for, so one layer's float32
    weights stand at a time. Each history still runs alone; it is
    padded AT ITS END to a power of two, at least 128 (with item 0),
    which no position of a causal stack can see, so that a handful of
    lengths compile and not one a history."""
    a = arch(doc)
    share = (a["first"], a["held"])
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
        share = (a["first"], a["held"])
        _STEPS[key] = jax.jit(
            lambda p, x: layer(a, i, p, x, share, **dict(faults)))
    return _STEPS[key]
