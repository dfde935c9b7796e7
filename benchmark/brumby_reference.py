"""The plain reference of the Brumby backbone: the forward pass of a
Brumby-14B-Base-style stack (`model_type: brumby`) in straightforward
`jax.numpy`, float32, under `jax.default_matmul_precision("highest")`,
one history at a time and the retention in its QUADRATIC form: the
matrix of weights w_ts, masked and normalised. No degree-2 embedding,
no chunks, no state, no kernels, no cache, no batching. It imports
nothing of `predictionio_tpu`. The tests import this same file.

The equations (u is the normed input of a block, t and s positions of
the one history; from the configuration's own `config.json` keys):

  RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g
  layer i: h = x + Retention_i(RMSNorm_1(x)); y = h + FFN_i(RMSNorm_2(h))
  input    x0 = E[item]; logits = RMSNorm_f(y_last) . Head^T (untied)
  Retention: q = u Wq -> [T, H, Dh]; k = u Wk and v = u Wv -> [T, Hkv,
           Dh]; q and k RMSNormed over their Dh dimensions, each with
           one gain vector that the heads share, then rotary on all Dh
           dimensions (half-split, rope_theta) at each event's index in
           the history; query head i reads KV head i // (H / Hkv).
           log g_t = logsigmoid(u_t W_g + b_g) [Hkv]; G_t = sum of log g
           over s <= t.
           w_ts = exp(G_t - G_s) (q_t . k_s / sqrt(Dh))^2 for s <= t,
           0 for s > t;
           y_t = sum_s w_ts v_s / (sum_s w_ts + retention_eps);
           Retention = [y_t over heads] Wo. No biases but b_g.
  FFN:     Wdown(silu(u Wgate) * (u Wup)).

Departures from a published model, each because the catalog row's
config does not settle it (the configuration's file lists them under
`assumed`): degree 2; the normaliser and its epsilon; the gate's form,
one a KV head; the q/k norm and rotary kept from Qwen3, whose keys the
config has; the scale 1 / sqrt(Dh).

Parameters are a dict of arrays, layer i under `l<i>` (shapes in
`layer_shapes`); `brumby_datagen.py` draws them from the seed one layer
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
# operands of every matrix product inside the layers (the scores' and
# the weights-times-values' among them) are first rounded to bfloat16 /
# float8_e4m3 (products still accumulate in float32): the reference put
# in the program's place at the stated precision, and the control one
# precision below it (`brumby_control.py`). The norms, the rotary, the
# gates, the decays, the normaliser and the head stay float32, as the
# program's do.
_OPERANDS: Optional[str] = None

# what `layer` takes to plant a fault, and what each does
FAULTS = {
    "gate_ignored": "g = 1: nothing decays",
    "drop_normaliser": "y = sum_s w_ts v_s, not divided by the weights' "
                       "sum",
    "no_cut": "the weights run on over a stranger's events before the "
              "history's first (the layer's own input array, once more: "
              "what a state that is not zeroed at a history's first "
              "event serves)",
    "no_carry": "s and t in different chunks of retention_chunk events "
                "get no weight: what a kernel that carries no state from "
                "chunk to chunk serves",
    "drop_sqrt2": "the degree-2 embedding without its sqrt(2) on the "
                  "cross terms: every (q . k / sqrt(Dh))^2 becomes "
                  "((q . k)^2 + sum_a q_a^2 k_a^2) / (2 Dh)",
    "degree1": "w_ts = exp(G_t - G_s) q_t . k_s / sqrt(Dh): degree 1",
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
    if doc.get("model_type") != "brumby" or doc.get("tie_word_embeddings"):
        raise ValueError("this reference is of brumby's stack, untied")
    ids = list(doc.get("layer_ids") or range(doc["num_hidden_layers"]))
    assumed = doc["assumed"]
    return {
        "D": int(doc["hidden_size"]),
        "H": int(doc["num_attention_heads"]), "Dh": int(doc["head_dim"]),
        "hkv": int(doc["num_key_value_heads"]),
        "theta": float(doc["rope_theta"]),
        "eps": float(doc["rms_norm_eps"]),
        "dense": int(doc["intermediate_size"]),
        "ret_eps": float(assumed["retention_eps"]),
        "chunk": int(assumed["retention_chunk"]),
        "V": int(doc["vocab_size"]),
        "layers": len(ids),
    }


def layer_shapes(a: Dict[str, Any], i: int) -> Dict[str, Tuple[int, ...]]:
    """Shapes of layer i's arrays, in the order they are drawn (every
    layer alike)."""
    D, H, hkv, Dh = a["D"], a["H"], a["hkv"], a["Dh"]
    return {"norm1": (D,), "wq": (D, H * Dh), "wk": (D, hkv * Dh),
            "wv": (D, hkv * Dh), "wo": (H * Dh, D), "norm_q": (Dh,),
            "norm_k": (Dh,), "w_g": (D, hkv), "b_g": (hkv,),
            "norm2": (D,), "w_gate": (D, a["dense"]),
            "w_up": (D, a["dense"]), "w_down": (a["dense"], D)}


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


def retention(a, p, u, *, gate_ignored=False, drop_normaliser=False,
              no_cut=False, no_carry=False, drop_sqrt2=False,
              degree1=False):
    T = u.shape[0]
    H, hkv, Dh = a["H"], a["hkv"], a["Dh"]
    pos = jnp.arange(T)
    if no_cut:                  # a stranger's events stand before
        u, pos = jnp.concatenate([u, u]), jnp.concatenate([pos, pos])
    n = u.shape[0]
    q = rms_norm(mm(u, p["wq"]).reshape(n, H, Dh), p["norm_q"], a["eps"])
    k = rms_norm(mm(u, p["wk"]).reshape(n, hkv, Dh), p["norm_k"], a["eps"])
    v = mm(u, p["wv"]).reshape(n, hkv, Dh)
    q, k = rotary(q, pos, a["theta"]), rotary(k, pos, a["theta"])
    log_g = jax.nn.log_sigmoid(mm(u, p["w_g"]) + p["b_g"])      # [n, hkv]
    if gate_ignored:
        log_g = jnp.zeros_like(log_g)
    t_ = jnp.arange(n)
    keep = t_[:, None] >= t_[None, :]
    if no_carry:
        at = t_ % T // a["chunk"]
        keep = keep & (at[:, None] == at[None, :])

    def kv_head(args):
        """The KV head's query heads [n, H / Hkv, Dh], its keys and
        values [n, Dh] and its sums G [n] -> y [n, H / Hkv, Dh]: one KV
        head at a time, so that the weights of a long history fit."""
        q_j, k_j, v_j, G_j = args
        s = jnp.einsum("thd,sd->hts", _r(q_j), _r(k_j)) / math.sqrt(Dh)
        if degree1:
            w = s
        elif drop_sqrt2:
            w = (s * s + jnp.einsum("thd,sd->hts", _r(q_j) ** 2,
                                    _r(k_j) ** 2) / Dh) / 2.0
        else:
            w = s * s
        gap = jnp.minimum(G_j[:, None] - G_j[None, :], 0.0)
        w = jnp.where(keep[None], jnp.exp(gap)[None] * w, 0.0)
        y = jnp.einsum("hts,sd->thd", _r(w), _r(v_j))
        if drop_normaliser:
            return y
        return y / (w.sum(axis=-1).T[:, :, None] + a["ret_eps"])

    y = jax.lax.map(kv_head, (
        q.reshape(n, hkv, H // hkv, Dh).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        jnp.cumsum(log_g, axis=0).T))              # [hkv, n, H/hkv, Dh]
    y = y.transpose(1, 0, 2, 3)[n - T:]        # query head i: KV i // (H/hkv)
    return mm(y.reshape(T, H * Dh), p["wo"])


def dense_ffn(p, u):
    return mm(jax.nn.silu(mm(u, p["w_gate"])) * mm(u, p["w_up"]),
              p["w_down"])


def layer(a, i: int, p, x, **faults):
    """One layer. The keywords plant the faults `brumby_control.py`
    reads (`FAULTS`)."""
    h = x + retention(a, p, rms_norm(x, p["norm1"], a["eps"]), **faults)
    return h + dense_ffn(p, rms_norm(h, p["norm2"], a["eps"]))


def logits_of(a, norm_f, head, y_last):
    return rms_norm(y_last, norm_f, a["eps"]) @ head.T


def forward(doc: Dict[str, Any], params: Dict[str, Any],
            history: Sequence[int], **faults) -> np.ndarray:
    """Logits [V] for one history (item ids, oldest first) with the
    whole parameter dict in memory: the tests' form."""
    a = arch(doc)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[np.asarray(history)]
        for i in range(a["layers"]):
            p = {k: jnp.asarray(v, jnp.float32)
                 for k, v in params[f"l{i}"].items()}
            x = layer(a, i, p, x, **faults)
        out = logits_of(a, jnp.asarray(params["norm_f"], jnp.float32),
                        jnp.asarray(params["head"], jnp.float32), x[-1])
    return np.asarray(out)


def padded_lengths(lengths: Sequence[int]) -> List[int]:
    """The length each history is padded to: the longest's next power
    of two (at least 128), or a quarter of that where the history fits
    in it. Two lengths compile, and most histories are short."""
    long = max(_MIN_PAD, 1 << (max(lengths) - 1).bit_length())
    short = max(_MIN_PAD, long // 4)
    return [short if n <= short else long for n in lengths]


def forward_layerwise(doc: Dict[str, Any], layer_params: Iterator,
                      histories: List[Sequence[int]],
                      **faults) -> np.ndarray:
    """Logits [n, V] for several histories, layer by layer: the chip's
    form. `layer_params` yields ("embed", array), then ("l<i>", dict)
    for each layer in order, then ("final", {"norm_f", "head"}); each
    is dropped before the next is asked for, so one layer's float32
    weights stand at a time. Each history still runs alone; it is
    padded AT ITS END (with item 0), which no position of a causal
    stack can see, to one of two lengths (`padded_lengths`)."""
    a = arch(doc)
    it = iter(layer_params)
    with jax.default_matmul_precision("highest"):
        name, embed = next(it)
        assert name == "embed"
        embed = jnp.asarray(embed, jnp.float32)
        xs = []
        for h, n in zip(histories, padded_lengths([len(h)
                                                   for h in histories])):
            padded = np.zeros(n, np.int64)
            padded[:len(h)] = np.asarray(h)
            xs.append(embed[padded])
        del embed
        for i in range(a["layers"]):
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
    """One jitted layer for each (configuration, faults, operand
    rounding): every layer shares it."""
    a = arch(doc)
    key = (repr(sorted((k, repr(v)) for k, v in a.items())), faults,
           _OPERANDS)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda p, x: layer(a, i, p, x, **dict(faults)))
    return _STEPS[key]
