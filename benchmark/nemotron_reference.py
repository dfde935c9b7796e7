"""The plain reference of the Nemotron-H backbone: the forward pass of
the context tower of Nemotron-Labs-TwoTower-30B-A3B (`model_type:
nemotron_h`) in straightforward `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`, one history at a time and
the state-space recurrence one event at a time: no packing, no
kernels, no chunks, no cache, no batching. It imports nothing of
`predictionio_tpu`. The tests import this same file.

The equations (u is the normed input of a block, t a position of the
one history; from the configuration's own `config.json` keys):

  RMSNorm(x) = x / sqrt(mean(x^2) + layer_norm_epsilon) * g
  every layer is one block: x <- x + Block_i(RMSNorm_i(x)), the kind by
  hybrid_override_pattern[i]; x0 = E[item]; logits = RMSNorm_f(x_last) .
  Head^T (the head is its own table: tie_word_embeddings false). No
  bias anywhere but the convolution's.

  "M", Mamba-2: H = mamba_num_heads heads of P = mamba_head_dim, G =
      n_groups groups of N = ssm_state_size, K = conv_kernel taps.
      [z | xBC | dt] = u W_in, widths H P | H P + 2 G N | H.
      xBC_t <- silu(b + sum_{j<K} k[K-1-j] * xBC_{t-j}), xBC_s = 0 for
      s < 0: depthwise, causal.
      x_t [H, P], B_t [G, N], C_t [G, N] are xBC_t's parts; head h reads
      group h // (H / G). step_t = softplus(dt_t + dt_bias) [H];
      A = -exp(A_log) [H].
      S_t = exp(step_t A) S_{t-1} + step_t x_t (x) B_t, S_{-1} = 0;
      y_t = S_t C_t + D * x_t.
      Block = RMSNorm_G(y * silu(z)) W_out, the norm over each of the G
      groups of H P / G channels separately, with one gain a channel.
  "*", attention: q = u Wq -> [T, H, Dh]; k = u Wk and v = u Wv -> [T,
      Hkv, Dh]; query head h reads KV head h // (H / Hkv); scores
      q.k / sqrt(Dh), causal; output [T, H Dh] Wo. No position
      encoding of any kind.
  "E", experts: s = sigmoid(u Wr) over all experts; the top_k by s + c
      are selected; w_e = s_e / (sum over selected of s +
      route_norm_eps) * routed_scaling_factor; Expert_e(u) =
      relu(u Wup_e)^2 Wdown_e; Shared(u) the same form at the shared
      width, unweighted. Block = sum over selected AND held of w_e
      Expert_e(u) + Shared(u): with `expert_share` {index, count} the
      held experts are one of `count` equal shares of the router's
      width; what the other shares' experts would add is left out.

Departures from the published model, each because the catalog row's
config does not settle it (the configuration's file lists them under
`assumed`): H P is the mixer's inner width (`expand` x hidden is not);
the gated norm gates first and norms the G groups separately; W_in's
chunks are z | x | B | C | dt in this order; attention applies no
rotary (`rope_theta` and `partial_rotary_factor` are read by nothing);
1e-20 joins the routing normaliser. All are the family's published
modelling code's. The second tower (the denoiser), its conditioning
and the block-diffusion decode are absent: this is the causal context
tower, which is what encodes a history.

Parameters are a dict of arrays, layer i under `l<i>` (shapes in
`layer_shapes`); `nemotron_datagen.py` draws them from the seed one
layer at a time, so the reference at width never holds the whole
model.
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
# operands of every matrix product inside the layers, and x, B and C as
# the recurrence reads them, are first rounded to bfloat16 /
# float8_e4m3 (products still accumulate in float32): the reference put
# in the program's place at the stated precision, and the control one
# precision below it (`nemotron_control.py`). The router, the norms,
# the convolution, the step sizes, the state, the softmax and the head
# stay float32, as the program's do.
_OPERANDS: Optional[str] = None

# what `layer` takes to plant a fault, and what each does
FAULTS = {
    "no_reset": "the state and the K - 1 rows before a history's first "
                "event are not zero but what the array the layer was "
                "handed leaves behind (a stranger's events: the padding's "
                "in forward_layerwise): what a scan that runs across the "
                "pack's boundary serves",
    "drop_conv_bias": "the convolution's bias is left out",
    "drop_skip": "D * x is left out of the mixer's output",
    "drop_shared": "the shared expert is left out",
    "relu_not_squared": "the experts, the shared one too, apply relu "
                        "where relu^2 is meant",
    "drop_routed_scale": "routed_scaling_factor is read as 1",
    "other_share": "the held weights stand in for the NEXT share's "
                   "experts: what a chip that was told the wrong share "
                   "index serves",
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


_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def arch(doc: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the equations need, from a configuration file."""
    ids = list(doc.get("layer_ids") or range(doc["num_hidden_layers"]))
    share = doc.get("expert_share") or {"index": 0, "count": 1}
    held = int(doc["n_routed_experts"])
    if doc["mlp_hidden_act"] != "relu2" or not doc["use_conv_bias"]:
        raise ValueError("this reference's experts are relu^2 and its "
                         "convolution has a bias")
    return {
        "D": int(doc["hidden_size"]),
        "H": int(doc["num_attention_heads"]), "Dh": int(doc["head_dim"]),
        "hkv": int(doc["num_key_value_heads"]),
        "mH": int(doc["mamba_num_heads"]), "mP": int(doc["mamba_head_dim"]),
        "mG": int(doc["n_groups"]), "mN": int(doc["ssm_state_size"]),
        "K": int(doc["conv_kernel"]),
        "eps": float(doc["layer_norm_epsilon"]),
        "F": int(doc["moe_intermediate_size"]),
        "Fs": int(doc["moe_shared_expert_intermediate_size"]),
        "E": held * int(share["count"]), "held": held,
        "first": int(share["index"]) * held,
        "top_k": int(doc["num_experts_per_tok"]),
        "norm_topk": bool(doc.get("norm_topk_prob", True)),
        "rscale": float(doc.get("routed_scaling_factor") or 1.0),
        "route_eps": float(doc.get("route_norm_eps") or 0.0),
        "V": int(doc["vocab_size"]),
        "layers": [_KINDS[doc["hybrid_override_pattern"][i]] for i in ids],
    }


def layer_shapes(a: Dict[str, Any], i: int) -> Dict[str, Tuple[int, ...]]:
    """Shapes of layer i's arrays, in the order they are drawn."""
    D = a["D"]
    s: Dict[str, Tuple[int, ...]] = {"norm": (D,)}
    if a["layers"][i] == "mamba":
        inner = a["mH"] * a["mP"]
        conv = inner + 2 * a["mG"] * a["mN"]
        s.update({"w_in": (D, inner + conv + a["mH"]),
                  "kernel": (a["K"], conv), "conv_bias": (conv,),
                  "dt_bias": (a["mH"],), "a_log": (a["mH"],),
                  "d": (a["mH"],), "norm_g": (inner,),
                  "w_out": (inner, D)})
    elif a["layers"][i] == "attn":
        s.update({"wq": (D, a["H"] * a["Dh"]), "wk": (D, a["hkv"] * a["Dh"]),
                  "wv": (D, a["hkv"] * a["Dh"]),
                  "wo": (a["H"] * a["Dh"], D)})
    else:
        s.update({"router": (D, a["E"]), "bias": (a["E"],),
                  "w_up": (a["held"], D, a["F"]),
                  "w_down": (a["held"], a["F"], D),
                  "shared_up": (D, a["Fs"]), "shared_down": (a["Fs"], D)})
    return s


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def mamba_mixer(a, p, u, *, no_reset=False, drop_conv_bias=False,
                drop_skip=False):
    T = u.shape[0]
    H, P, G, N, K = a["mH"], a["mP"], a["mG"], a["mN"], a["K"]
    inner = H * P
    zxd = mm(u, p["w_in"])
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:-H], zxd[:, -H:]
    acc = jnp.zeros_like(xbc) if drop_conv_bias \
        else jnp.broadcast_to(p["conv_bias"], xbc.shape)
    for j in range(K):
        if no_reset:
            back = jnp.roll(xbc, j, axis=0)
        else:
            back = jnp.concatenate([jnp.zeros((j, xbc.shape[1]), xbc.dtype),
                                    xbc[:T - j]])[:T]
        acc = acc + p["kernel"][K - 1 - j] * back
    xbc = jax.nn.silu(acc)
    x = xbc[:, :inner].reshape(T, H, P)
    B = xbc[:, inner:inner + G * N].reshape(T, G, N)
    C = xbc[:, inner + G * N:].reshape(T, G, N)
    step = jax.nn.softplus(dt + p["dt_bias"])              # [T, H]
    A = -jnp.exp(p["a_log"])

    def event(S, ev):
        x_t, step_t, B_t, C_t = ev
        B_h = jnp.repeat(B_t, H // G, axis=0)      # head h reads h // (H/G)
        C_h = jnp.repeat(C_t, H // G, axis=0)
        S = (jnp.exp(step_t * A)[:, None, None] * S
             + _r(step_t[:, None] * x_t)[:, :, None] * _r(B_h)[:, None, :])
        return S, (S * _r(C_h)[:, None, :]).sum(axis=-1)

    S = jnp.zeros((H, P, N), jnp.float32)
    if no_reset:                    # a stranger's events came first
        S, _ = jax.lax.scan(event, S, (x, step, B, C))
    _, y = jax.lax.scan(event, S, (x, step, B, C))
    if not drop_skip:
        y = y + p["d"][:, None] * x
    y = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + a["eps"])
    return mm(y.reshape(T, inner) * p["norm_g"], p["w_out"])


def attention(a, p, u):
    T = u.shape[0]
    H, hkv, Dh = a["H"], a["hkv"], a["Dh"]
    q = mm(u, p["wq"]).reshape(T, H, Dh)
    k = mm(u, p["wk"]).reshape(T, hkv, Dh)
    v = mm(u, p["wv"]).reshape(T, hkv, Dh)
    k = jnp.repeat(k, H // hkv, axis=1)        # head h reads h // (H/hkv)
    v = jnp.repeat(v, H // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", _r(q), _r(k)) / math.sqrt(Dh)
    pos = jnp.arange(T)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    e = jnp.exp(s - s.max(axis=-1, keepdims=True))
    w = e / e.sum(axis=-1, keepdims=True)
    out = jnp.einsum("hts,shd->thd", _r(w), _r(v))
    return mm(out.reshape(T, H * Dh), p["wo"])


def route(a, p, u, *, drop_routed_scale=False):
    """(selected [T, k], weights [T, k]) over ALL experts."""
    s = jax.nn.sigmoid(u @ p["router"])
    _, sel = jax.lax.top_k(s + p["bias"], a["top_k"])
    w = jnp.take_along_axis(s, sel, axis=1)
    if a["norm_topk"]:
        w = w / (w.sum(axis=1, keepdims=True) + a["route_eps"])
    return sel, w if drop_routed_scale else w * a["rscale"]


def _relu2(h, relu_not_squared):
    h = jax.nn.relu(h)
    return h if relu_not_squared else h * h


def routed_experts(a, p, u, share: Share, *, p_first: int = 0,
                   relu_not_squared=False, drop_routed_scale=False):
    """sum over selected & in-share of w_e Expert_e(u). `p` holds the
    weights of experts p_first ..; the share (first, held; None: every
    expert) has to lie inside them."""
    sel, w = route(a, p, u, drop_routed_scale=drop_routed_scale)
    first, held = (0, a["E"]) if share is None else share
    out = jnp.zeros_like(u)
    for e in range(first, first + held):
        w_e = jnp.where(sel == e, w, 0.0).sum(axis=1)      # [T]
        h = _relu2(mm(u, p["w_up"][e - p_first]), relu_not_squared)
        out = out + w_e[:, None] * mm(h, p["w_down"][e - p_first])
    return out


def shared_expert(p, u, *, relu_not_squared=False):
    return mm(_relu2(mm(u, p["shared_up"]), relu_not_squared),
              p["shared_down"])


def layer(a, i: int, p, x, *, no_reset=False, drop_conv_bias=False,
          drop_skip=False, drop_shared=False, relu_not_squared=False,
          drop_routed_scale=False, other_share=False):
    """One block, with this chip's share of the experts. The keywords
    plant the faults `nemotron_control.py` reads (`FAULTS`)."""
    kind = a["layers"][i]
    u = rms_norm(x, p["norm"], a["eps"])
    if kind == "mamba":
        return x + mamba_mixer(a, p, u, no_reset=no_reset,
                               drop_conv_bias=drop_conv_bias,
                               drop_skip=drop_skip)
    if kind == "attn":
        return x + attention(a, p, u)
    first = a["first"]
    if other_share:
        first = (first + a["held"]) % a["E"]
    y = routed_experts(a, p, u, (first, a["held"]), p_first=first,
                       relu_not_squared=relu_not_squared,
                       drop_routed_scale=drop_routed_scale)
    if not drop_shared:
        y = y + shared_expert(p, u, relu_not_squared=relu_not_squared)
    return x + y


def logits_of(a, norm_f, head, y_last):
    return rms_norm(y_last, norm_f, a["eps"]) @ head.T


def forward(doc: Dict[str, Any], params: Dict[str, Any],
            history: Sequence[int], **faults) -> np.ndarray:
    """Logits [V] for one history (item ids, oldest first) with the
    whole parameter dict in memory: the tests' form."""
    a = arch(doc)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"], jnp.float32)[np.asarray(history)]
        for i in range(len(a["layers"])):
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
    """One jitted layer for each (configuration, kind of layer i,
    faults, operand rounding): layers of the same kind share it."""
    a = arch(doc)
    key = (repr(sorted((k, repr(v)) for k, v in a.items())),
           a["layers"][i], faults, _OPERANDS)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda p, x: layer(a, i, p, x, **dict(faults)))
    return _STEPS[key]
