"""Seeded weights and histories of the sequence cells.

Weights: every array of the stack is N(0, 1 / fan_in) rounded to
bfloat16 (norm gains 1; the router's correction bias N(0, 0.01^2) and
the sinks N(0, 1), both float32), drawn on the device from a key that
folds in the seed, the layer and the array's place in
`seq_reference.layer_shapes`. `layer_stream` yields them in the
reference's form, one layer at a time, as float32 (the same bfloat16
values); `program_params` puts the same draws into the program's
pytree as bfloat16, which is what a deployment holds.

Histories: user rank r has a fixed length, the same for every seed:
the log-normal quantile (median 256, sigma 1.0) at frac((r + 0.5) x
0.6180339887), rounded and clipped to the configuration's range. The
seed decides the items (Zipf over the catalog slice).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, Tuple

import numpy as np

import seq_reference as ref
from loadgen import ZipfRanks

_GOLDEN = 0.6180339887


def history_lengths(n_users: int, *, median: float, sigma: float,
                    lo: int, hi: int) -> np.ndarray:
    """[n_users] events of user rank r, for every seed."""
    nd = NormalDist()
    out = np.empty(n_users, np.int64)
    for r in range(n_users):
        u = math.modf((r + 0.5) * _GOLDEN)[0]
        out[r] = min(hi, max(lo, round(median * math.exp(
            sigma * nd.inv_cdf(u)))))
    return out


def histories(lengths: np.ndarray, n_items: int, item_zipf_s: float,
              seed: int) -> np.ndarray:
    """All users' histories on one axis (user r's events are
    [ends[r] - lengths[r], ends[r]), oldest first): item ranks, Zipf."""
    rng = np.random.default_rng([int(seed), 29])
    return ZipfRanks(n_items, item_zipf_s).sample(rng, int(lengths.sum()))


def _key(seed: int):
    """--seed may exceed 32 signed bits: its low and high parts are
    folded in. The generator is the device's own bit generator (`rbg`):
    3.4 billion normals by threefry took 54 s of every run's set-up and
    as long again for the reference (my chip run, PR 27). Its stream
    depends on the backend, and both sides of every comparison are
    drawn in one process on one device."""
    import jax
    return jax.random.fold_in(
        jax.random.key(int(seed) % (2**31 - 1), impl="rbg"),
        int(seed) >> 31)


def _draw(key, name: str, shape: Tuple[int, ...], fan_in: int):
    """One array, float32 values that bfloat16 holds exactly (but for
    the small float32 vectors)."""
    import jax
    import jax.numpy as jnp
    if name.startswith("norm"):
        return jnp.ones(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "sink":
        return z
    if name == "bias":
        return 0.01 * z
    return (z / math.sqrt(fan_in)).astype(jnp.bfloat16).astype(jnp.float32)


def _layer(doc: Dict[str, Any], a: Dict[str, Any], seed: int, i: int):
    import jax
    base = jax.random.fold_in(_key(seed), 100 + i)
    out = {}
    for j, (name, shape) in enumerate(ref.layer_shapes(a, i).items()):
        out[name] = _draw(jax.random.fold_in(base, j), name, shape,
                          shape[-2] if len(shape) > 1 else 1)
    return out


def _table(a: Dict[str, Any], seed: int, which: int):
    """0: the embedding, 1: the head; both [V, D], N(0, 1 / D)."""
    import jax
    return _draw(jax.random.fold_in(_key(seed), which), "table",
                 (a["V"], a["D"]), a["D"])


def layer_stream(doc: Dict[str, Any], seed: int) -> Iterator:
    """What `seq_reference.forward_layerwise` consumes."""
    import jax.numpy as jnp
    a = ref.arch(doc)
    yield "embed", _table(a, seed, 0)
    for i in range(len(a["layers"])):
        yield f"l{i}", _layer(doc, a, seed, i)
    yield "final", {"norm_f": jnp.ones((a["D"],), jnp.float32),
                    "head": _table(a, seed, 1)}


def reference_params(doc: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The whole model in the reference's form (tests, toy sizes)."""
    out: Dict[str, Any] = {}
    for name, value in layer_stream(doc, seed):
        if name == "final":
            out.update(value)
        else:
            out[name] = value
    return out


def program_params(doc: Dict[str, Any], seed: int, dtype=None):
    """The same draws as the program's pytree (`ops/backbone.py`
    `param_shapes`), matrices in `dtype` (default bfloat16), on the
    device; a layer's float32 form is dropped as soon as it is cast."""
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    out: Dict[str, Any] = {}
    for name, value in layer_stream(doc, seed):
        if name == "embed":
            out["embed"] = value.astype(dtype)
        elif name == "final":
            out["head"] = value["head"].astype(dtype)
            out["norm_f"] = {"g": value["norm_f"]}
        else:
            attn = {k: value[k].astype(dtype)
                    for k in ("wq", "wk", "wv", "wo")}
            if "sink" in value:
                attn["sink"] = value["sink"]
            ffn = {k: (v if k == "bias" else v.astype(dtype))
                   for k, v in value.items()
                   if k in ("router", "bias", "w_gate_up", "w_down",
                            "w_gate", "w_up")}
            out[name] = {"norm1": {"g": value["norm1"]}, "attn": attn,
                         "norm2": {"g": value["norm2"]}, "ffn": ffn}
        del value
    return out
