"""Seeded weights of the Brumby cell, and the histories of every
sequence cell.

Weights: as `seq_datagen.py`'s, from its own key and draw (every matrix
N(0, 1 / fan_in) rounded to bfloat16; norm gains 1, the q/k gains too),
drawn on the device from a key that folds in the seed, the layer and
the array's place in `brumby_reference.layer_shapes`. The retention
gate is the configuration's `assumed.seeded`: W_g N(0, 1 / (4 fan_in))
rounded to bfloat16, so that the input moves log g by about a factor
e^0.5 without setting it, and b_g float32 with sigmoid(b_g) = 2^(-1 /
h), each KV head's half-life h at zero input log-uniform in [64, 4096]
tokens: a shorter memory would leave the carried state numerically
invisible (`assumed.gate_half_life` of the configuration: the toy's
histories are a fortieth as long, and so are its half-lives). The
embedding and the head are two tables (untied).
`layer_stream` yields them in the reference's form, one layer at a
time, as float32; `program_params` puts the same draws into the
program's pytree, matrices as bfloat16, which is what a deployment
holds.

Histories: `seq_datagen.history_lengths` / `histories`, unchanged.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterator

import brumby_reference as ref
from seq_datagen import (                                  # noqa: F401
    _draw, _key, _table, histories, history_lengths,
)

_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_RETENTION = ("wq", "wk", "wv", "wo")
_FFN = ("w_gate", "w_up", "w_down")


def _draw_any(doc: Dict[str, Any], key, name: str, shape, fan_in: int,
              dtype=None):
    """One array of `layer_shapes`, float32, or a matrix in `dtype`
    where one is given (the same values: every matrix is drawn as
    bfloat16 values)."""
    import jax
    import jax.numpy as jnp
    if name == "b_g":
        h = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32,
            *map(math.log, doc["assumed"]["gate_half_life"])))
        g = jnp.exp2(-1.0 / h)
        return jnp.log(g) - jnp.log1p(-g)
    if name == "w_g":
        x = (jax.random.normal(key, shape, jnp.float32)
             / math.sqrt(4.0 * fan_in)).astype(jnp.bfloat16)
    else:
        x = _draw(key, name, shape, fan_in)
    if name in _MATRICES + ("w_g",) and dtype is not None:
        return x.astype(dtype)
    return x.astype(jnp.float32)


def _arrays(doc: Dict[str, Any], a: Dict[str, Any], seed: int, i: int,
            dtype=None) -> Iterator:
    """(name, array) of layer i, one at a time in `layer_shapes`'
    order, the matrices in `dtype` where one is given."""
    import jax
    base = jax.random.fold_in(_key(seed), 100 + i)
    for j, (name, shape) in enumerate(ref.layer_shapes(a, i).items()):
        yield name, _draw_any(doc, jax.random.fold_in(base, j), name,
                              shape, shape[-2] if len(shape) > 1 else 1,
                              dtype)


def layer_stream(doc: Dict[str, Any], seed: int) -> Iterator:
    """What `brumby_reference.forward_layerwise` consumes."""
    import jax.numpy as jnp
    a = ref.arch(doc)
    yield "embed", _table(a, seed, 0)
    for i in range(a["layers"]):
        yield f"l{i}", dict(_arrays(doc, a, seed, i))
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


def program_layer(value: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's arrays as the program's (retention, dense) layer."""
    return {"norm1": {"g": value["norm1"]},
            "retention": {**{k: value[k] for k in _RETENTION},
                          "q_norm": {"g": value["norm_q"]},
                          "k_norm": {"g": value["norm_k"]},
                          "w_gate": value["w_g"], "b_gate": value["b_g"]},
            "norm2": {"g": value["norm2"]},
            "ffn": {k: value[k] for k in _FFN}}


def program_params(doc: Dict[str, Any], seed: int, dtype=None):
    """The same draws as the program's pytree (`ops/backbone.py`
    `param_shapes`), matrices in `dtype` (default bfloat16), on the
    device; no matrix stands whole in float32 beside the others."""
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    a = ref.arch(doc)
    out: Dict[str, Any] = {"embed": _table(a, seed, 0).astype(dtype)}
    for i in range(a["layers"]):
        out[f"l{i}"] = program_layer(dict(_arrays(doc, a, seed, i,
                                                  dtype)))
    out["head"] = _table(a, seed, 1).astype(dtype)
    out["norm_f"] = {"g": jnp.ones((a["D"],), jnp.float32)}
    return out
