"""Seeded weights of the Nemotron-H cell, and the histories of every
sequence cell.

Weights: as `seq_datagen.py`'s, from its own key and draw (every matrix
N(0, 1 / fan_in) rounded to bfloat16, the convolution's taps [K, C]
among them with fan-in K; norm gains 1, the gated norm's too; the
router's correction bias N(0, 0.01^2), float32), drawn on the device
from a key that folds in the seed, the layer and the array's place in
`nemotron_reference.layer_shapes`; an expert layer's stacked matrices
[experts, ., .] are drawn eight experts at a time from keys that fold
in the first of the eight, because one draw of 1.28 GB of normals
beside 7 GB of held weights ran the set-up to 15.3-15.9 of the chip's
16.9 GB (my chip runs, PR 36). What a Mamba-2 mixer holds beside
its matrices is float32 and follows the family's published
initialisation: A_log = log of uniform(1, 16); dt_bias the inverse
softplus of a step that is log-uniform in [time_step_min,
time_step_max] and at least time_step_floor; D = 1; the convolution's
bias N(0, 1 / (3 K)), the variance of the uniform(-1 / sqrt(K),
1 / sqrt(K)) a depthwise convolution starts from. The embedding and
the head are two tables (untied). `layer_stream` yields them in the
reference's form, one layer at a time, as float32; `program_params`
puts the same draws into the program's pytree, matrices as bfloat16,
which is what a deployment holds.

Histories: `seq_datagen.history_lengths` / `histories`, unchanged.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterator

import nemotron_reference as ref
from seq_datagen import (                                  # noqa: F401
    _draw, _key, _table, histories, history_lengths,
)


_MATRICES = ("w_in", "kernel", "w_out", "wq", "wk", "wv", "wo", "router",
             "w_up", "w_down", "shared_up", "shared_down")
_EXPERTS_A_DRAW = 8


def _draw_any(doc: Dict[str, Any], key, name: str, shape, fan_in: int,
              dtype=None):
    """One array of `layer_shapes`, float32, or a matrix in `dtype`
    where one is given (the same values: every matrix is drawn as
    bfloat16 values)."""
    import jax
    import jax.numpy as jnp
    if name in _MATRICES:
        def matrix(k, sh):
            x = _draw(k, name, sh, fan_in)
            return x if dtype is None else x.astype(dtype)

        if len(shape) < 3:
            return matrix(key, shape)
        return jnp.concatenate([
            matrix(jax.random.fold_in(key, first),
                   (min(_EXPERTS_A_DRAW, shape[0] - first),) + shape[1:])
            for first in range(0, shape[0], _EXPERTS_A_DRAW)])
    if name == "d":
        return jnp.ones(shape, jnp.float32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(float(doc["time_step_min"])),
            math.log(float(doc["time_step_max"])))),
            float(doc["time_step_floor"]))
        return step + jnp.log(-jnp.expm1(-step))
    if name == "conv_bias":
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(3.0 * int(doc["conv_kernel"])))
    return _draw(key, name, shape, fan_in)


def _arrays(doc: Dict[str, Any], a: Dict[str, Any], seed: int, i: int,
            dtype=None) -> Iterator:
    """(name, array) of layer i, one at a time in `layer_shapes`'
    order, the matrices in `dtype` where one is given."""
    import jax
    base = jax.random.fold_in(_key(seed), 100 + i)
    for j, (name, shape) in enumerate(ref.layer_shapes(a, i).items()):
        yield name, _draw_any(doc, jax.random.fold_in(base, j), name, shape,
                              shape[-2] if len(shape) > 1 else 1, dtype)


def layer_stream(doc: Dict[str, Any], seed: int) -> Iterator:
    """What `nemotron_reference.forward_layerwise` consumes."""
    import jax.numpy as jnp
    a = ref.arch(doc)
    yield "embed", _table(a, seed, 0)
    for i in range(len(a["layers"])):
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
    """One layer's arrays as the program's block."""
    out = {"norm1": {"g": value["norm"]}}
    if "w_in" in value:
        out["ssm"] = {**{k: value[k] for k in (
            "w_in", "kernel", "w_out", "conv_bias", "dt_bias", "a_log",
            "d")}, "norm": {"g": value["norm_g"]}}
    elif "wq" in value:
        out["attn"] = {k: value[k] for k in ("wq", "wk", "wv", "wo")}
    else:
        out["ffn"] = {**{k: value[k] for k in ("router", "bias", "w_up",
                                               "w_down")},
                      "shared": {"w_up": value["shared_up"],
                                 "w_down": value["shared_down"]}}
    return out


def program_params(doc: Dict[str, Any], seed: int, dtype=None):
    """The same draws as the program's pytree (`ops/backbone.py`
    `param_shapes`), matrices in `dtype` (default bfloat16), on the
    device; no matrix stands whole in float32."""
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    a = ref.arch(doc)
    out: Dict[str, Any] = {"embed": _table(a, seed, 0).astype(dtype)}
    for i in range(len(a["layers"])):
        out[f"l{i}"] = program_layer(dict(_arrays(doc, a, seed, i, dtype)))
    out["head"] = _table(a, seed, 1).astype(dtype)
    out["norm_f"] = {"g": jnp.ones((a["D"],), jnp.float32)}
    return out
