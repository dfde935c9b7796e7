"""Seeded weights of the LFM2 cells, and the histories of every
sequence cell.

Weights: as `seq_datagen.py`'s, from its own key and draw (every matrix
N(0, 1 / fan_in) rounded to bfloat16, a convolution's kernel [L, D]
among them with fan-in L; norm gains 1, the q/k gains too; the router's
correction bias N(0, 0.01^2), float32), drawn on the device from a key
that folds in the seed, the layer and the array's place in
`lfm2_reference.layer_shapes`. The table is drawn once and is the head
(tied). `layer_stream` yields them in the reference's form, one layer
at a time, as float32 (the same bfloat16 values); `program_params` puts
the same draws into the program's pytree as bfloat16, which is what a
deployment holds.

Histories: `seq_datagen.history_lengths` / `histories`, unchanged.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator

import lfm2_reference as ref
from seq_datagen import (                                  # noqa: F401
    _draw, _key, histories, history_lengths,
)


def _layer(a: Dict[str, Any], seed: int, i: int):
    import jax
    base = jax.random.fold_in(_key(seed), 100 + i)
    out = {}
    for j, (name, shape) in enumerate(ref.layer_shapes(a, i).items()):
        out[name] = _draw(jax.random.fold_in(base, j), name, shape,
                          shape[-2] if len(shape) > 1 else 1)
    return out


def _table(a: Dict[str, Any], seed: int):
    """[V, D], N(0, 1 / D): the embedding and, tied, the head."""
    import jax
    return _draw(jax.random.fold_in(_key(seed), 0), "table",
                 (a["V"], a["D"]), a["D"])


def layer_stream(doc: Dict[str, Any], seed: int) -> Iterator:
    """What `lfm2_reference.forward_layerwise` consumes."""
    import jax.numpy as jnp
    a = ref.arch(doc)
    yield "embed", _table(a, seed)
    for i in range(len(a["layers"])):
        yield f"l{i}", _layer(a, seed, i)
    yield "final", {"norm_f": jnp.ones((a["D"],), jnp.float32),
                    "head": _table(a, seed)}


def reference_params(doc: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The whole model in the reference's form (tests, toy sizes)."""
    out: Dict[str, Any] = {}
    for name, value in layer_stream(doc, seed):
        if name == "final":
            out["norm_f"] = value["norm_f"]
        else:
            out[name] = value
    return out


_MIXER = {"conv": ("w_in", "kernel", "w_out"),
          "attn": ("wq", "wk", "wv", "wo")}
_FFN = ("router", "bias", "w_gate_up", "w_down", "w_gate", "w_up")


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
            out["norm_f"] = {"g": value["norm_f"]}
        else:
            kind = "conv" if "w_in" in value else "attn"
            mixer = {k: value[k].astype(dtype) for k in _MIXER[kind]}
            if "norm_q" in value:
                mixer["q_norm"] = {"g": value["norm_q"]}
                mixer["k_norm"] = {"g": value["norm_k"]}
            ffn = {k: (v if k == "bias" else v.astype(dtype))
                   for k, v in value.items() if k in _FFN}
            out[name] = {"norm1": {"g": value["norm1"]}, kind: mixer,
                         "norm2": {"g": value["norm2"]}, "ffn": ffn}
        del value
    return out
