"""The readings that the limits of the Nemotron-H cell's `correct` are
set from, as `lfm2_control.py` for the LFM2 cell: the PROGRAM's stack
called directly (no server), the plain reference put in its place at
the STATED precision (bfloat16 operands), the CONTROL one precision
below (float8_e4m3 operands), and seven planted FAULTS
(`nemotron_reference.FAULTS`: no reset at a history's first event; the
convolution's bias dropped; D * x dropped; the shared expert left out;
relu^2 read as relu; the routed scale dropped; the other chip's share
held; each at the stated precision, as a program with that fault would
serve). Each is served in the program's place and goes through
`reference.verdict` with the cell's limits: the program and the stated
precision have to come out correct, the control and every fault not
correct, or the exit code is 1.

  python3 benchmark/nemotron_control.py --workload nemotron-tt-hist-c32 \\
      --seeds 1 2

runs on the chip at the cell's own widths over a few histories (the
longest and the shortest among them: a state that is not reset shows
most where a history is short) and prints one JSON line a seed;
`--dump DIR` keeps every reading's logits there. The benchmark's own
runs never run it; `tests/test_nemotron.py` keeps it at the toy size.
PERF.md section 2 lists the readings and the limits set from them.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent))

import harness                                             # noqa: E402
import nemotron_datagen                                    # noqa: E402
import nemotron_reference                                  # noqa: E402
import seq_control                                         # noqa: E402
from lfm2_control import histories_of                      # noqa: E402,F401
from seq_control import MUST_PASS, failures, numbers       # noqa: E402,F401


def program_logits(cfg: Dict[str, Any], config_file: str, seed: int,
                   hs: List[np.ndarray]) -> np.ndarray:
    """The program's packed stack and head over the histories."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.ops import backbone
    from predictionio_tpu.ops.seqrec import PackedEncoder, SeqRecModel
    bcfg = backbone.load_config(config_file)
    params = nemotron_datagen.program_params(cfg, seed)
    model = SeqRecModel(params=params, n_items=bcfg.vocab,
                        backbone=backbone.config_dict(bcfg))
    enc = PackedEncoder(model, rows=int(cfg["assumed"]["batch_max"]))
    enc.warm()
    vecs = enc([h.tolist() for h in hs])
    out = np.asarray(jnp.matmul(
        jnp.asarray(vecs), params["head"].astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST))
    del enc, model, params
    harness.free_device()
    return out


def readings(cell: Dict[str, Any], cfg: Dict[str, Any], config_file: str,
             seed: int, n_histories: int, dump: str = ""
             ) -> Dict[str, Dict[str, float]]:
    hs = histories_of(cell, cfg, seed, n_histories)

    def ref(kind=None, **faults):
        with nemotron_reference.operands(kind):
            return nemotron_reference.forward_layerwise(
                cfg, nemotron_datagen.layer_stream(cfg, seed), hs, **faults)

    got = {"program": program_logits(cfg, config_file, seed, hs),
           "stated_bf16": ref("bf16"), "control_fp8": ref("fp8")}
    for fault in nemotron_reference.FAULTS:
        got[f"fault_{fault}"] = ref("bf16", **{fault: True})
    truth = ref()
    if dump:
        Path(dump).mkdir(parents=True, exist_ok=True)
        np.savez_compressed(Path(dump) / f"nemotron_control_{seed}.npz",
                            reference=truth, **got)
    return {name: numbers(alt, truth, int(cfg["assumed"]["k"]))
            for name, alt in got.items()}


def main(argv=None) -> int:
    """`seq_control.main` (arguments, chip claim, one JSON line a seed,
    the exit code) over this module's `readings`."""
    theirs, seq_control.readings = seq_control.readings, readings
    try:
        return seq_control.main(argv)
    finally:
        seq_control.readings = theirs


if __name__ == "__main__":
    sys.exit(main())
