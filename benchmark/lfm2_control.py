"""The readings that the limits of an LFM2 cell's `correct` are set
from, as `seq_control.py` for the MiMo cell: the PROGRAM's stack called
directly (no server), the plain reference put in its place at the
STATED precision (bfloat16 operands), the CONTROL one precision below
(float8_e4m3 operands), and four planted FAULTS
(`lfm2_reference.FAULTS`: a tap dropped; a stranger's events read
before a history's first; the q/k norm dropped; the expert bias left
out of the selection; each at the stated precision, as a program with
that fault would serve). Each is served in the program's place and goes
through `reference.verdict` with the cell's limits: the program and the
stated precision have to come out correct, the control and every fault
not correct, or the exit code is 1.

  python3 benchmark/lfm2_control.py --workload lfm2-hist-c32 --seeds 1 2

runs on the chip at the cell's own widths over a few histories (the
longest and the shortest among them: the leak is widest where a history
is short) and prints one JSON line a seed; `--dump DIR` keeps every
reading's logits there. The benchmark's own runs
never run it; `tests/test_lfm2.py` keeps it at the toy size. PERF.md
section 2 lists the readings and the limits set from them.
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
import lfm2_datagen                                        # noqa: E402
import lfm2_reference                                      # noqa: E402
from drivers.seq_http_closed import user_lengths           # noqa: E402
import seq_control                                         # noqa: E402
from seq_control import MUST_PASS, failures, numbers       # noqa: E402,F401


def program_logits(cfg: Dict[str, Any], config_file: str, seed: int,
                   hs: List[np.ndarray]) -> np.ndarray:
    """The program's packed stack and tied head over the histories."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.ops import backbone
    from predictionio_tpu.ops.seqrec import PackedEncoder, SeqRecModel
    bcfg = backbone.load_config(config_file)
    params = lfm2_datagen.program_params(cfg, seed)
    model = SeqRecModel(params=params, n_items=bcfg.vocab,
                        backbone=backbone.config_dict(bcfg))
    enc = PackedEncoder(model, rows=int(cfg["assumed"]["batch_max"]))
    enc.warm()
    vecs = enc([h.tolist() for h in hs])
    out = np.asarray(jnp.matmul(
        jnp.asarray(vecs), params["embed"].astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST))
    del enc, model, params
    harness.free_device()
    return out


def histories_of(cell: Dict[str, Any], cfg: Dict[str, Any], seed: int,
                 n: int) -> List[np.ndarray]:
    """n of the cell's users' histories: the longest, the shortest and
    a seeded draw of the others."""
    traffic = cell["traffic"]
    lengths = user_lengths(cfg, traffic)
    items = lfm2_datagen.histories(lengths, int(traffic["n_items"]),
                                   float(traffic["item_zipf_s"]), seed)
    ends = np.cumsum(lengths)
    ends_of = {int(np.argmax(lengths)), int(np.argmin(lengths))}
    others = np.setdiff1d(np.arange(len(lengths)), sorted(ends_of))
    rng = np.random.default_rng([int(seed), 31])
    users = ends_of | set(rng.choice(others, max(n - 2, 0),
                                     replace=False).tolist())
    return [items[ends[u] - lengths[u]:ends[u]] for u in sorted(users)]


def readings(cell: Dict[str, Any], cfg: Dict[str, Any], config_file: str,
             seed: int, n_histories: int, dump: str = ""
             ) -> Dict[str, Dict[str, float]]:
    hs = histories_of(cell, cfg, seed, n_histories)

    def ref(kind=None, **faults):
        with lfm2_reference.operands(kind):
            return lfm2_reference.forward_layerwise(
                cfg, lfm2_datagen.layer_stream(cfg, seed), hs, **faults)

    got = {"program": program_logits(cfg, config_file, seed, hs),
           "stated_bf16": ref("bf16"), "control_fp8": ref("fp8")}
    for fault in lfm2_reference.FAULTS:
        got[f"fault_{fault}"] = ref("bf16", **{fault: True})
    truth = ref()
    if dump:
        Path(dump).mkdir(parents=True, exist_ok=True)
        np.savez_compressed(Path(dump) / f"lfm2_control_{seed}.npz",
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
