"""The readings that the limits of a sequence cell's `correct` are set
from, other than the program's own in the served window (those every
run prints): the PROGRAM's stack called directly (no server), the plain
reference put in its place at the STATED precision (bfloat16 operands),
the CONTROL one precision below (float8_e4m3 operands), and three
planted FAULTS (the window ignored; the sink dropped; the share's
experts shifted by one; each at the stated precision, as a program
with that fault would serve). Each is served in the program's place and
goes through `reference.verdict` with the cell's limits: the program
and the stated precision have to come out correct, the control and
every fault not correct, or the exit code is 1.

  python3 benchmark/seq_control.py --workload mimo25-hist-c32 --seeds 1 2

runs on the chip at the cell's own widths over a few histories (the
longest among them) and prints one JSON line a seed; `--dump DIR`
keeps every reading's logits there. The benchmark's own runs never run
it; `tests/test_seq.py` keeps it at the toy size. PERF.md section 2
lists the readings and the limits set from them.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent))

import harness                                             # noqa: E402
import reference                                           # noqa: E402
import seq_datagen                                         # noqa: E402
import seq_reference                                       # noqa: E402
from drivers.seq_http_closed import compare_logits, user_lengths  # noqa: E402


MUST_PASS = ("program", "stated_bf16")


def numbers(alt: np.ndarray, ref: np.ndarray, k: int) -> Dict[str, float]:
    """The cell's compared numbers for logits `alt` served in place of
    the program's: its k best, their scores, no bans."""
    ids = np.argsort(-alt, axis=1, kind="stable")[:, :k]
    replies = [{"ids": [int(i) for i in row],
                "scores": [float(alt[q, i]) for i in row], "banned": None}
               for q, row in enumerate(ids)]
    return compare_logits(replies, ref, k, 0)


def program_logits(cfg: Dict[str, Any], config_file: str, seed: int,
                   hs: List[np.ndarray]) -> np.ndarray:
    """The program's packed stack and head over the same histories."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.ops import backbone
    from predictionio_tpu.ops.seqrec import PackedEncoder, SeqRecModel
    bcfg = backbone.load_config(config_file)
    params = seq_datagen.program_params(cfg, seed)
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


def logits(cell: Dict[str, Any], cfg: Dict[str, Any], config_file: str,
           seed: int, n_histories: int) -> Dict[str, np.ndarray]:
    """[histories, V] logits of every reading, and `reference`."""
    traffic = cell["traffic"]
    lengths = user_lengths(cfg, traffic)
    items = seq_datagen.histories(lengths, int(traffic["n_items"]),
                                  float(traffic["item_zipf_s"]), seed)
    ends = np.cumsum(lengths)
    rng = np.random.default_rng([int(seed), 31])
    users = set(rng.choice(len(lengths), n_histories - 1,
                           replace=False).tolist())
    users.add(int(np.argmax(lengths)))
    hs = [items[ends[u] - lengths[u]:ends[u]] for u in sorted(users)]

    def ref(kind=None, **faults):
        with seq_reference.operands(kind):
            return seq_reference.forward_layerwise(
                cfg, seq_datagen.layer_stream(cfg, seed), hs, **faults)

    return {"program": program_logits(cfg, config_file, seed, hs),
            "reference": ref(),
            "stated_bf16": ref("bf16"),
            "control_fp8": ref("fp8"),
            "fault_window_ignored": ref("bf16", ignore_window=True),
            "fault_sink_dropped": ref("bf16", drop_sink=True),
            "fault_share_shifted": ref("bf16", shift_share=1)}


def readings(cell: Dict[str, Any], cfg: Dict[str, Any], config_file: str,
             seed: int, n_histories: int, dump: str = ""
             ) -> Dict[str, Dict[str, float]]:
    got = logits(cell, cfg, config_file, seed, n_histories)
    if dump:
        Path(dump).mkdir(parents=True, exist_ok=True)
        np.savez_compressed(Path(dump) / f"seq_control_{seed}.npz", **got)
    truth = got.pop("reference")
    return {name: numbers(alt, truth, int(cfg["assumed"]["k"]))
            for name, alt in got.items()}


def failures(got: Dict[str, Dict[str, float]],
             limits: Dict[str, float]) -> List[str]:
    """The readings that came out the wrong way under `limits`."""
    return [name for name, n in got.items()
            if reference.verdict(n, limits)[0] != (name in MUST_PASS)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--histories", type=int, default=12)
    ap.add_argument("--dump", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_json(BENCH_DIR / "workloads" / f"{args.workload}.json")
    config_file = BENCH_DIR / "configs" / f"{cell['config']}.json"
    cfg = harness.load_json(config_file)
    if args.rehearse_cpu:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    device = harness.claim_chips(int(cell["chips"]), args.rehearse_cpu)
    limits, wrong = cell["correct"]["limits"], 0
    for seed in args.seeds:
        got = readings(cell, cfg, str(config_file), seed, args.histories,
                       args.dump)
        bad = failures(got, limits)
        wrong += len(bad)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": device["device_kind"],
                          "limits": limits, "readings": got,
                          "came_out_wrong": bad}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
