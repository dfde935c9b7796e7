"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell, one process. Checks the manifest, claims the chip
(and fails without one), hands the cell to its driver, and prints as its
last line of standard output one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and
last `compared`, each compared number beside its limit. Everything else
worth reading goes on earlier `# key: value` lines. See README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import importlib                                           # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import sys                                                 # noqa: E402
from pathlib import Path                                   # noqa: E402
from typing import Any, Dict, List, Optional               # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent))

import harness                                             # noqa: E402
from manifest import Manifest, ManifestError               # noqa: E402


def layer_metrics(manifest: Manifest, cell: str, facts: Dict[str, Any]
                  ) -> Dict[str, Dict[str, Any]]:
    """The cell's per-layer metrics: each from its own file's reader; one
    whose reader finds nothing to read is left out."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in manifest.per_layer(cell):
        spec = harness.load_json(
            BENCH_DIR / "layer_metrics" / f"{m['name']}.json")
        mod, _, fn = spec["reader"].rpartition(".")
        value = getattr(importlib.import_module(mod), fn)(
            facts, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(manifest: Manifest, cell_name: str, seed: int, seconds: float,
             trace: bool, rehearse_cpu: bool = False,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """Everything of a run after the arguments: returns the result
    object. Raises harness.NoChip before any work where there is no
    chip."""
    cell = harness.load_json(BENCH_DIR / "workloads" / f"{cell_name}.json")
    config = harness.load_json(BENCH_DIR / "configs" / f"{cell['config']}.json")
    # no run may warm only the batch sizes an earlier run saw
    os.environ["PIO_DISPATCH_STATE"] = "off"
    device = harness.claim_chips(int(cell["chips"]), rehearse_cpu)
    rc = harness.RunContext(cell=cell, config=config, seed=seed,
                            seconds=seconds, trace=trace,
                            t_start=T_START if t_start is None else t_start)
    rc.note("cell", cell_name)
    rc.note("device", device)
    driver = importlib.import_module(f"drivers.{cell['driver']}")
    res = driver.run(rc)

    import opcount
    facts = res["facts"]
    facts["config"] = config
    if device["platform"] != "cpu":   # a share of a peak is never read on a CPU
        facts["peaks"] = opcount.peaks_for(device["device_kind"])
    # a rehearsal cell reports as the cell it rehearses
    as_cell = cell.get("rehearses", cell_name)
    if trace:
        metrics = layer_metrics(manifest, as_cell, facts)
        rc.note("roofline_bounds", facts.get("bounds", {}))
    else:
        metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in manifest.end_to_end(as_cell)}
    out: Dict[str, Any] = {
        "correct": bool(res["correct"]),
        "attempted": int(res["attempted"]), "failed": int(res["failed"]),
        "metrics": metrics,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": int(device["device_count"]),
                   "memory_peak_bytes": int(res["memory_peak_bytes"])},
    }
    tr = facts.get("trace")
    if trace and tr is not None:
        out["device"]["busy_s"] = tr.mean_busy_s
        out["device"]["window_s"] = tr.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in tr.gaps[:10]]}
    out["compared"] = res["compared"]
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run a rehearsal cell (one BENCHMARK.json does "
                         "not list) on the CPU; never a listed cell")
    args = ap.parse_args(argv)
    try:
        manifest = Manifest.load(queued=args.rehearse_cpu)
        manifest.check()
    except (OSError, ManifestError, KeyError) as e:
        print(f"benchmark: manifest refused: {e}", file=sys.stderr)
        return 2
    listed = args.workload in Manifest.load().cells
    if listed == args.rehearse_cpu:
        print("benchmark: a cell of BENCHMARK.json runs on the chip only, "
              "and only a rehearsal cell runs with --rehearse-cpu",
              file=sys.stderr)
        return 2
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import predictionio_tpu                            # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not here: {e}", file=sys.stderr)
        return 3
    try:
        out = run_cell(manifest, args.workload, args.seed, args.seconds,
                       bool(args.trace), rehearse_cpu=args.rehearse_cpu)
    except harness.NoChip as e:
        print(f"benchmark: no chip: {e}", file=sys.stderr)
        return 4
    for name, c in out["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
