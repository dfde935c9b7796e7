"""The readings that the limits of `correct` are set from, other than the
program's own (those every run prints): the CONTROL, the plain reference
put in the program's place and computed in the nearest precision below
the one the configuration states, and the FAULTS a cell can have, planted
in the reference put in the program's place. Both have to come out as
not correct.

  python3 benchmark/control.py --workload ml25m-train --seeds 1 2 3

runs on the chip at the cell's own size (no window, no server: neither
reading needs one) and prints one JSON line a seed. The benchmark's own
runs never run it; `tests/test_control.py` keeps it at a size a test can
hold. PERF.md section 2 lists the readings and the limits set from them.
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

import datagen                                             # noqa: E402
import harness                                             # noqa: E402
import reference                                           # noqa: E402
from loadgen import RequestStream                          # noqa: E402


def train_readings(cfg: Dict[str, Any], seed: int) -> Dict[str, Dict]:
    """Control: bfloat16 is what the configuration states for the
    gathered operands, so they are rounded to float8_e4m3's 3 mantissa
    bits. Faults: the
    state returned unchanged (the init); half of the ratings left
    out."""
    a = cfg["assumed"]
    n_users, n_items = int(cfg["n_users"]), int(cfg["n_items"])
    kw = dict(rank=int(cfg["rank"]), iterations=int(a["iterations"]),
              reg=float(a["lambda"]), seed=harness.als_seed(seed))
    u, i, r = datagen.ratings(cfg, seed)
    layouts = (reference._layout(u, i, r, n_users),
               reference._layout(i, u, r, n_items))
    start = reference.als_init(
        n_users, n_items, kw["rank"], kw["seed"],
        np.bincount(u, minlength=n_users) > 0,
        np.bincount(i, minlength=n_items) > 0)
    ref = reference.als_reference(u, i, r, n_users, n_items,
                                  layouts=layouts, start=start, **kw)
    out = {}
    for name, bits in (("control_fp8", 3), ("stated_bf16", 7)):
        got = reference.als_reference(u, i, r, n_users, n_items,
                                      layouts=layouts, start=start,
                                      gather_bits=bits, **kw)
        out[name] = reference.compare_factors(got, ref, u, i, r, seed)
    del layouts
    out["fault_unchanged"] = reference.compare_factors(start, ref, u, i, r,
                                                       seed)
    half = slice(0, len(r), 2)
    got = reference.als_reference(u[half], i[half], r[half], n_users,
                                  n_items, start=start, **kw)
    out["fault_half"] = reference.compare_factors(got, ref, u, i, r, seed)
    return out


def serve_readings(cfg: Dict[str, Any], cell: Dict[str, Any], seed: int
                   ) -> Dict[str, Dict]:
    """Control: the configuration states float32 products at `highest`,
    so the same requests are answered by a `high` (three-pass) scoring;
    a bfloat16 scoring, one step further down, is read beside it.
    Faults: one item of an answer altered (the best allowed item left
    out, the eleventh served); the ban list ignored. Requests are drawn
    from the cell's own stream; each "banned" request first bans its
    user's three best items, as a `ban_seen` request in the window bans
    what the user was served."""
    traffic, k = cell["traffic"], int(cfg["assumed"]["k"])
    n_users, n_items, rank = (int(cfg["n_users"]), int(cfg["n_items"]),
                              int(cfg["rank"]))
    stream = RequestStream(traffic, seed)
    reqs = [stream.get(j) for j in range(int(cell["correct"]["sample"]))]
    users = datagen.factors_host(n_users, rank, seed, 0)
    vecs = users[[u for u, _ in reqs]]
    del users
    k_ref = k + int(traffic["banned_max"]) + 6
    none = np.full((len(reqs), k), -1, np.int64)
    passes = {lo: reference.topk_reference(
        datagen.factor_blocks(n_items, rank, seed, 1), vecs, none, k_ref,
        low_precision=lo) for lo in ("high", "bf16")}
    ref = passes["high"]

    def best(q, src, s_key, i_key, ref_key, banned, n):
        keep = np.array([x not in banned for x in src[i_key][q]])
        order = np.argsort(-src[s_key][q][keep], kind="stable")[:n]
        return (src[i_key][q][keep][order], src[s_key][q][keep][order],
                src[ref_key][q][keep][order])

    asked = []
    for q, (_, fill) in enumerate(reqs):
        if fill is None:
            asked.append([])
        else:
            top3 = [int(x) for x in best(q, ref, "top_s", "top_i", "top_s",
                                         set(), 3)[0]]
            asked.append((top3 + fill)[:max(len(fill), 3)])

    def readings(answer) -> Dict[str, float]:
        replies, served_ref = [], np.zeros((len(reqs), k))
        for q in range(len(reqs)):
            ids, scores, sref = answer(q, set(asked[q]))
            replies.append({"ids": [int(x) for x in ids],
                            "scores": [float(x) for x in scores],
                            "banned": asked[q]})
            served_ref[q] = sref
        return reference.compare_replies(replies, ref["top_s"], ref["top_i"],
                                         served_ref, k)

    def exact(q, banned):
        return best(q, ref, "top_s", "top_i", "top_s", banned, k)

    def altered(q, banned):
        return tuple(x[1:] for x in best(q, ref, "top_s", "top_i", "top_s",
                                         banned, k + 1))

    out = {"reference_itself": readings(exact),
           "fault_altered": readings(altered),
           "fault_ban_ignored": readings(lambda q, banned: exact(q, set()))}
    for lo, src in passes.items():
        out[f"control_{lo}"] = readings(
            lambda q, banned: best(q, src, "lo_s", "lo_i", "ref_of_lo",
                                   banned, k))
    return out


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_json(BENCH_DIR / "workloads" / f"{args.workload}.json")
    cfg = harness.load_json(BENCH_DIR / "configs" / f"{cell['config']}.json")
    import jax
    dev = jax.devices()[0]
    for seed in args.seeds:
        out = (train_readings(cfg, seed) if cell["driver"] == "train_loop"
               else serve_readings(cfg, cell, seed))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": f"{dev.platform}/{dev.device_kind}",
                          "readings": out}), flush=True)
        harness.free_device()
    return 0


if __name__ == "__main__":
    sys.exit(main())
