"""Operations and bytes that the algorithms REQUIRE, from their shapes.

These are the numerators of every roofline and MFU share the benchmark
reports. They count what the mathematics needs (ratings, rank,
iterations; catalog rows, rank, batch), never what an implementation
happens to execute: padding slots, the junk cross blocks of a paired
Gram, recomputation and CG iterations do not count, so an
implementation that wastes work reads a LOWER share, and one that
takes a kernel off the path cannot raise its share by doing less.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device not in peaks.json is an
    error, never a default."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_PEAKS.name}: "
            "add it with its source")
    return table[device_kind]


def als_train(nnz: int, n_users: int, n_items: int, rank: int,
              iterations: int, gather_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one explicit-ALS train requires.

    Per half-step and rating: one rank x rank outer-product accumulate
    (2 R^2) and one right-hand-side accumulate (2 R). Per solved row:
    a Cholesky factorisation (R^3 / 3) and two triangular solves
    (2 R^2). Two half-steps an iteration.

    Bytes per half-step: every rating reads one factor row of the
    opposite side (`gather_bytes` a value: 2 where the configuration
    states bf16 gathers), its int32 index and its float32 value; every
    row's float32 solution is written once.
    """
    r = float(rank)
    rows = n_users + n_items
    flops = iterations * (2.0 * nnz * (2.0 * r * r + 2.0 * r)
                          + rows * (r ** 3 / 3.0 + 2.0 * r * r))
    bytes_ = iterations * (2.0 * nnz * (r * gather_bytes + 4 + 4)
                           + rows * r * 4.0)
    return flops, bytes_


def topk_call(n_items: int, rank: int, batch: float) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one top-k call over the whole catalog
    requires: `batch` query rows against n_items float32 factor rows.
    The factors are read once whatever the batch; the same count
    whichever kernel implements the call."""
    return 2.0 * batch * rank * n_items, 4.0 * rank * n_items


def topk_query_flops(n_items: int, rank: int) -> float:
    """FLOPs one query requires (its row of the score matrix)."""
    return 2.0 * rank * n_items


def roofline_seconds(flops: float, bytes_: float,
                     peaks: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
