"""Readers of the sequence cells' per-layer metrics that `readers.py`
has no function for: the whole step's share of the chip's peak and the
two kernels' roofline shares. Each returns None, never 0, where it
finds nothing to read (a program without the stack, an untraced run, a
CPU): the line then leaves the metric out.

The driver puts under `facts["seq"]` what it counted over the window's
completed queries with `seq_opcount`: `serve_flops`, `attn` and `moe`
as (FLOPs, bytes) over the whole window, and `calls`, the stack calls
the window made (the count of the program's `pio_seq_call_tokens`).
The experts' part of `moe` and `serve_flops` is counted from the pairs
the window's calls computed (the sum of `pio_moe_expert_pairs`); a
program without that counter leaves both out.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

import opcount


def serve_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Required FLOPs of the queries completed in the window over
    (window x the chip's bf16 peak)."""
    seq = facts.get("seq")
    if not seq or "serve_flops" not in seq or not facts.get("window_s") \
            or "peaks" not in facts:
        return None
    return (100.0 * seq["serve_flops"]
            / (facts["window_s"] * facts["peaks"]["bf16_flops_per_s"]))


def kernel_roofline(facts: Dict[str, Any], ops: str, work: str,
                    module: str) -> Optional[float]:
    """Least time the chip could take for the kernel's required
    operations and bytes over the device time of its events in the
    traced seconds. The window's required work is scaled to the traced
    seconds by the stack calls that started in them over the calls of
    the whole window."""
    seq, tr = facts.get("seq"), facts.get("trace")
    if not seq or tr is None or "peaks" not in facts \
            or not seq.get("calls") or work not in seq:
        return None
    rx = re.compile(ops)
    spent = sum(s for name, s in tr.ops.items() if rx.search(name))
    traced_calls = len(tr.module_seconds(module))
    if spent <= 0 or not traced_calls:
        return None
    share = traced_calls / seq["calls"]
    flops, bytes_ = seq[work]
    least, bound = opcount.roofline_seconds(flops * share, bytes_ * share,
                                            facts["peaks"])
    facts.setdefault("bounds", {})[work] = bound
    return 100.0 * least / spent
