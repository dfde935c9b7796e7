"""The per-layer metrics' readers. A metric is `layer_metrics/<name>.json`:
{"reader": "<module>.<function>", "args": {...}, "about": "..."}; the
harness calls `function(facts, **args)` and leaves the metric out of the
line when it returns None. A reader that finds nothing to read returns
None, never 0. A later PR adds a metric by adding a file, and a reader of
a new kind by adding a module beside this one.

`facts` is what the driver gathered: `phase_timings` (one dict a train),
`train_seconds`, `hist` (the metrics registry's window delta),
`trace` (trace_reduce.Reduced of the traced window, or None),
`completed`, `window_s`, `client_p95_ms`, `config`, `peaks`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import opcount


def phase_mean_ms(facts: Dict[str, Any], key: str) -> Optional[float]:
    """Mean over the window's trains of one of the program's own train
    phase timings (host clocks that end in block_until_ready)."""
    vals = [p[key] for p in facts.get("phase_timings", ()) if key in p]
    return 1e3 * sum(vals) / len(vals) if vals else None


def _module_seconds(facts, match: str):
    tr = facts.get("trace")
    return tr.module_seconds(match) if tr is not None else []


def module_device_ms(facts: Dict[str, Any], match: str) -> Optional[float]:
    """Mean device time of one run of the compiled programs whose name
    matches, over the runs that started in the traced window."""
    runs = _module_seconds(facts, match)
    return 1e3 * sum(runs) / len(runs) if runs else None


def als_roofline(facts: Dict[str, Any], match: str) -> Optional[float]:
    """Least time the chip could take for one train's required
    operations and bytes, over the device time of one run of the ALS
    program."""
    runs = _module_seconds(facts, match)
    if not runs or "peaks" not in facts:
        return None
    c = facts["config"]
    flops, bytes_ = opcount.als_train(
        c["n_ratings"], c["n_users"], c["n_items"], c["rank"],
        c["assumed"]["iterations"], gather_bytes=2)
    least, bound = opcount.roofline_seconds(flops, bytes_, facts["peaks"])
    facts.setdefault("bounds", {})["als_roofline"] = bound
    return 100.0 * least / (sum(runs) / len(runs))


def train_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Required FLOPs of one train over (one train's wall time x the
    chip's bf16 peak), over the trains of this run's window."""
    secs = facts.get("train_seconds")
    if not secs or "peaks" not in facts:
        return None
    c = facts["config"]
    flops, _ = opcount.als_train(
        c["n_ratings"], c["n_users"], c["n_items"], c["rank"],
        c["assumed"]["iterations"])
    wall = sum(secs) / len(secs)
    return 100.0 * flops / (wall * facts["peaks"]["bf16_flops_per_s"])


def topk_roofline(facts: Dict[str, Any], match: str,
                  batch_hist: str) -> Optional[float]:
    """Least time for one top-k call (its batch the mean coalesced batch
    of the window, rounded up to the plan's bucket) over the mean device
    time of one call."""
    runs = _module_seconds(facts, match)
    batch = hist_mean(facts, batch_hist)
    if not runs or batch is None or "peaks" not in facts:
        return None
    c = facts["config"]
    bucket = 1
    while bucket < batch:
        bucket *= 2
    flops, bytes_ = opcount.topk_call(c["n_items"], c["rank"], bucket)
    least, bound = opcount.roofline_seconds(flops, bytes_, facts["peaks"])
    facts.setdefault("bounds", {})["topk_roofline"] = bound
    return 100.0 * least / (sum(runs) / len(runs))


def serve_mfu(facts: Dict[str, Any]) -> Optional[float]:
    """Required FLOPs of the queries completed in the window over
    (window x the chip's bf16 peak)."""
    if (not facts.get("completed") or not facts.get("window_s")
            or "peaks" not in facts):
        return None
    c = facts["config"]
    flops = opcount.topk_query_flops(c["n_items"], c["rank"])
    return (100.0 * flops * facts["completed"]
            / (facts["window_s"] * facts["peaks"]["bf16_flops_per_s"]))


def device_idle(facts: Dict[str, Any]) -> Optional[float]:
    """1 - (union of the device's operation intervals) / traced window,
    averaged over the chips used."""
    tr = facts.get("trace")
    if tr is None or not tr.busy_s or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s / tr.window_s)


def fact(facts: Dict[str, Any], key: str,
         scale: float = 1.0) -> Optional[float]:
    """A number the driver itself took in the window (`facts[key]`), as
    it is; nothing where the driver took none."""
    v = facts.get(key)
    if isinstance(v, (int, float)) and math.isfinite(v):
        return scale * v
    return None


def _hist(facts, name: str):
    h = (facts.get("hist") or {}).get(name)
    return h if h and h.get("count") else None


def hist_mean(facts: Dict[str, Any], name: str,
              scale: float = 1.0) -> Optional[float]:
    """Mean of a registry histogram over the window."""
    h = _hist(facts, name)
    return scale * h["sum"] / h["count"] if h else None


def hist_quantile(facts: Dict[str, Any], name: str, q: float,
                  scale: float = 1.0) -> Optional[float]:
    """q-quantile of a registry histogram over the window, interpolated
    inside its bucket (the histogram_quantile model; a value beyond the
    last finite bound reads as that bound)."""
    h = _hist(facts, name)
    if not h:
        return None
    target, cum = q * h["count"], 0
    for i, c in enumerate(h["buckets"]):
        cum += c
        if c > 0 and cum >= target:
            if i >= len(h["bounds"]):
                return scale * h["bounds"][-1]
            lo = h["bounds"][i - 1] if i else 0.0
            frac = (target - (cum - c)) / c
            return scale * (lo + (h["bounds"][i] - lo) * frac)
    return None
