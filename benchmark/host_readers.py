"""Readers of the host process's per-layer metrics (PR 38): how much of
the drainer's host time it spent off its CPU, and what share of the
window the collector and the observers held the interpreter. Each
returns None, never 0, where it finds nothing to read (a program
without the family): the line then leaves the metric out.

`facts["hist"]` is the registry's window delta, keyed
`family{label=value}` (`harness.registry_snapshot`).
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def _hist(facts: Dict[str, Any], name: str):
    return (facts.get("hist") or {}).get(name)


def offcpu_mean(facts: Dict[str, Any], wall: str, cpu: str,
                stage: str = "host", less: str = "window",
                scale: float = 1000.0) -> Optional[float]:
    """(Σ wall `stage` − Σ wall `less` − Σ CPU `stage` + Σ CPU `less`)
    over the count of `stage`: the time a cycle's thread was ready and
    not running in `stage` outside `less`, the wait for the interpreter
    lock, a lock or the OS. With `host` and `window` the device's wait
    (`fetch`, which `host` leaves out) and the window's are excluded by
    construction."""
    parts = [_hist(facts, f"{fam}{{stage={s}}}")
             for fam in (wall, cpu) for s in (stage, less)]
    if any(p is None or "sum" not in p for p in parts) \
            or not parts[0]["count"]:
        return None
    w_stage, w_less, c_stage, c_less = (p["sum"] for p in parts)
    return (scale * (w_stage - w_less - c_stage + c_less)
            / parts[0]["count"])


def window_share(facts: Dict[str, Any], family: str,
                 scale: float = 100.0) -> Optional[float]:
    """Σ over every series of the histogram `family` of the window
    delta's `sum`, over the window's seconds: the share of the window
    that what the family times held."""
    prefix = family + "{"
    sums = [h["sum"] for name, h in (facts.get("hist") or {}).items()
            if (name == family or name.startswith(prefix)) and "sum" in h]
    window = facts.get("window_s")
    if not sums or not window:
        return None
    return scale * sum(sums) / window
