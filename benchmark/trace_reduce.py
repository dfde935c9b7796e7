"""From the profiler's `.xplane.pb` to the numbers the benchmark reports:
device busy time (the union of the intervals in which an operation ran),
time by program and by operation name, the operations that took most
time, and the longest idle gaps with what the host was doing in each.

Read with `jax.profiler.ProfileData` and nothing else. Every PR computes
these numbers with this file, and no PR that claims a gain may change
it. `tests/test_trace_reduce.py` checks it on a small recorded trace.

Layout of a TPU trace (jax 0.9, libtpu 0.0.34): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Modules` holds one event per run of a
compiled program (`jit_<name>(<fingerprint>)`) and whose line `XLA Ops`
holds one event per operation inside it; host threads are lines of the
plane `/host:CPU`, where `jax.profiler.TraceAnnotation` spans land.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_MODULE_LINE = "XLA Modules"
_OPS_LINE = "XLA Ops"
# device lines that restate others (steps, annotations): never counted
# as work
_NOT_WORK = re.compile(r"^(Steps|XLA TraceMe|Framework|Source code|"
                       r"Launch Stats)", re.I)

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclass
class Reduced:
    """One traced window, reduced. Times in seconds."""
    window_s: float
    # per device plane, in plane order
    busy_s: List[float] = field(default_factory=list)
    # program name (fingerprint stripped) -> durations of its runs, all
    # devices together
    modules: Dict[str, List[float]] = field(default_factory=dict)
    # operation name -> total seconds, all devices together
    ops: Dict[str, float] = field(default_factory=dict)
    # (name of what the host was doing, seconds), longest first; the
    # first device only
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]

    def module_seconds(self, pattern: str) -> List[float]:
        """Durations of every run of the programs whose name matches."""
        rx = re.compile(pattern)
        return [d for name, ds in self.modules.items() if rx.search(name)
                for d in ds]


def find_xplane(trace_dir: str) -> str:
    """The one `.xplane.pb` a `jax.profiler.start_trace(trace_dir)` ...
    `stop_trace()` pair wrote."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_seconds(intervals: Iterable[Interval]) -> float:
    """Length of the union of intervals given in nanoseconds."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total / 1e9


def idle_gaps(intervals: Iterable[Interval], lo: float, hi: float
              ) -> List[Interval]:
    """The complement of the union inside [lo, hi], longest first."""
    gaps, at = [], lo
    for a, b in sorted(intervals):
        if a > at:
            gaps.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])


def _clip(lo: float, hi: float, w_lo: float, w_hi: float
          ) -> Optional[Interval]:
    lo, hi = max(lo, w_lo), min(hi, w_hi)
    return (lo, hi) if hi > lo else None


def _strip(name: str) -> str:
    """`jit_fn(1234567)` -> `jit_fn`."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """The `XLA Ops` line names an event by its whole HLO instruction,
    `%fusion.16 = (u32[1]{0:T(128)}, ...) fusion(...)`: keep the
    instruction's own name."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_profile(profile, window_name: str = "bench:traced",
                   top_gaps: int = 10) -> Reduced:
    """Reduce a `ProfileData`. The window is the host span named
    `window_name` (a `TraceAnnotation` the benchmark puts around what it
    traces); without one it is the extent of the device events."""
    device_planes, host_events = [], []
    window: Optional[Interval] = None
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                span = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if ev.name == window_name and window is None:
                    window = (span[0], span[1])
                elif ev.duration_ns > 0:
                    host_events.append(span)

    per_device: List[List[Interval]] = []
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    raw_modules, raw_ops = [], []
    for plane in device_planes:
        lines = {ln.name: ln for ln in plane.lines}
        work_lines = ([lines[_OPS_LINE]] if _OPS_LINE in lines else
                      [ln for name, ln in lines.items()
                       if name != _MODULE_LINE and not _NOT_WORK.match(name)])
        ivs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ln in work_lines for ev in ln.events
               if ev.duration_ns > 0]
        raw_ops.append(ivs)
        raw_modules.append(
            [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for ev in lines[_MODULE_LINE].events]
            if _MODULE_LINE in lines else [])
    if window is None:
        flat = [iv for ivs in raw_ops for iv in ivs]
        if not flat:
            return Reduced(window_s=0.0)
        window = (min(iv[0] for iv in flat), max(iv[1] for iv in flat))
    w_lo, w_hi = window
    for ivs, mods in zip(raw_ops, raw_modules):
        clipped = []
        for lo, hi, name in ivs:
            c = _clip(lo, hi, w_lo, w_hi)
            if c is not None:
                clipped.append(c)
                op = _op_name(name)
                ops[op] = ops.get(op, 0.0) + (c[1] - c[0]) / 1e9
        per_device.append(clipped)
        for lo, hi, name in mods:
            # a program counts where it STARTS: one cut by the window's
            # end is still one whole run
            if w_lo <= lo < w_hi:
                modules.setdefault(_strip(name), []).append((hi - lo) / 1e9)

    gaps: List[Tuple[str, float]] = []
    if per_device:
        for lo, hi in idle_gaps(per_device[0], w_lo, w_hi)[:top_gaps]:
            gaps.append((_host_activity(host_events, lo, hi),
                         (hi - lo) / 1e9))
    return Reduced(window_s=(w_hi - w_lo) / 1e9,
                   busy_s=[union_seconds(ivs) for ivs in per_device],
                   modules=modules, ops=ops, gaps=gaps)


def _host_activity(host_events: Sequence[Tuple[float, float, str]],
                   lo: float, hi: float) -> str:
    """What the host was doing in a device gap: the host span that
    overlaps the gap most, the shortest such span on a tie (so a span
    inside `bench:window` wins over `bench:window` itself)."""
    best, best_key = "no host span", None
    for a, b, name in host_events:
        overlap = min(b, hi) - max(a, lo)
        if overlap <= 0:
            continue
        key = (round(overlap / (hi - lo), 2), -(b - a))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce_dir(trace_dir: str, **kw) -> Reduced:
    import jax
    return reduce_profile(
        jax.profiler.ProfileData.from_file(find_xplane(trace_dir)), **kw)
