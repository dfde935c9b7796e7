"""Memory-pressure guard: soft/hard watermarks with trim + drain.

The PR-14 observatory exports `pio_host_rss_bytes` and
`pio_device_memory_bytes` but nothing *acts* before the kernel OOM
killer does. This guard closes the loop with two watermarks on the
fraction of the memory limit in use (host RSS against the cgroup /
MemTotal limit, and device bytes_in_use against bytes_limit where the
backend reports one):

  soft (`PIO_MEM_SOFT_FRAC`, default 0.85)
       trim bounded state — every registered trim callback runs (trace
       ring, tsdb rings, quality accumulators, tenant key cache,
       prepared-ingest cache) — and shed NEW work `503 surface=memory`
       while over the watermark; inflight work completes.
  hard (`PIO_MEM_HARD_FRAC`, default 0.95)
       additionally fail `/ready` (the fleet ejects / stops routing to
       this process) and fire the drain callback ONCE — a graceful
       stop() beats an OOM kill mid-request.

`check()` is swept by the watchdog thread (`attach_guard`), so there
is no extra thread; `PIO_MEM_LIMIT_BYTES` overrides limit discovery
and the chaos seams `mem.pressure.soft` / `mem.pressure.hard` force a
state for scenario runs.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional, Tuple

from predictionio_tpu.obs import get_logger, get_registry
from predictionio_tpu.resilience.faults import faults

_log = get_logger(__name__)

OK, SOFT, HARD = "ok", "soft", "hard"
_LEVELS = {OK: 0.0, SOFT: 1.0, HARD: 2.0}
DEFAULT_SOFT_FRAC = 0.85
DEFAULT_HARD_FRAC = 0.95
TRIM_INTERVAL_S = 10.0


def _envf(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def host_memory_limit() -> Optional[int]:
    """Best available host memory budget in bytes: the explicit
    `PIO_MEM_LIMIT_BYTES` override, else the cgroup v2/v1 limit, else
    /proc/meminfo MemTotal. None when nothing is discoverable (the
    guard then only watches device watermarks)."""
    override = os.environ.get("PIO_MEM_LIMIT_BYTES", "").strip()
    if override:
        try:
            return int(float(override))
        except ValueError:
            pass
    for path in ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
        try:
            raw = open(path, "rb").read().strip()
        except OSError:
            continue
        if raw and raw != b"max":
            try:
                limit = int(raw)
            except ValueError:
                continue
            if 0 < limit < (1 << 60):    # v1 reports ~2^63 for "none"
                return limit
    try:
        with open("/proc/meminfo", "rb") as fh:
            for line in fh:
                if line.startswith(b"MemTotal:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


def _rss_bytes() -> Optional[int]:
    try:
        with open("/proc/self/statm", "rb") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


def device_memory_frac() -> Optional[float]:
    """Worst bytes_in_use / bytes_limit across devices, or None when
    this process has not initialised a backend (the guard never
    initialises one) or the backend reports no limits (CPU)."""
    from predictionio_tpu.utils.device import live_devices
    worst: Optional[float] = None
    for d in live_devices():
        stats = d.memory_stats() or {}
        limit = stats.get("bytes_limit")
        in_use = stats.get("bytes_in_use")
        if not limit or in_use is None:
            continue
        frac = float(in_use) / float(limit)
        if worst is None or frac > worst:
            worst = frac
    return worst


class MemoryGuard:
    """Watermark state machine + trim registry; see module docstring.

    `check()` is cheap (two /proc reads) and idempotent; tests call it
    directly, production piggybacks on the watchdog sweep.
    """

    def __init__(self, soft_frac: Optional[float] = None,
                 hard_frac: Optional[float] = None,
                 limit_bytes: Optional[int] = None,
                 trim_interval_s: float = TRIM_INTERVAL_S):
        self.soft_frac = soft_frac if soft_frac is not None else _envf(
            "PIO_MEM_SOFT_FRAC", DEFAULT_SOFT_FRAC)
        self.hard_frac = hard_frac if hard_frac is not None else _envf(
            "PIO_MEM_HARD_FRAC", DEFAULT_HARD_FRAC)
        self.limit_bytes = limit_bytes if limit_bytes is not None \
            else host_memory_limit()
        self.trim_interval_s = trim_interval_s
        self.state = OK
        self._trims: List[Tuple[str, Callable[[], int]]] = []
        self._on_hard: List[Callable[[], None]] = []
        self._hard_fired = False
        self._last_trim = 0.0
        reg = get_registry()
        self._state_gauge = reg.gauge(
            "pio_mem_pressure_state",
            "Memory watermark state: 0 ok, 1 soft (trim+shed), "
            "2 hard (drain)")
        self._frac_gauge = reg.gauge(
            "pio_mem_used_frac",
            "Worst observed memory fraction (host RSS/limit vs device "
            "in_use/limit)")
        self._trim_counter = reg.counter(
            "pio_mem_trims_total",
            "Soft-watermark trim passes, by target", labels=("target",))
        self._trim_bytes = reg.counter(
            "pio_mem_trimmed_bytes_total",
            "Approximate bytes released by soft-watermark trims",
            labels=("target",))
        self._state_gauge.set(0.0)

    # -- registration -------------------------------------------------------
    def add_trim(self, target: str, fn: Callable[[], int]) -> None:
        """Register a bounded-state trimmer; `fn()` returns the
        approximate bytes released."""
        self._trims.append((target, fn))

    def on_hard(self, fn: Callable[[], None]) -> None:
        """Callback fired exactly once when the hard watermark trips
        (the owner starts its graceful drain)."""
        self._on_hard.append(fn)

    # -- admission hooks ----------------------------------------------------
    def shedding(self) -> bool:
        """True while new work should be refused `503 surface=memory`."""
        return self.state != OK

    def ready(self) -> bool:
        """False once the hard watermark tripped: `/ready` degrades so
        routers stop sending work here."""
        return self.state != HARD

    def detail(self) -> Dict:
        return {"state": self.state, "softFrac": self.soft_frac,
                "hardFrac": self.hard_frac,
                "limitBytes": self.limit_bytes}

    # -- the periodic check -------------------------------------------------
    def observed_frac(self) -> Optional[float]:
        """Worst of host RSS/limit and device in_use/limit; None when
        neither is measurable."""
        fracs = []
        if self.limit_bytes:
            rss = _rss_bytes()
            if rss is not None:
                fracs.append(rss / float(self.limit_bytes))
        dev = device_memory_frac()
        if dev is not None:
            fracs.append(dev)
        return max(fracs) if fracs else None

    def check(self) -> str:
        """Sample, transition, and act; returns the new state."""
        f = faults()
        forced: Optional[str] = None
        if f.armed:
            if f.dropped("mem.pressure.hard"):
                forced = HARD
            elif f.dropped("mem.pressure.soft"):
                forced = SOFT
        frac = self.observed_frac()
        if frac is not None:
            self._frac_gauge.set(frac)
        if forced is not None:
            state = forced
        elif frac is None:
            state = OK
        elif frac >= self.hard_frac:
            state = HARD
        elif frac >= self.soft_frac:
            state = SOFT
        else:
            state = OK
        if state != self.state:
            _log.warning("mem_pressure_transition", previous=self.state,
                         state=state,
                         frac=round(frac, 4) if frac is not None else None)
        self.state = state
        self._state_gauge.set(_LEVELS[state])
        if state == OK:
            self._hard_fired = False        # re-arm the drain latch
            return state
        self._maybe_trim()
        if state == HARD and not self._hard_fired:
            self._hard_fired = True
            for fn in list(self._on_hard):
                try:
                    fn()
                except Exception as e:   # noqa: BLE001 — drain best-effort
                    _log.warning("mem_hard_callback_failed",
                                 error=f"{type(e).__name__}: {e}")
        return state

    def _maybe_trim(self) -> int:
        now = time.monotonic()
        if now - self._last_trim < self.trim_interval_s:
            return 0
        self._last_trim = now
        total = 0
        for target, fn in list(self._trims):
            try:
                freed = int(fn() or 0)
            except Exception as e:   # noqa: BLE001 — trims independent
                _log.warning("mem_trim_failed", target=target,
                             error=f"{type(e).__name__}: {e}")
                continue
            self._trim_counter.labels(target=target).inc()
            if freed > 0:
                self._trim_bytes.labels(target=target).inc(freed)
                total += freed
        _log.warning("mem_pressure_trimmed", state=self.state,
                     freed_bytes=total, targets=len(self._trims))
        return total
