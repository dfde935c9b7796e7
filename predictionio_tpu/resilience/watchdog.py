"""Thread-liveness watchdog: beats, stall detection, loop restart.

The serve plane runs on ~15 long-lived loop threads (wire reactors,
micro-batch drain, refresher, fleet health/lease, quality joiner, tsdb
scraper, fsck scheduler, replica agent). Before this module, a dead or
wedged loop was silent: a dead refresher froze freshness, a dead lease
loop forfeited leadership, a wedged drainer hung every request. Here
every loop registers a named `Beat` and stamps it once per tick; the
`pio-watchdog` thread sweeps beat ages and reacts:

  stall    age past max(role budget, PIO_WATCHDOG_STALL_S): count
           `pio_watchdog_stalls_total{role}`, dump the offender's stack
           (same `sys._current_frames()` walk the profiler uses), and —
           for restartable loops — supersede and respawn the thread.
  death    the loop body raised (the `guard()` trampoline logs the
           traceback and counts `pio_thread_deaths_total{role}`) or the
           thread vanished: respawn with jittered exponential backoff.
  breaker  K rapid deaths inside a sliding window → give up, mark the
           beat degraded so the owner's `/ready` flips and the fleet
           ejection / standby-takeover paths take over. Non-restartable
           roles (reactor, lease) degrade on the first death/stall.

`Beat.beat()` is ONE GIL-atomic monotonic store — safe on the wire hot
path (lint enforces the single-statement body). Background loops call
`Beat.tick()` instead, which additionally consults the chaos seams
`thread.<role>.stall` (latency rule) and `thread.<role>.die` (error
rule) so scenarios can wedge or kill any loop deterministically.

Knobs: `PIO_WATCHDOG` (`off` disables the sweeper; beats and death
accounting stay live), `PIO_WATCHDOG_STALL_S` (default 10).
"""

from __future__ import annotations

import os
import random
import threading
import time
from typing import Callable, Dict, List, Optional

from predictionio_tpu.obs import get_logger, get_registry
from predictionio_tpu.obs.profiler import obs_tick, obs_tick_seconds
from predictionio_tpu.resilience.faults import faults

_log = get_logger(__name__)

DEFAULT_STALL_S = 10.0
# jittered exponential respawn backoff, and the crash-loop breaker:
# BREAKER_K deaths inside BREAKER_WINDOW_S gives up on the loop
BACKOFF_BASE_S = 0.2
BACKOFF_MAX_S = 5.0
BREAKER_K = 5
BREAKER_WINDOW_S = 30.0


class Superseded(Exception):
    """Raised by `Beat.tick()` inside a loop thread the watchdog has
    already replaced (it was stalled, a fresh thread took over): the
    stale thread must exit quietly, not double-run the loop."""


class Beat:
    """One liveness stamp per long-lived loop thread.

    The loop calls `tick()` (background cadence, chaos seams) or
    `beat()` (hot path, stamp only) once per iteration; the watchdog
    compares `time.monotonic() - stamp` against the role budget.
    """

    __slots__ = ("role", "budget_s", "restart", "restartable", "stamp",
                 "thread_ident", "closed", "dead", "degraded", "reason",
                 "restarts", "stalled", "death_times", "next_restart_at")

    def __init__(self, role: str, budget_s: float = 0.0,
                 restart: Optional[Callable[[], None]] = None):
        self.role = role
        self.budget_s = budget_s
        self.restart = restart
        self.restartable = restart is not None
        self.stamp = time.monotonic()
        self.thread_ident: Optional[int] = None
        self.closed = False
        self.dead = False
        self.degraded = False
        self.reason = ""
        self.restarts = 0
        self.stalled = False
        self.death_times: List[float] = []
        self.next_restart_at: Optional[float] = None

    # -- loop-side API ------------------------------------------------------
    def beat(self) -> None:
        """Hot-path stamp: exactly one GIL-atomic attribute store."""
        self.stamp = time.monotonic()

    def tick(self) -> None:
        """Background-loop stamp: honors the `thread.<role>.stall` /
        `thread.<role>.die` chaos seams and exits superseded threads."""
        ident = threading.get_ident()
        if self.thread_ident is not None and self.thread_ident != ident:
            raise Superseded(self.role)
        f = faults()
        if f.armed:
            # a latency rule at thread.<role>.stall wedges the loop; an
            # error rule at thread.<role>.die kills the thread (the
            # guard trampoline then counts the death)
            f.check(f"thread.{self.role}.stall")
            f.check(f"thread.{self.role}.die")
        self.stamp = time.monotonic()

    def attach(self) -> None:
        """Bind the beat to the calling thread (loop entry / respawn)."""
        self.thread_ident = threading.get_ident()
        self.stamp = time.monotonic()
        self.dead = False
        self.stalled = False

    def close(self) -> None:
        """Clean shutdown: the watchdog drops the beat on next sweep."""
        self.closed = True
        if self.degraded:
            # the owner is going away; don't leave the degraded gauge
            # stuck at 1 for a role nobody runs anymore
            _degraded_gauge().labels(role=self.role).set(0.0)

    def guard(self, body: Callable[[], None]) -> None:
        """Crash trampoline: run the loop body; an escape is logged with
        the traceback and counted (`pio_thread_deaths_total{role}`)
        before the thread exits — death is visible even with the
        watchdog sweeper disabled."""
        self.attach()
        try:
            body()
        except Superseded:
            _log.info("thread_superseded", role=self.role)
        except BaseException as e:   # noqa  one-line obit, then exit
            _deaths().labels(role=self.role).inc()
            self.dead = True
            _log.exception("thread_died", role=self.role,
                           error=f"{type(e).__name__}: {e}")

    # -- watchdog-side helpers ---------------------------------------------
    def age(self, now: Optional[float] = None) -> float:
        return (now if now is not None else time.monotonic()) - self.stamp

    def mark_degraded(self, reason: str) -> None:
        self.degraded = True
        self.reason = reason
        _degraded_gauge().labels(role=self.role).set(1.0)

    def snapshot(self) -> Dict:
        return {"role": self.role, "age_s": round(self.age(), 3),
                "budget_s": self.budget_s,
                "restartable": self.restartable,
                "restarts": self.restarts, "dead": self.dead,
                "degraded": self.degraded, "reason": self.reason}


def _deaths():
    return get_registry().counter(
        "pio_thread_deaths_total",
        "Loop threads that exited on an uncaught exception",
        labels=("role",))


def _degraded_gauge():
    return get_registry().gauge(
        "pio_thread_degraded",
        "1 when the watchdog has given up on this role (crash loop, "
        "or a non-restartable loop died/stalled)", labels=("role",))


class Watchdog:
    """Sweeps registered beats, dumps stalled stacks, restarts loops.

    Process-wide singleton by default (`watchdog()`), like the metrics
    registry and the fault injector; servers call `ensure_started()`
    and components register their beats directly.
    """

    def __init__(self, stall_s: Optional[float] = None,
                 interval_s: Optional[float] = None):
        if stall_s is None:
            try:
                stall_s = float(os.environ.get("PIO_WATCHDOG_STALL_S",
                                               DEFAULT_STALL_S))
            except ValueError:
                stall_s = DEFAULT_STALL_S
        self.stall_s = max(stall_s, 0.1)
        self.interval_s = interval_s if interval_s is not None \
            else max(min(1.0, self.stall_s / 4.0), 0.05)
        self._lock = threading.Lock()
        self._beats: List[Beat] = []
        self._guards: List = []      # memory-pressure guards swept too
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        reg = get_registry()
        self._age_gauge = reg.gauge(
            "pio_thread_beat_age_seconds",
            "Seconds since each loop thread last stamped its beat",
            labels=("role",))
        self._stalls = reg.counter(
            "pio_watchdog_stalls_total",
            "Stalls detected (beat age past the role budget)",
            labels=("role",))
        self._restarts = reg.counter(
            "pio_thread_restarts_total",
            "Loop threads respawned by the watchdog", labels=("role",))
        self._tick_seconds = obs_tick_seconds(reg, "watchdog")

    # -- registration -------------------------------------------------------
    def register(self, role: str, budget_s: float = 0.0,
                 restart: Optional[Callable[[], None]] = None) -> Beat:
        """A new beat for `role`. `budget_s` widens the stall threshold
        beyond PIO_WATCHDOG_STALL_S (slow-cadence loops pass their
        interval); `restart` makes the loop restartable."""
        beat = Beat(role, budget_s=budget_s, restart=restart)
        with self._lock:
            self._beats.append(beat)
        return beat

    def attach_guard(self, guard) -> None:
        """Sweep-piggybacked periodic check (the memory-pressure
        guard): `guard.check()` runs every watchdog interval."""
        with self._lock:
            if guard not in self._guards:
                self._guards.append(guard)

    def detach_guard(self, guard) -> None:
        with self._lock:
            if guard in self._guards:
                self._guards.remove(guard)

    def beats(self) -> List[Beat]:
        with self._lock:
            return list(self._beats)

    def degraded_roles(self) -> List[str]:
        with self._lock:
            return [b.role for b in self._beats
                    if b.degraded and not b.closed]

    # -- lifecycle ----------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def ensure_started(self) -> bool:
        if os.environ.get("PIO_WATCHDOG", "").strip().lower() == "off":
            return False
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return True
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="pio-watchdog", daemon=True)
            self._thread.start()
        return True

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5)
        self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sweep()
            except Exception as e:   # noqa: BLE001 — sweeper survives
                _log.warning("watchdog_sweep_failed",
                             error=f"{type(e).__name__}: {e}")

    # -- the sweep ----------------------------------------------------------
    def sweep(self) -> None:
        """One pass over all beats: export ages, detect stalls/deaths,
        run due restarts. Public so tests drive it synchronously."""
        with obs_tick(self._tick_seconds, "watchdog"):
            self._sweep()

    def _sweep(self) -> None:
        now = time.monotonic()
        alive = {t.ident for t in threading.enumerate()}
        with self._lock:
            self._beats = [b for b in self._beats if not b.closed]
            beats = list(self._beats)
            guards = list(self._guards)
        for beat in beats:
            self._age_gauge.labels(role=beat.role).set(beat.age(now))
            if beat.degraded:
                continue
            if beat.next_restart_at is not None:
                if now >= beat.next_restart_at:
                    self._respawn(beat)
                continue
            thread_gone = (beat.thread_ident is not None
                           and beat.thread_ident not in alive)
            if beat.dead or thread_gone:
                self._on_death(beat, now,
                               "uncaught exception" if beat.dead
                               else "thread vanished")
                continue
            limit = max(beat.budget_s, self.stall_s)
            if beat.age(now) > limit and not beat.stalled:
                self._on_stall(beat, now)
        for guard in guards:
            try:
                guard.check()
            except Exception as e:   # noqa: BLE001 — guard never kills
                _log.warning("pressure_check_failed",
                             error=f"{type(e).__name__}: {e}")

    def _on_stall(self, beat: Beat, now: float) -> None:
        beat.stalled = True
        self._stalls.labels(role=beat.role).inc()
        stack = ""
        if beat.thread_ident is not None:
            from predictionio_tpu.obs import profiler
            stack = profiler.format_thread_stack(beat.thread_ident)
        _log.warning("thread_stalled", role=beat.role,
                     age_s=round(beat.age(now), 3),
                     budget_s=max(beat.budget_s, self.stall_s),
                     stack=stack)
        if beat.restartable:
            # can't kill a wedged Python thread: supersede it (its next
            # tick() raises Superseded) and respawn a fresh one
            self._on_death(beat, now, "stalled")
        else:
            beat.mark_degraded(f"stalled (age {beat.age(now):.1f}s)")

    def _on_death(self, beat: Beat, now: float, why: str) -> None:
        if not beat.restartable:
            beat.mark_degraded(why)
            _log.warning("thread_degraded", role=beat.role, reason=why)
            return
        beat.death_times = [t for t in beat.death_times
                            if now - t <= BREAKER_WINDOW_S]
        beat.death_times.append(now)
        if len(beat.death_times) >= BREAKER_K:
            beat.mark_degraded(
                f"crash loop: {len(beat.death_times)} deaths in "
                f"{BREAKER_WINDOW_S:.0f}s ({why})")
            _log.warning("thread_crash_loop_giveup", role=beat.role,
                         deaths=len(beat.death_times), reason=why)
            return
        n = len(beat.death_times)
        backoff = min(BACKOFF_BASE_S * (2.0 ** (n - 1)), BACKOFF_MAX_S)
        backoff *= 1.0 + random.random() * 0.25     # jitter
        beat.thread_ident = None      # stale stalled thread exits
        beat.next_restart_at = now + backoff
        _log.warning("thread_restart_scheduled", role=beat.role,
                     reason=why, backoff_s=round(backoff, 3))

    def _respawn(self, beat: Beat) -> None:
        beat.next_restart_at = None
        beat.restarts += 1
        self._restarts.labels(role=beat.role).inc()
        _log.info("thread_restarting", role=beat.role,
                  restarts=beat.restarts)
        try:
            beat.restart()
        except Exception as e:   # noqa: BLE001 — counted as a death
            _log.warning("thread_restart_failed", role=beat.role,
                         error=f"{type(e).__name__}: {e}")
            beat.dead = True

    def snapshot(self) -> Dict:
        return {"running": self.running, "stall_s": self.stall_s,
                "beats": [b.snapshot() for b in self.beats()]}


_default = Watchdog()


def watchdog() -> Watchdog:
    """The process-default watchdog every loop registers with."""
    return _default
