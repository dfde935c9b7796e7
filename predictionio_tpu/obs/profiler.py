"""Always-on sampling profiler + runtime telemetry samplers.

A named background thread (`pio-prof-sampler`) wakes `PIO_PROF_HZ`
times per second (default 19 — a prime, so the sampler never phase-
locks with 10ms/100ms periodic work; `0` disables), walks
`sys._current_frames()`, and folds every thread's stack into a
bounded frame-trie. Threads are attributed to *roles* by their name
prefix (the wire names its reactors/workers, serving names its
drainers, the fleet names its heartbeat loops — the lint gate
enforces `name=` on every `threading.Thread` in the package), so
`/profile.json` can answer "what share of CPU samples land in wire
workers vs the batch drainer" without any per-call instrumentation.

Exports, via `HTTPServerBase` on every server:

  - ``GET /profile.json``  — per-role sample shares plus top frames by
    self and cumulative samples;
  - ``GET /profile.txt?fmt=collapsed`` — flamegraph-ready collapsed
    stacks (``role;frame;frame;... count`` per line; pipe into
    ``flamegraph.pl`` or speedscope).

The trie is bounded (`PIO_PROF_MAX_NODES`, default 4096): once the
node budget is spent, deeper frames fold into the deepest allocated
node, so memory stays O(budget) under pathological stack churn while
hot paths (allocated early, sampled often) keep full depth.

A tick keys and looks up only what changed since the last one. The
sampler keeps, per thread, the code objects of the last tick's chain,
their `id`s and the path they folded to. It follows each thread's
chain to the outermost frame collecting the code objects; where they
are the last tick's, height by height, the thread is parked and takes
its last path whole: nothing is keyed, built or looked up. Any other
chain is keyed from a memo ("module:function" built once per code
object) and its path looked up. This is exact: a frame's key is a
function of its code object alone, and the code objects are held, so
an `id` names the same one from tick to tick. No frame object is held:
one held past its return would keep its locals, a call's device arrays
among them, until the sampler let it go on its own tick (at a cost
above the walk it saves) or, at exit, during finalization.

Samples are counted per distinct path (role and stack): a path's trie
leaf is found on its first sight, by the same walk and budget rule as
ever, and a sample after that costs one count on the path and one on
its leaf. The per-frame self and cumulative counts of `/profile.json`
are derived from the path counts when it is read. The path table is
bounded by the trie's budget, `max_nodes` paths; a path first seen past
it is counted per frame on every sample, as before. Once a tick the
share of the tick's frames in chains unchanged since the last tick,
whose keys were not looked up anew, goes into `pio_prof_fold_reused`.

Alongside the sampler, this module owns the cheap runtime gauges:
GC pauses via `gc.callbacks` (`pio_gc_pause_seconds{generation}`,
folded into the registry by every read of it, and a `pio:gc.gen<n>`
span on the profiler's clock around each collection), host
RSS/CPU/threads from `/proc/self`, and per-device memory from
`jax.Device.memory_stats()` — sampled on the tsdb scrape tick, not
per-request.

The observers' own cost: each tick of the sampler, the tsdb scraper and
the watchdog runs inside a `pio:obs.<loop>` span (`obs_tick`) and
observes its wall time into `pio_obs_tick_seconds{loop}`.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from types import CodeType, FrameType
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from predictionio_tpu.obs import trace
from predictionio_tpu.obs.logs import get_logger
from predictionio_tpu.obs.metrics import MetricsRegistry, get_registry
from predictionio_tpu.utils.device import backend_initialized, live_devices

_log = get_logger("profiler")

DEFAULT_HZ = 19.0
DEFAULT_MAX_NODES = 4096

# thread-name prefix -> role, first match wins (order matters:
# "wire-reactor-" before the generic "wire-" worker catch-all)
_ROLE_PREFIXES: Tuple[Tuple[str, str], ...] = (
    ("wire-reactor-", "reactor"),
    ("wire-", "worker"),
    ("pio-batch-drain", "drainer"),
    ("pio-feedback-drain", "drainer"),
    ("pio-plugin-drain", "drainer"),
    ("pio-refresher", "refresher"),
    ("pio-fleet-", "heartbeat"),
    ("pio-replica-agent", "heartbeat"),
    ("pio-heartbeat-", "heartbeat"),
    ("pio-fsck-sched", "heartbeat"),
    ("pio-quality-join", "joiner"),
    ("pio-prof", "obs"),
    ("pio-tsdb", "obs"),
    ("pio-watchdog", "obs"),
    ("pio-supervisor", "supervisor"),
    ("pio-http-serve", "http"),
    ("MainThread", "main"),
)


def role_of(thread_name: str) -> str:
    """Map a thread name to its serving role (see _ROLE_PREFIXES);
    unrecognized names — test harness threads, user code — are
    "other"."""
    for prefix, role in _ROLE_PREFIXES:
        if thread_name.startswith(prefix):
            return role
    return "other"


def format_thread_stack(ident: int, limit: int = 40) -> str:
    """One thread's current stack as a compact one-line string
    (`mod:func:line < mod:func:line < ...`, innermost first) from the
    same `sys._current_frames()` walk the sampler folds — the
    watchdog's stall dump. Empty string when the thread is gone."""
    frame = sys._current_frames().get(ident)
    if frame is None:
        return ""
    parts: List[str] = []
    f = frame
    while f is not None and len(parts) < limit:
        code = f.f_code
        mod = code.co_filename.rsplit("/", 1)[-1]
        parts.append(f"{mod}:{code.co_name}:{f.f_lineno}")
        f = f.f_back
    return " < ".join(parts)


OBS_TICK_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                    0.025, 0.05, 0.1, 0.25, 1.0)


def obs_tick_seconds(metrics: MetricsRegistry, loop: str):
    """`loop`'s child of pio_obs_tick_seconds, resolved once by the
    observer that owns the loop."""
    return metrics.histogram(
        "pio_obs_tick_seconds",
        "Wall time of one tick of an observer loop (sampler, scraper, "
        "watchdog): what watching the process costs it",
        labels=("loop",), buckets=OBS_TICK_BUCKETS).labels(loop=loop)


@contextmanager
def obs_tick(child, loop: str) -> Iterator[None]:
    """One tick of the observer `loop`: a `pio:obs.<loop>` span on the
    profiler's clock, and its wall time into `child` (from
    `obs_tick_seconds`)."""
    ann = trace.span_open("pio:obs." + loop)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        trace.span_close(ann)
        child.observe(dt)


def _envf(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class _Node:
    """One frame-trie node: children keyed by "module:function" and
    the count of samples whose stack ended exactly here."""

    __slots__ = ("children", "ended")

    def __init__(self):
        self.children: Dict[str, "_Node"] = {}
        self.ended = 0


# frame keys remembered by code object before the memo starts afresh
_KEYS_MAX = 1 << 14


class _Path:
    """One distinct (role, stack) the sampler has folded: the trie node
    its samples end at (None where the budget left its role no root),
    whether the budget cut it short, and its sample count where the
    path table holds it (None where it is counted per frame)."""

    __slots__ = ("stack", "leaf", "truncated", "samples")

    def __init__(self, stack: Tuple[str, ...], leaf: Optional[_Node],
                 truncated: bool, samples: Optional[int]):
        self.stack = stack
        self.leaf = leaf
        self.truncated = truncated
        self.samples = samples


class _Held:
    """What the sampler keeps of one thread from one tick to the next:
    the code objects of its chain (innermost first) and their `id`s,
    the thread name its role came from, and the path the chain folded
    to. No frame is kept: a frame held past its return would keep its
    locals, a call's device arrays among them, until the sampler let
    them go."""

    __slots__ = ("codes", "ids", "name", "role", "path")

    def __init__(self, codes: List[CodeType], ids: List[int], name: str,
                 role: str, path: _Path):
        self.codes = codes
        self.ids = ids
        self.name = name
        self.role = role
        self.path = path


def _count_frames(self_counts: Dict[str, int], cum_counts: Dict[str, int],
                  stack: Tuple[str, ...], n: int) -> None:
    """`n` samples of `stack` into the per-frame counts: its innermost
    frame's self count, and the cumulative count of each distinct frame
    on it."""
    innermost = stack[-1]
    self_counts[innermost] = self_counts.get(innermost, 0) + n
    for key in set(stack):
        cum_counts[key] = cum_counts.get(key, 0) + n


class SamplingProfiler:
    """Bounded folded-stack sampler over `sys._current_frames()`.

    Directly instantiable for tests; the process-global instance
    (one sampler sees every thread, so per-server instances would
    multiply the overhead for identical data) comes from
    `ensure_started()`. Its ticks are observed into the process-default
    registry, as the process is what it samples.
    """

    def __init__(self, hz: Optional[float] = None,
                 max_nodes: Optional[int] = None):
        self.hz = _envf("PIO_PROF_HZ", DEFAULT_HZ) if hz is None else hz
        self.max_nodes = int(
            _envf("PIO_PROF_MAX_NODES", DEFAULT_MAX_NODES)
            if max_nodes is None else max_nodes)
        self.max_nodes = max(16, self.max_nodes)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # per-role trie roots; role itself is the first collapsed segment
        self._roots: Dict[str, _Node] = {}
        self._nodes = 0              # allocated trie nodes across roles
        self._truncated = 0          # samples folded at the node budget
        # (role, stack) -> its path, at most max_nodes of them
        self._paths: Dict[Tuple[str, Tuple[str, ...]], _Path] = {}
        # per-frame counts of the paths past the table's bound
        self._self_counts: Dict[str, int] = {}   # innermost frame
        self._cum_counts: Dict[str, int] = {}    # anywhere on stack
        self._held: Dict[int, _Held] = {}        # thread ident -> last tick
        self._keys: Dict[int, str] = {}          # id(code) -> frame key
        self._codes: List[CodeType] = []         # what _keys is keyed by
        self._role_samples: Dict[str, int] = {}
        self._samples = 0            # thread-samples folded
        self._ticks = 0              # sampler wakeups
        self._started_at = 0.0
        registry = get_registry()
        self._tick_seconds = obs_tick_seconds(registry, "sampler")
        self._reused = registry.histogram(
            "pio_prof_fold_reused",
            "Share of a sampler tick's frames in chains unchanged since "
            "the previous tick (the same code objects), not keyed anew",
            buckets=(0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0)).labels()

    # -- lifecycle -----------------------------------------------------------
    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> bool:
        """Spawn the sampler thread; False (and no thread) when hz<=0
        — hooks stay installed, the loop simply never exists, so
        `PIO_PROF_HZ=0` is zero-overhead."""
        if self.hz <= 0 or self.running:
            return False
        self._stop.clear()
        self._started_at = time.time()
        self._thread = threading.Thread(
            target=self._run, name="pio-prof-sampler", daemon=True)
        self._thread.start()
        return True

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)

    def _run(self) -> None:
        interval = 1.0 / self.hz
        me = threading.get_ident()
        while not self._stop.wait(interval):
            try:
                self.sample_once(skip_ident=me)
            except Exception as e:     # never kill the sampler loop
                _log.warning("prof_sample_failed",
                             error=f"{type(e).__name__}: {e}")

    # -- sampling ------------------------------------------------------------
    def sample_once(self, skip_ident: Optional[int] = None) -> int:
        """Fold one sample of every live thread's stack; returns the
        number of threads folded. Public so tests can drive the fold
        deterministically without a live sampler thread."""
        with obs_tick(self._tick_seconds, "sampler"):
            names = {t.ident: t.name for t in threading.enumerate()}
            return self._sample(sys._current_frames(), names, skip_ident)

    def _sample(self, frames: Dict[int, FrameType], names: Dict[int, str],
                skip_ident: Optional[int]) -> int:
        """Fold one tick of `frames` (thread ident -> innermost frame, as
        `sys._current_frames()` gives them), each thread's role from
        `names` (ident -> thread name); a chain whose code objects are
        the last tick's takes its path whole (see the module
        docstring)."""
        me = threading.get_ident()
        folded = keyed = reused = 0
        with self._lock:
            self._ticks += 1
            before, held = self._held, {}
            role_samples = self._role_samples
            for ident, frame in frames.items():
                if ident == skip_ident:
                    continue
                folded += 1
                name = names.get(ident, "")
                codes: List[CodeType] = []
                f: Optional[FrameType] = frame
                while f is not None:
                    codes.append(f.f_code)
                    f = f.f_back
                ids = list(map(id, codes))
                h = before.get(ident)
                if h is None or h.ids != ids or h.name != name:
                    h = self._refold_locked(h, codes, ids, name)
                    keyed += len(ids)
                else:                    # parked: the same chain
                    reused += len(ids)
                if ident != me:
                    held[ident] = h
                role_samples[h.role] = role_samples.get(h.role, 0) + 1
                path = h.path
                if path.truncated:
                    self._truncated += 1
                if path.leaf is not None:
                    path.leaf.ended += 1
                    if path.samples is None:
                        _count_frames(self._self_counts, self._cum_counts,
                                      path.stack, 1)
                    else:
                        path.samples += 1
            self._held = held
            self._samples += folded
        total = keyed + reused
        self._reused.observe(reused / total if total else 0.0)
        return folded

    def _refold_locked(self, prev: Optional[_Held], codes: List[CodeType],
                       ids: List[int], name: str) -> _Held:
        """The path of the chain whose code objects are `codes`
        (innermost first), each frame's key from the memo."""
        if prev is not None and prev.name == name:
            role = prev.role
        else:
            role = role_of(name)
        keys = self._keys
        stack = [keys.get(i) or self._key(c) for i, c in zip(ids, codes)]
        stack.reverse()                  # outermost first, flamegraph order
        return _Held(codes, ids, name, role,
                     self._path_locked(role, tuple(stack)))

    def _key(self, code: CodeType) -> str:
        """A frame's "module:function", built once per code object. The
        memo is keyed by `id`: a code object's own hash walks its
        constants, nested code included, on every call; the memo holds
        the code objects, so an `id` stays theirs."""
        key = self._keys.get(id(code))
        if key is None:
            if len(self._keys) >= _KEYS_MAX:
                self._keys.clear()
                self._codes.clear()
            mod = code.co_filename.rsplit("/", 1)[-1]
            key = self._keys[id(code)] = f"{mod}:{code.co_name}"
            self._codes.append(code)
        return key

    def _path_locked(self, role: str, stack: Tuple[str, ...]) -> _Path:
        """The path of (role, stack), its trie leaf found (and the trie
        grown, within the budget) on the path's first sight."""
        path = self._paths.get((role, stack))
        if path is None:
            tabled = len(self._paths) < self.max_nodes
            path = _Path(stack, *self._leaf_locked(role, stack),
                         samples=0 if tabled else None)
            if tabled:
                self._paths[(role, stack)] = path
        return path

    def _leaf_locked(self, role: str, stack: Tuple[str, ...]
                     ) -> Tuple[Optional[_Node], bool]:
        """Walk the trie along `stack` under `role`'s root, allocating
        nodes while the budget lasts: the node a sample of it ends at
        (None where the role has no root and the budget is spent) and
        whether the budget cut the walk short. Once the budget is spent
        no node is ever allocated again (until `reset`), so a path's
        leaf is the same on every later sight."""
        node = self._roots.get(role)
        if node is None:
            if self._nodes >= self.max_nodes:   # budget covers roots too
                return None, True
            node = self._roots[role] = _Node()
            self._nodes += 1
        for key in stack:
            child = node.children.get(key)
            if child is None:
                if self._nodes >= self.max_nodes:
                    return node, True
                child = node.children[key] = _Node()
                self._nodes += 1
            node = child
        return node, False

    # -- export --------------------------------------------------------------
    def snapshot_json(self, top: int = 30) -> Dict:
        """Shape served at /profile.json: role shares + top frames (ties
        in frame order)."""
        with self._lock:
            samples = self._samples
            roles = dict(self._role_samples)
            self_counts = dict(self._self_counts)
            cum_counts = dict(self._cum_counts)
            for path in self._paths.values():
                if path.samples and path.leaf is not None:
                    _count_frames(self_counts, cum_counts, path.stack,
                                  path.samples)
            nodes, truncated = self._nodes, self._truncated
            ticks = self._ticks
        self_top = sorted(self_counts.items(),
                          key=lambda kv: (-kv[1], kv[0]))[:top]
        cum_top = sorted(cum_counts.items(),
                         key=lambda kv: (-kv[1], kv[0]))[:top]
        denom = float(samples) or 1.0

        def _frames(pairs: Iterable[Tuple[str, int]]) -> List[Dict]:
            return [{"frame": k, "samples": v,
                     "share": round(v / denom, 4)} for k, v in pairs]

        return {
            "hz": self.hz,
            "running": self.running,
            "ticks": ticks,
            "samples": samples,
            "since": self._started_at,
            "roles": {r: {"samples": n, "share": round(n / denom, 4)}
                      for r, n in sorted(roles.items(),
                                         key=lambda kv: -kv[1])},
            "top_self": _frames(self_top),
            "top_cumulative": _frames(cum_top),
            "trie": {"nodes": nodes, "max_nodes": self.max_nodes,
                     "truncated_samples": truncated},
        }

    def collapsed(self) -> str:
        """Flamegraph collapsed-stack format, one line per unique
        path: ``role;frame;frame;... count``."""
        lines: List[str] = []
        with self._lock:
            for role in sorted(self._roots):
                stack = [(self._roots[role], role)]
                while stack:
                    node, path = stack.pop()
                    if node.ended:
                        lines.append(f"{path} {node.ended}")
                    for key in sorted(node.children):
                        stack.append((node.children[key],
                                      f"{path};{key}"))
        return "\n".join(lines) + ("\n" if lines else "")

    def reset(self) -> None:
        with self._lock:
            self._roots.clear()
            self._nodes = 0
            self._truncated = 0
            self._paths.clear()
            self._self_counts.clear()
            self._cum_counts.clear()
            self._held = {}
            self._role_samples.clear()
            self._samples = 0
            self._ticks = 0


# -- process-global sampler ---------------------------------------------------
_global_lock = threading.Lock()
_global_profiler: Optional[SamplingProfiler] = None


def get_profiler() -> SamplingProfiler:
    """The process-global sampler (created from env knobs on first
    use; NOT started — see ensure_started)."""
    global _global_profiler
    with _global_lock:
        if _global_profiler is None:
            _global_profiler = SamplingProfiler()
        return _global_profiler


def ensure_started() -> SamplingProfiler:
    """Idempotently start the process-global sampler. With
    PIO_PROF_HZ=0 the instance exists (endpoints keep serving an
    empty profile) but no thread runs."""
    prof = get_profiler()
    if not prof.running:
        prof.start()
    return prof


def _reset_global_for_tests() -> None:
    global _global_profiler
    with _global_lock:
        prof, _global_profiler = _global_profiler, None
    if prof is not None:
        prof.stop()


# -- GC pause hook ------------------------------------------------------------
_gc_lock = threading.Lock()
# id(registry) -> pauses the hook has seen and nobody has folded yet,
# as (generation, seconds)
_gc_pending: Dict[int, "deque"] = {}
# the collection in progress: its start (ns) and its span. CPython runs
# collections (and hence callbacks) under a per-interpreter guard, so
# one slot suffices
_gc_open: List = [0, None]
_GC_SPANS = {0: "pio:gc.gen0", 1: "pio:gc.gen1", 2: "pio:gc.gen2"}


def _gc_histogram(metrics: MetricsRegistry):
    return metrics.histogram(
        "pio_gc_pause_seconds",
        "Stop-the-world GC collection pauses by generation",
        buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5),
        labels=("generation",))


def _on_gc(phase: str, info: Dict) -> None:
    """The one `gc.callbacks` hook: a `pio:gc.gen<n>` span around the
    collection on the collecting thread, and its pause queued for every
    hooked registry. It takes no lock and touches no registry family: a
    collection runs inside whichever thread happened to allocate, one
    that may be holding this very family's (non-reentrant) lock, e.g.
    halfway through rendering it, and a hook that observed directly
    deadlocked that thread against itself and wedged every later
    `/metrics` behind it."""
    if phase == "start":
        _gc_open[1] = trace.span_open(
            _GC_SPANS.get(info.get("generation"), "pio:gc"))
        _gc_open[0] = time.perf_counter_ns()
    elif phase == "stop" and _gc_open[0]:
        dt = (time.perf_counter_ns() - _gc_open[0]) / 1e9
        ann, _gc_open[1] = _gc_open[1], None
        trace.span_close(ann)
        pause = (str(info.get("generation", "?")), dt)
        # a tuple is built in C with the GIL held: no install can
        # change the dict under the loop
        for pending in tuple(_gc_pending.values()):
            pending.append(pause)


def install_gc_callbacks(metrics: Optional[MetricsRegistry] = None) -> bool:
    """Time every collection for `pio_gc_pause_seconds{generation}` in
    `metrics`. Idempotent per registry (a test registry gets its own
    queue); one hook serves them all. Returns True on install, False
    for already-installed.

    The hook only appends to a bounded deque; `flush_gc_pauses` folds
    the pauses into the histogram, as a collector of the registry
    (`MetricsRegistry.add_collector`), so every read of the registry
    (`/metrics`, a snapshot, the tsdb tick, the benchmark's window
    delta) holds every pause that ended before it."""
    metrics = metrics if metrics is not None else get_registry()
    _gc_histogram(metrics)      # the family exists before the first pause
    with _gc_lock:
        if id(metrics) in _gc_pending:
            return False
        _gc_pending[id(metrics)] = deque(maxlen=65536)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
    metrics.add_collector(lambda: flush_gc_pauses(metrics))
    return True


def flush_gc_pauses(metrics: Optional[MetricsRegistry] = None) -> int:
    """Fold the pauses the hook has queued for `metrics` into its
    histogram; returns how many. Called from ordinary code (never from
    the hook), where taking the family lock is safe."""
    metrics = metrics if metrics is not None else get_registry()
    pending = _gc_pending.get(id(metrics))
    if not pending:
        return 0
    hist = _gc_histogram(metrics)
    folded = 0
    while True:
        try:
            generation, dt = pending.popleft()
        except IndexError:
            return folded
        hist.labels(generation=generation).observe(dt)
        folded += 1


# -- host /proc sampler -------------------------------------------------------
class HostSampler:
    """RSS / CPU seconds / thread count from `/proc/self` and whether
    this process holds a JAX backend — set on the tsdb tick and on
    every `/metrics`. CPU is a monotone counter advanced by delta."""

    def __init__(self, metrics: Optional[MetricsRegistry] = None):
        m = metrics if metrics is not None else get_registry()
        self._rss = m.gauge("pio_host_rss_bytes",
                            "Resident set size of this process")
        self._threads = m.gauge("pio_host_threads",
                                "Live threads in this process")
        self._cpu = m.counter("pio_host_cpu_seconds_total",
                              "Process CPU time (user+system)")
        self._backend = m.gauge(
            "pio_jax_backend_initialized",
            "1 once this process has initialised a JAX backend (and so "
            "holds whatever chip it found); stays 0 in processes that "
            "do not compute")
        self._page = os.sysconf("SC_PAGE_SIZE")
        self._tick = float(os.sysconf("SC_CLK_TCK")) or 100.0
        self._last_cpu = 0.0

    def sample(self) -> None:
        self._backend.set(1.0 if backend_initialized() else 0.0)
        try:
            with open("/proc/self/statm", "rb") as fh:
                self._rss.set(int(fh.read().split()[1]) * self._page)
            with open("/proc/self/stat", "rb") as fh:
                raw = fh.read()
            # field 2 is "(comm)" and may contain spaces: split after
            # the closing paren, stat fields 14/15 are utime/stime and
            # 20 is num_threads (1-indexed in proc(5))
            fields = raw[raw.rindex(b")") + 2:].split()
            cpu = (int(fields[11]) + int(fields[12])) / self._tick
            self._threads.set(int(fields[17]))
            if cpu > self._last_cpu:
                self._cpu.inc(cpu - self._last_cpu)
            self._last_cpu = cpu
        except (OSError, ValueError, IndexError):
            pass                      # non-procfs hosts: gauges stay 0


def sample_device_memory(metrics: Optional[MetricsRegistry] = None) -> int:
    """Per-device allocator stats into
    `pio_device_memory_bytes{device,kind}` (kind: in_use / peak).
    Returns the number of devices sampled; 0 when this process has not
    initialised a backend (sampling never initialises one) or the
    backend exposes no memory_stats (CPU)."""
    m = metrics if metrics is not None else get_registry()
    gauge = m.gauge("pio_device_memory_bytes",
                    "Device allocator bytes by device and kind",
                    labels=("device", "kind"))
    sampled = 0
    for d in live_devices():
        stats = d.memory_stats() or {}
        if not stats:
            continue
        dev = f"{d.platform}:{d.id}"
        for kind, key in (("in_use", "bytes_in_use"),
                          ("peak", "peak_bytes_in_use")):
            if key in stats:
                gauge.labels(device=dev, kind=kind).set(
                    float(stats[key]))
        sampled += 1
    return sampled
