"""Flight recorder: allocation-light request tracing for the serve path.

Every request travelling the selector wire gets a `PendingTrace` — a
preallocated list of monotonic stamp slots plus a handful of scalar
attribute fields — attached to the `RawRequest`. Hot-path code only
*stamps* (`st[slot] = perf_counter()`) and never builds dicts or
strings; the span tree is materialized once, after the response bytes
hit the socket, and only for requests the sampler keeps (tools/lint.py
enforces the stamps-only discipline on the hot routes).

Sampling is head-rate (`PIO_TRACE_SAMPLE`, fraction of requests marked
`sampled` at arrival) plus tail-based keep: errored requests and the
slowest decile (a frugal-streaming p90 estimate, O(1) state) are kept
even when the head sampler passed them by. Kept traces land in a
bounded ring (`PIO_TRACE_RING`) served by `/traces.json`, and the kept
trace id is attached to the matching `pio_serve_seconds` bucket as an
exemplar so the p99 bucket links to a real trace.

Fleet stitching: routers forward `X-PIO-Trace`
(`traceid-spanid-flag[-hmac]`, signed with the same shared key as the
`X-PIO-App` identity header) on proxy hops and standby 307 redirects;
a replica adopts the incoming trace id and records its spans under it,
so one `/queries.json` call through a fleet yields router + replica
entries that stitch under a single 128-bit trace id.

Background work (refresher ticks/fold-ins, rolling reloads) records
spans through `background()` into the same ring with `kind=
"background"`.

Batch cycles: each of the micro-batcher's drainers owns one `BatchTrace` per
cycle (one window, one take, one device call). `stage(name)` is the one
instrument of that path: inside a cycle it stamps the interval into the
record (from which `pio_serve_stage_seconds{stage=...}` is observed at
the cycle's end, recorder on or off), and on any thread it enters a
`jax.profiler.TraceAnnotation("pio:batch.<name>")`, so the interval
lies in the `/host:CPU` plane of whatever profile is being taken. The
record reads the drainer thread's own CPU time (`time.thread_time()`)
at the same instants as its wall clock and charges it by the same rule,
into `pio_serve_stage_cpu_seconds{stage=...}`: a stage's wall time less
its CPU time is what the thread waited (the interpreter lock, a lock or
condition, a socket, the device, the OS). With the recorder enabled
every cycle lands in the ring as `kind="batch"` and its members point
at it by `batch_id`.
"""

from __future__ import annotations

import contextvars
import hashlib
import hmac
import json
import os
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from predictionio_tpu.obs.logs import get_logger
from predictionio_tpu.obs.metrics import MetricsRegistry, get_registry

TRACE_HEADER = "X-PIO-Trace"

# Stamp slots, in request order. A slot left at 0.0 means the request
# never passed that stage (e.g. shed before enqueue); materialization
# spans consecutive *present* stamps so the tree always tiles the full
# first->last interval regardless of which stages ran.
S_WIRE_READ = 0      # first socket read of the bytes framing this request
S_FRAMED = 1         # request framed out of the connection buffer
S_HANDLER = 2        # worker picked it up, handler entered
S_AUTH = 3           # authenticated + admitted (tenancy)
S_ENQ = 4            # enqueued on its micro-batch lane
S_DRAIN = 5          # drained out of the lane into a batch
S_EXEC = 6           # the member's batch came back from predict_batch
S_SPLICE = 7         # response payload spliced/encoded
S_DONE = 8           # handler returned the response object
S_SENT = 9           # response bytes written to the socket
N_STAMPS = 10

# Segment names, keyed by the stamp that *ends* the segment.
_SEG_NAMES = {
    S_FRAMED: "wire_frame",
    S_HANDLER: "worker_queue",
    S_AUTH: "auth_admission",
    S_ENQ: "batch_submit",
    S_DRAIN: "lane_wait",
    S_EXEC: "batch",
    S_SPLICE: "response_splice",
    S_DONE: "respond",
    S_SENT: "wire_write",
}

_log = get_logger("trace")

# Latency buckets for pio_serve_seconds and the two wire waits beside
# it; public: the server creates the same families in its own registry.
SERVE_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                 0.25, 0.5, 1.0, 2.5, 5.0)
# One meaning, recorder on or off: the handler's own interval. What the
# request waited before it (pio_wire_worker_wait_seconds) and after it
# (pio_wire_reply_seconds) has its own family; a kept trace's
# duration_ms is the whole, wire read to wire write.
SERVE_SECONDS_HELP = ("Serve latency inside the /queries.json handler "
                      "(handler entry to handler return; lane wait "
                      "included, worker wait and reply not)")

# The stages of one batch cycle, in the order the drainer passes them.
# They tile the cycle: a stage runs from the close of the stage before
# it to its own close, so the glue between two blocks is charged to the
# later one; `predict` is the parent of lookup..unpack.
# A sequence model's predict reads histories and runs its stack before
# the plan's own pack / launch / fetch (SEQ_STAGES); a cycle of any other
# template never enters them and observes nothing under their names.
SEQ_STAGES = ("history", "seq_pack", "seq_launch", "seq_fetch")
STAGES = ("window", "take", "supplement", "predict") + SEQ_STAGES + (
    "lookup", "pack", "launch", "fetch", "unpack", "serve", "encode",
    "wake")
_STAGE_IX = {name: i for i, name in enumerate(STAGES)}
_IX_FETCH = _STAGE_IX["fetch"]
_IX_SEQ_FETCH = _STAGE_IX["seq_fetch"]
STAGE_SECONDS_HELP = (
    "Serve-chain stage wall time. Per request: extract, feedback. Per "
    "batch cycle on the drainer's thread: window, take, supplement, "
    "(a sequence model's history, seq_pack, seq_launch, seq_fetch,) "
    "lookup, pack, launch, fetch, unpack, serve, encode, wake tile the "
    "cycle. Sums of others: predict = history..unpack, cycle = all of "
    "them, host = cycle - fetch - seq_fetch")
STAGE_CPU_SECONDS_HELP = (
    "The drainer thread's own CPU time (time.thread_time) in each stage "
    "of a batch cycle, charged as pio_serve_stage_seconds charges wall "
    "time; wall less CPU is time the thread waited: the interpreter "
    "lock, a lock or condition, a socket, the device, the OS")


class PendingTrace:
    """Per-request stamp slots + scalar attributes; no dicts, no
    strings built until (and unless) the sampler keeps the request."""

    __slots__ = ("st", "trace_id", "span_id", "parent_id", "sampled",
                 "kind", "app", "route", "status", "dispatch", "error",
                 "batch_id", "batch_size", "rid", "extra", "reactor",
                 "query", "serve_s")

    def __init__(self):
        self.st = [0.0] * N_STAMPS
        self.trace_id = ""
        self.span_id = ""
        self.parent_id = ""
        self.sampled = False
        self.kind = ""           # "serve" | "router" | "" (generic)
        self.app = ""
        self.route = ""
        self.status = 0
        self.dispatch = ""       # host|device|sharded|fused
        self.error = ""
        self.batch_id = 0
        self.batch_size = 0
        self.rid = ""
        self.extra = None        # optional [(name, t0, t1), ...]
        self.reactor = -1        # accept-shard index (set by the wire)
        self.query = None        # (user, num) tuple or query dict —
        #                          replayable by the reload canary
        self.serve_s = 0.0       # the handler's own interval, as the
        #                          handler observed it (pio_serve_seconds)


class BatchTrace:
    """One batch cycle of the micro-batcher's drainer, made like
    `PendingTrace`: slots and scalars, nothing built until the cycle
    ends. `start[i]` is the first start of stage i, `dur[i]` the sum of
    its intervals (a batch past the biggest bucket packs, launches and
    fetches more than once), `cpu[i]` the thread's CPU time in them,
    read at the same instants. `hist` maps a stage's name (and `cycle`,
    `host`) to its child of pio_serve_stage_seconds and `cpu_hist` to
    its child of pio_serve_stage_cpu_seconds, so code below the server
    never looks up a registry. A `solo` record is one
    `predict_batch` outside the drainer (no batcher, canary, batch
    predict): it observes the stages it passed and is neither a cycle
    nor kept."""

    __slots__ = ("start", "dur", "cpu", "t_begin", "t_last", "c_begin",
                 "c_last", "last", "closed", "batch_id", "rows", "bucket",
                 "path", "hist", "cpu_hist", "solo")

    def __init__(self, hist=None, solo: bool = False, cpu_hist=None):
        self.start = [0.0] * len(STAGES)
        self.dur = [0.0] * len(STAGES)
        self.cpu = [0.0] * len(STAGES)
        self.t_begin = self.t_last = time.perf_counter()
        self.c_begin = self.c_last = time.thread_time()
        self.last = -1           # the stage that closed last
        self.closed = 0          # how many closed so far
        self.batch_id = 0
        self.rows = 0
        self.bucket = 0          # rows the plan padded the call to
        self.path = ""           # host|device|sharded|fused, as the
        #                          call that dispatched set it
        self.hist = hist
        self.cpu_hist = cpu_hist
        self.solo = solo

    def cycle_s(self) -> float:
        return self.t_last - self.t_begin

    def host_s(self) -> float:
        return (self.t_last - self.t_begin - self.dur[_IX_FETCH]
                - self.dur[_IX_SEQ_FETCH])

    def cycle_cpu_s(self) -> float:
        return self.c_last - self.c_begin

    def host_cpu_s(self) -> float:
        return (self.c_last - self.c_begin - self.cpu[_IX_FETCH]
                - self.cpu[_IX_SEQ_FETCH])


# -- X-PIO-Trace codec (signed-header compatible with X-PIO-App) -------------

def _sign(payload: str, key: str) -> str:
    return hmac.new(key.encode(), payload.encode(),
                    hashlib.sha256).hexdigest()[:16]


def encode_header(trace_id: str, span_id: str, sampled: bool,
                  key: str = "") -> str:
    """`traceid-spanid-flag[-hmac16]`: the value a router asserts to
    its replicas (and a standby attaches to its 307 redirect)."""
    payload = f"{trace_id}-{span_id}-{'1' if sampled else '0'}"
    if not key:
        return payload
    return f"{payload}-{_sign(payload, key)}"


def decode_header(value: Optional[str],
                  key: str = "") -> Optional[Tuple[str, str, bool]]:
    """Parse + verify an X-PIO-Trace value -> (trace_id, parent_span,
    sampled), or None on malformed/unverified input (the request then
    starts a fresh trace — refuse-by-default, like X-PIO-App)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) not in (3, 4):
        return None
    tid, sid, flag = parts[0], parts[1], parts[2]
    if len(tid) != 32 or len(sid) != 16 or flag not in ("0", "1"):
        return None
    try:
        int(tid, 16)
        int(sid, 16)
    except ValueError:
        return None
    if key:
        if len(parts) != 4:
            return None
        payload = f"{tid}-{sid}-{flag}"
        if not hmac.compare_digest(parts[3], _sign(payload, key)):
            return None
    return tid, sid, flag == "1"


def _new_trace_id() -> str:
    return os.urandom(16).hex()


def _new_span_id() -> str:
    return os.urandom(8).hex()


# -- the recorder ------------------------------------------------------------

class TraceRecorder:
    """Process-global flight recorder: head/tail sampling, the bounded
    keep ring, serve-latency exemplars, and the slow-request log."""

    def __init__(self, sample: float = 0.0, ring: int = 512,
                 slow_ms: float = 0.0, key: str = "",
                 metrics: Optional[MetricsRegistry] = None):
        self.sample = max(0.0, min(1.0, float(sample)))
        self.enabled = self.sample > 0.0
        self.slow_ms = max(0.0, float(slow_ms))
        self.key = key or ""
        self._metrics = metrics if metrics is not None else get_registry()
        self._ring: "deque" = deque(maxlen=max(1, int(ring)))
        self._lock = threading.Lock()
        # frugal-streaming p90 estimate of request duration: O(1)
        # state, no reservoir — accurate enough to flag the slow tail
        self._q90 = 0.0
        self._q_n = 0
        self._kept = self._metrics.counter(
            "pio_trace_kept_total", "Traces kept in the ring, by reason",
            labels=("why",))
        self._serve_hist = self._metrics.histogram(
            "pio_serve_seconds", SERVE_SECONDS_HELP,
            labels=("app",), buckets=SERVE_BUCKETS)
        # app -> histogram child: labels() rebuilds key tuples and takes
        # the family lock per call; finish() runs once per request, so
        # resolve each app's child once (cardinality already bounded by
        # admission's label sanitization; capped regardless)
        self._hist_by_app: Dict[str, Any] = {}

    # -- hot-path entry points (called via the wire hooks) -------------------
    def new_stamps(self, t0: float) -> Optional[PendingTrace]:
        """Allocate stamp slots for an arriving request; None when
        tracing is off (the wire then skips all further trace work)."""
        if not self.enabled:
            return None
        p = PendingTrace()
        if t0 > 0.0:
            p.st[S_WIRE_READ] = t0
        # the hook runs as the request is framed out of the buffer
        p.st[S_FRAMED] = time.perf_counter()
        if random.random() < self.sample:
            p.sampled = True
        return p

    # -- finish / keep -------------------------------------------------------
    def finish(self, p: PendingTrace) -> None:
        st = p.st
        t0 = 0.0
        tend = 0.0
        for t in st:
            if t > 0.0:
                if t0 == 0.0:
                    t0 = t
                if t > tend:
                    tend = t
        if t0 == 0.0:
            return
        dur = max(tend - t0, 0.0)
        why = ""
        with self._lock:
            slow = self._tail_slow_locked(dur)
            if p.sampled:
                why = "sampled"
            elif p.error or p.status >= 400:
                why = "error"
            elif slow:
                why = "slow"
            if why:
                entry = self._materialize(p, t0, dur, why)
                self._ring.append(entry)
        if why:
            self._kept.labels(why=why).inc()
            if self.slow_ms > 0.0 and dur * 1000.0 >= self.slow_ms:
                self._slow_log(p, dur)
        if p.kind == "serve":
            # the same interval the handler observes with the recorder
            # off; a handler that died before it could say falls back
            # to its two stamps
            serve_s = p.serve_s
            if serve_s <= 0.0 and st[S_HANDLER] > 0.0:
                serve_s = max(
                    (st[S_DONE] if st[S_DONE] > 0.0 else tend)
                    - st[S_HANDLER], 0.0)
            child = self._hist_by_app.get(p.app)
            if child is None:
                child = self._serve_hist.labels(app=p.app)
                if len(self._hist_by_app) < 1024:
                    self._hist_by_app[p.app] = child
            child.observe(serve_s, exemplar=p.trace_id if why else None)

    def _tail_slow_locked(self, dur: float) -> bool:
        """Frugal-streaming quantile step toward p90; True once the
        estimate has warmed up and `dur` lands in the slow decile."""
        q = self._q90
        self._q_n += 1
        step = max(q * 0.05, 1e-5)
        if dur > q:
            self._q90 = q + step
        else:
            self._q90 = max(q - step / 9.0, 0.0)
        return self._q_n > 64 and dur >= self._q90

    def _materialize(self, p: PendingTrace, t0: float, dur: float,
                     why: str) -> Dict[str, Any]:
        if not p.trace_id:
            p.trace_id = _new_trace_id()
        if not p.span_id:
            p.span_id = _new_span_id()
        spans: List[Dict[str, Any]] = []
        prev = p.st[S_WIRE_READ] if p.st[S_WIRE_READ] > 0.0 else 0.0
        for slot in range(1, N_STAMPS):
            t = p.st[slot]
            if t <= 0.0:
                continue
            if prev > 0.0 and t >= prev:
                spans.append({
                    "name": _SEG_NAMES.get(slot, f"stage{slot}"),
                    "start_ms": round((prev - t0) * 1000.0, 3),
                    "dur_ms": round((t - prev) * 1000.0, 3),
                })
            prev = t
        if p.extra:
            for name, a, b in p.extra:
                spans.append({
                    "name": name,
                    "start_ms": round((a - t0) * 1000.0, 3),
                    "dur_ms": round((b - a) * 1000.0, 3),
                })
        entry: Dict[str, Any] = {
            "trace_id": p.trace_id,
            "span_id": p.span_id,
            "parent_id": p.parent_id,
            "kind": p.kind or "request",
            "name": p.route or "request",
            "app": p.app,
            "status": p.status,
            "dispatch": p.dispatch,
            "duration_ms": round(dur * 1000.0, 3),
            "keep": why,
            "ts": time.time(),
            "spans": spans,
        }
        if p.batch_size:
            entry["batch_id"] = p.batch_id
            entry["batch_size"] = p.batch_size
        if p.error:
            entry["error"] = p.error
        if p.rid:
            entry["request_id"] = p.rid
        if p.reactor >= 0:
            entry["reactor"] = p.reactor
        q = p.query
        if q is not None:
            if isinstance(q, tuple):
                entry["query"] = {"user": q[0], "num": q[1]}
            else:
                entry["query"] = q
        return entry

    def _slow_log(self, p: PendingTrace, dur: float) -> None:
        """One grep-able JSON line per kept-slow trace (PIO_SLOW_MS)."""
        stages = {}
        st = p.st
        prev = 0.0
        for slot in range(N_STAMPS):
            t = st[slot]
            if t <= 0.0:
                continue
            if prev > 0.0 and slot in _SEG_NAMES:
                stages[_SEG_NAMES[slot]] = round((t - prev) * 1000.0, 3)
            prev = t
        _log.warning("slow_request", trace_id=p.trace_id, app=p.app,
                     route=p.route, status=p.status, dispatch=p.dispatch,
                     duration_ms=round(dur * 1000.0, 3), stages=stages)

    # -- background spans ----------------------------------------------------
    def record_background(self, name: str, t0: float, t1: float,
                          app: str = "", error: str = "") -> None:
        entry = {
            "trace_id": _new_trace_id(),
            "span_id": _new_span_id(),
            "parent_id": "",
            "kind": "background",
            "name": name,
            "app": app,
            "status": 0,
            "dispatch": "",
            "duration_ms": round((t1 - t0) * 1000.0, 3),
            "keep": "background",
            "ts": time.time(),
            "spans": [],
        }
        if error:
            entry["error"] = error
        with self._lock:
            self._ring.append(entry)

    # -- batch cycles --------------------------------------------------------
    def record_batch(self, bt: BatchTrace) -> None:
        """One finished cycle into the ring (`kind="batch"`): rows,
        bucket, path and a span per stage it passed, on the cycle's own
        time scale. Kept members carry the same `batch_id`."""
        t0 = bt.t_begin
        spans = [{"name": STAGES[i],
                  "start_ms": round((bt.start[i] - t0) * 1000.0, 3),
                  "dur_ms": round(bt.dur[i] * 1000.0, 3),
                  "cpu_ms": round(bt.cpu[i] * 1000.0, 3)}
                 for i in range(len(STAGES)) if bt.start[i] > 0.0]
        entry = {
            "trace_id": _new_trace_id(),
            "span_id": _new_span_id(),
            "parent_id": "",
            "kind": "batch",
            "name": "batch",
            "app": "",
            "status": 0,
            "dispatch": bt.path,
            "duration_ms": round(bt.cycle_s() * 1000.0, 3),
            "host_ms": round(bt.host_s() * 1000.0, 3),
            "host_cpu_ms": round(bt.host_cpu_s() * 1000.0, 3),
            "keep": "batch",
            "ts": time.time(),
            "batch_id": bt.batch_id,
            "rows": bt.rows,
            "bucket": bt.bucket,
            "spans": spans,
        }
        with self._lock:
            self._ring.append(entry)

    # -- export --------------------------------------------------------------
    def snapshot(self, app: Optional[str] = None,
                 min_ms: Optional[float] = None,
                 trace_id: Optional[str] = None,
                 limit: int = 0,
                 batch_id: Optional[int] = None) -> List[Dict[str, Any]]:
        """Ring contents newest-first, filtered by app / min duration /
        trace id / batch id (a batch and its kept members) — the body
        of `/traces.json`."""
        with self._lock:
            entries = list(self._ring)
        entries.reverse()
        out = []
        for e in entries:
            if app is not None and e.get("app") != app:
                continue
            if min_ms is not None and e.get("duration_ms", 0.0) < min_ms:
                continue
            if trace_id is not None and e.get("trace_id") != trace_id:
                continue
            if batch_id is not None and e.get("batch_id") != batch_id:
                continue
            out.append(e)
            if limit and len(out) >= limit:
                break
        return out

    def ring_len(self) -> int:
        with self._lock:
            return len(self._ring)

    def trim(self, keep_frac: float = 0.5) -> int:
        """Soft-memory-pressure hook: drop the oldest trace entries
        down to `keep_frac` of the current ring; returns approximate
        bytes released (entries are small dicts of spans/stamps)."""
        dropped = 0
        with self._lock:
            keep = max(1, int(len(self._ring) * keep_frac))
            while len(self._ring) > keep:
                self._ring.popleft()
                dropped += 1
        return dropped * 512     # span-list dict estimate


# -- process-global recorder + module-level stamp API ------------------------
# The functions below are the ONLY trace calls the hot-route lint
# allows inside hot functions (see tools/lint.py HOT_TRACE_API).

_REC: Optional[TraceRecorder] = None
_REC_LOCK = threading.Lock()


def configure(sample: Optional[float] = None, ring: Optional[int] = None,
              slow_ms: Optional[float] = None, key: Optional[str] = None,
              metrics: Optional[MetricsRegistry] = None) -> TraceRecorder:
    """(Re)build the process recorder; env supplies any unset knob
    (PIO_TRACE_SAMPLE / PIO_TRACE_RING / PIO_SLOW_MS /
    PIO_SERVER_ACCESS_KEY)."""
    global _REC
    env = os.environ

    def _envf(name: str, default: float) -> float:
        try:
            return float(env.get(name, "") or default)
        except ValueError:
            return default

    if sample is None:
        sample = _envf("PIO_TRACE_SAMPLE", 0.0)
    if ring is None:
        ring = int(_envf("PIO_TRACE_RING", 512))
    if slow_ms is None:
        slow_ms = _envf("PIO_SLOW_MS", 0.0)
    if key is None:
        key = env.get("PIO_SERVER_ACCESS_KEY", "") or ""
    with _REC_LOCK:
        _REC = TraceRecorder(sample=sample, ring=ring, slow_ms=slow_ms,
                             key=key, metrics=metrics)
        return _REC


def get_recorder() -> TraceRecorder:
    rec = _REC
    if rec is None:
        rec = configure()
    return rec


def new_stamps(t0: float) -> Optional[PendingTrace]:
    """Wire hook: stamp slots for an arriving request (None = off)."""
    rec = _REC
    if rec is None or not rec.enabled:
        return None
    return rec.new_stamps(t0)


def on_sent(raw) -> None:
    """Wire hook: response bytes on the socket. Observes the reply's
    interval (handler return to here) into whatever the handler left on
    `raw.reply_obs`, recorder on or off, and finishes the trace."""
    now = time.perf_counter()
    obs = raw.reply_obs
    if obs is not None:
        obs.observe(max(now - raw.t_done, 0.0))
    p = raw.trace
    rec = _REC
    if p is not None and rec is not None:
        p.st[S_SENT] = now
        rec.finish(p)


def stamp(raw, slot: int) -> None:
    """Stamp one stage slot on a RawRequest's pending trace."""
    p = raw.trace
    if p is not None:
        p.st[slot] = time.perf_counter()


def mark(p: Optional[PendingTrace], slot: int) -> None:
    """Stamp one stage slot on a PendingTrace (or None: no-op)."""
    if p is not None:
        p.st[slot] = time.perf_counter()


def begin_raw(raw, header_value: Optional[str] = None,
              kind: str = "") -> Optional[PendingTrace]:
    """Handler entry on the raw fast path: stamp S_HANDLER, adopt any
    incoming X-PIO-Trace context, tag the entry kind."""
    p = raw.trace
    if p is None:
        return None
    p.st[S_HANDLER] = time.perf_counter()
    if kind:
        p.kind = kind
    if header_value:
        adopt(p, header_value)
    return p


def adopt(p: Optional[PendingTrace],
          header_value: Optional[str]) -> None:
    """Join the trace asserted by an upstream hop: same trace id, our
    span parented under the asserting span; an upstream sampled flag
    forces keep so the stitched view is complete."""
    if p is None or not header_value:
        return
    rec = _REC
    ctx = decode_header(header_value, rec.key if rec is not None else "")
    if ctx is None:
        return
    p.trace_id, p.parent_id, flag = ctx
    if flag:
        p.sampled = True


def ensure_ids(p: PendingTrace) -> None:
    if not p.trace_id:
        p.trace_id = _new_trace_id()
    if not p.span_id:
        p.span_id = _new_span_id()


def child_header(p: PendingTrace) -> str:
    """The X-PIO-Trace value to assert downstream of `p`'s span."""
    ensure_ids(p)
    rec = _REC
    return encode_header(p.trace_id, p.span_id, p.sampled,
                         rec.key if rec is not None else "")


def annotate(raw, status: int = 0, app: Optional[str] = None,
             route: Optional[str] = None, dispatch: Optional[str] = None,
             error: Optional[str] = None,
             kind: Optional[str] = None, query=None,
             serve_s: float = 0.0) -> None:
    """Attach scalar attributes to a RawRequest's pending trace —
    keyword scalars only, nothing allocated on the hot path."""
    p = raw.trace
    if p is None:
        return
    if serve_s:
        p.serve_s = serve_s
    if status:
        p.status = status
    if app is not None:
        p.app = app
    if route is not None:
        p.route = route
    if dispatch is not None:
        p.dispatch = dispatch
    if error is not None:
        p.error = error
    if kind is not None:
        p.kind = kind
    if query is not None:
        p.query = query


def annotate_pending(p: Optional[PendingTrace], status: int = 0,
                     app: Optional[str] = None, route: Optional[str] = None,
                     dispatch: Optional[str] = None,
                     error: Optional[str] = None,
                     kind: Optional[str] = None, query=None) -> None:
    """`annotate` for call sites that hold the PendingTrace itself."""
    if p is None:
        return
    if status:
        p.status = status
    if app is not None:
        p.app = app
    if route is not None:
        p.route = route
    if dispatch is not None:
        p.dispatch = dispatch
    if error is not None:
        p.error = error
    if kind is not None:
        p.kind = kind
    if query is not None:
        p.query = query


def add_span(p: Optional[PendingTrace], name: str, t0: float,
             t1: float) -> None:
    """Append a named sub-span (router proxy attempts, redirects)."""
    if p is None:
        return
    if p.extra is None:
        p.extra = []
    p.extra.append((name, t0, t1))


# -- batch cycles: the record of the thread's cycle, and the stage helper -----
_TLS = threading.local()
_ANNOTATION = None


def _annotation():
    """`jax.profiler.TraceAnnotation`, once this process has loaded jax
    itself. `obs` never imports it: an event server or a router has no
    use for it and must not load it, and a process that computes has it
    in `sys.modules` long before its first stage."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        try:
            _ANNOTATION = jax.profiler.TraceAnnotation
        except AttributeError:       # not loaded (yet): ask again later
            return None
    return _ANNOTATION


def span_open(name: str):
    """Enter a `name` span on the profiler's clock (a TraceAnnotation,
    on this thread's line of `/host:CPU`), or nothing where this
    process has not loaded jax; hand the result to `span_close`. Takes
    no lock and touches no registry, so a `gc.callbacks` hook may call
    it."""
    cls = _annotation()
    if cls is None:
        return None
    ann = cls(name)
    ann.__enter__()
    return ann


def span_close(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


class _Stage:
    """One interval of `stage()`: a context manager, and the handle of
    the `stage_open` / `stage_close` pair."""

    __slots__ = ("name", "ix", "bt", "t0", "c0", "mark", "ann")

    def __init__(self, name: str):
        self.name = name
        self.ix = _STAGE_IX.get(name, -1)
        self.bt = None
        self.ann = None

    def __enter__(self):
        bt = getattr(_TLS, "batch", None)
        if bt is not None and self.ix >= 0:
            self.bt = bt
            self.t0 = bt.t_last
            self.c0 = bt.c_last
            self.mark = bt.closed
            if bt.start[self.ix] == 0.0:
                bt.start[self.ix] = bt.t_last
        self.ann = span_open("pio:batch." + self.name)
        return self

    def __exit__(self, *exc):
        span_close(self.ann)
        bt = self.bt
        if bt is not None:
            now = time.perf_counter()
            cnow = time.thread_time()
            if bt.closed != self.mark:
                # stages closed inside this one: it is their parent, and
                # what it did after the last of them is that one's
                bt.dur[bt.last] += now - bt.t_last
                bt.cpu[bt.last] += cnow - bt.c_last
            bt.dur[self.ix] += now - self.t0
            bt.cpu[self.ix] += cnow - self.c0
            bt.t_last = now
            bt.c_last = cnow
            bt.last = self.ix
            bt.closed += 1
        return False


def stage(name: str) -> _Stage:
    """`with trace.stage("pack"):` — one interval of the serve chain.
    On the thread of a batch cycle it is stamped into the cycle's
    record and so observed into pio_serve_stage_seconds{stage=name};
    on any thread it is a `pio:batch.<name>` span of the profile being
    taken, if one is. Outside a cycle (eval, batch predict, a
    template's own call into ops/) it is the span only."""
    return _Stage(name)


def stage_open(name: str) -> _Stage:
    """`stage()` where a `with` does not fit; hand the result to
    `stage_close`."""
    return _Stage(name).__enter__()


def stage_close(handle: _Stage) -> None:
    handle.__exit__(None, None, None)


def batch_begin(hist=None, solo: bool = False,
                cpu_hist=None) -> BatchTrace:
    """Open this thread's cycle record, now: a drainer calls it when
    its turn to form comes and its window opens, so stages tile a cycle
    and the cycles of two drainers overlap. `hist` and `cpu_hist` are
    `stage_children` of the wall and the CPU family."""
    bt = BatchTrace(hist, solo, cpu_hist)
    _TLS.batch = bt
    return bt


def current_batch() -> Optional[BatchTrace]:
    return getattr(_TLS, "batch", None)


def batch_drop() -> None:
    """Close the thread's record unobserved (a window nothing came in:
    the drainer retires)."""
    _TLS.batch = None


def batch_end(bt: BatchTrace) -> float:
    """The cycle is over: observe every stage it passed, `cycle` and
    `host` into the record's histogram children, wall and CPU, and with
    the recorder on put it in the ring. Returns the instant the cycle
    ended."""
    _TLS.batch = None
    for hist, vals, cycle, host in (
            (bt.hist, bt.dur, bt.cycle_s, bt.host_s),
            (bt.cpu_hist, bt.cpu, bt.cycle_cpu_s, bt.host_cpu_s)):
        if hist is None:
            continue
        for i in range(len(STAGES)):
            if bt.start[i] > 0.0:
                hist[STAGES[i]].observe(vals[i])
        if not bt.solo:
            hist["cycle"].observe(cycle())
            hist["host"].observe(host())
    if not bt.solo:
        rec = _REC
        if rec is not None and rec.enabled:
            rec.record_batch(bt)
    return bt.t_last


def note_dispatch(path: str, bucket: int = 0) -> None:
    """The plan call says which path it took and how many rows it
    padded to; lands on the thread's cycle record, if it has one."""
    bt = getattr(_TLS, "batch", None)
    if bt is not None:
        bt.path = path
        if bucket:
            bt.bucket = bucket


def stage_children(family) -> Dict[str, Any]:
    """Every cycle stage's child of a pio_serve_stage_seconds (or
    pio_serve_stage_cpu_seconds) family, resolved once (the drainer
    then observes without a labels() call)."""
    return {name: family.labels(stage=name)
            for name in STAGES + ("cycle", "host")}


# -- contextvar plumbing for the generic (non-fast) route --------------------
_current: "contextvars.ContextVar[Optional[PendingTrace]]" = \
    contextvars.ContextVar("pio_trace", default=None)


def set_current(p: Optional[PendingTrace]):
    return _current.set(p)


def reset_current(token) -> None:
    _current.reset(token)


def current() -> Optional[PendingTrace]:
    return _current.get()


@contextmanager
def background(name: str, app: str = ""):
    """Record a background span (refresher tick/fold-in, rolling
    reload) into the ring; no-op when tracing is off."""
    rec = _REC
    if rec is None or not rec.enabled:
        yield None
        return
    t0 = time.perf_counter()
    err = ""
    try:
        yield None
    except BaseException as e:
        err = type(e).__name__
        raise
    finally:
        rec.record_background(name, t0, time.perf_counter(), app=app,
                              error=err)


def traces_json_body(query_get) -> bytes:
    """Build the `/traces.json` response body. `query_get(name)` pulls
    one query parameter (the Request.query_get shape)."""
    rec = get_recorder()
    app = query_get("app")
    min_ms = query_get("min_ms") or query_get("min_duration_ms")
    tid = query_get("trace_id")
    limit = query_get("limit")
    bid = query_get("batch_id")
    try:
        min_ms_f = float(min_ms) if min_ms else None
    except ValueError:
        min_ms_f = None
    try:
        limit_i = int(limit) if limit else 0
    except ValueError:
        limit_i = 0
    try:
        bid_i = int(bid) if bid else None
    except ValueError:
        bid_i = None
    entries = rec.snapshot(app=app or None, min_ms=min_ms_f,
                           trace_id=tid or None, limit=limit_i,
                           batch_id=bid_i)
    return json.dumps({"traces": entries, "count": len(entries),
                       "enabled": rec.enabled}).encode()
