"""The prediction REST server.

Parity: `core/.../workflow/CreateServer.scala` — MasterActor/ServerActor
collapse into one HTTPServerBase with a swappable `_Deployment` (reload
replaces it atomically, the `/reload` hot-swap of `ServerActor`,
CreateServer.scala:316-342).

Serve chain per request (CreateServer.scala:470-591): extract typed query
-> serving.supplement -> per-algorithm predict -> serving.serve -> output
blockers -> optional feedback event -> JSON. With `batch_window_ms > 0`
concurrent requests are coalesced into one device batch through the
algorithms' `batch_predict` (the reference's "TODO: Parallelize" answered
with MXU batching).

Resilience (predictionio_tpu.resilience): the micro-batch queue is
BOUNDED (`queue_max`) and sheds with 503 + Retry-After when full; every
submit waits with a timeout (request deadline, else `submit_timeout_ms`)
so a dead drainer yields a 504, never a stranded request; one failing
algorithm degrades the serve result instead of failing the whole query
(unless it is the only one); /reload keeps the previous deployment
serving when the new load fails; feedback posts retry with backoff and
then DROP (counted) rather than block the queue forever.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import queue
import random
import re
import string
import threading
import time
import typing
from dataclasses import dataclass
from json.encoder import encode_basestring_ascii as _json_str
from typing import Any, Callable, Dict, List, Optional, Sequence
from urllib.parse import unquote_plus

import numpy as np

from predictionio_tpu.core import (
    RuntimeContext, WorkflowParams, extract_params,
)
from predictionio_tpu.core.workflow import CoreWorkflow, resolve_engine
from predictionio_tpu.data.event import format_time, utcnow
from predictionio_tpu.obs import MetricsRegistry, get_logger, get_registry
from predictionio_tpu.obs import trace
from predictionio_tpu.obs.quality import (
    CanaryGate, QualityStats, quality_enabled,
)
from predictionio_tpu.obs.slo import SLOTracker, dao_overrides_loader
from predictionio_tpu.resilience import (
    DEADLINE_HEADER, CircuitOpenError, Deadline, DeadlineExceeded,
    OverloadedError, RetryPolicy, call_with_retry, current_deadline,
    deadline_from_header, faults,
)
from predictionio_tpu.serving.plugins import (
    EngineServerPluginContext, QueryInfo,
)
from predictionio_tpu.tenancy import (
    DEFAULT_TENANT, TENANT_HEADER, AdmissionController, DRRQueue,
    TenancyConfig, TenantIdentity,
)
from predictionio_tpu.utils.http import (
    HTTPError, HTTPServerBase, Request, Response,
)
from predictionio_tpu.utils.wire import (
    BIN_CONTENT_TYPE, RawRequest, build_response, decode_bin_query,
)

BATCH_SIZE_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                      256.0, 512.0)

_log = get_logger("serving")

# shared executor for the per-algorithm fan-out in predict_batch: device
# dispatch releases the GIL, so independent algorithms overlap. Module
# level + lazy so /reload swapping deployments never leaks pools.
_ALGO_POOL = None
_ALGO_POOL_LOCK = threading.Lock()


def _algo_pool():
    global _ALGO_POOL
    if _ALGO_POOL is None:
        with _ALGO_POOL_LOCK:
            if _ALGO_POOL is None:
                from concurrent.futures import ThreadPoolExecutor
                _ALGO_POOL = ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix="pio-algo")
    return _ALGO_POOL


class _ServeInstruments:
    """The serve-chain metric families, shared by the server, its
    deployments, and the micro-batcher (one registry, one set of
    instruments)."""

    def __init__(self, metrics: Optional[MetricsRegistry] = None):
        metrics = metrics if metrics is not None else get_registry()
        self.stage = metrics.histogram(
            "pio_serve_stage_seconds", trace.STAGE_SECONDS_HELP,
            labels=("stage",))
        # a batch cycle's stages, resolved once: the cycle's record
        # (obs/trace.BatchTrace) carries them down to ops/
        self.cycle_stages = trace.stage_children(self.stage)
        self.algo = metrics.histogram(
            "pio_serve_algo_predict_seconds",
            "Per-algorithm batch_predict wall time", labels=("algo",))
        self.batch_size = metrics.histogram(
            "pio_serve_batch_size",
            "Coalesced device batch size per drain",
            buckets=BATCH_SIZE_BUCKETS)
        self.cycles_in_flight = metrics.histogram(
            "pio_serve_cycles_in_flight",
            "Batch cycles between take and the end of wake, observed "
            "at every take with the taken one counted (1 = serial, "
            "2 = a batch was launched while one was in flight)",
            buckets=(1.0, 2.0))
        self.queue_depth = metrics.gauge(
            "pio_serve_batch_queue_depth",
            "Requests waiting in the micro-batcher")
        self.queue_delay = metrics.histogram(
            "pio_queue_delay_seconds",
            "Micro-batch enqueue->drain latency (feeds the adaptive "
            "shed decision)")
        # a /queries.json request's life is four adjoining intervals:
        # worker wait, the handler (pio_serve_seconds), of which lane
        # wait (pio_queue_delay_seconds), and reply. Each has an exact
        # sum / count, recorder on or off.
        self.worker_wait = metrics.histogram(
            "pio_wire_worker_wait_seconds",
            "First read of a /queries.json request's bytes to handler "
            "entry: the time it waited for a wire worker",
            buckets=trace.SERVE_BUCKETS)
        self.reply = metrics.histogram(
            "pio_wire_reply_seconds",
            "/queries.json handler return to the response's last byte "
            "written to the socket", buckets=trace.SERVE_BUCKETS)
        # `app` on the feedback families follows the shed-metric
        # convention: the authenticated tenant, "" with tenancy off
        self.feedback = metrics.counter(
            "pio_feedback_events_total",
            "Feedback events by outcome (sent/failed/dropped)",
            labels=("outcome", "app"))
        self.feedback_dropped = metrics.counter(
            "pio_feedback_dropped_total",
            "Feedback events dropped (queue full / send retries "
            "exhausted)", labels=("reason", "app"))
        # the `app` label is the shedding tenant ("" on surfaces with no
        # tenant attribution — HTTP-plane inflight, fleet pre-dial)
        self.shed = metrics.counter(
            "pio_shed_total", "Requests shed by surface at admission",
            labels=("surface", "app"))
        self.tenant_serve = metrics.histogram(
            "pio_tenant_serve_seconds",
            "End-to-end serve latency per authenticated app",
            labels=("app",))
        self.algo_errors = metrics.counter(
            "pio_algo_errors_total",
            "Per-algorithm predict failures isolated by graceful "
            "degradation", labels=("algo",))
        self.reloads = metrics.counter(
            "pio_reload_total",
            "Deployment (re)loads by outcome (ok/failed)",
            labels=("outcome",))


@dataclass
class ServerConfig:
    """(ServerConfig, CreateServer.scala:106-162)"""
    ip: str = "0.0.0.0"
    port: int = 8000
    engine_factory: str = ""
    engine_variant: str = "default"
    batch: str = ""
    feedback: bool = False
    event_server_ip: str = "localhost"
    event_server_port: int = 7070
    access_key: Optional[str] = None
    batch_window_ms: int = 0     # 0 = serve each request immediately
    batch_max: int = 64
    verbose: bool = False
    # resilience knobs ----------------------------------------------------
    # micro-batcher pending-queue cap; a full queue sheds with 503 +
    # Retry-After instead of growing without bound
    queue_max: int = 256
    # default per-request deadline (ms; 0 = none) applied when the client
    # sends no X-PIO-Deadline-Ms header
    default_deadline_ms: int = 0
    # hard backstop on a batched submit when no deadline applies: a dead
    # drainer surfaces as 504 after this long, never an eternal hang
    submit_timeout_ms: int = 30000
    # HTTP-plane in-flight cap (0 = unlimited; excess sheds with 429)
    max_inflight: int = 0
    # feedback loop: queue bound, and send attempts before dropping
    feedback_queue_max: int = 1024
    feedback_retries: int = 3
    # Optional server key protecting /reload and /stop (the reference
    # guards both with authenticate(withAccessKeyFromFile),
    # CreateServer.scala:624-637). Sourced from PIO_SERVER_ACCESS_KEY.
    server_key: str = ""
    # run the startup fsck/janitor pass and own the scheduled-fsck
    # thread. Fleet replicas set False: the control plane runs ONE
    # sweep per fleet, not one per replica hammering the same store
    startup_check: bool = True
    # how long stop() waits for accepted requests to drain before the
    # socket closes
    drain_timeout_ms: int = 10000
    # serving mesh spec (e.g. "items=8" or "data=8"); a non-empty value
    # lands in the server's runtime_conf and FORCES the mesh-sharded
    # serve path at warm_deploy (ops/topk_sharded.serve_mesh_from_conf).
    # Empty = auto: shard only when the trained instance recorded a mesh
    # or the catalog exceeds one device's capacity
    mesh: str = ""
    # streaming freshness: > 0 starts a background Refresher thread that
    # delta-scans the journal tail every this-many seconds and fold-swaps
    # updated factors into the live serve plans (0 = disabled; the
    # PIO_REFRESH_INTERVAL_S env knob applies when this is 0)
    refresh_interval_s: float = 0.0
    # fleet rolling variant: delay before the refresher's first tick,
    # set per replica by FleetServer so at most one replica of a fleet
    # is folding at any instant
    refresh_stagger_s: float = 0.0
    # multi-tenant admission (tenancy/): None = read the PIO_TENANCY /
    # PIO_TENANT_* env knobs (default off — the serve path then runs
    # the exact pre-tenancy code shape). FleetServer hands replicas a
    # trust-header variant of the leader's config.
    tenancy: Optional[TenancyConfig] = None
    # prediction-quality observatory (obs/quality.py): None = the
    # PIO_QUALITY env knob (default on; the accumulators are
    # allocation-light and gauge sync is amortised)
    quality: Optional[bool] = None
    # feedback-join attribution window in seconds; <= 0 = the
    # PIO_ATTRIBUTION_S env knob (default 300)
    attribution_s: float = 0.0
    # reload canary: traced queries replayed old-vs-new per reload
    # (< 0 = PIO_CANARY_SAMPLE, default 16; 0 disables the check) and
    # the overlap below which the reload is vetoed (< 0 =
    # PIO_CANARY_MIN_OVERLAP, default 0 = report-only)
    canary_sample: int = -1
    canary_min_overlap: float = -1.0


def to_jsonable(obj: Any) -> Any:
    """Prediction/query dataclasses -> JSON-ready structures."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        # shallow per level: asdict() recurses AND deep-copies the
        # whole tree, then the old code re-traversed its output —
        # measured on the serving hot path (one call per ItemScore)
        return {f.name: to_jsonable(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, (list, tuple)):
        return [to_jsonable(v) for v in obj]
    if isinstance(obj, dict):
        return {k: to_jsonable(v) for k, v in obj.items()}
    if hasattr(obj, "item") and callable(getattr(obj, "item", None)) \
            and type(obj).__module__ in ("numpy", "jax.numpy"):
        return obj.item()   # numpy scalar
    return obj


# -- wire fast path ----------------------------------------------------------
# The compiled query shape: exactly {"user": "<str>", "num": <int>} with
# JSON's optional insignificant whitespace. Anything else — extra fields,
# escapes in the user id, a numeric user, nested anything — falls through
# to the generic json.loads route, which IS the fallback parser, so the
# fast path never has to be complete, only correct on what it claims.
_FAST_QUERY_RE = re.compile(
    rb'\A[ \t\r\n]*\{[ \t\r\n]*"user"[ \t\r\n]*:[ \t\r\n]*'
    rb'"([^"\\\x00-\x1f]{0,512})"[ \t\r\n]*,[ \t\r\n]*'
    rb'"num"[ \t\r\n]*:[ \t\r\n]*(-?(?:0|[1-9]\d{0,8}))[ \t\r\n]*\}'
    rb'[ \t\r\n]*\Z')
# accessKey scanned straight out of the raw query string (the generic
# path runs parse_qs over the whole thing)
_ACCESS_KEY_RE = re.compile(r"(?:^|&)accessKey=([^&]*)")
_CHANNEL_RE = re.compile(r"(?:^|&)channel=([^&]*)")

_EMPTY_SCORES = b'{"itemScores": []}'


def _scan_access_key(qs: str) -> Optional[str]:
    """parse_qs-equivalent extraction of the one parameter the serve
    route reads; percent/plus decoding only when actually present."""
    if "accessKey" not in qs:
        return None
    m = _ACCESS_KEY_RE.search(qs)
    if m is None:
        return None
    v = m.group(1)
    if "%" in v or "+" in v:
        v = unquote_plus(v)
    return v


def _scan_channel(qs: str) -> Optional[str]:
    """Same raw-scan treatment for the optional per-app `channel`
    selector so the binary/fast path resolves channel-scoped quotas
    identically to the generic path."""
    if "channel" not in qs:
        return None
    m = _CHANNEL_RE.search(qs)
    if m is None:
        return None
    v = m.group(1)
    if "%" in v or "+" in v:
        v = unquote_plus(v)
    return v


def _derive_fast_ctor(qc) -> Optional[Callable[[str, int], Any]]:
    """A (user, num) -> Query constructor when — and only when — the
    deployment's query class has a str `user` and an int `num` and every
    other field defaults; else None and the fast path stays dark for
    this deployment. Computed once per (re)load, never per request."""
    if qc is None or not dataclasses.is_dataclass(qc):
        return None
    try:
        hints = typing.get_type_hints(qc)
    except Exception:
        return None
    if hints.get("user") is not str or hints.get("num") is not int:
        return None
    for f in dataclasses.fields(qc):
        if f.name in ("user", "num"):
            continue
        if f.default is dataclasses.MISSING \
                and f.default_factory is dataclasses.MISSING:
            return None
    try:
        qc(user="", num=1)
    except Exception:
        return None
    return lambda u, n: qc(user=u, num=n)


# result type -> encodable? (a dataclass whose ONLY field is itemScores)
_WIRE_RESULT_TYPES: Dict[type, bool] = {}


def _wire_encodable(t: type) -> bool:
    ok = _WIRE_RESULT_TYPES.get(t)
    if ok is None:
        ok = (dataclasses.is_dataclass(t)
              and [f.name for f in dataclasses.fields(t)] == ["itemScores"])
        _WIRE_RESULT_TYPES[t] = ok
    return ok


def _encode_scores_batch(dep, results: Sequence[Any]
                         ) -> Optional[List[Optional[bytes]]]:
    """Pre-serialized response fragments for one drained batch: every
    score in the batch is formatted in ONE vectorized numpy pass
    (%.12g — exact for float32 device scores, 12 significant digits for
    host float64) and spliced between static envelope bytes; item ids go
    through the C JSON string escaper. Returns one wire body per result,
    or None when any result is not a bare itemScores record (the caller
    then serves that batch through to_jsonable + json.dumps)."""
    counts: List[int] = []
    items: List[str] = []
    scores: List[float] = []
    for r in results:
        if not _wire_encodable(type(r)):
            return None
        iss = r.itemScores
        counts.append(len(iss))
        for s in iss:
            it = getattr(s, "item", None)
            if type(it) is not str:
                return None
            items.append(it)
            scores.append(s.score)
    if scores:
        txt = np.char.mod(
            b"%.12g",
            np.asarray(scores, np.float64))  # lint: ok (host floats)
    out: List[Optional[bytes]] = []
    pos = 0
    for n in counts:
        if n == 0:
            out.append(_EMPTY_SCORES)
            continue
        frags = [b'{"item": ' + _json_str(items[j]).encode("utf-8")
                 + b', "score": ' + bytes(txt[j]) + b'}'
                 for j in range(pos, pos + n)]
        pos += n
        out.append(b'{"itemScores": [' + b", ".join(frags) + b']}')
    return out


class _Deployment:
    """One loaded (engine, instance, algorithms, models, serving) set;
    replaced wholesale by /reload."""

    def __init__(self, engine, instance, algos, models, serving,
                 obs: Optional[_ServeInstruments] = None):
        self.engine = engine
        self.instance = instance
        self.algos = algos
        self.models = models
        self.serving = serving
        self.obs = obs if obs is not None else _ServeInstruments()
        self.query_class = next(
            (a.query_class for a in algos if a.query_class is not None), None)
        # wire fast path: a (user, num) constructor when the query class
        # fits the compiled shape — derived once here, consulted per
        # request with a single attribute read
        self.fast_ctor = _derive_fast_ctor(self.query_class)
        # entity maps consulted by the quality accumulators' cold-start
        # (unknown-entity) detection — derived once, read per request
        self.user_maps = tuple(
            um for um in (getattr(m, "users", None) for m in models)
            if um is not None and hasattr(um, "get"))
        # item name -> global id maps, consulted by the mesh shard route
        # to return GLOBAL ids the router can merge and dedupe on
        self.item_maps = tuple(
            im for im in (getattr(m, "items", None) for m in models)
            if im is not None and hasattr(im, "get"))

    def predict_batch(self, queries: Sequence[Any]) -> List[Any]:
        """supplement -> per-algo batch_predict -> serve, for a batch;
        each stage lands in pio_serve_stage_seconds through the batch
        cycle's record — the drainer's, or for a call from anywhere
        else (no batcher, canary, batch predict) a solo one of its own.

        Per-algorithm error isolation: one failing algorithm is dropped
        from the ensemble for this batch (counted in
        pio_algo_errors_total) and serving.serve runs on the surviving
        predictions — a degraded answer instead of a failed query. Only
        when EVERY algorithm fails does the batch error.

        Multi-algorithm ensembles fan out across the shared algo pool —
        device dispatch releases the GIL, so independent algorithms'
        predict work overlaps; ordering and the isolation contract are
        unchanged (results land positionally)."""
        obs = self.obs
        solo = (trace.batch_begin(obs.cycle_stages, solo=True)
                if trace.current_batch() is None else None)
        try:
            return self._predict_batch(queries)
        finally:
            if solo is not None:
                trace.batch_end(solo)

    def _predict_batch(self, queries: Sequence[Any]) -> List[Any]:
        obs = self.obs

        def run_one(i, a, m):
            label = f"{i}:{type(a).__name__}"
            try:
                faults().check(f"serve.predict.{label}")
                with obs.algo.labels(algo=label).time():
                    return dict(a.batch_predict(m, indexed)), None
            except Exception as e:
                obs.algo_errors.labels(algo=label).inc()
                _log.warning(
                    "algo_predict_failed", algo=label,
                    error=f"{type(e).__name__}: {e}",
                    degraded=len(self.algos) > 1)
                return None, e

        with trace.stage("supplement"):
            supplemented = [self.serving.supplement(q) for q in queries]
        indexed = list(enumerate(supplemented))
        # one algorithm predicts on this thread and its stages (lookup,
        # pack, launch, fetch, unpack) are predict's children; several
        # run on the algo pool's threads, which have no cycle record
        with trace.stage("predict"):
            if len(self.algos) == 1:
                outcomes = [run_one(0, self.algos[0], self.models[0])]
            else:
                futures = [
                    _algo_pool().submit(run_one, i, a, m)
                    for i, (a, m) in enumerate(zip(self.algos, self.models))]
                outcomes = [f.result() for f in futures]
        per_algo = [pa for pa, _ in outcomes]
        errors = [e for _, e in outcomes if e is not None]
        alive = [pa for pa in per_algo if pa is not None]
        if not alive:
            raise errors[0]
        with trace.stage("serve"):
            return [self.serving.serve(q, [pa[i] for pa in alive])
                    for i, q in enumerate(queries)]


class _MicroBatcher:
    """Coalesces concurrent requests into device batches.

    Design: double-buffered dynamic batching. Up to TWO drainers live at
    a time, each on its own thread running the whole cycle (window,
    take, predict, encode, wake). At most one of them is FORMING, that
    is between the opening of its window and its take, so batches form
    as one stream under `_lock` and a row is in exactly one batch; at
    most two cycles are IN FLIGHT, between take and the end of wake. As
    soon as a drainer has taken, the other may open its window: the
    next batch forms, and is launched, while one is on the device (its
    call queues there behind the first's, and the first's unpack,
    encode and wake run while it computes). Behind a cycle in flight a
    window opens only once the lane holds as many rows as that cycle
    took (`_my_turn_locked`); otherwise it opens when that cycle ends
    and its callers can come back, as the serial loop's would. A submit
    starts a drainer when fewer than two are alive and none is forming;
    a drainer whose window stays empty retires. The forming drainer
    waits the batching window, takes EVERYTHING pending (up to
    batch_max; a full batch ships at once) and processes it. Because
    processing happens while new requests accumulate, batch sizes grow
    automatically under load until they cross the device-dispatch
    threshold
    (`ops.topk.HOST_CROSSOVER_CELLS`) — the r4 large-catalog bench
    measured the earlier one-thread-per-window design serving 99% of a
    512-request burst in tiny HOST batches (concurrent GIL-bound numpy
    flushes); two cycles keep that property, since whatever arrives
    while both are in flight waits for the next window.

    Device compute always runs OUTSIDE the lock so a drain never stalls
    submitters.

    Resilience: the pending queue is BOUNDED (`queue_max`; full queue
    raises OverloadedError -> 503 + Retry-After upstream) and every
    submit waits with a TIMEOUT — the request deadline when one applies,
    else the `submit_timeout_s` backstop — so a wedged or crashed drainer
    turns into a 504, never a stranded handler thread. A drainer that
    dies on an unexpected error fails its own taken batch, and every
    pending waiter too when no other drainer is alive to serve them;
    the next submit starts a fresh one.

    Adaptive shedding: every drained item's enqueue->drain latency
    lands in pio_queue_delay_seconds and an EWMA of it; a submit whose
    deadline budget (or the submit-timeout backstop) is already below
    that EWMA is shed at ADMISSION with 503 + Retry-After instead of
    being queued to die into a 504 — the queue-delay signal reacts to
    slow drains long before the static queue_max cap fills. The EWMA
    only sheds while work is actually pending, so it self-corrects:
    admitted traffic keeps draining and decays a stale spike.

    Multi-tenancy: the pending store is a DRR queue of per-tenant lanes
    (tenancy/drr.py). Each lane is bounded by the tenant's own
    `queue_max` quota, so one aggressor saturates its lane, not the
    global cap; the drainer composes batches weighted-fair across
    lanes; and the adaptive shed above runs on the SUBMITTING TENANT's
    lane EWMA — the tenant causing the backlog is the one whose items
    wait, so it sheds first while well-behaved tenants keep admitting.
    With tenancy off every item lands in the single default lane and
    all of this reduces exactly to the legacy FIFO behavior.

    Deadline-aware admission: a submit whose deadline cannot survive
    one batching window plus the observed drain time (EWMA of
    `_process` wall time) is shed 504 at the door — no point occupying
    a batch slot with work that expires before its batch returns
    (pio_shed_total{surface=deadline_batch}).

    The batcher also keeps a pow2 histogram of the batch sizes it
    actually formed (`size_counts`); the server persists it beside the
    dispatch-policy snapshot and the next warm_deploy pre-compiles
    exactly the observed shapes instead of the full pow2 ladder."""

    # EWMA smoothing for the observed enqueue->drain latency
    DELAY_ALPHA = 0.2
    # cycles between take and the end of wake, and so live drainers:
    # one on the device and one formed behind it is double buffering
    CYCLES = 2

    def __init__(self, window_s: float, batch_max: int,
                 obs: Optional[_ServeInstruments] = None,
                 queue_max: int = 256, submit_timeout_s: float = 30.0):
        self.window_s = window_s
        self.batch_max = batch_max
        self.queue_max = queue_max
        self.submit_timeout_s = submit_timeout_s
        self.obs = obs if obs is not None else _ServeInstruments()
        # optional batch wire encoder: (deployment, results) -> one
        # pre-serialized body per result (or None to decline the batch).
        # Runs in the DRAINER, once per batch, so the per-request wire
        # fast path never serializes anything itself.
        self.encoder: Optional[
            Callable[[Any, Sequence[Any]],
                     Optional[List[Optional[bytes]]]]] = None
        # optional cross-wakeup to the wire: called once after every
        # drained batch completes, so the reactors can flush deferred
        # pipelined responses at the batch boundary instead of waiting
        # for each owning worker (SelectorWire.flush_hint)
        self.drain_hook: Optional[Callable[[], None]] = None
        self._lock = threading.Lock()
        # wakes the drainer the moment a full batch forms, so a batch
        # that fills mid-window ships immediately instead of sleeping
        # out the rest of the window; also signals close() waiters on
        # retire (predicate re-checked, spurious wakeups harmless)
        self._full = threading.Condition(self._lock)
        # a drainer waits here for its turn to form (_my_turn_locked)
        self._turn = threading.Condition(self._lock)
        # per-tenant DRR lanes; each item: (deployment, query, done
        # event, result slot, enqueue perf_counter, tenant label,
        # pending trace or None)
        self._queue = DRRQueue()
        # links every member trace of one drained batch (batch_id)
        self._batch_seq = itertools.count(1)
        # live drainers (0..CYCLES); of them at most one is forming
        # (window open, not yet taken) and `_in_flight` hold a taken
        # batch, of `_rows_in_flight` rows together, that has not
        # finished its wake
        self._draining = 0
        self._forming = False
        self._in_flight = 0
        self._rows_in_flight = 0
        self._closed = False
        self._delay_ewma = 0.0
        # EWMA of _process wall time, take to wake of ONE cycle — the
        # deadline_batch admission check's estimate of "how long until
        # a batch admitted now actually returns" (with two cycles in
        # flight it includes the wait behind the other cycle's device
        # call, which is what an admitted request will see)
        self._drain_ewma = 0.0
        # when the estimate last saw a real drain: the deadline check
        # ages the EWMA toward zero from here, so a one-off stall (a
        # serve-time recompile, say) cannot shed ALL deadlined traffic
        # forever — shed requests never enqueue, so without decay no
        # batch would ever drain to correct the estimate
        self._drain_t = time.perf_counter()
        # observed pow2 batch-size counts (≤ log2(batch_max) keys, so
        # bounded by construction); feeds warm_deploy bucket autotune
        self._size_counts: Dict[int, int] = {}
        # every live drainer's watchdog beat (empty while idle): a
        # WEDGED drainer can't be killed or safely superseded (its
        # batch is taken), so the watchdog degrades the owner's /ready
        # instead and the fleet routes around it
        self._drain_beats: List[Any] = []

    def queue_delay_ewma(self) -> float:
        """Current smoothed enqueue->drain latency estimate (seconds)."""
        with self._lock:
            return self._delay_ewma

    def drain_time_ewma(self) -> float:
        """Smoothed batch-processing wall time (seconds), aged."""
        with self._lock:
            return self._drain_estimate_locked()

    def _drain_estimate_locked(self) -> float:
        """The drain EWMA, halved per grace interval without a drain.

        Unlike the queue_delay shedder — whose pending-work gate lets
        admitted traffic decay a stale spike — the deadline check runs
        BEFORE enqueue, so a poisoned estimate would be
        self-sustaining: everything sheds, nothing drains, nothing
        corrects. Aging the estimate on the wall clock breaks that
        loop; the grace period (a few expected drain cycles) keeps the
        estimate honest under normal traffic gaps."""
        if self._drain_ewma <= 0.0:
            return self._drain_ewma
        grace = max(4.0 * (self.window_s + self._drain_ewma), 1.0)
        idle = time.perf_counter() - self._drain_t
        if idle <= grace:
            return self._drain_ewma
        return self._drain_ewma * 0.5 ** ((idle - grace) / grace)

    def drain_beats(self) -> List[Any]:
        """The watchdog beats of the drainers alive now."""
        with self._lock:
            return list(self._drain_beats)

    def size_counts(self) -> Dict[int, int]:
        """Observed batch sizes, rounded up to pow2 -> drain count."""
        with self._lock:
            return dict(self._size_counts)

    def restore_size_counts(self, counts: Dict[int, int]) -> None:
        """Seed the size histogram from a persisted snapshot."""
        with self._lock:
            for k, v in counts.items():
                try:
                    k, v = int(k), int(v)  # lint: ok (JSON host values)
                except (TypeError, ValueError):
                    continue
                # pow2 keys only: bounded at log2(batch_max) entries
                self._size_counts[k] = self._size_counts.get(k, 0) + v

    def tenant_depth(self, tenant: str) -> int:
        with self._lock:
            return self._queue.depth(tenant)

    def submit(self, deployment: _Deployment, query: Any,
               deadline: Optional[Deadline] = None,
               tenant: str = DEFAULT_TENANT, weight: float = 1.0,
               tenant_queue_max: int = 0, pending=None) -> Any:
        return self.submit_slot(deployment, query, deadline=deadline,
                                tenant=tenant, weight=weight,
                                tenant_queue_max=tenant_queue_max,
                                pending=pending)["result"]

    def submit_slot(self, deployment: _Deployment, query: Any,
                    deadline: Optional[Deadline] = None,
                    tenant: str = DEFAULT_TENANT, weight: float = 1.0,
                    tenant_queue_max: int = 0,
                    pending=None) -> Dict[str, Any]:
        """submit(), but returns the drained slot dict — "result" plus,
        when the batch encoder ran, the pre-serialized "wire" body the
        fast path writes straight to the socket. `pending` is the
        request's trace stamp slots (obs/trace.PendingTrace) or None;
        the batcher stamps lane/exec/splice stages on it."""
        done = threading.Event()
        slot: Dict[str, Any] = {}
        item = (deployment, query, done, slot, time.perf_counter(),
                tenant, pending)
        with self._lock:
            if self._closed:
                self.obs.shed.labels(surface="queries", app=tenant).inc()
                raise OverloadedError(
                    "server draining for shutdown", retry_after=1.0)
            if self.queue_max > 0 and len(self._queue) >= self.queue_max:
                self.obs.shed.labels(surface="queries", app=tenant).inc()
                raise OverloadedError(
                    "micro-batch queue full",
                    retry_after=max(self.window_s, 0.05))
            budget = self.submit_timeout_s
            if deadline is not None:
                budget = min(budget, max(deadline.remaining(), 0.0))
                # deadline-aware admission: even an EMPTY queue costs
                # one window + one drain; a budget below that dies in
                # the batch, so shed it 504 now and keep the slot for
                # work that can finish (the aged estimate, so a one-off
                # stall cannot lock deadlined traffic out for good)
                drain_est = self._drain_estimate_locked()
                if drain_est > 0.0 and \
                        budget < self.window_s + drain_est:
                    self.obs.shed.labels(surface="deadline_batch",
                                         app=tenant).inc()
                    raise DeadlineExceeded(
                        f"deadline budget {budget * 1e3:.0f}ms below "
                        f"batch window + drain estimate "
                        f"{(self.window_s + drain_est) * 1e3:.0f}ms")
            # adaptive shed: don't queue work predicted to expire
            # there. Tenanted submits judge their OWN lane's delay
            # EWMA — the tenant whose backlog grows is the one shed —
            # while the default lane keeps the global estimate
            ewma = (self._delay_ewma if tenant == DEFAULT_TENANT
                    else self._queue.delay_ewma(tenant))
            if len(self._queue) and ewma > budget:
                self.obs.shed.labels(surface="queue_delay",
                                     app=tenant).inc()
                raise OverloadedError(
                    f"predicted queue delay {ewma * 1e3:.0f}ms"
                    f" exceeds request budget {budget * 1e3:.0f}ms",
                    retry_after=ewma)
            if not self._queue.push(tenant, item, weight=weight,
                                    queue_max=tenant_queue_max):
                # the tenant's own lane is at ITS cap — shed just this
                # tenant; other lanes (and the global cap) are untouched
                self.obs.shed.labels(surface="queries", app=tenant).inc()
                raise OverloadedError(
                    f"per-tenant micro-batch queue full "
                    f"({tenant_queue_max} pending)",
                    retry_after=max(self.window_s, 0.05))
            trace.mark(pending, trace.S_ENQ)
            self.obs.queue_depth.set(float(len(self._queue)))
            if len(self._queue) >= self.batch_max:
                self._full.notify()
            if self._rows_in_flight and not self._forming:
                # a drainer may be waiting for the lane to reach the
                # rows of the cycle in flight (_my_turn_locked)
                self._turn.notify()
            # a second drainer forms the next batch while the first's
            # is on the device; never a third, and none beside one
            # whose window is open (it will take this item)
            drain = self._draining < self.CYCLES and not self._forming
            if drain:
                self._draining += 1
        if drain:
            threading.Thread(target=self._drain_loop, daemon=True,
                             name="pio-batch-drain").start()
        timeout = self.submit_timeout_s
        if deadline is not None:
            timeout = min(timeout, max(deadline.remaining(), 0.0))
        if not done.wait(timeout):  # lint: ok — bounded by construction
            # expired while queued (or the drainer is wedged): withdraw
            # the item if it hasn't been taken yet, then report 504
            with self._lock:
                if self._queue.remove(tenant, item):
                    self.obs.queue_depth.set(float(len(self._queue)))
            raise DeadlineExceeded(
                "request deadline expired in micro-batch queue"
                if deadline is not None else
                f"micro-batch submit timed out after "
                f"{self.submit_timeout_s:.1f}s")
        if "error" in slot:
            raise slot["error"]
        return slot

    def _my_turn_locked(self) -> bool:
        """May a drainer open its window now? Not while the other's is
        open; and behind a cycle in flight only once the lane holds as
        many rows as that cycle took (or a full batch). A smaller batch
        launched behind a larger one fragments the stream: each call
        reads the whole catalog, or pads its tokens, for fewer rows, and
        the callers it would have batched with are inside the cycle in
        flight and come back when it ends. Then this drainer's turn
        comes with them, and its window is the one the serial loop
        would have opened. Both sides of the comparison are what the
        batcher sees under its lock; nothing here is set by a user."""
        if self._forming:
            return False
        return len(self._queue) >= min(self._rows_in_flight,
                                       self.batch_max)

    def _drain_loop(self):
        batch: List[tuple] = []
        from predictionio_tpu.resilience.watchdog import watchdog
        # transient registration: a drainer lives for one busy burst
        # and retires on an idle window; while live, a stall past the
        # submit timeout means every waiter is already timing out
        wd_beat = watchdog().register("drainer",
                                      budget_s=self.submit_timeout_s)
        wd_beat.attach()
        with self._lock:
            self._drain_beats.append(wd_beat)
        # one record a cycle (obs/trace.BatchTrace), kept by the thread:
        # it opens with the cycle's window, so stages tile a cycle and
        # the two drainers' cycles overlap; a drainer's wait for its
        # turn belongs to no cycle, as an empty window belongs to none
        stages = self.obs.cycle_stages
        forming = False
        flying = 0                   # rows of this drainer's taken batch
        try:
            while True:
                wd_beat.tick()
                with self._lock:
                    while not self._my_turn_locked():
                        self._turn.wait(self.submit_timeout_s)
                        wd_beat.tick()
                    self._forming = forming = True
                    bt = trace.batch_begin(stages)
                    h = trace.stage_open("window")
                    # wait out the window — but a full batch forming
                    # mid-window notifies the condition and ships NOW
                    self._full.wait_for(
                        lambda: len(self._queue) >= self.batch_max,
                        timeout=self.window_s)
                    trace.stage_close(h)
                    h = trace.stage_open("take")
                    batch = self._queue.take(self.batch_max)
                    self._forming = forming = False
                    self._turn.notify_all()
                    self.obs.queue_depth.set(float(len(self._queue)))
                    if not batch:
                        # nothing arrived during the window: retire. The
                        # count falls under the same lock any submit
                        # checks, so the next arrival starts a fresh
                        # drainer; close() waiters re-check now. The
                        # empty window is no cycle and is not observed.
                        trace.stage_close(h)
                        trace.batch_drop()
                        self._draining -= 1
                        self._full.notify_all()
                        return
                    self._in_flight += 1
                    flying = len(batch)
                    self._rows_in_flight += flying
                    self.obs.cycles_in_flight.observe(
                        float(self._in_flight))  # lint: ok (host int)
                    now = time.perf_counter()
                    for _, _, _, _, t_enq, tenant, pend in batch:
                        delay = max(now - t_enq, 0.0)
                        self.obs.queue_delay.observe(delay)
                        self._delay_ewma += self.DELAY_ALPHA * (
                            delay - self._delay_ewma)
                        self._queue.observe_delay(tenant, delay)
                        trace.mark(pend, trace.S_DRAIN)
                    trace.stage_close(h)
                bt.batch_id = next(self._batch_seq)
                bt.rows = len(batch)
                t0 = time.perf_counter()
                self._process(batch, bt)
                dt = time.perf_counter() - t0
                trace.batch_end(bt)
                with self._lock:
                    self._in_flight -= 1
                    self._rows_in_flight -= flying
                    flying = 0
                    self._turn.notify_all()
                    # blend into the AGED estimate: recovering from a
                    # stall starts from the decayed value instead of
                    # dragging the stale spike back in
                    base = self._drain_estimate_locked()
                    self._drain_ewma = base + self.DELAY_ALPHA * (
                        dt - base)
                    self._drain_t = time.perf_counter()
                batch = []
        except BaseException as e:
            # drainer crash: fail its own batch NOW and, when no other
            # drainer is alive to serve them, everything still pending,
            # instead of leaving them to their timeouts; the count
            # falls so the next submit spawns a healthy drainer
            with self._lock:
                if forming:
                    self._forming = False
                if flying:
                    self._in_flight -= 1
                    self._rows_in_flight -= flying
                self._draining -= 1
                stranded = batch
                if self._draining == 0:
                    stranded = batch + self._queue.drain_all()
                    self.obs.queue_depth.set(0.0)
                self._turn.notify_all()
                self._full.notify_all()
            for _, _, done, slot, _, _, _ in stranded:
                slot["error"] = e
                done.set()
            from predictionio_tpu.resilience.watchdog import _deaths
            _deaths().labels(role="drainer").inc()
            _log.error("batch_drainer_crashed",
                       error=f"{type(e).__name__}: {e}",
                       stranded=len(stranded))
        finally:
            wd_beat.close()
            with self._lock:
                self._drain_beats.remove(wd_beat)

    def close(self, timeout: float = 30.0) -> bool:
        """Stop admitting (new submits shed with 503) and wait for
        every accepted request to drain; True when fully drained. The
        graceful half of PredictionServer.stop() — a replica being
        rotated out of a rolling reload finishes what it accepted."""
        with self._lock:
            self._closed = True
            return self._full.wait_for(
                lambda: not len(self._queue) and self._draining == 0,
                timeout=timeout)

    def reopen(self) -> None:
        """Re-admit after a drain (a reload drains without stopping)."""
        with self._lock:
            self._closed = False

    def _process(self, pending: List[tuple], bt) -> None:
        """One taken batch through predict, encode and wake; `bt` is the
        cycle's record (obs/trace.BatchTrace)."""
        with trace.stage("take"):
            n = len(pending)
            self.obs.batch_size.observe(float(n))  # lint: ok (host int)
            pow2 = 1
            while pow2 < n:
                pow2 <<= 1
            with self._lock:
                self._size_counts[pow2] = \
                    self._size_counts.get(pow2, 0) + 1
            # group by deployment (reload may swap mid-flight)
            by_dep: Dict[int, List] = {}
            for item in pending:
                by_dep.setdefault(id(item[0]), []).append(item)
        for items in by_dep.values():
            dep = items[0][0]
            queries = [item[1] for item in items]
            try:
                results = dep.predict_batch(queries)
                with trace.stage("encode"):
                    for item in items:
                        p = item[6]
                        if p is not None:
                            trace.mark(p, trace.S_EXEC)
                            p.batch_id = bt.batch_id
                            p.batch_size = len(items)
                            if bt.path:
                                p.dispatch = bt.path
                    wires: Optional[List[Optional[bytes]]] = None
                    if self.encoder is not None:
                        try:
                            wires = self.encoder(dep, results)
                        except Exception:
                            wires = None  # encoder bugs degrade, not fail
                with trace.stage("wake"):
                    for i, ((_, _, done, slot, _, _, p), r) in enumerate(
                            zip(items, results)):
                        slot["result"] = r
                        if wires is not None and wires[i] is not None:
                            slot["wire"] = wires[i]
                        trace.mark(p, trace.S_SPLICE)
                        done.set()
            except Exception as e:
                with trace.stage("wake"):
                    for _, _, done, slot, _, _, p in items:
                        slot["error"] = e
                        trace.annotate_pending(p, error=type(e).__name__)
                        done.set()
        hook = self.drain_hook
        if hook is not None:
            with trace.stage("wake"):
                try:
                    hook()
                except Exception:
                    pass       # a wire nudge must never kill the drainer


class PredictionServer(HTTPServerBase):
    """(CreateServer.scala MasterActor+ServerActor)"""

    def __init__(self, config: ServerConfig, registry=None,
                 plugins: Optional[Sequence] = None,
                 engine=None, instance=None,
                 metrics: Optional[MetricsRegistry] = None):
        super().__init__(host=config.ip, port=config.port, metrics=metrics,
                         default_deadline_ms=config.default_deadline_ms,
                         max_inflight=config.max_inflight)
        from predictionio_tpu.utils.device import claim_device
        from predictionio_tpu.utils.security import KeyAuthentication

        self.config = config
        # this is the server that computes: take the device (and fail
        # here, not at the first query, when there is none to take)
        self.device, self._compile_cache = claim_device()
        self._serve_obs = _ServeInstruments(self.metrics)
        # a --mesh deploy flag rides in the server runtime_conf, where
        # prepare_deploy's serve-mesh derivation (merged with the
        # instance's trained mesh) picks it up
        wp = (WorkflowParams(runtime_conf={"mesh": config.mesh})
              if config.mesh else None)
        self.ctx = RuntimeContext(registry=registry, workflow_params=wp)
        self.plugin_context = EngineServerPluginContext(plugins)
        self.auth = KeyAuthentication(config.server_key or None)
        # per-app auth + quotas on /queries.json; off by default so a
        # bare deploy keeps the open serve path
        tcfg = (config.tenancy if config.tenancy is not None
                else TenancyConfig.from_env())
        self.admission = AdmissionController(
            tcfg, registry=self.ctx.registry, metrics=self.metrics)
        # per-app SLO burn rates (obs/slo.py); objectives come from env
        # with per-app DAO overrides, the TenantQuotas pattern
        self._slo = SLOTracker(
            metrics=self.metrics,
            loader=dao_overrides_loader(self.ctx.registry))
        # serve latency inside the handler, one interval on both paths
        # and recorder on or off. With the recorder ON it observes the
        # family itself when the reply is written (the same interval,
        # handed over as `serve_s`, with trace-id exemplars); these
        # prebound children are the direct observation path when it is
        # off, so the histogram exists either way.
        self._serve_seconds = self.metrics.histogram(
            "pio_serve_seconds", trace.SERVE_SECONDS_HELP,
            labels=("app",), buckets=trace.SERVE_BUCKETS)
        self._ss0 = self._serve_seconds.labels(app="")
        self._worker_wait = self._serve_obs.worker_wait.labels()
        self._reply = self._serve_obs.reply.labels()
        self._engine_arg = engine
        self._dep: Optional[_Deployment] = None
        self._dep_lock = threading.Lock()
        self._batcher = (_MicroBatcher(config.batch_window_ms / 1000.0,
                                       config.batch_max,
                                       obs=self._serve_obs,
                                       queue_max=config.queue_max,
                                       submit_timeout_s=(
                                           config.submit_timeout_ms / 1000.0))
                        if config.batch_window_ms > 0 else None)
        if self._batcher is not None:
            self._batcher.encoder = _encode_scores_batch
        # wire fast path instrument children resolved ONCE — the hot
        # route increments them without a labels() dict round-trip
        self._fq_ok = self._req_counter.labels(
            route="/queries.json", method="POST", status="200")
        self._fq_hist = self._req_hist.labels(route="/queries.json")
        # latency bookkeeping (CreateServer.scala:399-401,584-591);
        # updated from concurrent handler threads, hence the lock.
        self._stats_lock = threading.Lock()
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0
        self.start_time = utcnow()
        # feedback loop: bounded queue + one worker instead of a thread
        # per request (send failures logged, not retried,
        # CreateServer.scala:557-566)
        self._feedback_queue: "queue.Queue" = queue.Queue(
            maxsize=config.feedback_queue_max)
        self._feedback_beat = None
        if config.feedback:
            from predictionio_tpu.resilience.watchdog import watchdog
            # blocking-get loop: no tick cadence to budget against, so
            # an infinite budget disables stall detection — the beat
            # exists for death accounting + respawn only
            self._feedback_beat = watchdog().register(
                "feedback", budget_s=float("inf"),
                restart=self._spawn_feedback)
            self._spawn_feedback()
        # restart-recovery pass BEFORE the first model load: report-only
        # fsck + acting janitor, so a crashed train's ghost row can't
        # win get_latest_completed (PIO_FSCK_ON_STARTUP=off disables;
        # fleet replicas skip it wholesale — the control plane owns the
        # one sweep per fleet, including the scheduled background pass)
        self._fsck_sched = None
        self._stopping = False
        if config.startup_check:
            from predictionio_tpu.data.fsck import (
                start_scheduled_fsck, startup_check,
            )
            startup_check(self.ctx.registry, log=_log.warning)
            self._fsck_sched = start_scheduled_fsck(
                self.ctx.registry, log=_log.warning)
        # prediction-quality observatory: serve-path accumulators +
        # the reload canary gate (PIO_QUALITY=off disables both)
        q_on = (config.quality if config.quality is not None
                else quality_enabled())
        self._quality = (QualityStats(metrics=self.metrics)
                         if q_on else None)
        self._canary = (CanaryGate(
            sample=config.canary_sample,
            min_overlap=config.canary_min_overlap,
            metrics=self.metrics) if q_on else None)
        self._joiner = None
        self._pager = None
        # warm-start the topk dispatch policy from the last run's learned
        # host/device crossover before any serve traffic arrives
        self._restore_dispatch_state()
        self._load(instance)
        self._routes()
        # streaming freshness: the config interval wins; otherwise the
        # PIO_REFRESH_INTERVAL_S env knob applies (0/absent = disabled)
        self._refresher = None
        interval = config.refresh_interval_s
        if interval <= 0:
            import os
            try:
                interval = float(  # lint: ok (env string, host value)
                    os.environ.get("PIO_REFRESH_INTERVAL_S", "0") or 0)
            except ValueError:
                interval = 0.0
        if interval > 0:
            from predictionio_tpu.streaming import Refresher
            self._refresher = Refresher(
                self, interval, stagger_s=config.refresh_stagger_s,
                metrics=self.metrics)
            self._refresher.start()
        # the feedback joiner closes the loop the feedback writer opens:
        # it only makes sense when this server posts feedback events
        if config.feedback and self._quality is not None:
            from predictionio_tpu.obs.quality import QualityJoiner
            self._joiner = QualityJoiner(
                self, attribution_s=config.attribution_s,
                metrics=self.metrics)
            self._joiner.start()
        # memory-pressure guard: soft watermark trims this server's
        # bounded state and sheds new work 503 surface=memory; hard
        # fails /ready and starts the graceful drain. Swept by the
        # watchdog thread (attach in start()), checked inline by tests.
        from predictionio_tpu.resilience.pressure import MemoryGuard
        self._pressure = MemoryGuard()
        self._pressure.add_trim("tsdb", self.tsdb.trim)
        self._pressure.add_trim(
            "trace", lambda: trace.get_recorder().trim())
        if self._quality is not None:
            self._pressure.add_trim("quality", self._quality.trim)
        self._pressure.add_trim("tenant_keys",
                                self.admission.trim_key_cache)
        from predictionio_tpu.ingest.pipeline import trim_prepared_cache
        self._pressure.add_trim("ingest_cache", trim_prepared_cache)
        self._pressure.on_hard(self._drain_on_pressure)

    # -- continuous observatory ---------------------------------------------
    def _obs_collectors(self):
        """The serve plane owns device state, so its tsdb tick (alone
        among the servers) samples device memory and the live plans'
        device residency."""
        return super()._obs_collectors() + [self._sample_device_memory,
                                            self._sample_plan_bytes]

    def _sample_device_memory(self) -> None:
        from predictionio_tpu.obs.profiler import sample_device_memory
        sample_device_memory(self.metrics)

    def _sample_plan_bytes(self) -> None:
        """Device residency of the live serving plans into
        `pio_plan_resident_bytes{device,bucket}`: bucket="factors" is
        the pinned factor matrix's actual bytes; numbered buckets are
        per-executable activation estimates (query block + scores +
        indices), so a reload to a bigger catalog or bucket ladder is
        visible in the ring."""
        with self._dep_lock:
            dep = self._dep
        if dep is None:
            return
        gauge = self.metrics.gauge(
            "pio_plan_resident_bytes",
            "Device-resident bytes of live serving plans by bucket",
            labels=("device", "bucket"))
        for _, plan in _plans_of(dep):
            factors = plan.factors
            try:
                dev_obj = next(iter(factors.devices()))
                device = f"{dev_obj.platform}:{dev_obj.id}"
                nbytes = int(factors.nbytes)
            except (AttributeError, StopIteration, TypeError):
                continue
            gauge.labels(device=device, bucket="factors").set(
                float(nbytes))  # lint: ok — host int
            for b in plan.buckets:
                gauge.labels(device=device, bucket=str(b)).set(
                    float(b * (plan.rank * 4 + plan.k * 8)))

    # -- deployment lifecycle ----------------------------------------------
    def _resolve_instance(self):
        instances = self.ctx.registry.get_meta_data_engine_instances()
        inst = instances.get_latest_completed(
            "default", "default", self.config.engine_variant)
        if inst is None:
            raise RuntimeError(
                f"No valid engine instance found for variant "
                f"{self.config.engine_variant}. Try running 'train' before "
                "'deploy' (commands/Engine.scala:235-236)")
        return inst

    def _load(self, instance=None) -> None:
        """Build a full deployment, then swap atomically. Any failure
        (resolve, storage read, model prepare, canary veto) propagates
        BEFORE the swap, so the previous deployment — if any — keeps
        serving untouched (graceful-degradation contract of /reload)."""
        try:
            engine = (self._engine_arg if self._engine_arg is not None
                      else resolve_engine(self.config.engine_factory))
            if instance is None:
                instance = self._resolve_instance()
            # warm the pow2 buckets the micro-batcher can actually
            # form; when a previous run recorded which batch sizes real
            # traffic produced, warm exactly THOSE shapes instead of
            # the whole ladder. Without batching only the single-query
            # shape matters.
            observed = (self._batcher.size_counts()
                        if self._batcher is not None else None)
            algos, models, serving = CoreWorkflow.prepare_deploy(
                engine, instance, self.ctx,
                warm_batch_max=(self.config.batch_max
                                if self._batcher is not None else 1),
                observed_sizes=observed or None)
            new_dep = _Deployment(engine, instance, algos, models,
                                  serving, obs=self._serve_obs)
            # reload canary: replay recently-kept traced queries
            # against old and new plans BEFORE the swap; a CanaryVeto
            # is a load failure — previous deployment keeps serving
            if self._canary is not None and self._dep is not None:
                self._canary.check(self._dep, new_dep,
                                   self._canary_replay)
        except Exception:
            self._serve_obs.reloads.labels(outcome="failed").inc()
            raise
        with self._dep_lock:
            self._dep = new_dep
        self._serve_obs.reloads.labels(outcome="ok").inc()
        self._sync_pager(new_dep)
        # each successful (re)load starts a fresh drift reference
        # window: the new model's own scores are the new baseline
        if self._quality is not None:
            self._quality.freeze_reference()
        # checkpoint the learned dispatch EWMAs on every successful
        # (re)load, so the NEXT process start resumes warm
        self._save_dispatch_state()

    @staticmethod
    def _tiered_plans(dep: _Deployment):
        """The deployment's tiered (demand-paged) serving plans, if
        any — unwrapping one mesh-slice layer, where a giant slice
        tiers itself."""
        out = []
        for _, plan in _plans_of(dep):
            plan = getattr(plan, "_inner", plan)
            if hasattr(plan, "fold_accesses") and plan not in out:
                out.append(plan)
        return out

    def _sync_pager(self, dep: _Deployment) -> None:
        """Bind the async page thread to the deployment's tiered
        plans: started on first sight, rebound across /reload (the
        new plans' access stats start cold), retired when a reload
        drops tiering entirely."""
        plans = self._tiered_plans(dep)
        if plans:
            if self._pager is None:
                from predictionio_tpu.serving.paging import PageManager
                self._pager = PageManager(metrics=self.metrics)
            self._pager.bind(plans)
            self._pager.start()
        elif self._pager is not None:
            pager, self._pager = self._pager, None
            pager.stop()

    def _canary_replay(self, dep: _Deployment,
                       qdicts: List[Dict]) -> List[Any]:
        """Parse + predict a batch of traced query dicts against `dep`
        (the CanaryGate's replay callback — the gate owns sampling and
        scoring, the server owns query parsing and the predict path)."""
        if dep.query_class is not None:
            queries = [extract_params(dep.query_class, qd)
                       for qd in qdicts]
        else:
            queries = list(qdicts)
        return dep.predict_batch(queries)

    def _refresh_deployment(self, dep: _Deployment,
                            new_models: Sequence[Any]) -> _Deployment:
        """A streaming fold's publish step: same engine/instance/
        algos/serving, fresh models. The caller (streaming.Refresher)
        swaps the device factors first, then installs this under
        `_dep_lock` — both model sets score identically mid-swap, so
        in-flight requests never see a torn deployment."""
        return _Deployment(dep.engine, dep.instance, dep.algos,
                           list(new_models), dep.serving,
                           obs=self._serve_obs)

    # -- dispatch-policy persistence ----------------------------------------
    @staticmethod
    def _dispatch_state_path():
        """Where the serve DispatchPolicy EWMA snapshot lives.
        `PIO_DISPATCH_STATE=off` disables persistence; any other value
        overrides the default `~/.pio_store/serving/` location."""
        import os
        from pathlib import Path
        p = os.environ.get("PIO_DISPATCH_STATE", "").strip()
        if p.lower() == "off":
            return None
        if p:
            return Path(p).expanduser()
        return Path("~/.pio_store/serving/dispatch_policy.json").expanduser()

    @classmethod
    def _batch_sizes_path(cls):
        """The observed batch-size histogram lives beside the dispatch
        snapshot (same PIO_DISPATCH_STATE off/override semantics)."""
        path = cls._dispatch_state_path()
        if path is None:
            return None
        return path.with_name("batch_sizes.json")

    def _restore_dispatch_state(self) -> None:
        path = self._dispatch_state_path()
        if path is None:
            return
        from predictionio_tpu.ops.topk import DISPATCH_POLICY
        try:
            state = json.loads(path.read_text())
        except (OSError, ValueError):
            state = None                 # absent/corrupt: cold start
        if isinstance(state, dict):
            DISPATCH_POLICY.restore(state)
        # the previous run's observed batch sizes seed both this run's
        # histogram and the warm_deploy bucket derivation in _load
        if self._batcher is None:
            return
        sizes_path = self._batch_sizes_path()
        try:
            sizes = json.loads(sizes_path.read_text())
        except (OSError, ValueError):
            return
        if isinstance(sizes, dict):
            self._batcher.restore_size_counts(sizes)

    def _save_dispatch_state(self) -> None:
        path = self._dispatch_state_path()
        if path is None:
            return
        from predictionio_tpu.data.integrity import atomic_write_text
        from predictionio_tpu.ops.topk import DISPATCH_POLICY
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            atomic_write_text(path, json.dumps(DISPATCH_POLICY.snapshot()))
            if self._batcher is not None:
                counts = self._batcher.size_counts()
                if counts:
                    atomic_write_text(
                        self._batch_sizes_path(),
                        json.dumps({str(k): v
                                    for k, v in sorted(counts.items())}))
        except OSError:
            pass                         # persistence is best-effort

    def _wire_cover(self) -> int:
        """With a batcher a wire worker sleeps in `submit_slot` until
        its batch wakes, so the pool covers what the batcher admits:
        what may be pending plus what may be in flight. A request that
        waited for a worker would wait where `queue_max`, the DRR lanes
        and the queue-delay shedder cannot see it, and where the
        forming batch cannot take it."""
        b = self._batcher
        if b is None:
            return 0
        cover = b.queue_max + b.CYCLES * b.batch_max
        if self.config.max_inflight > 0:
            cover = min(cover, self.config.max_inflight)
        return cover

    def _own_beats(self):
        """The watchdog beats whose degradation should flip THIS
        server's /ready (never another server's beats in the shared
        process — test suites run many servers side by side)."""
        beats = []
        if self._refresher is not None:
            beats.append(self._refresher.beat)
        if self._joiner is not None:
            beats.append(self._joiner.beat)
        if self._fsck_sched is not None:
            beats.append(self._fsck_sched.beat)
        if self._batcher is not None:
            beats.extend(self._batcher.drain_beats())
        if self._pager is not None:
            beats.append(self._pager.beat)
        beats.append(self._feedback_beat)
        scraper = self._scraper
        if scraper is not None:
            beats.append(scraper._beat)
        return [b for b in beats if b is not None]

    def readiness(self):
        """/ready: a model must be loaded, no storage breaker OPEN, no
        owned loop thread given up on by the watchdog, and the memory
        guard below its hard watermark."""
        states = {}
        try:
            states = self.ctx.registry.breaker_states()
        except Exception:
            pass
        open_breakers = [s for s, st in states.items() if st == "open"]
        loaded = self._dep is not None
        detail = {"modelLoaded": loaded, "storageBreakers": states}
        # SLO burn is surfaced as degradation detail, never as a reason
        # to pull the replica from rotation (a page, not an outage)
        slo = self._slo.snapshot()
        if slo:
            detail["slo"] = slo
            detail["sloDegraded"] = self._slo.degraded()
        degraded = [b.role for b in self._own_beats() if b.degraded]
        if degraded:
            detail["degradedLoops"] = degraded
        if not self._pressure.ready():
            detail["memPressure"] = self._pressure.detail()
            return (False, detail)
        return (loaded and not open_breakers and not degraded, detail)

    def shard_spec(self) -> str:
        """`"i/n"` when this server was deployed as cross-host mesh
        shard i of n (`--mesh items=N@fleet:i`), else "" — advertised
        by the replica agent's heartbeats so the fleet router can map
        shard ownership without extra control traffic."""
        from predictionio_tpu.parallel.mesh import parse_fleet_mesh
        try:
            parsed = parse_fleet_mesh(self.config.mesh)
        except ValueError:
            return ""
        if parsed is None or parsed[1] is None:
            return ""
        return f"{parsed[1]}/{parsed[0]}"

    def current_instance_id(self) -> str:
        """Engine-instance id of the deployment currently serving, ""
        when none is loaded — what a fleet replica agent reports in its
        heartbeats so the router can see model skew across members."""
        dep = self._dep
        return dep.instance.id if dep is not None else ""

    @staticmethod
    def _probe_occupant(host: str, port: int):
        """GET /status.json from whatever occupies the port. Returns the
        parsed status dict if it identifies as one of this framework's
        prediction servers, else None."""
        import urllib.request
        try:
            with urllib.request.urlopen(
                    f"http://{host}:{port}/status.json", timeout=2) as r:
                obj = json.loads(r.read())
            return obj if "engineInstanceId" in obj else None
        except Exception:
            return None

    @staticmethod
    def _await_release(host: str, port: int, timeout: float = 5.0) -> None:
        """Wait until nothing accepts on the port any more. `/stop`
        answers before the squatter has drained and closed, and the
        selector wire's listeners are SO_REUSEPORT: a bind beside them
        succeeds at once, and until they close the kernel hands part of
        the NEW server's connections to them, to be reset. Past
        `timeout` the bind goes ahead as it did."""
        import socket
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                socket.create_connection((host, port), timeout=0.5).close()
            except ConnectionRefusedError:
                return
            except OSError:
                pass
            time.sleep(0.02)  # lint: ok — polls a listener, retries no call

    def start(self, background: bool = True) -> int:
        """Deploy first undeploys any server squatting on the target port
        (CreateServer.scala:347-357: the MasterActor sends StopServer to
        the existing actor before binding) — but only after PROBING that
        the occupant is one of this framework's prediction servers
        deployed for the SAME engine variant. A foreign service, or a
        different deployment, is never sent an unsolicited /stop; the
        base class's bind retry surfaces EADDRINUSE instead so the
        operator decides."""
        if self.port:
            from predictionio_tpu.cli.ops import undeploy
            host = "127.0.0.1" if self.host == "0.0.0.0" else self.host
            occ = self._probe_occupant(host, self.port)
            if occ is not None and occ.get("engineVariant") == \
                    self.config.engine_variant:
                try:
                    undeploy(host, self.port,
                             access_key=self.config.server_key)
                except Exception:
                    # key-protected with a different key: let the bind
                    # retry surface EADDRINUSE
                    pass
                else:
                    self._await_release(host, self.port)
        port = super().start(background)
        from predictionio_tpu.resilience.watchdog import watchdog
        watchdog().attach_guard(self._pressure)
        watchdog().ensure_started()
        return port

    def _on_bound(self) -> None:
        if self._batcher is not None:
            # cross-wakeup: a completed batch drain nudges the wire
            # reactors to flush deferred pipelined responses (None on
            # the threaded wire — the hook stays unset there)
            self._batcher.drain_hook = getattr(
                self._httpd, "flush_hint", None)

    def stop(self) -> None:
        """Graceful shutdown: drain the micro-batcher (accepted
        requests finish; new submits shed 503), flush the feedback
        queue, stop the scheduled-fsck thread, THEN close the socket —
        a replica rotated out during a rolling reload, or a plain
        undeploy, never abandons a request it already accepted."""
        with self._stats_lock:
            if self._stopping:
                return
            self._stopping = True
        from predictionio_tpu.resilience.watchdog import watchdog
        watchdog().detach_guard(self._pressure)
        beat, self._feedback_beat = self._feedback_beat, None
        if beat is not None:
            beat.close()
        if self._refresher is not None:
            self._refresher.stop()
        if self._joiner is not None:
            self._joiner.stop()
        if self._pager is not None:
            self._pager.stop()
        budget = max(self.config.drain_timeout_ms / 1000.0, 0.1)
        t0 = time.perf_counter()
        if self._batcher is not None:
            if not self._batcher.close(timeout=budget):
                _log.warning("stop_drain_incomplete",
                             waited_s=round(time.perf_counter() - t0, 3))
        self._flush_feedback(max(budget - (time.perf_counter() - t0), 0.0))
        if self._fsck_sched is not None:
            self._fsck_sched.stop()
        # checkpoint the dispatch EWMAs AND the batch-size histogram
        # accumulated while serving, so the next start's warm_deploy
        # pre-compiles the shapes this run actually saw
        self._save_dispatch_state()
        self.shutdown()

    def shutdown(self) -> None:
        # every exit path (graceful stop() ends here, tests/benches
        # call shutdown() directly) must detach the pressure guard —
        # a stale guard on the singleton watchdog keeps getting swept
        # against a dead server and eats armed mem.pressure.* fault
        # hits meant for live ones
        from predictionio_tpu.resilience.watchdog import watchdog
        watchdog().detach_guard(self._pressure)
        super().shutdown()

    def _drain_on_pressure(self) -> None:
        """Hard memory watermark: start the graceful drain off the
        watchdog sweep thread — a clean stop() beats an OOM kill
        mid-request. /ready is already failing, so the fleet has
        stopped routing here by the time the socket closes."""
        _log.error("mem_hard_watermark_draining",
                   detail=self._pressure.detail())
        threading.Thread(target=self.stop, daemon=True,
                         name="pio-mem-drain").start()

    def _flush_feedback(self, timeout_s: float) -> None:
        """Bounded wait for the feedback worker to clear its queue
        (every drained serve may have enqueued a predict event)."""
        if not self.config.feedback:
            return
        waiter = threading.Event()
        end = time.perf_counter() + timeout_s
        while (self._feedback_queue.unfinished_tasks
               and time.perf_counter() < end):
            waiter.wait(0.05)
        if self._feedback_queue.unfinished_tasks:
            _log.warning("stop_feedback_unflushed",
                         remaining=self._feedback_queue.unfinished_tasks)

    # -- serving -------------------------------------------------------------
    def _serve_one(self, query_json: Any,
                   tenant: Optional[TenantIdentity] = None) -> Any:
        t0 = time.perf_counter()
        # the generic route's pending trace rides the contextvar set by
        # _handle_raw; tag it as a serve entry so the recorder lands it
        # in pio_serve_seconds (the router kind stays excluded)
        p = trace.current()
        trace.annotate_pending(
            p, kind="serve",
            app=tenant.label if tenant is not None else "",
            query=query_json if isinstance(query_json, dict) else None)
        dep = self._dep
        with self._serve_obs.stage.labels(stage="extract").time():
            if dep.query_class is not None:
                query = extract_params(dep.query_class, query_json)
            else:
                query = query_json
        if self._batcher is not None:
            label, weight, tqmax = self.admission.batch_params(tenant)
            prediction = self._batcher.submit(dep, query,
                                              deadline=current_deadline(),
                                              tenant=label, weight=weight,
                                              tenant_queue_max=tqmax,
                                              pending=p)
        else:
            prediction = dep.predict_batch([query])[0]
            trace.mark(p, trace.S_EXEC)
        app = tenant.label if tenant is not None else ""
        if self._quality is not None:
            self._quality.observe_result(
                app, prediction, getattr(query, "user", None),
                dep.user_maps)
        # feedback loop + prId injection (CreateServer.scala:506-576)
        response_extra = {}
        if self.config.feedback:
            with self._serve_obs.stage.labels(stage="feedback").time():
                pr_id = getattr(prediction, "prId", None) or _gen_pr_id()
                if p is not None:
                    trace.ensure_ids(p)
                self._post_feedback(dep, query, prediction, pr_id, app,
                                    trace_id=(p.trace_id if p is not None
                                              else ""))
            if hasattr(prediction, "prId"):
                response_extra["prId"] = pr_id
        prediction = self.plugin_context.run_blockers(
            QueryInfo(dep.instance.engine_variant, query, prediction))
        self.plugin_context.notify_sniffers(
            QueryInfo(dep.instance.engine_variant, query, prediction))
        dt = time.perf_counter() - t0
        if tenant is not None:
            self._serve_obs.tenant_serve.labels(app=tenant.label).observe(dt)
        with self._stats_lock:
            self.request_count += 1
            self.last_serving_sec = dt
            self.avg_serving_sec += (
                (dt - self.avg_serving_sec) / self.request_count)
        if p is None:
            # tracing off (or legacy wire): observe serve latency here;
            # with tracing on the recorder observes this same interval
            # at wire write
            app = tenant.label if tenant is not None else ""
            (self._ss0 if not app
             else self._serve_seconds.labels(app=app)).observe(dt)
        else:
            p.serve_s = dt
        out = to_jsonable(prediction)
        if isinstance(out, dict):
            out.update(response_extra)
        return out

    # -- wire fast path ------------------------------------------------------
    def _fast_queries(self, raw: RawRequest) -> Optional[bytes]:
        """/queries.json answered straight off the raw frame: compiled
        query-shape match, header-lite auth, micro-batch submit, and a
        response spliced from the batch encoder's pre-serialized body —
        no header dict, no Request object, no per-request json.dumps or
        json.loads. Returns None to delegate to the generic Router route
        (which IS the json.loads fallback) whenever the request or the
        server configuration falls outside the compiled shape: no
        batcher, no fast constructor, feedback or plugins active, or a
        body that is not exactly {"user": <str>, "num": <int>}."""
        batcher = self._batcher
        dep = self._dep
        if batcher is None or dep is None or dep.fast_ctor is None \
                or self.config.feedback \
                or self.plugin_context.output_blockers \
                or self.plugin_context.output_sniffers:
            return None
        m = _FAST_QUERY_RE.match(raw.body)
        if m is not None:
            try:
                user = m.group(1).decode("utf-8")
            except UnicodeDecodeError:
                return None
            num = int(m.group(2))
        else:
            # binary SDK framing: the same {"user", "num"} query as a
            # msgpack-subset map (Content-Type: application/x-pio-bin)
            # decoded by direct byte indexing — no JSON at all. A
            # malformed binary frame is a terminal 400 here: the
            # generic Router fallback only speaks JSON.
            ct = raw.header("Content-Type")
            if ct is None or not ct.startswith(BIN_CONTENT_TYPE):
                return None
            decoded = decode_bin_query(raw.body)
            if decoded is None:
                return self._fast_finish(
                    400, "malformed binary query frame",
                    raw.header("X-Request-ID") or "", raw.keep_alive,
                    time.perf_counter(), raw=raw)
            user, num = decoded
        t0 = time.perf_counter()
        if raw.t_read > 0.0:
            self._worker_wait.observe(t0 - raw.t_read)
        rid = raw.header("X-Request-ID") or ""
        keep = raw.keep_alive
        if raw.trace is not None:
            trace.begin_raw(raw, raw.header(trace.TRACE_HEADER),
                            kind="serve")
        tenant: Optional[TenantIdentity] = None
        admitted = False
        try:
            try:
                deadline = deadline_from_header(
                    raw.header(DEADLINE_HEADER), self.default_deadline_ms)
            except ValueError as e:
                return self._fast_finish(400, str(e), rid, keep, t0,
                                         raw=raw, tenant=tenant)
            if deadline is not None and deadline.expired:
                return self._fast_finish(
                    504, "deadline expired before processing", rid, keep,
                    t0, raw=raw, tenant=tenant)
            if self._pressure.shedding():
                self._shed_counter.labels(surface="memory", app="").inc()
                return self._fast_finish(
                    503, "memory pressure: shedding new work", rid, keep,
                    t0, retry_after=1.0, raw=raw, tenant=tenant)
            if self.admission.enabled:
                tenant = self.admission.resolve_raw(
                    _scan_access_key(raw.query_string),
                    raw.header(TENANT_HEADER), raw.header("Authorization"),
                    channel=_scan_channel(raw.query_string))
            with self._limiter:
                admitted = True
                with self.admission.admit(tenant):
                    trace.stamp(raw, trace.S_AUTH)
                    label, weight, tqmax = \
                        self.admission.batch_params(tenant)
                    slot = batcher.submit_slot(
                        dep, dep.fast_ctor(user, num),
                        deadline=deadline, tenant=label, weight=weight,
                        tenant_queue_max=tqmax, pending=raw.trace)
        except HTTPError as e:
            return self._fast_finish(e.status, e.message, rid, keep, t0,
                                     extra=e.headers or None,
                                     raw=raw, tenant=tenant)
        except DeadlineExceeded as e:
            return self._fast_finish(504, str(e), rid, keep, t0,
                                     raw=raw, tenant=tenant)
        except CircuitOpenError as e:
            return self._fast_finish(503, str(e), rid, keep, t0,
                                     retry_after=e.retry_after,
                                     raw=raw, tenant=tenant)
        except OverloadedError as e:
            if not admitted:
                # the HTTP-plane inflight shed, counted exactly where
                # the generic middleware counts it
                self._shed_counter.labels(
                    surface=self._limiter.surface, app="").inc()
            return self._fast_finish(e.status, e.message, rid, keep, t0,
                                     retry_after=e.retry_after,
                                     raw=raw, tenant=tenant)
        except ValueError as e:
            return self._fast_finish(400, str(e), rid, keep, t0,
                                     raw=raw, tenant=tenant)
        except Exception as e:
            _log.exception(
                "unhandled_error", request_id=rid, method="POST",
                path="/queries.json",
                error=f"{type(e).__name__}: {e}")  # lint: ok (error path)
            return self._fast_finish(500, str(e), rid, keep, t0,
                                     raw=raw, tenant=tenant)
        wire = slot.get("wire")
        if wire is None:
            # the batch encoder declined (exotic result type): one
            # serialization here keeps the contract
            wire = json.dumps(  # lint: ok (encoder-declined fallback)
                to_jsonable(slot["result"])).encode("utf-8")
        dt = time.perf_counter() - t0
        app = tenant.label if tenant is not None else ""
        if tenant is not None:
            self._serve_obs.tenant_serve.labels(
                app=tenant.label).observe(dt)
        with self._stats_lock:
            self.request_count += 1
            self.last_serving_sec = dt
            self.avg_serving_sec += (
                (dt - self.avg_serving_sec) / self.request_count)
        self._fq_ok.inc()
        self._fq_hist.observe(dt)
        self._slo.record(app, dt, ok=True)
        if self._quality is not None:
            self._quality.observe_result(app, slot["result"], user,
                                         dep.user_maps)
        trace.annotate(raw, status=200, app=app, route="/queries.json",
                       query=(user, num), serve_s=dt)
        trace.stamp(raw, trace.S_DONE)
        if raw.trace is None:
            # tracing off: direct serve-latency observation (the
            # recorder observes the same dt at wire write when it is on)
            (self._ss0 if not app
             else self._serve_seconds.labels(app=app)).observe(dt)
        # the reply's interval starts where the handler's ended
        raw.t_done = t0 + dt
        raw.reply_obs = self._reply
        return build_response(200, "application/json", wire, rid,
                              keep_alive=keep)

    def _fast_finish(self, status: int, message: str, rid: str,
                     keep: bool, t0: float, extra=None,
                     retry_after: Optional[float] = None,
                     raw: Optional[RawRequest] = None,
                     tenant: Optional[TenantIdentity] = None) -> bytes:
        """Terminal encode for a fast-path non-200: same metrics the
        generic middleware would record, same JSON error envelope."""
        dt = time.perf_counter() - t0
        app = tenant.label if tenant is not None else ""
        if retry_after is not None:
            extra = dict(extra or ())
            extra["Retry-After"] = str(max(1, round(retry_after)))
        if status == 504:
            self._deadline_counter.labels(route="/queries.json").inc()
        self._req_counter.labels(route="/queries.json", method="POST",
                                 status=str(status)).inc()
        self._fq_hist.observe(dt)
        self._slo.record(app, dt, ok=status < 500)
        if raw is not None:
            trace.annotate(raw, status=status, app=app,
                           route="/queries.json", error=message,
                           serve_s=dt)
            trace.stamp(raw, trace.S_DONE)
            raw.t_done = t0 + dt
            raw.reply_obs = self._reply
        if raw is None or raw.trace is None:
            (self._ss0 if not app
             else self._serve_seconds.labels(app=app)).observe(dt)
        body = b'{"message": ' + _json_str(message).encode("utf-8") + b'}'
        return build_response(status, "application/json", body, rid,
                              extra or None, keep_alive=keep)

    def _post_feedback(self, dep: _Deployment, query, prediction,
                       pr_id: str, app: str = "",
                       trace_id: str = "") -> None:
        """Async POST of the predict event back to the event server via a
        bounded queue drained by one worker thread (no thread-per-request
        spawn at serving throughput); sends retry with jittered backoff
        up to `feedback_retries` attempts and then DROP (counted in
        pio_feedback_dropped_total), and enqueue overflow drops the
        event with a log line rather than stalling the serve path.

        `prId` (and the trace id, when tracing is on) ride in the event
        properties so the quality joiner — and any downstream reward
        pipeline — joins feedback to the served prediction exactly."""
        props = {
            "engineInstanceId": dep.instance.id,
            "prId": pr_id,
            "query": to_jsonable(query),
            "prediction": to_jsonable(prediction),
        }
        if trace_id:
            props["traceId"] = trace_id
        data = {
            "event": "predict",
            "eventTime": format_time(utcnow()),
            "entityType": "pio_pr",
            "entityId": pr_id,
            "properties": props,
        }
        try:
            self._feedback_queue.put_nowait((data, app))
        except queue.Full:
            self._serve_obs.feedback.labels(outcome="dropped",
                                            app=app).inc()
            self._serve_obs.feedback_dropped.labels(
                reason="queue_full", app=app).inc()
            self.obs_log.warning("feedback_dropped", reason="queue full")

    def _send_feedback(self, data: Dict[str, Any]) -> None:
        """One POST attempt; non-201 raises OSError so the retry policy
        treats a refusing/erroring event server as transient."""
        import urllib.request
        url = (f"http://{self.config.event_server_ip}:"
               f"{self.config.event_server_port}/events.json"
               f"?accessKey={self.config.access_key or ''}")
        req = urllib.request.Request(
            url, data=json.dumps(data).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=5) as resp:
            if resp.status != 201:
                raise OSError(f"event server replied {resp.status}")

    def _spawn_feedback(self) -> None:
        threading.Thread(target=self._drain_feedback, daemon=True,
                         name="pio-feedback-drain").start()

    def _drain_feedback(self) -> None:
        beat = self._feedback_beat
        if beat is not None:
            beat.guard(self._drain_feedback_body)
        else:
            self._drain_feedback_body()

    def _drain_feedback_body(self) -> None:
        beat = self._feedback_beat
        policy = RetryPolicy(
            attempts=max(1, self.config.feedback_retries),
            base_delay=0.1, max_delay=2.0, retryable=(OSError,))
        while True:
            data, app = self._feedback_queue.get()
            if beat is not None:
                beat.tick()
            try:
                call_with_retry(self._send_feedback, data, policy=policy)
                self._serve_obs.feedback.labels(outcome="sent",
                                                app=app).inc()
            except Exception as e:
                # retries exhausted (or non-transient): drop, count, move
                # on — feedback is best-effort and must never wedge the
                # worker
                self._serve_obs.feedback.labels(outcome="failed",
                                                app=app).inc()
                self._serve_obs.feedback_dropped.labels(
                    reason="send_failed", app=app).inc()
                self.obs_log.warning("feedback_dropped",
                                     reason="send failed", error=str(e))
            finally:
                # unfinished_tasks bookkeeping feeds the stop() flush
                self._feedback_queue.task_done()

    def quality_snapshot(self) -> Dict[str, Any]:
        """The `/quality.json` payload: per-app accumulators, the
        feedback joiner's reward view, and the last canary report."""
        out: Dict[str, Any] = {
            "enabled": self._quality is not None,
            "apps": (self._quality.snapshot()
                     if self._quality is not None else {}),
        }
        if self._joiner is not None:
            out["joiner"] = self._joiner.snapshot()
        if self._canary is not None:
            out["canary"] = self._canary.last
        return out

    # -- routes ---------------------------------------------------------------
    def _routes(self) -> None:
        r = self.router

        @r.post("/queries.json")
        def queries(req: Request) -> Response:
            raw = req.raw
            if raw is not None:
                # the request's waits on the generic route: the worker
                # wait ends here and the reply starts where the route
                # returns or raises; pio_serve_seconds is _serve_one's
                if raw.t_read > 0.0:
                    self._worker_wait.observe(
                        time.perf_counter() - raw.t_read)
                raw.reply_obs = self._reply
            try:
                return _queries(req)
            finally:
                if raw is not None:
                    raw.t_done = time.perf_counter()

        def _queries(req: Request) -> Response:
            # with tenancy on, this is the same contract the event
            # server enforces on ingest: authenticate the app key, then
            # charge the app's rate/concurrency quota (429 + Retry-After
            # over quota); tenancy off -> tenant is None, open serve
            tenant = self.admission.resolve(req)
            app = tenant.label if tenant is not None else ""
            if self._pressure.shedding():
                self._shed_counter.labels(surface="memory", app=app).inc()
                raise OverloadedError(
                    "memory pressure: shedding new work", retry_after=1.0)
            t0 = time.perf_counter()
            try:
                with self.admission.admit(tenant):
                    ct = req.header("Content-Type") or ""
                    if ct.startswith(BIN_CONTENT_TYPE):
                        # binary SDK framing on the generic path: a
                        # non-wire replica behind a fleet router must
                        # speak the same frame the wire fast path does
                        # (routers proxy bodies opaquely)
                        decoded = decode_bin_query(req.body)
                        if decoded is None:
                            raise HTTPError(
                                400, "malformed binary query frame")
                        payload = {"user": decoded[0],
                                   "num": decoded[1]}
                    else:
                        try:
                            payload = req.json()
                        except ValueError as e:
                            raise HTTPError(400, str(e))
                    resp = Response.json(self._serve_one(payload,
                                                         tenant=tenant))
            except Exception as e:
                status = getattr(e, "status", 500)
                self._slo.record(app, time.perf_counter() - t0,
                                 ok=status < 500)
                raise
            self._slo.record(app, time.perf_counter() - t0, ok=True)
            return resp

        @r.post("/shard/queries.json")
        def shard_queries(req: Request) -> Response:
            """Cross-host mesh member surface: serve this member's
            catalog slice and return candidates WITH GLOBAL ITEM IDS,
            so the router's merge re-top-k is exact (stable
            (-score, gid) order + gid dedupe). Answers on non-mesh
            members too (shard "", full catalog) — a mixed fleet
            degrades to plain routing instead of 404ing."""
            tenant = self.admission.resolve(req)
            if self._pressure.shedding():
                self._shed_counter.labels(
                    surface="memory",
                    app=tenant.label if tenant is not None else "").inc()
                raise OverloadedError(
                    "memory pressure: shedding new work", retry_after=1.0)
            with self.admission.admit(tenant):
                try:
                    payload = req.json()
                except ValueError as e:
                    raise HTTPError(400, str(e))
                dep = self._dep
                if dep.query_class is not None:
                    query = extract_params(dep.query_class, payload)
                else:
                    query = payload
                prediction = dep.predict_batch([query])[0]
            out = to_jsonable(prediction)
            scores = (out.get("itemScores") or ()) \
                if isinstance(out, dict) else ()
            cands = []
            for s in scores:
                name = s.get("item")
                gid = None
                for im in dep.item_maps:
                    gid = im.get(name)
                    if gid is not None:
                        break
                cands.append([-1 if gid is None else int(gid),  # lint: ok — host json
                              s.get("score", 0.0), name])
            num = getattr(query, "num", None) if not isinstance(
                query, dict) else query.get("num")
            return Response.json({
                "shard": self.shard_spec(),
                "num": int(num) if num else len(cands),  # lint: ok — host json
                "cands": cands})

        @r.get("/")
        def index(req: Request) -> Response:
            dep = self._dep
            return Response.html(_status_page(self, dep))

        @r.get("/status.json")
        def status(req: Request) -> Response:
            dep = self._dep
            return Response.json({
                "status": "alive",
                "engineInstanceId": dep.instance.id,
                "engineVariant": dep.instance.engine_variant,
                "startTime": format_time(self.start_time),
                "requestCount": self.request_count,
                "avgServingSec": self.avg_serving_sec,
                "lastServingSec": self.last_serving_sec,
                "device": self.device,
                "compileCache": self._compile_cache,
                "servePlans": _serve_plans(dep),
            })

        @r.get("/quality.json")
        def quality_json(req: Request) -> Response:
            return Response.json(self.quality_snapshot())

        @r.post("/reload")
        def reload(req: Request) -> Response:
            """Hot-swap to the latest COMPLETED instance
            (CreateServer.scala:316-342); key-authenticated like the
            reference's authenticate(withAccessKeyFromFile) guard
            (CreateServer.scala:624-637). A failed load ROLLS BACK: the
            previous deployment keeps serving and the client gets a 500
            naming the error (counted in pio_reload_total{outcome})."""
            self.auth.check(req)
            prev = self._dep
            try:
                self._load()
            except Exception as e:
                _log.error("reload_failed_rolled_back",
                           error=f"{type(e).__name__}: {e}",
                           serving_instance=(prev.instance.id
                                             if prev else None))
                raise HTTPError(
                    500,
                    f"Reload failed ({type(e).__name__}: {e}); previous "
                    "deployment still serving")
            return Response.json({"message": "Reloaded"})

        @r.post("/stop")
        def stop(req: Request) -> Response:
            self.auth.check(req)
            # graceful: drain accepted work before the socket closes
            threading.Thread(target=self.stop, daemon=True,
                             name="pio-server-stop").start()
            return Response.json({"message": "Shutting down"})

        @r.get("/plugins.json")
        def plugins_json(req: Request) -> Response:
            return Response.json(self.plugin_context.describe())

        def plugin_rest(req: Request) -> Response:
            pname = req.params["pname"]
            args = [a for a in req.params.get("args", "").split("/") if a]
            table = {**self.plugin_context.output_blockers,
                     **self.plugin_context.output_sniffers}
            if pname not in table:
                raise HTTPError(404, f"Unknown plugin {pname}")
            return Response.json(table[pname].handle_rest(args))

        r.get("/plugins/<pname>")(plugin_rest)
        r.get("/plugins/<pname>/<args:path>")(plugin_rest)
        # selector wire only: the raw-bytes hot route; everything it
        # declines (return None) drops into the generic POST handler
        # registered above
        self.fast_route("POST", "/queries.json", self._fast_queries)


def install_signal_handlers(server, on_stopped=None) -> None:
    """Route SIGTERM/SIGINT through the server's graceful `stop()`
    drain (accepted requests finish; new work sheds 503) instead of
    dying mid-request. Explicit — never auto-installed by start(), so
    embedding processes and test runners keep their own handlers.
    `on_stopped` (optional) runs after the drain completes, e.g. the
    CLI's exit flag. Main-thread only (signal module contract)."""
    import signal

    def _drain_and_exit():
        try:
            # servers without a graceful drain (dashboard, admin, event
            # server) fall back to the plain shutdown
            stop = getattr(server, "stop", None)
            (stop if callable(stop) else server.shutdown)()
        finally:
            if on_stopped is not None:
                on_stopped()

    def _handle(signum, frame):
        # the handler itself must return immediately: drain on a named
        # thread so in-flight work (including the main loop) proceeds
        _log.warning("signal_graceful_stop",
                     signal=signal.Signals(signum).name)
        threading.Thread(target=_drain_and_exit, daemon=True,
                         name="pio-signal-stop").start()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _handle)


def _plans_of(dep: _Deployment):
    """(algorithm, plan) for every top-k plan the deployment's
    algorithms warmed (`Algorithm.serve_plans`)."""
    return [(algo, plan) for algo in dep.algos
            for plan in algo.serve_plans()]


def _serve_plans(dep: _Deployment) -> List[Dict[str, Any]]:
    """What the deploy warm-up built, per algorithm: the plan class,
    its shard count, and for every warmed batch bucket which kernel
    serves it ("fused": the single-launch Pallas kernel, "xla": the AOT
    XLA chain)."""
    return [{"algorithm": type(algo).__name__,
             "plan": type(plan).__name__,
             "shards": int(getattr(plan, "n_shards", 1)),  # lint: ok — host int
             "buckets": {str(b): kernel
                         for b, kernel in plan.bucket_kernels().items()}}
            for algo, plan in _plans_of(dep)]


def _gen_pr_id() -> str:
    return "".join(random.choices(string.ascii_letters + string.digits, k=64))


def _status_page(server: PredictionServer, dep: _Deployment) -> str:
    """Minimal HTML status page (the spray Twirl template analog,
    CreateServer.scala:442-468)."""
    algo_rows = "".join(
        f"<tr><td>{type(a).__name__}</td><td>{a.params}</td></tr>"
        for a in dep.algos)
    return f"""<html><head><title>PredictionIO-TPU engine server</title></head>
<body>
<h1>Engine server is running</h1>
<table>
<tr><td>Engine instance</td><td>{dep.instance.id}</td></tr>
<tr><td>Variant</td><td>{dep.instance.engine_variant}</td></tr>
<tr><td>Started</td><td>{format_time(server.start_time)}</td></tr>
<tr><td>Requests</td><td>{server.request_count}</td></tr>
<tr><td>Average serving (s)</td><td>{server.avg_serving_sec:.6f}</td></tr>
<tr><td>Last serving (s)</td><td>{server.last_serving_sec:.6f}</td></tr>
</table>
<h2>Algorithms</h2>
<table>{algo_rows}</table>
</body></html>"""
