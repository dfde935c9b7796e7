"""Minimal HTTP framework used by every host-side server.

The reference builds its REST planes on spray/akka actors
(`data/.../api/EventServer.scala`, `core/.../workflow/CreateServer.scala`,
`tools/.../dashboard/Dashboard.scala`). Here one stdlib-based router serves
all of them, over one of two interchangeable wires:

  - `selector` (default): the readiness-loop front end in
    `utils/wire.py` — persistent keep-alive connections multiplexed by
    one reactor thread over a small worker pool, incremental framing,
    and a `fast_route` hook that lets a server answer a hot route
    straight from the raw bytes (no header dict, no Request object) —
    the serve-plane wire overhaul behind the 10k-qps path;
  - `threaded`: the original `ThreadingHTTPServer` thread-per-connection
    stack, kept as the `PIO_SERVE_WIRE=threaded` escape hatch and used
    automatically when TLS is configured (the selector loop does not
    speak TLS).

Routing, middleware, and handler contracts are identical on both wires.

Features: method+path-pattern routing with `<name>` captures, JSON
request/response helpers, query params, per-request context, graceful
shutdown, optional TLS via an ssl context.

Observability middleware (predictionio_tpu.obs): every request gets a
request id (X-Request-ID in, generated otherwise; always echoed back),
one structured JSON log line (method, path, route, status, duration_ms,
request_id), a route/method/status counter and a per-route latency
histogram; every server serves its registry on `GET /metrics` in
Prometheus text format. Unhandled handler errors are logged structured
with the request id instead of a bare traceback print.

Resilience middleware (predictionio_tpu.resilience): `X-PIO-Deadline-Ms`
(or the server's `default_deadline_ms`) becomes a propagated Deadline —
expiry anywhere under the handler maps to 504; an open storage circuit
breaker maps to 503 + Retry-After; admission past `max_inflight` sheds
with 429 + Retry-After. Every server also answers `GET /health`
(liveness: the process responds) and `GET /ready` (readiness: the
subclass `readiness()` hook — model loaded, breakers closed).
"""

from __future__ import annotations

import json
import os
import re
import ssl as ssl_module
import threading
import time
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple
from urllib.parse import parse_qs, unquote, urlparse

from predictionio_tpu.obs import (
    MetricsRegistry, get_logger, get_registry, new_request_id,
)
from predictionio_tpu.obs import profiler as prof_mod
from predictionio_tpu.obs import trace
from predictionio_tpu.obs import tsdb as tsdb_mod
from predictionio_tpu.resilience import (
    DEADLINE_HEADER, Deadline, DeadlineExceeded, CircuitOpenError,
    InflightLimiter, OverloadedError, deadline_from_header, deadline_scope,
)
from predictionio_tpu.utils.wire import (
    RawRequest, SelectorWire, ShardedWire, build_response,
    reactor_count, set_trace_hooks, worker_count,
)

_log = get_logger("http")


@dataclass
class Request:
    method: str
    path: str
    query: Mapping[str, str]
    headers: Mapping[str, str]
    body: bytes
    params: Mapping[str, str] = field(default_factory=dict)  # path captures
    client: str = ""
    request_id: str = ""       # assigned by the middleware, never empty there
    route: str = ""            # matched route pattern (metrics label)
    deadline: Optional[Deadline] = None   # from X-PIO-Deadline-Ms / default
    # the selector wire's frame, if that wire carried the request
    raw: Optional[RawRequest] = field(default=None, repr=False,
                                      compare=False)

    def json(self) -> Any:
        if not self.body:
            raise ValueError("Empty request body")
        try:
            return json.loads(self.body.decode("utf-8"))
        except json.JSONDecodeError as e:
            raise ValueError(f"Invalid JSON: {e}") from e

    def header(self, name: str, default: Optional[str] = None
               ) -> Optional[str]:
        """Case-insensitive header lookup (clients and proxies disagree
        on canonical casing; RFC 7230 says names are case-insensitive)."""
        v = self.headers.get(name)
        if v is not None:
            return v
        lname = name.lower()
        for k, val in self.headers.items():
            if k.lower() == lname:
                return val
        return default

    def query_get(self, name: str, default: Optional[str] = None) -> Optional[str]:
        return self.query.get(name, default)


@dataclass
class Response:
    status: int = 200
    body: Any = None              # JSON-serializable, or bytes, or str
    content_type: str = "application/json"
    headers: Mapping[str, str] = field(default_factory=dict)

    @staticmethod
    def json(obj: Any, status: int = 200, **headers) -> "Response":
        return Response(status=status, body=obj, headers=headers)

    @staticmethod
    def text(s: str, status: int = 200, content_type: str = "text/plain") -> "Response":
        return Response(status=status, body=s, content_type=content_type)

    @staticmethod
    def html(s: str, status: int = 200) -> "Response":
        return Response(status=status, body=s, content_type="text/html")


Handler = Callable[[Request], Response]


class HTTPError(Exception):
    """Raise from a handler to produce a JSON error response."""

    def __init__(self, status: int, message: str,
                 headers: Optional[Mapping[str, str]] = None):
        super().__init__(message)
        self.status = status
        self.message = message
        self.headers: Dict[str, str] = dict(headers or {})


def _compile(pattern: str) -> re.Pattern:
    """`<name>` captures one segment; `<name:path>` captures across slashes."""
    parts = []
    for piece in re.split(r"(<[a-zA-Z_]+(?::path)?>)", pattern):
        if piece.startswith("<") and piece.endswith(">"):
            inner = piece[1:-1]
            if inner.endswith(":path"):
                parts.append(f"(?P<{inner[:-5]}>.+)")
            else:
                parts.append(f"(?P<{inner}>[^/]+)")
        else:
            parts.append(re.escape(piece))
    return re.compile("^" + "".join(parts) + "$")


class Router:
    def __init__(self):
        self.routes: List[Tuple[str, str, re.Pattern, Handler]] = []

    def route(self, method: str, pattern: str):
        def deco(fn: Handler) -> Handler:
            self.routes.append(
                (method.upper(), pattern, _compile(pattern), fn))
            return fn
        return deco

    def get(self, pattern: str):
        return self.route("GET", pattern)

    def post(self, pattern: str):
        return self.route("POST", pattern)

    def delete(self, pattern: str):
        return self.route("DELETE", pattern)

    def dispatch(self, req: Request) -> Response:
        path_matched = False
        for method, pattern, regex, fn in self.routes:
            m = regex.match(req.path)
            if m:
                path_matched = True
                if method == req.method:
                    # captures are matched against the raw (still-encoded)
                    # path, then decoded individually — decoding first would
                    # let %2F alter routing and make such ids unreachable
                    req.route = pattern
                    req.params = {k: unquote(v)
                                  for k, v in m.groupdict().items()}
                    try:
                        return fn(req)
                    except HTTPError as e:
                        return Response.json({"message": e.message}, e.status,
                                             **e.headers)
                    except DeadlineExceeded as e:
                        return Response.json({"message": str(e)}, 504)
                    except CircuitOpenError as e:
                        return Response.json(
                            {"message": str(e)}, 503,
                            **{"Retry-After": str(max(1, round(
                                e.retry_after)))})
                    except OverloadedError as e:
                        return Response.json(
                            {"message": e.message}, e.status,
                            **{"Retry-After": str(max(1, round(
                                e.retry_after)))})
                    except ValueError as e:
                        return Response.json({"message": str(e)}, 400)
                    except Exception as e:
                        _log.exception(
                            "unhandled_error", request_id=req.request_id,
                            method=req.method, path=req.path,
                            error=f"{type(e).__name__}: {e}")
                        return Response.json({"message": f"{e}"}, 500)
        if path_matched:
            return Response.json({"message": "Method Not Allowed"}, 405)
        return Response.json({"message": "Not Found"}, 404)


class HTTPServerBase:
    """A threaded HTTP server wrapping a Router; start()/shutdown() API.

    Subclasses populate `self.router`. Parity note: plays the role of
    spray-can's `IO(Http) ! Http.Bind` + actor routing in the reference
    servers.
    """

    def __init__(self, host: str = "0.0.0.0", port: int = 0,
                 ssl_context: Optional[ssl_module.SSLContext] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 default_deadline_ms: int = 0,
                 max_inflight: int = 0):
        self.host = host
        self.port = port
        self.router = Router()
        self._ssl_context = ssl_context
        # ThreadingHTTPServer or SelectorWire — same lifecycle surface
        self._httpd: Optional[Any] = None
        self._thread: Optional[threading.Thread] = None
        self._lifecycle_lock = threading.Lock()
        # one process-default registry unless a test passes its own, so a
        # single /metrics scrape sees every server in the process
        self.metrics = metrics if metrics is not None else get_registry()
        self.obs_log = get_logger(type(self).__name__)
        self._req_counter = self.metrics.counter(
            "pio_http_requests_total", "HTTP requests served",
            labels=("route", "method", "status"))
        self._req_hist = self.metrics.histogram(
            "pio_http_request_duration_seconds",
            "HTTP request wall time by matched route", labels=("route",))
        # resilience: per-request deadline default + HTTP-plane admission
        self.default_deadline_ms = default_deadline_ms
        self._limiter = InflightLimiter(
            max_inflight, surface=type(self).__name__)
        # `app` attributes the shed to a tenant where one is known; the
        # HTTP-plane inflight shed happens before auth, hence app=""
        self._shed_counter = self.metrics.counter(
            "pio_shed_total", "Requests shed by surface at admission",
            labels=("surface", "app"))
        self._deadline_counter = self.metrics.counter(
            "pio_deadline_expired_total",
            "Requests that exhausted their deadline", labels=("route",))
        self.router.get("/metrics")(self._metrics_endpoint)
        self.router.get("/health")(self._health_endpoint)
        self.router.get("/ready")(self._ready_endpoint)
        self.router.get("/traces.json")(self._traces_endpoint)
        self.router.get("/profile.json")(self._profile_json_endpoint)
        self.router.get("/profile.txt")(self._profile_txt_endpoint)
        self.router.get("/tsdb.json")(self._tsdb_endpoint)
        # continuous observatory: every server keeps its own bounded
        # time-series ring over its registry, scraped on a background
        # tick (PIO_TSDB_INTERVAL_S=0 disables the loop; the ring and
        # endpoint stay, just empty)
        self.tsdb = tsdb_mod.TSDB()
        self._scraper: Optional[tsdb_mod.Scraper] = None
        self._host_sampler = prof_mod.HostSampler(self.metrics)
        # last-seen absolute wire counters, so monotone pio_wire_*
        # counters can be advanced by delta on each /metrics scrape
        self._wire_last: Dict[str, float] = {}
        # hot-route hook (selector wire only): (method, path) -> a
        # handler taking the RAW framed request and returning complete
        # response bytes, or None to fall through to the Router path.
        # Only /queries.json rides this; every legacy route keeps the
        # full Request/middleware pipeline.
        self._fast_routes: Dict[Tuple[str, str],
                                Callable[[RawRequest], Optional[bytes]]] = {}
        self.wire = "unstarted"

    def fast_route(self, method: str, path: str,
                   fn: Callable[[RawRequest], Optional[bytes]]) -> None:
        """Register a raw-bytes handler for one exact (method, path).
        The handler returns a full HTTP response as bytes, or None to
        delegate to the normal Router dispatch (the fallback path MUST
        exist as a registered route)."""
        self._fast_routes[(method.upper(), path)] = fn

    def _metrics_endpoint(self, req: Request) -> Response:
        self._sync_wire_metrics()
        self._host_sampler.sample()
        return Response.text(
            self.metrics.render(),
            content_type="text/plain; version=0.0.4; charset=utf-8")

    def _traces_endpoint(self, req: Request) -> Response:
        """The flight recorder's keep ring (filter: ?app= / ?min_ms= /
        ?trace_id= / ?limit=)."""
        return Response(status=200, body=trace.traces_json_body(
            req.query_get), content_type="application/json")

    # -- continuous observatory ----------------------------------------------
    def _profile_json_endpoint(self, req: Request) -> Response:
        """Sampling-profiler snapshot: per-role CPU shares + top
        frames by self and cumulative samples."""
        try:
            top = int(req.query_get("top") or 30)
        except ValueError:
            top = 30
        return Response.json(
            prof_mod.get_profiler().snapshot_json(top=max(1, top)))

    def _profile_txt_endpoint(self, req: Request) -> Response:
        """?fmt=collapsed (the default) serves flamegraph-ready
        collapsed stacks; ?fmt=top a terminal-friendly summary."""
        prof = prof_mod.get_profiler()
        if (req.query_get("fmt") or "collapsed") != "collapsed":
            snap = prof.snapshot_json(top=15)
            lines = [f"samples={snap['samples']} hz={snap['hz']}"]
            for role, st in snap["roles"].items():
                lines.append(f"role {role:<12} {st['share']:>7.2%}"
                             f"  ({st['samples']})")
            for row in snap["top_self"]:
                lines.append(f"self {row['share']:>7.2%}  {row['frame']}")
            return Response.text("\n".join(lines) + "\n")
        return Response.text(prof.collapsed())

    def _tsdb_endpoint(self, req: Request) -> Response:
        """The local time-series ring (?series=prefix,prefix &
        ?since=unix-ts filter)."""
        return Response.json(self.tsdb.to_json(
            req.query_get("series"), req.query_get("since")))

    def _obs_collectors(self) -> List[Callable[[], None]]:
        """Collectors the tsdb scraper runs before each snapshot —
        subclasses extend (fleet member scrape, device memory and plan
        bytes on the one server that owns device state). Nothing here
        may touch jax: most servers never compute, and a process that
        initialises a backend takes the chip from the ones that do."""
        return [self._sync_wire_metrics, self._host_sampler.sample]

    def _sync_wire_metrics(self) -> None:
        """Scrape the selector wire's raw counters into pio_wire_*
        families (called on /metrics; the wire itself stays obs-free).
        Monotone values advance their counter by delta since the last
        scrape; instantaneous ones land in gauges. Every family carries
        a `reactor` label — one series per accept shard under
        ShardedWire ("0" for the single-reactor wire), so shard skew is
        visible straight from /metrics."""
        httpd = self._httpd
        snap_fn = getattr(httpd, "stats_snapshot", None)
        if snap_fn is None:
            return
        snap = snap_fn()
        # ShardedWire returns the aggregate plus per-reactor snapshots;
        # a plain SelectorWire snapshot IS its own single shard
        shards = snap.get("reactors") or [snap]
        listen = f"{self.host}:{self.port}"
        m = self.metrics
        last = self._wire_last

        def _cdelta(name: str, help_text: str, key: str, value: float,
                    **extra) -> None:
            prev = last.get(name + key + str(sorted(extra.items())), 0.0)
            delta = value - prev
            if delta > 0:
                m.counter(name, help_text,
                          labels=("listen",) + tuple(sorted(extra))
                          ).labels(listen=listen, **extra).inc(delta)
            last[name + key + str(sorted(extra.items()))] = value

        for rs in shards:
            r = str(rs.get("reactor", 0))
            _cdelta("pio_wire_connections_accepted_total",
                    "Connections accepted by the selector wire",
                    f"accepted[{r}]", float(rs["accepted"]), reactor=r)
            _cdelta("pio_wire_requests_total",
                    "Requests framed off the selector wire",
                    f"requests[{r}]", float(rs["requests"]), reactor=r)
            _cdelta("pio_wire_responses_total",
                    "Responses fully written by the selector wire",
                    f"responses[{r}]", float(rs["responses"]), reactor=r)
            _cdelta("pio_wire_egress_flushes_total",
                    "Gathered egress syscalls (sendmsg batches); "
                    "responses/flushes is the writev coalescing ratio",
                    f"flushes[{r}]", float(rs.get("flushes", 0)),
                    reactor=r)
            _cdelta("pio_wire_send_failures_total",
                    "Response writes that failed or timed out",
                    f"send_failures[{r}]", float(rs["send_failures"]),
                    reactor=r)
            _cdelta("pio_wire_bytes_total", "Wire bytes by direction",
                    f"bytes_in[{r}]", float(rs["bytes_in"]),
                    dir="in", reactor=r)
            _cdelta("pio_wire_bytes_total", "Wire bytes by direction",
                    f"bytes_out[{r}]", float(rs["bytes_out"]),
                    dir="out", reactor=r)
            for status, count in dict(rs["errors"]).items():
                _cdelta("pio_wire_errors_total",
                        "Wire-level framing error responses by status",
                        f"err{status}[{r}]", float(count),
                        status=str(status), reactor=r)
            gauges = (
                ("pio_wire_connections_open",
                 "Connections currently registered with the reactor",
                 float(rs["open_conns"])),
                ("pio_wire_queue_depth",
                 "Connections waiting for a wire worker",
                 float(rs["queue_depth"])),
                ("pio_wire_workers_busy",
                 "Wire workers currently running a handler",
                 float(rs["busy_workers"])),
                ("pio_wire_workers", "Wire worker pool size",
                 float(rs["workers"])),
                ("pio_wire_pipeline_depth_hwm",
                 "High-water mark of framed-but-unserved pipelined "
                 "requests on one connection",
                 float(rs["pipeline_hwm"])),
                ("pio_wire_worker_utilization",
                 "Busy fraction of the wire worker pool "
                 "(busy_workers / workers)",
                 float(rs.get("utilization", 0.0))),
            )
            for name, help_text, value in gauges:
                m.gauge(name, help_text,
                        labels=("listen", "reactor")).labels(
                            listen=listen, reactor=r).set(value)
            reqs = float(rs["requests"])
            reuse = ((reqs - float(rs["accepted"])) / reqs
                     if reqs > 0 else 0.0)
            m.gauge("pio_wire_keepalive_reuse_ratio",
                    "Fraction of requests that reused a kept-alive "
                    "connection", labels=("listen", "reactor")).labels(
                        listen=listen, reactor=r).set(max(0.0, reuse))

    # -- health/readiness ---------------------------------------------------
    def readiness(self) -> Tuple[bool, Dict[str, Any]]:
        """Subclass hook: (ready?, detail). Default: serving = ready."""
        return True, {}

    def _health_endpoint(self, req: Request) -> Response:
        """Liveness: the process accepts connections and can respond."""
        return Response.json({"status": "ok"})

    def _ready_endpoint(self, req: Request) -> Response:
        """Readiness: fit to take traffic (model loaded, breakers
        closed); 503 tells the load balancer to route elsewhere."""
        ok, detail = self.readiness()
        body = {"ready": ok}
        body.update(detail)
        return Response.json(body, 200 if ok else 503)

    def _handle(self, req: Request) -> Response:
        """Resilience middleware around dispatch: deadline extraction +
        propagation (contextvar, for storage/batcher calls below the
        handler) and in-flight admission control."""
        try:
            req.deadline = deadline_from_header(
                req.header(DEADLINE_HEADER), self.default_deadline_ms)
        except ValueError as e:
            return Response.json({"message": str(e)}, 400)
        if req.deadline is not None and req.deadline.expired:
            return Response.json(
                {"message": "deadline expired before processing"}, 504)
        try:
            with self._limiter:
                with deadline_scope(req.deadline):
                    return self.router.dispatch(req)
        except OverloadedError as e:
            self._shed_counter.labels(surface=self._limiter.surface,
                                      app="").inc()
            return Response.json(
                {"message": e.message}, e.status,
                **{"Retry-After": str(max(1, round(e.retry_after)))})

    # -- selector-wire raw path ---------------------------------------------
    def _wire_cover(self) -> int:
        """How many requests this server's own admission layer lets
        wait or run at once, for the wire to size its handler pool by
        (`utils/wire.worker_count`); 0 = it has no such number and the
        pool goes by the core count."""
        return 0

    def _handle_raw(self, raw: RawRequest) -> Tuple[bytes, bool]:
        """The selector wire's single entry point: try the fast-route
        table on the raw frame, else materialize a full Request and run
        the identical middleware + Router pipeline the threaded wire
        uses. Returns (response bytes, close connection?)."""
        fast = self._fast_routes.get((raw.method, raw.path))
        if fast is not None:
            out = fast(raw)
            if out is not None:
                return out, not raw.keep_alive
        rid = raw.header("X-Request-ID") or new_request_id()
        raw_q = parse_qs(raw.query_string, keep_blank_values=True)
        req = Request(
            method=raw.method, path=raw.path,
            query={k: v[0] for k, v in raw_q.items()},
            headers=dict(raw.header_items()), body=raw.body,
            client=raw.client, request_id=rid, raw=raw)
        p = raw.trace
        tok = None
        if p is not None:
            trace.begin_raw(raw, raw.header(trace.TRACE_HEADER))
            p.rid = rid
            # expose the pending trace to handlers below (fleet router
            # spans, batcher submit on the legacy route)
            tok = trace.set_current(p)
        started = time.perf_counter()
        try:
            resp = self._handle(req)
        finally:
            if tok is not None:
                trace.reset_current(tok)
        if p is not None:
            trace.annotate_pending(p, status=resp.status,
                                   route=req.route or raw.path)
            trace.mark(p, trace.S_DONE)
        self._observe_request(req, resp, time.perf_counter() - started)
        payload = resp.body
        if isinstance(payload, bytes):
            data = payload
        elif isinstance(payload, str):
            data = payload.encode("utf-8")
        else:
            data = json.dumps(payload).encode("utf-8")
        out = build_response(
            resp.status, resp.content_type, data, rid,
            dict(resp.headers) if resp.headers else None,
            keep_alive=raw.keep_alive, head_only=raw.method == "HEAD")
        return out, not raw.keep_alive

    def _observe_request(self, req: Request, resp: Response,
                         duration: float) -> None:
        route = req.route or "(unmatched)"
        if resp.status == 504:
            self._deadline_counter.labels(route=route).inc()
        self._req_counter.labels(
            route=route, method=req.method, status=str(resp.status)).inc()
        self._req_hist.labels(route=route).observe(duration)
        self.obs_log.info(
            "request", request_id=req.request_id, method=req.method,
            path=req.path, route=route, status=resp.status,
            duration_ms=round(duration * 1000.0, 3))

    # -- lifecycle ----------------------------------------------------------
    def start(self, background: bool = True) -> int:
        router = self.router
        server_ref = self

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _respond(self):
                parsed = urlparse(self.path)
                raw_q = parse_qs(parsed.query, keep_blank_values=True)
                query = {k: v[0] for k, v in raw_q.items()}
                rid = self.headers.get("X-Request-ID") or new_request_id()
                try:
                    length = int(self.headers.get("Content-Length") or 0)
                    if length < 0:
                        raise ValueError("negative Content-Length")
                except ValueError:
                    # malformed framing: answer 400 instead of resetting
                    # the connection with no response at all; the body
                    # was never read, so the connection must close
                    self.close_connection = True
                    self._reply(Response.json(
                        {"message": "Invalid Content-Length header"},
                        400), rid)
                    return
                body = self.rfile.read(length) if length else b""
                req = Request(
                    method=self.command, path=parsed.path, query=query,
                    headers={k: v for k, v in self.headers.items()},
                    body=body, client=self.client_address[0],
                    request_id=rid)
                started = time.perf_counter()
                resp = server_ref._handle(req)
                server_ref._observe_request(
                    req, resp, time.perf_counter() - started)
                self._reply(resp, rid)

            def _reply(self, resp: Response, rid: str) -> None:
                payload = resp.body
                if isinstance(payload, bytes):
                    data = payload
                elif isinstance(payload, str):
                    data = payload.encode("utf-8")
                else:
                    data = json.dumps(payload).encode("utf-8")
                self.send_response(resp.status)
                self.send_header("Content-Type", resp.content_type)
                self.send_header("Content-Length", str(len(data)))
                self.send_header("X-Request-ID", rid)
                for k, v in resp.headers.items():
                    self.send_header(k, v)
                self.end_headers()
                if self.command != "HEAD":
                    self.wfile.write(data)

            do_GET = do_POST = do_DELETE = do_PUT = do_HEAD = _respond

            def log_message(self, fmt, *args):  # quiet by default
                server_ref.log_request_line(fmt % args)

        # Deep listen backlog: the stdlib default of 5 drops connections
        # (ECONNRESET) under concurrent client bursts. On the threaded
        # wire, daemon thread-per-connection stays (an earlier
        # worker-pool variant let idle keep-alive connections starve
        # every worker — the selector wire solves that with readiness
        # multiplexing instead); the handler timeout bounds how long an
        # idle keep-alive connection can pin its (daemon) thread.
        _Server = type("_Server", (ThreadingHTTPServer,),
                       {"request_queue_size": 128})
        _Handler.timeout = 60
        # wire selection: the selector readiness loop is the default;
        # PIO_SERVE_WIRE=threaded is the escape hatch, and TLS always
        # takes the threaded wire (the selector loop does not speak
        # ssl's WantRead/WantWrite dance)
        want = os.environ.get("PIO_SERVE_WIRE", "selector").lower()
        use_selector = want != "threaded" and self._ssl_context is None
        self.wire = "selector" if use_selector else "threaded"
        if use_selector:
            # flight-recorder hooks: process-global and idempotent; the
            # recorder reads PIO_TRACE_SAMPLE and returns None stamps
            # when tracing is off, so this costs ~nothing by default
            trace.get_recorder()
            set_trace_hooks(trace.new_stamps, trace.on_sent)

        def _bind():
            if use_selector:
                # PIO_WIRE_REACTORS > 1 shards the accept loop across
                # N reactors (SO_REUSEPORT, or fd handoff where that is
                # unavailable); at 1 the single-reactor wire is used
                # unchanged.
                n = reactor_count()
                workers = worker_count(self._wire_cover())
                if n > 1:
                    return ShardedWire((self.host, self.port),
                                       self._handle_raw, reactors=n,
                                       workers=workers)
                return SelectorWire((self.host, self.port),
                                    self._handle_raw, workers=workers)
            return _Server((self.host, self.port), _Handler)

        # 3-attempt bind with backoff (the reference retries Http.Bind
        # three times before giving up, CreateServer.scala:260-285) —
        # covers the port-release lag after stopping a previous server.
        # Only EADDRINUSE is transient; EACCES/EADDRNOTAVAIL etc. can
        # never succeed and raise immediately.
        import errno
        for attempt in range(3):
            try:
                self._httpd = _bind()
                break
            except OSError as e:
                if attempt == 2 or e.errno != errno.EADDRINUSE:
                    raise
                time.sleep(0.5 * (attempt + 1))
        if self._ssl_context is not None:
            self._httpd.socket = self._ssl_context.wrap_socket(
                self._httpd.socket, server_side=True)
        self.port = self._httpd.server_address[1]
        self._on_bound()
        # continuous observatory: process-global sampler (one thread
        # samples every thread once, however many servers run) + a GC
        # pause hook per registry + this server's tsdb scraper. Both
        # loops honor their =0 env escape inside start().
        prof_mod.ensure_started()
        prof_mod.install_gc_callbacks(self.metrics)
        if self._scraper is None:
            self._scraper = tsdb_mod.Scraper(
                self.tsdb, self.metrics,
                collectors=self._obs_collectors())
        self._scraper.start()
        if background:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever, daemon=True,
                name=f"pio-http-serve-{self.port}")
            self._thread.start()
        else:
            self._httpd.serve_forever()
        return self.port

    def _on_bound(self) -> None:
        """Subclass hook: runs after the wire is bound (self._httpd
        set, self.port final) and before serve_forever — the place to
        connect wire-facing callbacks like the micro-batcher's
        flush_hint cross-wakeup."""

    def shutdown(self) -> None:
        # idempotent + thread-safe: the /stop handler thread and a caller
        # (test teardown, signal handler) may race into shutdown
        with self._lifecycle_lock:
            httpd, self._httpd = self._httpd, None
            thread, self._thread = self._thread, None
            scraper, self._scraper = self._scraper, None
        if scraper is not None:
            scraper.stop()
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if thread is not None:
            thread.join(timeout=5)

    def is_running(self) -> bool:
        return self._httpd is not None

    def log_request_line(self, line: str) -> None:
        pass


def parse_basic_auth_value(auth: Optional[str]) -> Optional[str]:
    """Username out of one raw `Authorization` header value — the
    header-lite form the wire fast path feeds straight from its scan."""
    import base64
    if not auth or not auth.startswith("Basic "):
        return None
    try:
        decoded = base64.b64decode(auth[len("Basic "):]).decode("utf-8")
    except Exception:
        return None
    return decoded.split(":")[0].strip() or None


def parse_basic_auth_user(headers: Mapping[str, str]) -> Optional[str]:
    """Extract the username of a Basic Authorization header (the reference
    accepts the access key as the Basic username, EventServer.scala:114-126)."""
    return parse_basic_auth_value(
        headers.get("Authorization") or headers.get("authorization"))
