"""Wire-path tests for the selector front end (`utils/wire.py`).

Two layers, mirroring the module split:

  - framing as a pure function: `frame_request` over hand-built byte
    buffers — partial delivery, pipelining, every malformed-input
    status (400/413/431/501), and the HTTP/1.0 vs 1.1 keep-alive
    defaults;
  - the live reactor: raw sockets against a `SelectorWire` running a
    trivial echo handler — keep-alive reuse, pipelined response
    ordering, trickled byte-at-a-time delivery, error-close behavior,
    and graceful drain of an in-flight handler across `shutdown()`.

Plus the fast-path parity fuzz: `_FAST_QUERY_RE` (the compiled
/queries.json shape in `serving/server.py`) must never accept a body
`json.loads` rejects, and must read the same (user, num) out of every
body both can parse.

PR 13 layers on top of both:

  - gathered egress: a pipelined burst leaves in strictly fewer
    `sendmsg` flushes than responses, still in request order, and the
    micro-batcher's `flush_hint()` cross-wakeup pushes a deferred
    response without waiting for the blocked owning worker;
  - `ShardedWire`: N reactors behind one port over SO_REUSEPORT, the
    round-robin fd-handoff fallback when that is unavailable, and a
    shutdown that drains every reactor with no connection stranded;
  - the binary query codec: round-trip, strict rejects, and the fuzzed
    accept-containment gate — every frame `decode_bin_query` accepts
    must map onto a (user, num) the JSON route reads identically.
"""

import json
import os
import random
import select
import socket
import string
import threading
import time
import types

import pytest

from predictionio_tpu.serving.server import _FAST_QUERY_RE
from predictionio_tpu.utils.wire import (
    MAX_BODY_BYTES, MAX_HEADER_BYTES, RawRequest, SelectorWire, ShardedWire,
    WireError, build_response, decode_bin_query, encode_bin_query,
    frame_request, set_trace_hooks,
)

pytestmark = pytest.mark.wire


def _req(path="/echo", body=b"", version="1.1", method="POST",
         headers=()):
    head = [f"{method} {path} HTTP/{version}".encode("ascii"),
            b"Host: t"]
    if body or method == "POST":
        head.append(b"Content-Length: %d" % len(body))
    head.extend(headers)
    return b"\r\n".join(head) + b"\r\n\r\n" + body


# -- framing as a pure function ----------------------------------------------

class TestFraming:
    def test_partial_head_needs_more(self):
        buf = bytearray(b"POST /q HTTP/1.1\r\nHost: t\r\n")
        assert frame_request(buf) == (None, 0)
        buf.extend(b"Content-Length: 2\r\n\r\n")
        # head complete but body short by 2
        assert frame_request(buf) == (None, 0)
        buf.extend(b"hi")
        raw, consumed = frame_request(buf)
        assert raw is not None and consumed == len(buf)
        assert raw.method == "POST" and raw.path == "/q"
        assert raw.body == b"hi"

    def test_pipelined_requests_frame_in_order(self):
        buf = bytearray(_req(body=b"one") + _req(body=b"three")
                        + _req(body=b"two")[:-1])
        bodies = []
        for _ in range(2):
            raw, consumed = frame_request(buf)
            assert raw is not None
            del buf[:consumed]
            bodies.append(raw.body)
        assert bodies == [b"one", b"three"]
        # the third is short one body byte; completes after delivery
        assert frame_request(buf) == (None, 0)
        buf.extend(b"o")
        raw, consumed = frame_request(buf)
        assert raw.body == b"two" and consumed == len(buf)

    def test_query_string_split(self):
        buf = bytearray(_req(path="/queries.json?accessKey=K&x=1"))
        raw, _ = frame_request(buf)
        assert raw.path == "/queries.json"
        assert raw.query_string == "accessKey=K&x=1"

    @pytest.mark.parametrize("cl", [b"abc", b"-1", b"1e3", b"0x10", b""])
    def test_malformed_content_length_400(self, cl):
        buf = bytearray(b"POST / HTTP/1.1\r\nContent-Length: " + cl
                        + b"\r\n\r\n")
        with pytest.raises(WireError) as ei:
            frame_request(buf)
        assert ei.value.status == 400

    def test_oversized_declared_body_413(self):
        buf = bytearray(b"POST / HTTP/1.1\r\nContent-Length: %d\r\n\r\n"
                        % (MAX_BODY_BYTES + 1))
        with pytest.raises(WireError) as ei:
            frame_request(buf)
        assert ei.value.status == 413

    def test_at_limit_body_is_not_413(self):
        buf = bytearray(b"POST / HTTP/1.1\r\nContent-Length: %d\r\n\r\n"
                        % MAX_BODY_BYTES)
        # not an error — just waiting on the body bytes
        assert frame_request(buf) == (None, 0)

    def test_unterminated_header_block_431(self):
        buf = bytearray(b"POST / HTTP/1.1\r\nX: "
                        + b"a" * (MAX_HEADER_BYTES + 8))
        with pytest.raises(WireError) as ei:
            frame_request(buf)
        assert ei.value.status == 431

    @pytest.mark.parametrize("line", [
        b"POST /\r\n",                  # two fields
        b"POST / HTTP/1.1 extra\r\n",   # four fields
        b"POST / SPDY/3\r\n",           # wrong protocol
        b"POST / HTTP/2\r\n",           # unsupported major version
    ])
    def test_bad_request_line_400(self, line):
        buf = bytearray(line + b"\r\n")
        with pytest.raises(WireError) as ei:
            frame_request(buf)
        assert ei.value.status == 400

    def test_transfer_encoding_rejected_501(self):
        buf = bytearray(b"POST / HTTP/1.1\r\n"
                        b"Transfer-Encoding: chunked\r\n\r\n")
        with pytest.raises(WireError) as ei:
            frame_request(buf)
        assert ei.value.status == 501

    def test_keep_alive_defaults(self):
        r11, _ = frame_request(bytearray(_req()))
        assert r11.keep_alive
        r11c, _ = frame_request(bytearray(
            _req(headers=(b"Connection: close",))))
        assert not r11c.keep_alive
        r10, _ = frame_request(bytearray(_req(version="1.0")))
        assert not r10.keep_alive
        r10k, _ = frame_request(bytearray(
            _req(version="1.0", headers=(b"Connection: keep-alive",))))
        assert r10k.keep_alive

    def test_header_scan_case_insensitive(self):
        raw, _ = frame_request(bytearray(_req(
            headers=(b"X-Request-ID: rid-7", b"AUTHORIZATION: Bearer t"))))
        assert raw.header("x-request-id") == "rid-7"
        assert raw.header("X-Request-Id") == "rid-7"
        assert raw.header("authorization") == "Bearer t"
        assert raw.header("X-Missing") is None
        assert ("Host", "t") in raw.header_items()

    def test_build_response_round_trips(self):
        data = build_response(200, "application/json", b'{"a": 1}',
                              rid="r1", extra={"Retry-After": "2"},
                              keep_alive=False)
        head, _, body = data.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 200 OK\r\n")
        assert b"Content-Length: 8\r\n" in head
        assert b"X-Request-ID: r1\r\n" in head
        assert b"Retry-After: 2\r\n" in head
        assert head.endswith(b"Connection: close")
        assert body == b'{"a": 1}'


# -- the live reactor --------------------------------------------------------

def _echo(raw: RawRequest):
    if raw.path == "/slow":
        time.sleep(0.5)
    body = b"%s %s %s" % (raw.method.encode("ascii"),
                          raw.path.encode("ascii"), raw.body)
    return (build_response(200, "text/plain", body,
                           keep_alive=raw.keep_alive),
            not raw.keep_alive)


def test_default_worker_pool_covers_admission_concurrency(monkeypatch):
    """Workers block in the handler, so the default pool must exceed
    the serve-layer shed limits even on a 1-core host — a smaller pool
    serializes bursts at the wire and the 429/503 admission paths
    (queue_max, max_inflight) never engage."""
    monkeypatch.delenv("PIO_WIRE_WORKERS", raising=False)
    srv = SelectorWire(("127.0.0.1", 0), _echo)
    try:
        assert srv._n_workers >= 16
    finally:
        srv.server_close()


@pytest.mark.parametrize("env,cover,expect", [
    ("", 0, "cores"),            # no admission layer known: by cores
    ("", 384, 384),              # a batcher's queue_max + 2 * batch_max
    ("", 8, "cores"),            # never under the pool by cores
    ("6", 0, 6),                 # PIO_WIRE_WORKERS wins over both
    ("6", 384, 6),
])
def test_worker_count_env_then_cover_then_cores(monkeypatch, env, cover,
                                                expect):
    from predictionio_tpu.utils.wire import worker_count
    if env:
        monkeypatch.setenv("PIO_WIRE_WORKERS", env)
    else:
        monkeypatch.delenv("PIO_WIRE_WORKERS", raising=False)
    cores = max(16, min(64, 4 * (os.cpu_count() or 4)))
    assert worker_count(cover) == (cores if expect == "cores" else expect)


def test_sharded_slice_covers_an_uneven_share_of_the_callers(monkeypatch):
    """SO_REUSEPORT does not deal connections evenly: of a pool that
    covers the batcher (384 at the defaults) one of four reactors gets
    ceil(384 / 4) = 96 workers, three quarters of c128's 128 callers."""
    monkeypatch.delenv("PIO_WIRE_WORKERS", raising=False)
    srv = ShardedWire(("127.0.0.1", 0), _echo, reactors=4, workers=384)
    try:
        assert [r._n_workers for r in srv.reactors] == [96] * 4
        assert srv.stats_snapshot()["workers"] == 384
    finally:
        srv.server_close()


@pytest.fixture()
def wire():
    srv = SelectorWire(("127.0.0.1", 0), _echo, workers=2)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        yield srv
    finally:
        srv.shutdown()
        srv.server_close()
        t.join(timeout=5)


def _connect(srv) -> socket.socket:
    s = socket.create_connection(srv.server_address, timeout=5)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def _read_response(f):
    status = int(f.readline().split(b" ")[1])
    length, closing = 0, False
    while True:
        line = f.readline().rstrip(b"\r\n")
        if not line:
            break
        name, _, value = line.partition(b":")
        if name.lower() == b"content-length":
            length = int(value)
        if (name.lower() == b"connection"
                and value.strip().lower() == b"close"):
            closing = True
    return status, f.read(length), closing


class TestSelectorWire:
    def test_keepalive_connection_reuse(self, wire):
        with _connect(wire) as s, s.makefile("rb") as f:
            for i in range(12):
                s.sendall(_req(body=b"n=%d" % i))
                status, body, closing = _read_response(f)
                assert status == 200 and body == b"POST /echo n=%d" % i
                assert not closing
            # same TCP connection served all twelve

    def test_connection_close_honored(self, wire):
        with _connect(wire) as s, s.makefile("rb") as f:
            s.sendall(_req(headers=(b"Connection: close",)))
            status, _, closing = _read_response(f)
            assert status == 200 and closing
            assert f.read(1) == b""      # server closed after responding

    def test_pipelined_responses_in_order(self, wire):
        n = 8
        with _connect(wire) as s, s.makefile("rb") as f:
            s.sendall(b"".join(_req(body=b"p%d" % i) for i in range(n)))
            for i in range(n):
                status, body, _ = _read_response(f)
                assert status == 200 and body == b"POST /echo p%d" % i

    def test_trickled_bytes_frame_incrementally(self, wire):
        data = _req(body=b"slow-drip")
        with _connect(wire) as s, s.makefile("rb") as f:
            for i in range(0, len(data), 7):
                s.sendall(data[i:i + 7])
                time.sleep(0.002)
            status, body, _ = _read_response(f)
            assert status == 200 and body == b"POST /echo slow-drip"

    def test_malformed_content_length_400_closes(self, wire):
        with _connect(wire) as s, s.makefile("rb") as f:
            s.sendall(b"POST / HTTP/1.1\r\nContent-Length: zz\r\n\r\n")
            status, body, _ = _read_response(f)
            assert status == 400 and b"Content-Length" in body
            assert f.read(1) == b""      # framing errors close the stream

    def test_oversized_body_413_closes(self, wire):
        with _connect(wire) as s, s.makefile("rb") as f:
            s.sendall(b"POST / HTTP/1.1\r\nContent-Length: %d\r\n\r\n"
                      % (MAX_BODY_BYTES + 1))
            status, body, _ = _read_response(f)
            assert status == 413 and b"size limit" in body
            assert f.read(1) == b""

    def test_valid_after_malformed_on_new_connection(self, wire):
        with _connect(wire) as s, s.makefile("rb") as f:
            s.sendall(b"BAD\r\n\r\n")
            status, _, _ = _read_response(f)
            assert status == 400
        with _connect(wire) as s, s.makefile("rb") as f:
            s.sendall(_req(body=b"ok"))
            status, body, _ = _read_response(f)
            assert status == 200 and body == b"POST /echo ok"

    def test_graceful_drain_of_inflight_request(self, wire):
        """shutdown() stops the reactor; a request already handed to a
        worker still completes and its response is delivered."""
        with _connect(wire) as s, s.makefile("rb") as f:
            s.sendall(_req(path="/slow", body=b"drain"))
            time.sleep(0.15)             # reactor has pumped it by now
            wire.shutdown()
            status, body, _ = _read_response(f)
            assert status == 200 and body == b"POST /slow drain"

    def test_concurrent_connections(self, wire):
        results = []
        lock = threading.Lock()

        def one(i):
            with _connect(wire) as s, s.makefile("rb") as f:
                s.sendall(_req(body=b"c%d" % i))
                status, body, _ = _read_response(f)
                with lock:
                    results.append((i, status, body))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert len(results) == 16
        for i, status, body in results:
            assert status == 200 and body == b"POST /echo c%d" % i


# -- fast-path vs json.loads parity ------------------------------------------

def _parse_generic(body: bytes):
    """The generic route's view of a /queries.json body: the (user, num)
    pair iff it is valid JSON of exactly that shape, else None."""
    try:
        obj = json.loads(body.decode("utf-8"))
    except (ValueError, UnicodeDecodeError):
        return None
    if (not isinstance(obj, dict) or set(obj) != {"user", "num"}
            or not isinstance(obj["user"], str)
            or not isinstance(obj["num"], int)
            or isinstance(obj["num"], bool)):
        return None
    return obj["user"], obj["num"]


def _parse_fast(body: bytes):
    m = _FAST_QUERY_RE.match(body)
    if m is None:
        return None
    try:
        return m.group(1).decode("utf-8"), int(m.group(2))
    except UnicodeDecodeError:
        return None


class TestFastPathParity:
    def test_canonical_shapes_take_the_fast_path(self):
        for body, want in [
            (b'{"user": "u1", "num": 4}', ("u1", 4)),
            (b'{"user":"u1","num":4}', ("u1", 4)),
            (b' \t\r\n{ "user" : "a b" , "num" : -3 }\n', ("a b", -3)),
            (b'{"user": "", "num": 0}', ("", 0)),
            ('{"user": "ünïcødé", "num": 7}'.encode("utf-8"),
             ("ünïcødé", 7)),
            (b'{"user": "u", "num": 999999999}', ("u", 999999999)),
        ]:
            assert _parse_fast(body) == want, body
            assert _parse_generic(body) == want, body

    def test_off_shape_bodies_fall_through(self):
        for body in [
            b'{"num": 4, "user": "u1"}',          # field order
            b'{"user": "u1", "num": 4, "x": 1}',  # extra field
            b'{"user": "a\\"b", "num": 4}',       # escape in string
            b'{"user": 5, "num": 4}',             # numeric user
            b'{"user": "u1", "num": 4.0}',        # float num
            b'{"user": "u1", "num": 1234567890}',  # >9 digits
            b'{"user": "u1", "num": true}',
            b'{"user": "u1"}',
            b'[]',
            b'',
        ]:
            assert _parse_fast(body) is None, body

    def test_fast_never_accepts_what_generic_rejects(self):
        # the leading-zero class specifically: 01 is not JSON
        for body in [b'{"user": "u", "num": 01}',
                     b'{"user": "u", "num": -012}',
                     b'{"user": "u", "num": 00}']:
            assert _parse_generic(body) is None
            assert _parse_fast(body) is None, body

    def test_fuzz_parity(self):
        rng = random.Random(0xA11CE)
        user_chars = (string.ascii_letters + string.digits
                      + " .:/@#$%&*()[]-_=+!?~^" + "üé漢")
        ws = [b"", b" ", b"  ", b"\t", b"\n", b"\r\n", b" \t "]

        def w():
            return rng.choice(ws)

        checked_fast = 0
        for _ in range(3000):
            roll = rng.random()
            if roll < 0.5:
                # structured generation around the compiled shape
                user = "".join(rng.choice(user_chars)
                               for _ in range(rng.randrange(0, 24)))
                num = rng.choice(
                    [0, 1, -1, rng.randrange(-10**9, 10**9)])
                body = (b"%s{%s\"user\"%s:%s\"%s\"%s,%s\"num\"%s:%s%d%s}%s"
                        % (w(), w(), w(), w(), user.encode("utf-8"), w(),
                           w(), w(), w(), num, w(), w()))
            elif roll < 0.75:
                # mutate a canonical body: flip/insert/delete one byte
                body = bytearray(b'{"user": "abc", "num": 12}')
                op = rng.randrange(3)
                pos = rng.randrange(len(body))
                if op == 0:
                    body[pos] = rng.randrange(32, 127)
                elif op == 1:
                    body.insert(pos, rng.randrange(32, 127))
                else:
                    del body[pos]
                body = bytes(body)
            else:
                # unstructured printable noise
                body = bytes(rng.randrange(32, 127)
                             for _ in range(rng.randrange(0, 48)))
            fast = _parse_fast(body)
            if fast is not None:
                checked_fast += 1
                # anything the fast path accepts, the generic parser
                # accepts with the identical reading
                assert _parse_generic(body) == fast, body
        assert checked_fast > 500     # the fuzz actually hit the shape


# -- gathered egress (sendmsg coalescing + cross-wakeup) ----------------------

def _run_wire(**kw):
    srv = SelectorWire(("127.0.0.1", 0), _echo, **kw)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, t


def _stop_wire(srv, t):
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)


class TestPoolGrowsWithTheLoad:
    """The pool's size is a cap: workers start when more connections
    have work than there are workers, not all before the first accept
    (a PredictionServer with a batcher asks for 384)."""

    @pytest.mark.parametrize("conns,cap,expect", [
        (1, 8, 1), (3, 8, 3), (5, 2, 2)])
    def test_workers_start_as_connections_ask(self, conns, cap, expect):
        srv, t = _run_wire(workers=cap)
        try:
            assert srv._workers == []
            assert srv.stats_snapshot()["workers"] == cap
            socks = [_connect(srv) for _ in range(conns)]
            try:
                for s in socks:               # all blocked in the handler
                    s.sendall(_req(path="/slow", body=b"x"))
                for s in socks:
                    with s.makefile("rb") as f:
                        status, body, _ = _read_response(f)
                        assert status == 200 and body == b"POST /slow x"
                assert len(srv._workers) == expect
                for s in socks[:1]:           # an idle worker is reused
                    s.sendall(_req(body=b"y"))
                    with s.makefile("rb") as f:
                        assert _read_response(f)[0] == 200
                assert len(srv._workers) == expect
            finally:
                for s in socks:
                    s.close()
        finally:
            _stop_wire(srv, t)


class TestGatheredEgress:
    def test_pipelined_burst_coalesces_in_order(self):
        srv, t = _run_wire(workers=2, sendmsg=True)
        try:
            n = 16
            with _connect(srv) as s, s.makefile("rb") as f:
                s.sendall(b"".join(_req(body=b"b%d" % i)
                                   for i in range(n)))
                for i in range(n):
                    status, body, _ = _read_response(f)
                    assert status == 200
                    assert body == b"POST /echo b%d" % i
            snap = _settled(srv, n)
            assert snap["responses"] == n
            # the burst left in gathered flushes, not one send each
            assert 0 < snap["flushes"] < n
        finally:
            _stop_wire(srv, t)

    def test_sendmsg_off_sends_per_response(self):
        srv, t = _run_wire(workers=2, sendmsg=False)
        try:
            n = 8
            with _connect(srv) as s, s.makefile("rb") as f:
                s.sendall(b"".join(_req(body=b"p%d" % i)
                                   for i in range(n)))
                for i in range(n):
                    status, body, _ = _read_response(f)
                    assert status == 200
                    assert body == b"POST /echo p%d" % i
            snap = _settled(srv, n)
            assert snap["responses"] == n
            assert snap["flushes"] >= n       # one syscall per response
        finally:
            _stop_wire(srv, t)

    def test_flush_hint_releases_deferred_response(self):
        """With the worker blocked in /slow (0.5 s), the already-served
        first response sits deferred on the egress queue; flush_hint()
        makes the reactor push it long before the handler returns."""
        srv, t = _run_wire(workers=1, sendmsg=True)
        try:
            with _connect(srv) as s, s.makefile("rb") as f:
                s.sendall(_req(body=b"first")
                          + _req(path="/slow", body=b"second"))
                t0 = time.monotonic()
                readable = []
                while time.monotonic() - t0 < 0.45:
                    srv.flush_hint()
                    readable, _, _ = select.select([s], [], [], 0.02)
                    if readable:
                        break
                assert readable, "hint never flushed the deferred response"
                assert time.monotonic() - t0 < 0.45
                status, body, _ = _read_response(f)
                assert status == 200 and body == b"POST /echo first"
                status, body, _ = _read_response(f)
                assert status == 200 and body == b"POST /slow second"
        finally:
            _stop_wire(srv, t)

    def test_trace_stamp_carries_reactor_index(self):
        stamps = []

        def stamp_new(t0):
            st = types.SimpleNamespace(reactor=None)
            stamps.append(st)
            return st

        set_trace_hooks(stamp_new, None)
        try:
            srv = SelectorWire(("127.0.0.1", 0), _echo, workers=1,
                               index=7)
            t = threading.Thread(target=srv.serve_forever, daemon=True)
            t.start()
            try:
                with _connect(srv) as s, s.makefile("rb") as f:
                    s.sendall(_req(body=b"x"))
                    status, _, _ = _read_response(f)
                    assert status == 200
            finally:
                _stop_wire(srv, t)
        finally:
            set_trace_hooks(None, None)
        assert stamps and stamps[0].reactor == 7

    @pytest.mark.parametrize("hooks", [False, True],
                             ids=["no_hooks", "sent_hook"])
    def test_first_read_stamp_and_reply_hook_without_a_trace(self, hooks):
        """Every framed request carries the first read of its bytes,
        hooks or none; the sent hook fires for a request whose handler
        left a `reply_obs`, though it has no trace, and for no other."""
        seen, sent = [], []

        def handler(raw):
            seen.append((raw.t_read, time.perf_counter(), raw.trace))
            if raw.path == "/echo":
                raw.t_done = time.perf_counter()
                raw.reply_obs = object()
            return _echo(raw)

        set_trace_hooks(None, sent.append if hooks else None)
        try:
            srv = SelectorWire(("127.0.0.1", 0), handler, workers=1)
            t = threading.Thread(target=srv.serve_forever, daemon=True)
            t.start()
            try:
                t0 = time.perf_counter()
                with _connect(srv) as s, s.makefile("rb") as f:
                    for path in ("/echo", "/other", "/echo"):
                        s.sendall(_req(path=path, body=b"x"))
                        status, _, _ = _read_response(f)
                        assert status == 200
                # the hook runs after the bytes left: wait for the last
                deadline = time.perf_counter() + 5.0
                while hooks and len(sent) < 2 \
                        and time.perf_counter() < deadline:
                    time.sleep(0.005)
            finally:
                _stop_wire(srv, t)
        finally:
            set_trace_hooks(None, None)
        assert len(seen) == 3
        reads = [t_read for t_read, _, _ in seen]
        assert reads == sorted(reads) and len(set(reads)) == 3
        for t_read, t_in, tr in seen:
            assert t0 < t_read <= t_in and tr is None
        if hooks:
            assert [r.path for r in sent] == ["/echo", "/echo"]
            assert all(r.t_done >= r.t_read for r in sent)
        else:
            assert sent == []


# -- sharded reactors ---------------------------------------------------------

def _run_sharded(n=3):
    srv = ShardedWire(("127.0.0.1", 0), _echo, reactors=n, workers=2)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, t


def _settled(srv, responses, timeout=5.0):
    """The wire's snapshot once it counts `responses`: a reply's last
    byte reaches the client before its reactor counts it as sent
    (`_mark_sent` follows the send), so a count read right after the
    client's read can be one short."""
    deadline = time.monotonic() + timeout
    while True:
        snap = srv.stats_snapshot()
        if snap["responses"] >= responses or time.monotonic() > deadline:
            return snap
        time.sleep(0.005)


class TestShardedWire:
    def test_reuse_port_shards_keepalive_connections(self):
        if not hasattr(socket, "SO_REUSEPORT"):
            pytest.skip("SO_REUSEPORT unavailable on this platform")
        srv, t = _run_sharded(3)
        try:
            assert srv.reuse_port is True
            assert all(r._listener is not None for r in srv.reactors)
            for i in range(12):
                with _connect(srv) as s, s.makefile("rb") as f:
                    for j in range(2):    # keep-alive reuse per conn
                        s.sendall(_req(body=b"c%d-%d" % (i, j)))
                        status, body, _ = _read_response(f)
                        assert status == 200
                        assert body == b"POST /echo c%d-%d" % (i, j)
            snap = _settled(srv, 24)
            assert snap["reactor"] == -1      # the aggregate row
            assert snap["requests"] == 24 and snap["responses"] == 24
            assert snap["accepted"] == 12
            per = snap["reactors"]
            assert [p["reactor"] for p in per] == [0, 1, 2]
            assert sum(p["accepted"] for p in per) == 12
        finally:
            _stop_wire(srv, t)

    def test_fallback_round_robin_spreads_accepts(self, monkeypatch):
        monkeypatch.delattr(socket, "SO_REUSEPORT", raising=False)
        srv, t = _run_sharded(3)
        try:
            assert srv.reuse_port is False
            assert srv.reactors[0]._listener is not None
            assert all(r._listener is None for r in srv.reactors[1:])
            for i in range(12):
                with _connect(srv) as s, s.makefile("rb") as f:
                    s.sendall(_req(body=b"f%d" % i))
                    status, body, _ = _read_response(f)
                    assert status == 200
                    assert body == b"POST /echo f%d" % i
            snap = _settled(srv, 12)
            assert snap["responses"] == 12
            # the deal is strict round-robin, so sequential connects
            # land a third on every reactor
            assert [p["accepted"] for p in snap["reactors"]] == [4, 4, 4]
        finally:
            _stop_wire(srv, t)

    def test_sharded_pipelining_in_order(self):
        srv, t = _run_sharded(2)
        try:
            n = 8
            with _connect(srv) as s, s.makefile("rb") as f:
                s.sendall(b"".join(_req(body=b"s%d" % i)
                                   for i in range(n)))
                for i in range(n):
                    status, body, _ = _read_response(f)
                    assert status == 200
                    assert body == b"POST /echo s%d" % i
        finally:
            _stop_wire(srv, t)

    def test_shutdown_drains_every_reactor(self, monkeypatch):
        """One in-flight /slow request per reactor (the fd-handoff deal
        is deterministic, so three sequential connects land on reactors
        1, 2, 0); shutdown() must deliver all three responses — no
        reactor may strand its connection."""
        monkeypatch.delattr(socket, "SO_REUSEPORT", raising=False)
        srv, t = _run_sharded(3)
        socks, files = [], []
        try:
            for i in range(3):
                s = _connect(srv)
                socks.append(s)
                files.append(s.makefile("rb"))
            for i, s in enumerate(socks):
                s.sendall(_req(path="/slow", body=b"d%d" % i))
            time.sleep(0.2)      # every reactor has pumped its request
            srv.shutdown()
            for i, f in enumerate(files):
                status, body, _ = _read_response(f)
                assert status == 200 and body == b"POST /slow d%d" % i
        finally:
            for f in files:
                f.close()
            for s in socks:
                s.close()
            srv.server_close()
            t.join(timeout=5)


# -- binary query framing -----------------------------------------------------

class TestBinaryCodec:
    def test_round_trip_boundary_shapes(self):
        for user, num in [
            ("", 0), ("u", 1),
            ("a" * 31, 127),          # fixstr / positive-fixint edges
            ("a" * 32, 128),          # str8 / uint16 edges
            ("a" * 255, 0xffff),
            ("a" * 256, 0x10000),     # str16 / int32 edges
            ("ünïcødé漢", -1), ("u", -32), ("u", -33),
            ("u", 999_999_999), ("u", -999_999_999),
        ]:
            frame = encode_bin_query(user, num)
            assert decode_bin_query(frame) == (user, num), (user, num)

    def test_encode_rejects_out_of_range(self):
        with pytest.raises(ValueError):
            encode_bin_query("u", 1_000_000_000)
        with pytest.raises(ValueError):
            encode_bin_query("u", -1_000_000_000)
        with pytest.raises(ValueError):
            encode_bin_query("x" * 70000, 1)

    def test_decode_rejects_malformed(self):
        good = encode_bin_query("abc", 12)
        assert decode_bin_query(good) == ("abc", 12)
        assert decode_bin_query(good + b"\x00") is None     # trailing
        assert decode_bin_query(good[:-1]) is None          # truncated
        assert decode_bin_query(b"") is None
        assert decode_bin_query(b'{"user": "u", "num": 1}') is None
        # keys out of order are not the canonical frame
        assert decode_bin_query(b"\x82\xa3num\x01\xa4user\xa1u") is None
        # invalid UTF-8 in the user id
        bad = bytearray(encode_bin_query("ab", 1))
        bad[7] = 0xff
        assert decode_bin_query(bytes(bad)) is None
        # int32-coded num over the JSON-parity cap
        over = (b"\x82\xa4user\xa1u\xa3num\xd2"
                + (1_000_000_000).to_bytes(4, "big", signed=True))
        assert decode_bin_query(over) is None

    def test_fuzz_accept_containment(self):
        """Every frame the binary decoder accepts must read the same
        (user, num) the JSON route would serve for the equivalent body
        — binary-accept is a strict subset of JSON-route-accept."""
        rng = random.Random(0xB1AB1A)
        checked = 0
        for _ in range(3000):
            roll = rng.random()
            if roll < 0.4:
                user = "".join(chr(rng.randrange(32, 0x2fff))
                               for _ in range(rng.randrange(0, 40)))
                num = rng.choice(
                    [0, 1, -1, 127, 128, -32, -33,
                     rng.randrange(-999_999_999, 10**9)])
                frame = encode_bin_query(user, num)
            elif roll < 0.8:
                # mutate a canonical frame: flip/insert/delete one byte
                frame = bytearray(encode_bin_query("abc", 12))
                op = rng.randrange(3)
                pos = rng.randrange(len(frame))
                if op == 0:
                    frame[pos] = rng.randrange(256)
                elif op == 1:
                    frame.insert(pos, rng.randrange(256))
                else:
                    del frame[pos]
                frame = bytes(frame)
            else:
                frame = bytes(rng.randrange(256)
                              for _ in range(rng.randrange(0, 24)))
            got = decode_bin_query(frame)
            if got is None:
                continue
            checked += 1
            user, num = got
            body = json.dumps({"user": user, "num": num}).encode("utf-8")
            assert _parse_generic(body) == got, frame
        assert checked > 500      # the fuzz actually hit the codec
