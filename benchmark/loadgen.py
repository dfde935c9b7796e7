"""The one traffic generator: a seeded request stream (Zipf users, query
shapes, ban lists), an open-loop arrival schedule, and a sender that
drives `POST /queries.json` from ONE thread over kept-alive connections.

A traffic mix is a data file (`workloads/<cell>.json`, key `traffic`);
this module reads its parameters and needs no edit for a new mix:

  loop          "closed": `callers` connections, each sending its next
                request when the last reply is in.
                "open": arrivals on a schedule whatever the replies do,
                Poisson at `rate_qps` (the same count for every seed),
                optionally with `burst_every_s`, `burst_len_s`,
                `burst_rate_qps`; latency is timed from
                the instant a request was DUE, and how late the sender
                ran is reported.
  n_users, user_zipf_s     users are Zipf ranks, "u<rank>"
  n_items, item_zipf_s     filler ban ids are Zipf ranks, "i<rank>"
  num                      items asked for
  mix                      {"plain": w, "banned": w}
  banned_min, banned_max   ban-list length, uniform
  ban_seen      true: a "banned" request first bans what the same user
                was last served (the template's use of blackList: do not
                show it again), then fills with seeded ids. Which
                requests find a previous reply depends on arrival
                order; every other field is a function of the seed.

Copied from `predictionio_tpu/tools/loadsim.py` (ZipfRanks, the query
shapes; its Poisson arrivals by thinning became arrivals of a fixed
count, see `arrival_times`) so that the program cannot change the
yardstick. Run as a child process (`python loadgen.py`, spec on
stdin) it never imports JAX and so never touches the chip.
"""

from __future__ import annotations

import json
import math
import selectors
import socket
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

_HEAD_CAP = 1 << 21
_BLOCK = 4096


class ZipfRanks:
    """Inverse-CDF Zipf(s) sampler over ranks [0, n): an exact pmf table
    for the head (up to 2^21 ranks), the tail mass by its integral with
    tail draws uniform."""

    def __init__(self, n: int, s: float):
        if n < 1:
            raise ValueError("population must be >= 1")
        self.n, self.s = int(n), float(s)
        head = min(self.n, _HEAD_CAP)
        w = 1.0 / np.arange(1, head + 1, dtype=np.float64) ** s
        tail = 0.0
        if self.n > head:
            tail = (math.log(self.n / head) if abs(s - 1.0) < 1e-9 else
                    (self.n ** (1.0 - s) - head ** (1.0 - s)) / (1.0 - s))
        self._head = head
        self._cdf = np.cumsum(w) / (float(w.sum()) + max(tail, 0.0))

    def sample(self, rng: np.random.Generator, size: int) -> np.ndarray:
        ix = np.searchsorted(self._cdf, rng.random(size), side="right")
        if self._head < self.n:
            in_tail = ix >= self._head
            ix[in_tail] = rng.integers(self._head, self.n,
                                       size=int(in_tail.sum()))
        else:
            np.clip(ix, 0, self.n - 1, out=ix)
        return ix.astype(np.int64)


class RequestStream:
    """Request i of a mix, a pure function of (traffic, seed, i):
    (user rank, seeded ban ids or None for a plain request)."""

    def __init__(self, traffic: Dict, seed: int):
        self.t, self.seed = traffic, int(seed)
        self._users = ZipfRanks(traffic["n_users"], traffic["user_zipf_s"])
        self._items = ZipfRanks(traffic["n_items"], traffic["item_zipf_s"])
        self._p_banned = (traffic["mix"].get("banned", 0.0)
                          / sum(traffic["mix"].values()))
        self._blocks: Dict[int, Tuple] = {}

    def _block(self, b: int):
        if b not in self._blocks:
            rng = np.random.default_rng([self.seed, 11, b])
            users = self._users.sample(rng, _BLOCK)
            banned = rng.random(_BLOCK) < self._p_banned
            n_ban = rng.integers(self.t["banned_min"],
                                 self.t["banned_max"] + 1, _BLOCK)
            fill = self._items.sample(
                rng, _BLOCK * self.t["banned_max"]).reshape(_BLOCK, -1)
            self._blocks[b] = (users, banned, n_ban, fill)
        return self._blocks[b]

    def get(self, i: int) -> Tuple[int, Optional[List[int]]]:
        users, banned, n_ban, fill = self._block(i // _BLOCK)
        j = i % _BLOCK
        if not banned[j]:
            return int(users[j]), None
        return int(users[j]), [int(x) for x in fill[j, :n_ban[j]]]


def arrival_times(traffic: Dict, seed: int, seconds: float) -> np.ndarray:
    """Open-loop due instants in [0, seconds): a Poisson process at
    `rate_qps`, with `burst_rate_qps` during the first `burst_len_s` of
    every `burst_every_s`, CONDITIONED ON ITS COUNT: every seed gives the
    same number of arrivals (the expected one, rounded) at other
    instants, so no seed offers more work than another. Given its count
    a Poisson process is a sorted uniform sample of its cumulative
    intensity, which is piecewise linear here and inverted exactly."""
    base = float(traffic["rate_qps"])
    every = float(traffic.get("burst_every_s") or 0.0)
    knots, mass = [0.0], [0.0]                # cumulative intensity
    if every:
        peak, length = (float(traffic["burst_rate_qps"]),
                        float(traffic["burst_len_s"]))
        t = 0.0
        while t < seconds:
            for end, rate in ((t + length, peak), (t + every, base)):
                end = min(end, seconds)
                if end > knots[-1]:
                    mass.append(mass[-1] + rate * (end - knots[-1]))
                    knots.append(end)
            t += every
    else:
        knots.append(seconds)
        mass.append(base * seconds)
    rng = np.random.default_rng([int(seed), 13])
    u = np.sort(rng.random(int(round(mass[-1])))) * mass[-1]
    return np.minimum(np.interp(u, mass, knots),
                      np.nextafter(seconds, 0.0))


def encode(traffic: Dict, user: int, banned: Optional[List[int]]) -> bytes:
    doc: Dict = {"user": f"u{user}", "num": traffic["num"]}
    if banned is not None:
        doc["blackList"] = [f"i{b}" for b in banned]
    body = json.dumps(doc).encode()
    return (b"POST /queries.json HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body)


def parse_reply(status: int, body: bytes) -> Optional[Tuple[List[int],
                                                            List[float]]]:
    """(item ranks, scores) of a well-formed 200 reply, else None."""
    if status != 200:
        return None
    try:
        rows = json.loads(body)["itemScores"]
        return ([int(r["item"][1:]) for r in rows],
                [float(r["score"]) for r in rows])
    except (ValueError, KeyError, TypeError, IndexError):
        return None


class _Conn:
    __slots__ = ("sock", "buf", "req", "t_due", "t_sent")

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.buf = b""
        self.req = -1                      # request in flight, -1 = idle

    def reply(self) -> Optional[Tuple[int, bytes]]:
        """(status, body) once a whole response is buffered."""
        head_end = self.buf.find(b"\r\n\r\n")
        if head_end < 0:
            return None
        head = self.buf[:head_end]
        length = 0
        for line in head.split(b"\r\n")[1:]:
            k, _, v = line.partition(b":")
            if k.strip().lower() == b"content-length":
                length = int(v)
        total = head_end + 4 + length
        if len(self.buf) < total:
            return None
        body, self.buf = self.buf[head_end + 4:total], self.buf[total:]
        return int(head.split(b" ", 2)[1]), body


class Sender:
    """Drives one phase (warm-up or window) and keeps every request's
    record: index, user, bans, due and sent instants, latency, status,
    the reply."""

    def __init__(self, traffic: Dict, seed: int, port: int):
        self.traffic, self.port = traffic, port
        self.stream = RequestStream(traffic, seed)
        self.sel = selectors.DefaultSelector()
        self.idle: List[_Conn] = []
        self.n_conns = 0
        self.seen: Dict[int, List[int]] = {}
        self.records: List[Dict] = []
        self.next_req = 0

    def _conn(self) -> _Conn:
        if self.idle:
            return self.idle.pop()
        c = _Conn(self.port)
        self.n_conns += 1
        self.sel.register(c.sock, selectors.EVENT_READ, c)
        return c

    def _send(self, due: float) -> None:
        i, self.next_req = self.next_req, self.next_req + 1
        user, fill = self.stream.get(i)
        banned = fill
        if fill is not None and self.traffic.get("ban_seen"):
            banned = (self.seen.get(user, []) + fill)[:len(fill)]
        c = self._conn()
        c.req, c.t_due, c.t_sent = len(self.records), due, time.perf_counter()
        self.records.append({"i": i, "user": user, "banned": banned,
                             "due": due, "sent": c.t_sent, "status": 0,
                             "latency": None, "ids": None, "scores": None})
        c.sock.setblocking(True)
        c.sock.sendall(encode(self.traffic, user, banned))
        c.sock.setblocking(False)

    def _pump(self, timeout: float) -> int:
        """Read what has arrived; returns how many replies completed."""
        done = 0
        for key, _ in self.sel.select(max(timeout, 0.0)):
            c: _Conn = key.data
            try:
                chunk = c.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            if not chunk:                  # closed on us: a failed request
                self.sel.unregister(c.sock)
                c.sock.close()
                self.n_conns -= 1
                if c.req >= 0:
                    self.records[c.req]["status"] = -1
                    self.records[c.req]["latency"] = (
                        time.perf_counter() - c.t_due)
                    done += 1
                continue
            c.buf += chunk
            got = c.reply()
            if got is None or c.req < 0:
                continue
            now = time.perf_counter()
            rec = self.records[c.req]
            rec["status"], rec["latency"] = got[0], now - c.t_due
            rec["done"] = now
            parsed = parse_reply(*got)
            if parsed is not None:
                rec["ids"], rec["scores"] = parsed
                self.seen[rec["user"]] = parsed[0]
            c.req = -1
            self.idle.append(c)
            done += 1
        return done

    def in_flight(self) -> int:
        return self.n_conns - len(self.idle)

    def burst(self, n: int) -> None:
        """Warm-up: n requests at once, then wait for all of them."""
        t = time.perf_counter()
        for _ in range(n):
            self._send(t)
        self.drain(120.0)

    def drain(self, timeout_s: float) -> None:
        end = time.perf_counter() + timeout_s
        while self.in_flight() and time.perf_counter() < end:
            self._pump(0.05)

    def closed_loop(self, callers: int, seconds: float) -> Tuple[float, float]:
        t0 = time.perf_counter()
        t_end = t0 + seconds
        for _ in range(callers):
            self._send(time.perf_counter())
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            for _ in range(self._pump(min(t_end - now, 0.05))):
                now = time.perf_counter()
                if now < t_end:
                    self._send(now)
        return t0, t_end

    def open_loop(self, seconds: float, seed: int) -> Tuple[float, float]:
        due = arrival_times(self.traffic, seed, seconds)
        t0 = time.perf_counter()
        k = 0
        while k < len(due):
            now = time.perf_counter()
            while k < len(due) and t0 + due[k] <= now:
                self._send(t0 + due[k])
                k += 1
            if k < len(due):
                self._pump(min(t0 + due[k] - time.perf_counter(), 0.05))
        return t0, t0 + seconds

    def close(self) -> None:
        for key in list(self.sel.get_map().values()):
            key.fileobj.close()
        self.sel.close()


def summarise(records: List[Dict], t0: float, t_end: float, num: int
              ) -> Dict:
    """The window's client-side counts and latencies. A request that
    failed, was refused, came back malformed or never came back counts
    as attempted and failed, and as slower than any limit (+inf) in the
    percentiles."""
    lat, ok_in_window, failed = [], 0, 0
    for r in records:
        good = (r["status"] == 200 and r["ids"] is not None
                and len(r["ids"]) <= num)
        if not good:
            failed += 1
            lat.append(math.inf)
            continue
        lat.append(r["latency"])
        if r["done"] <= t_end:
            ok_in_window += 1
    lat_a = np.sort(np.asarray(lat)) if lat else np.asarray([math.inf])

    def pct(q: float) -> float:            # nearest-rank: inf stays inf
        return float(lat_a[min(len(lat_a) - 1,
                               int(math.ceil(q * len(lat_a))) - 1)])

    late = np.asarray([r["sent"] - r["due"] for r in records] or [0.0])
    return {"attempted": len(records), "failed": failed,
            "ok_in_window": ok_in_window, "seconds": t_end - t0,
            "p50_ms": pct(0.50) * 1e3, "p95_ms": pct(0.95) * 1e3,
            "late_ms_p99": float(np.percentile(late, 99)) * 1e3}


def main() -> int:
    """Child-process entry. stdin: one JSON line {traffic, seed, port,
    seconds, warm_bursts}; then `GO` once the parent has opened the
    window. stdout: `WARM <json>` after the warm-up, `DONE <json>` with
    the window's summary and every record."""
    spec = json.loads(sys.stdin.readline())
    traffic, seed = spec["traffic"], spec["seed"]
    # warm-up draws from its own stream, so the window's request i is the
    # same whatever the warm-up sent
    warm = Sender(traffic, seed + 1, spec["port"])
    t = time.perf_counter()
    for n in spec["warm_bursts"]:
        warm.burst(n)
    bad = [r["status"] for r in warm.records if r["ids"] is None]
    warm.close()
    print("WARM " + json.dumps({"requests": len(warm.records),
                                "failed": len(bad), "statuses": bad[:5],
                                "seconds": time.perf_counter() - t}),
          flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    s = Sender(traffic, seed, spec["port"])
    cpu0 = time.process_time()
    if traffic["loop"] == "closed":
        t0, t_end = s.closed_loop(int(traffic["callers"]), spec["seconds"])
    else:
        t0, t_end = s.open_loop(spec["seconds"], seed)
    s.drain(60.0)                  # late is late, not wrong: wait a minute
    out = summarise(s.records, t0, t_end, traffic["num"])
    out["cpu_share"] = (time.process_time() - cpu0) / (
        time.perf_counter() - t0)
    out["connections"] = s.n_conns
    out["records"] = [{k: r[k] for k in ("i", "user", "banned", "ids",
                                         "scores", "status")}
                      for r in s.records]
    s.close()
    print("DONE " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
