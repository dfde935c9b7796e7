import math

import numpy as np

import harness
import loadgen

TRAFFIC = harness.load_json(
    harness.BENCH_DIR / "workloads" / "amazon23-serve-c128.json")["traffic"]


def _first(seed, n=6000):
    s = loadgen.RequestStream(TRAFFIC, seed)
    return [s.get(i) for i in range(n)]


def test_one_seed_builds_the_same_requests_twice():
    a, b = _first(2147483999), _first(2147483999)
    assert a == b
    assert [loadgen.encode(TRAFFIC, *r) for r in a[:200]] == \
           [loadgen.encode(TRAFFIC, *r) for r in b[:200]]
    assert _first(5, 300) != a[:300]


def test_the_mix_is_the_files():
    reqs = _first(7)
    banned = [b for _, b in reqs if b is not None]
    assert abs(len(banned) / len(reqs) - 1 / 3) < 0.03
    assert min(map(len, banned)) >= 1 and max(map(len, banned)) <= 32
    users = np.array([u for u, _ in reqs])
    assert users.max() < TRAFFIC["n_users"]
    assert (users == 0).mean() > 0.05          # Zipf 1.1: the head is hot


def test_arrivals_are_seeded_and_every_seed_offers_the_same_count():
    t = dict(TRAFFIC, loop="open", rate_qps=200.0)
    a, b = loadgen.arrival_times(t, 9, 20.0), loadgen.arrival_times(t, 9, 20.0)
    assert np.array_equal(a, b) and np.all(np.diff(a) >= 0) and a[-1] < 20.0
    other = loadgen.arrival_times(t, 2147484001, 20.0)
    assert len(a) == len(other) == 4000 and not np.array_equal(a, other)
    # exponential gaps: as many under the mean gap as 1 - 1/e
    assert abs((np.diff(a) < 1 / 200.0).mean() - (1 - math.exp(-1))) < 0.03
    burst = dict(t, burst_every_s=2.0, burst_len_s=0.5, burst_rate_qps=600.0)
    c = loadgen.arrival_times(burst, 9, 20.0)
    assert len(c) == 600 * 5 + 200 * 15
    in_burst = (c % 2.0) < 0.5
    assert abs(in_burst.sum() - 600 * 5) < 4 * math.sqrt(3000)
    assert len(loadgen.arrival_times(burst, 9, 3.2)) == round(
        600 * 0.5 * 2 + 200 * 1.5 + 200 * 0.7)


def test_a_failed_request_is_slower_than_any_limit():
    ok = {"status": 200, "ids": [1], "latency": 0.01, "done": 0.5,
          "sent": 0.0, "due": 0.0}
    bad = dict(ok, status=503, ids=None)
    s = loadgen.summarise([ok] * 9 + [bad], 0.0, 1.0, 10)
    assert (s["attempted"], s["failed"], s["ok_in_window"]) == (10, 1, 9)
    assert s["p50_ms"] == 10.0 and math.isinf(s["p95_ms"])
    late = dict(ok, done=1.5)            # late is late, not wrong
    s = loadgen.summarise([ok, late], 0.0, 1.0, 10)
    assert (s["failed"], s["ok_in_window"]) == (0, 1)


def test_replies_are_parsed_or_refused():
    body = b'{"itemScores":[{"item":"i12","score":0.5},{"item":"i3","score":0.25}]}'
    assert loadgen.parse_reply(200, body) == ([12, 3], [0.5, 0.25])
    assert loadgen.parse_reply(503, body) is None
    assert loadgen.parse_reply(200, b'{"message":"x"}') is None
