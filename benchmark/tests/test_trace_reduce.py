"""trace_reduce on hand-made intervals and on a small trace recorded on a
TPU v5e (`small.xplane.pb`: inside `bench:traced`, three times [one run
of a jitted matmul, then 20 ms asleep inside `bench:sleep`], then one
run of a second program). In this recording the device's clock leads the
host's by about a millisecond, so the first run starts before the host's
`bench:traced` span and is, rightly, not a run of the window."""

from pathlib import Path

import pytest

import trace_reduce as tr

TRACE = Path(__file__).resolve().parent / "small.xplane.pb"


def test_union_merges_overlaps():
    assert tr.union_seconds([]) == 0.0
    ivs = [(0, 10e9), (5e9, 12e9), (20e9, 21e9), (20.5e9, 20.6e9)]
    assert tr.union_seconds(ivs) == pytest.approx(13.0)


def test_gaps_are_the_complement_longest_first():
    ivs = [(2, 4), (3, 5), (9, 10)]
    assert tr.idle_gaps(ivs, 0, 12) == [(5, 9), (0, 2), (10, 12)]
    assert tr.idle_gaps([], 0, 3) == [(0, 3)]
    assert tr.idle_gaps([(0, 5)], 1, 4) == []


def test_names_are_shortened():
    assert tr._strip("jit_fn(1234567890)") == "jit_fn"
    assert tr._op_name("%fusion.16 = (u32[1]{0:T(128)}) fusion(%p)") == "fusion.16"


@pytest.fixture(scope="module")
def reduced():
    import jax
    return tr.reduce_profile(jax.profiler.ProfileData.from_file(str(TRACE)))


def test_recorded_trace_busy_union(reduced):
    assert len(reduced.busy_s) == 1                       # one chip
    assert 0.0 < reduced.busy_s[0] < reduced.window_s
    # three sleeps of 20 ms lie inside the window
    assert reduced.window_s > 0.06
    # the device is busy for the programs' runs and nothing else
    runs = sum(sum(v) for v in reduced.modules.values())
    assert reduced.busy_s[0] == pytest.approx(runs, rel=0.05)


def test_recorded_trace_time_by_name(reduced):
    runs = reduced.module_seconds(r"^jit__lambda")
    assert len(runs) == 3          # see the module docstring
    assert sorted(round(r * 1e6) for r in runs) == [24, 90, 90]
    assert reduced.module_seconds("no_such_program") == []
    top = reduced.top_ops(3)
    assert top and top[0][1] >= top[-1][1] > 0
    assert sum(reduced.ops.values()) == pytest.approx(reduced.busy_s[0],
                                                      rel=0.05)


def test_recorded_trace_gaps_name_what_the_host_did(reduced):
    longest = reduced.gaps[:3]
    assert [name for name, _ in longest] == ["bench:sleep"] * 3
    assert all(0.015 < s < 0.05 for _, s in longest)
    idle = reduced.window_s - reduced.busy_s[0]
    assert sum(s for _, s in reduced.gaps) <= idle * 1.0001
