"""The host process's per-layer metrics (PR 38): what each reads from a
window's registry delta (the driver's `facts["hist"]`), and that a
program without the families (the parent's) prints none of those."""

import pytest

import host_readers
import run as bench_run
from manifest import Manifest

NEW = ("batch_host_cpu_ms_mean", "batch_host_offcpu_ms_mean",
       "gc_pause_pct.serve", "obs_tick_pct.serve")


def _h(count, total):
    return {"bounds": [0.1], "buckets": [count, 0], "count": count,
            "sum": total}


def _facts():
    """40 cycles of a 30 s window: 2.0 s wall in host, 0.2 of it the
    window; 0.9 s CPU in host, 0.02 of it in the window; three
    collections and three observers' ticks."""
    return {"window_s": 30.0, "hist": {
        "pio_serve_stage_seconds{stage=host}": _h(40, 2.0),
        "pio_serve_stage_seconds{stage=window}": _h(40, 0.2),
        "pio_serve_stage_cpu_seconds{stage=host}": _h(40, 0.9),
        "pio_serve_stage_cpu_seconds{stage=window}": _h(40, 0.02),
        "pio_gc_pause_seconds{generation=0}": _h(100, 0.05),
        "pio_gc_pause_seconds{generation=2}": _h(2, 0.25),
        "pio_obs_tick_seconds{loop=sampler}": _h(570, 0.6),
        "pio_obs_tick_seconds{loop=scraper}": _h(6, 0.03),
        "pio_obs_tick_seconds{loop=watchdog}": _h(30, 0.003),
    }}


def test_offcpu_is_host_less_window_less_their_cpu_a_cycle():
    got = host_readers.offcpu_mean(
        _facts(), "pio_serve_stage_seconds", "pio_serve_stage_cpu_seconds")
    assert got == pytest.approx(1000.0 * (2.0 - 0.2 - 0.9 + 0.02) / 40)
    facts = _facts()
    del facts["hist"]["pio_serve_stage_cpu_seconds{stage=window}"]
    assert host_readers.offcpu_mean(
        facts, "pio_serve_stage_seconds",
        "pio_serve_stage_cpu_seconds") is None


def test_window_share_sums_every_series_of_the_family():
    assert host_readers.window_share(
        _facts(), "pio_gc_pause_seconds") == pytest.approx(100 * 0.3 / 30)
    assert host_readers.window_share(
        _facts(), "pio_obs_tick_seconds") == pytest.approx(
            100 * 0.633 / 30)
    assert host_readers.window_share(
        {"window_s": 30.0, "hist": {}}, "pio_obs_tick_seconds") is None
    assert host_readers.window_share(
        {"hist": _facts()["hist"]}, "pio_gc_pause_seconds") is None


@pytest.mark.parametrize("cell", [
    "amazon23q-serve-c128", "amazon23q-serve-open-80qps",
    "mimo25-hist-c32", "lfm2-hist-c32", "nemotron-tt-hist-c32"])
def test_every_cell_prints_the_four_and_the_parent_only_gc(cell):
    m = Manifest.load()
    entries = {x["name"]: x for x in m.per_layer(cell)}
    assert set(NEW) <= set(entries)
    assert all(entries[n]["moves"] == "serve_qps"
               and entries[n]["better"] == "lower" for n in NEW)
    got = bench_run.layer_metrics(m, cell, _facts())
    assert set(NEW) <= set(got)
    assert got["batch_host_cpu_ms_mean"]["value"] == pytest.approx(22.5)
    # the parent has the GC family and neither the CPU nor the ticks
    parent = _facts()
    parent["hist"] = {k: v for k, v in parent["hist"].items()
                      if k.startswith(("pio_gc", "pio_serve_stage_seconds"))}
    got = bench_run.layer_metrics(m, cell, parent)
    assert "gc_pause_pct.serve" in got
    assert not {"batch_host_cpu_ms_mean", "batch_host_offcpu_ms_mean",
                "obs_tick_pct.serve"} & set(got)
