"""`seq_history_in_order_pct`: where it is listed, and what it reads from
a window's registry delta (the harness's `facts["hist"]`)."""

import run as bench_run
from manifest import Manifest

CELLS = ["mimo25-hist-c32", "lfm2-hist-c32", "nemotron-tt-hist-c32"]


def test_the_metric_reads_the_in_order_share_of_the_cells_window():
    m = Manifest.load()
    for cell in CELLS:
        entry = {x["name"]: x for x in m.per_layer(cell)}[
            "seq_history_in_order_pct"]
        assert (entry["layer"], entry["moves"], entry["workloads"]) == (
            "history read", "serve_p50_ms", CELLS)
        # 240 reads answered in order; then a window where one read in
        # four scanned, and one of a program without the histogram (the
        # parent's)
        facts = {"hist": {"pio_mem_find_in_order": {
            "count": 240, "sum": 240.0}}}
        got = bench_run.layer_metrics(m, cell, facts)
        assert got["seq_history_in_order_pct"] == {"value": 100.0,
                                                   "unit": "%"}
        facts["hist"]["pio_mem_find_in_order"]["sum"] = 180.0
        assert bench_run.layer_metrics(m, cell, facts)[
            "seq_history_in_order_pct"]["value"] == 75.0
        assert "seq_history_in_order_pct" not in bench_run.layer_metrics(
            m, cell, {"hist": {}})
    assert "seq_history_in_order_pct" not in {
        x["name"] for x in m.per_layer("amazon23q-serve-c128")}
