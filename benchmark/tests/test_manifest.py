import copy
import importlib

import pytest

import harness
from manifest import Manifest, ManifestError


@pytest.mark.parametrize("queued", [False, True])
def test_real_manifest_keeps_the_rules(queued):
    m = Manifest.load(queued=queued)
    m.check()
    for cell in m.cells:
        names = {x["name"] for x in m.end_to_end(cell)}
        assert "setup_s" in names and len(names) >= 2
        for pl in m.per_layer(cell):
            assert pl["moves"] in names, (cell, pl["name"])


def test_the_rule_that_refused_pr22():
    doc = copy.deepcopy(Manifest.load(queued=True).doc)
    for pl in doc["per_layer"]:
        if pl["name"] == "serve_server_ms_p50":
            pl["workloads"] = ["ml25m-train"]
    with pytest.raises(ManifestError, match="serve_server_ms_p50 is reported "
                       "on workload ml25m-train, where serve_p50_ms"):
        Manifest(doc).check()


@pytest.mark.parametrize("key,value", [("unit", "tokens per second"),
                                       ("unit", "µs"), ("name", "a/b"),
                                       ("better", "faster"),
                                       ("source", "stopwatch")])
def test_bad_fields_are_refused(key, value):
    doc = copy.deepcopy(Manifest.load().doc)
    doc["per_layer"][0][key] = value
    with pytest.raises(ManifestError):
        Manifest(doc).check()


def test_every_metric_file_names_a_reader():
    m = Manifest.load(queued=True)
    for pl in m.doc["per_layer"]:
        spec = harness.load_json(
            harness.BENCH_DIR / "layer_metrics" / f"{pl['name']}.json")
        mod, _, fn = spec["reader"].rpartition(".")
        assert callable(getattr(importlib.import_module(mod), fn))


def test_a_reader_with_nothing_to_read_returns_nothing():
    import readers
    facts = {"config": {}, "hist": {}, "trace": None}
    assert readers.phase_mean_ms(facts, key="pack_s") is None
    assert readers.module_device_ms(facts, match="x") is None
    assert readers.device_idle(facts) is None
    assert readers.hist_mean(facts, name="h") is None
    assert readers.serve_mfu(facts) is None and readers.train_mfu(facts) is None
    assert readers.fact(facts, key="client_p95_ms") is None
    assert readers.fact({"client_p95_ms": float("inf")},
                        key="client_p95_ms") is None
    assert readers.fact({"client_p95_ms": 2.5}, key="client_p95_ms") == 2.5


def test_a_why_over_200_characters_is_refused():
    doc = copy.deepcopy(Manifest.load().doc)
    doc["workloads"][0]["why"] = "x" * 201
    with pytest.raises(ManifestError, match="201 characters"):
        Manifest(doc).check()
