"""`correct` has to come out false for the control and for every fault a
cell can have. Here at a size a test run can hold (the rehearsal cells,
on the CPU); the chip readings at the cells' own sizes are in PERF.md.

The fault tests skip the harness's look for a chip and drive the rest of
a run (`run.run_cell`) with the timed path broken underneath.
"""

import time

import numpy as np
import pytest

import control
import harness
import reference
import run as bench_run
from manifest import Manifest


def _cell(name):
    cell = harness.load_json(harness.BENCH_DIR / "workloads" / f"{name}.json")
    cfg = harness.load_json(
        harness.BENCH_DIR / "configs" / f"{cell['config']}.json")
    return cell, cfg


def _run(name, seed=2147483659):
    return bench_run.run_cell(Manifest.load(queued=True), name, seed, 1.0, False,
                              rehearse_cpu=True, t_start=time.perf_counter())


# -- train ------------------------------------------------------------------

def test_train_control_and_faults_read_as_not_correct():
    cell, cfg = _cell("rehearse-train")
    limits = cell["correct"]["limits"]
    readings = control.train_readings(cfg, 11)
    # the stated precision passes, the one below it and both faults fail
    assert reference.verdict(readings["stated_bf16"], limits)[0]
    for name in ("control_fp8", "fault_unchanged", "fault_half"):
        assert not reference.verdict(readings[name], limits)[0], name
    assert readings["fault_unchanged"]["user_gap"] > 0.5


def test_train_run_is_correct():
    out = _run("rehearse-train")
    assert out["correct"] and out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_s", "setup_s"}
    assert list(out)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in out["compared"].values())


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    from predictionio_tpu.ops import als
    monkeypatch.setattr(
        als, "_run_als_sharded",
        lambda x, y, *a, **k: (x, y, np.float32(0.0)))
    assert not _run("rehearse-train")["correct"]


def test_train_with_half_of_the_ratings_left_out(monkeypatch):
    from predictionio_tpu.ingest.arrays import RatingColumns
    from predictionio_tpu.ops import als
    whole = als.als_train

    def half(pd, *a, **k):
        keep = slice(0, pd.n, 2)
        return whole(RatingColumns(pd.user_ix[keep], pd.item_ix[keep],
                                   pd.rating[keep], pd.t_millis[keep],
                                   pd.users, pd.items), *a, **k)

    monkeypatch.setattr(als, "als_train", half)
    assert not _run("rehearse-train")["correct"]


# -- serve ------------------------------------------------------------------

def test_serve_control_and_faults_read_as_not_correct():
    cell, cfg = _cell("rehearse-serve")
    limits = cell["correct"]["limits"]
    readings = control.serve_readings(cfg, cell, 13)
    assert reference.verdict(readings["reference_itself"], limits)[0]
    # the CPU backend computes every matmul precision in float32, so the
    # control proper (`high`, three passes) differs from the reference
    # only on the chip (PERF.md section 2); here the step below it does
    for name in ("control_bf16", "fault_altered", "fault_ban_ignored"):
        assert not reference.verdict(readings[name], limits)[0], name
    assert "control_high" in readings


# rehearse-serve-open reports as the open-80qps cell, whose tail is an
# end-to-end metric; rehearse-serve as the c128 cell, where it is a
# per-layer one
@pytest.mark.parametrize("workload,tail", [("rehearse-serve", False),
                                           ("rehearse-serve-open", True)])
def test_serve_run_is_correct(workload, tail):
    out = _run(workload)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 50
    assert set(out["metrics"]) == {"serve_qps", "serve_p50_ms", "setup_s"} | (
        {"serve_p95_ms"} if tail else set())
    assert out["metrics"]["serve_qps"]["value"] > 0


def test_serve_answer_altered_where_it_is_produced(monkeypatch):
    from predictionio_tpu.models import recommendation as rec
    whole = rec.ALSAlgorithm.batch_predict

    def altered(self, model, queries):
        out = []
        for i, res in whole(self, model, queries):
            items = list(res.itemScores)
            if items:
                items[0] = rec.ItemScore("i7", items[0].score)
            out.append((i, rec.PredictedResult(tuple(items))))
        return out

    monkeypatch.setattr(rec.ALSAlgorithm, "batch_predict", altered)
    out = _run("rehearse-serve")
    assert not out["correct"]
    assert out["compared"]["rank_gap"]["value"] > out["compared"]["rank_gap"]["limit"]


def test_serve_ban_list_ignored(monkeypatch):
    from predictionio_tpu.models import recommendation as rec
    whole = rec.ALSAlgorithm.batch_predict

    def unbanned(self, model, queries):
        return whole(self, model, [
            (i, rec.Query(user=q.user, num=q.num)) for i, q in queries])

    monkeypatch.setattr(rec.ALSAlgorithm, "batch_predict", unbanned)
    out = _run("rehearse-serve")
    assert not out["correct"]
    assert out["compared"]["banned_served"]["value"] > 0
