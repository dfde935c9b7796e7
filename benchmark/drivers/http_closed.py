"""Driver `http_closed` (and, through `run_serve`, `http_open`): a real
`PredictionServer` in this process over seeded factors, and the traffic
generator as a child process
that never opens a JAX backend.

Set-up: factors and id maps from the seed behind a COMPLETED engine
instance; the server loads it (`prepare_deploy` -> `warm_deploy` ->
`serve_plan`); the child warms every bucket. Window: the child drives
the cell's traffic for --seconds; with --trace 1 a few seconds of it are
traced. After the close: a seeded sample of the replies the window
returned is compared with the plain reference.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List

import numpy as np

import datagen
import harness
import reference


def _store_instance(registry, params) -> str:
    """The COMPLETED engine-instance row `CoreWorkflow.run_train` leaves,
    with an empty model blob: see `run_serve` on why the blob round trip
    is not taken."""
    from predictionio_tpu.data.event import utcnow
    from predictionio_tpu.data.storage.base import (
        EngineInstance, EngineInstanceStatus, Model,
    )

    def named(name_params):
        name, p = name_params
        return json.dumps({"name": name, "params": dataclasses.asdict(p)})

    instances = registry.get_meta_data_engine_instances()
    row = EngineInstance(
        id="", status=EngineInstanceStatus.COMPLETED, start_time=utcnow(),
        end_time=utcnow(), engine_id="default", engine_version="default",
        engine_variant="default", engine_factory="",
        data_source_params=named(params.data_source_params),
        preparator_params=named(params.preparator_params),
        algorithms_params=json.dumps([
            {"name": n, "params": dataclasses.asdict(p)}
            for n, p in params.algorithm_params_list]),
        serving_params=named(params.serving_params))
    row = row.with_(id=instances.insert(row))
    instances.update(row)
    registry.get_model_data_models().insert(Model(row.id, b""))
    return row.id


def _read_tagged(child: subprocess.Popen, tag: str) -> Dict[str, Any]:
    line = child.stdout.readline()
    if not line.startswith(tag + " "):
        raise RuntimeError(f"traffic generator said {line[:200]!r} "
                           f"(exit {child.poll()}), expected {tag}")
    return json.loads(line[len(tag) + 1:])


def _sample(records: List[Dict], n: int, seed: int) -> List[Dict]:
    """A seeded sample of the well-formed replies, the one with the
    longest ban list in it."""
    good = [r for r in records if r["status"] == 200 and r["ids"] is not None]
    if not good:
        return []
    rng = np.random.default_rng([int(seed), 17])
    pick = set(rng.choice(len(good), size=min(n, len(good)),
                          replace=False).tolist())
    pick.add(max(range(len(good)),
                 key=lambda j: len(good[j]["banned"] or ())))
    return [good[j] for j in sorted(pick)]


def run_serve(rc: harness.RunContext, loop: str) -> Dict[str, Any]:
    from predictionio_tpu.core import EngineParams
    from predictionio_tpu.ingest.bimap import BiMap
    from predictionio_tpu.models import recommendation as rec
    from predictionio_tpu.obs import compile_count, install_compile_probe
    from predictionio_tpu.ops import als
    from predictionio_tpu.serving import PredictionServer, ServerConfig

    cfg, a, traffic = rc.config, rc.config["assumed"], rc.cell["traffic"]
    if traffic["loop"] != loop:
        raise ValueError(f"driver for a {loop} loop, traffic says "
                         f"{traffic['loop']}")
    n_users, n_items, rank, k = (int(cfg["n_users"]), int(cfg["n_items"]),
                                 int(cfg["rank"]), int(a["k"]))
    if (traffic["n_users"], traffic["n_items"]) != (n_users, n_items):
        raise ValueError("the traffic's populations are not the "
                         "configuration's")
    t = time.perf_counter()
    model = als.ALSModel(
        datagen.factors_host(n_users, rank, rc.seed, 0),
        datagen.factors_host(n_items, rank, rc.seed, 1),
        BiMap({f"u{n}": n for n in range(n_users)}),
        BiMap({f"i{n}": n for n in range(n_items)}))
    rc.note("model_make_s", round(time.perf_counter() - t, 3))
    t = time.perf_counter()
    registry = harness.mem_registry()
    engine = rec.engine()
    params = EngineParams(
        data_source_params=("", rec.DataSourceParams(app_name="benchapp")),
        algorithm_params_list=(("als", rec.ALSAlgorithmParams(rank=rank)),))
    _store_instance(registry, params)
    # The server loads the model through prepare_deploy -> warm_deploy ->
    # serve_plan as any deploy does, except that the pickled blob is not
    # written and read back: at 24 million string ids that round trip
    # alone takes minutes (PERF.md, Open questions), more than a run may
    # last. The read is handed the model, as the train cell's data source
    # is handed its columns.
    from predictionio_tpu.core import workflow
    install_compile_probe()
    original = workflow.deserialize_models
    workflow.deserialize_models = lambda *a_, **k_: [model]
    try:
        server = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0,
                         batch_window_ms=int(a["batch_window_ms"]),
                         batch_max=int(a["batch_max"])),
            registry=registry, engine=engine)
    finally:
        workflow.deserialize_models = original
    del model
    port = server.start()
    rc.note("server_load_s", round(time.perf_counter() - t, 3))
    plan = server._dep.algos[0]._serve_plan
    rc.note("serve_plan", {"class": type(plan).__name__,
                           "bucket_kernels": plan.bucket_kernels()})

    env = {k_: v for k_, v in os.environ.items()
           if not k_.startswith(("JAX_", "XLA_", "TPU_"))}
    child = subprocess.Popen(
        [sys.executable, str(harness.BENCH_DIR / "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    try:
        child.stdin.write(json.dumps({
            "traffic": traffic, "seed": rc.seed, "port": port,
            "seconds": rc.seconds,
            "warm_bursts": rc.cell["warm_bursts"]}) + "\n")
        child.stdin.flush()
        warm = _read_tagged(child, "WARM")
        rc.note("warmup", warm)
        if warm["failed"]:
            raise RuntimeError(f"warm-up requests failed: {warm}")

        before = harness.registry_snapshot(server.metrics)
        compiles0 = compile_count()
        t0 = time.perf_counter()
        setup_s = t0 - rc.t_start
        child.stdin.write("GO\n")
        child.stdin.flush()
        traced: Dict[str, Any] = {"reduced": None}
        if rc.trace:
            time.sleep(float(rc.cell["trace"]["after_s"]))
            with harness.profiler_window() as traced:
                time.sleep(min(float(rc.cell["trace"]["seconds"]),
                               max(rc.seconds - 1.5, 0.5)))
        done = _read_tagged(child, "DONE")
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    after = harness.registry_snapshot(server.metrics)
    rc.note("compiles_in_window", compile_count() - compiles0)
    peak = harness.device_memory_peak()
    rc.note("memory_peak_bytes", peak)
    rc.note("generator", {x: done[x] for x in (
        "attempted", "failed", "ok_in_window", "seconds", "cpu_share",
        "connections", "late_ms_p99", "p50_ms", "p95_ms")})
    hist = harness.snapshot_delta(before, after)
    rc.note("dispatch_paths", {n: v["value"] for n, v in hist.items()
                               if n.startswith("pio_topk_dispatch_total")})
    server.shutdown()
    del server, plan, registry, engine
    harness.free_device()
    rc.note("peak_rss_bytes", harness.peak_rss_bytes())

    t = time.perf_counter()
    sample = _sample(done["records"], int(rc.cell["correct"]["sample"]),
                     rc.seed)
    numbers = {"rank_gap": float("inf"), "score_err": float("inf"),
               "banned_served": 0.0, "short_replies": 0.0}
    if sample:
        users = datagen.factors_host(n_users, rank, rc.seed, 0)
        vecs = users[[r["user"] for r in sample]]
        del users
        served = np.full((len(sample), k), -1, np.int64)
        for q, r in enumerate(sample):
            served[q, :min(len(r["ids"]), k)] = r["ids"][:k]
        ref = reference.topk_reference(
            datagen.factor_blocks(n_items, rank, rc.seed, 1), vecs, served,
            k_ref=k + int(traffic["banned_max"]) + 6)
        numbers = reference.compare_replies(
            [{"ids": r["ids"], "scores": r["scores"],
              "banned": r["banned"] or []} for r in sample],
            ref["top_s"], ref["top_i"], ref["served_ref"], k)
    rc.note("reference_s", round(time.perf_counter() - t, 3))
    rc.note("replies_compared", len(sample))
    correct, compared = reference.verdict(numbers,
                                          rc.cell["correct"]["limits"])
    # a reply that never came, or came malformed, is for `correct` too
    correct = correct and done["failed"] == 0 and bool(sample)

    def ms(x: float) -> float:
        return x if np.isfinite(x) else harness.SLOWER_THAN_ANY_LIMIT_MS

    return {
        "correct": correct, "compared": compared,
        "attempted": done["attempted"], "failed": done["failed"],
        "memory_peak_bytes": peak,
        "end_to_end": {"serve_qps": done["ok_in_window"] / done["seconds"],
                       "serve_p50_ms": ms(done["p50_ms"]),
                       "serve_p95_ms": ms(done["p95_ms"]),
                       "setup_s": setup_s},
        "facts": {"hist": hist, "trace": traced["reduced"],
                  "completed": done["ok_in_window"],
                  "window_s": done["seconds"],
                  "client_p95_ms": done["p95_ms"],
                  "gen_late_ms_p99": done["late_ms_p99"]},
    }


def run(rc: harness.RunContext) -> Dict[str, Any]:
    return run_serve(rc, "closed")
