"""Driver `train_loop`: whole trains back to back through
`CoreWorkflow.run_train`, the path `pio-tpu train` takes, with the data
source's read handed the generated rating columns (the
`bench.py:_train_registry` pattern; ingest is bypassed, see PERF.md).

Window: a train is started while fewer than --seconds have passed; the
window closes when the train in flight completes. `train_s` is the
window over the trains it completed. With --trace 1 the first train of
the window is traced whole.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict

import numpy as np

import datagen
import harness
import reference


def run(rc: harness.RunContext) -> Dict[str, Any]:
    import jax

    from predictionio_tpu.core import CoreWorkflow, EngineParams, RuntimeContext
    from predictionio_tpu.core.persistence import loads
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.ingest.arrays import RatingColumns
    from predictionio_tpu.ingest.bimap import BiMap
    from predictionio_tpu.models import recommendation as rec
    from predictionio_tpu.obs import compile_cache_counts, compile_count

    cfg, a = rc.config, rc.config["assumed"]
    n_users, n_items = int(cfg["n_users"]), int(cfg["n_items"])
    t = time.perf_counter()
    u, i, r = datagen.ratings(cfg, rc.seed)
    rc.note("datagen_s", round(time.perf_counter() - t, 3))

    registry = harness.mem_registry()
    app_id = registry.get_meta_data_apps().insert(App(0, "benchapp"))
    registry.get_events().init(app_id)
    columns = RatingColumns(
        user_ix=u, item_ix=i, rating=r, t_millis=np.zeros(len(r), np.int64),
        users=BiMap.from_keys(f"u{n}" for n in range(n_users)),
        items=BiMap.from_keys(f"i{n}" for n in range(n_items)))
    engine = rec.engine()
    params = EngineParams(
        data_source_params=("", rec.DataSourceParams(app_name="benchapp")),
        algorithm_params_list=(("als", rec.ALSAlgorithmParams(
            rank=int(cfg["rank"]), num_iterations=int(a["iterations"]),
            lambda_=float(a["lambda"]), seed=harness.als_seed(rc.seed))),))

    def one_train():
        ctx = RuntimeContext(registry=registry)   # the mesh the CLI builds
        with jax.profiler.TraceAnnotation("bench:run_train"):
            row = CoreWorkflow.run_train(engine, params, ctx)
        return row, dict(ctx.phase_timings)

    original = rec.RecommendationDataSource._ratings
    rec.RecommendationDataSource._ratings = lambda self, ctx: columns
    try:
        t = time.perf_counter()
        _, warm = one_train()             # compiles, or reads the cache
        rc.note("warmup_train_s", round(time.perf_counter() - t, 3))
        rc.note("warmup_phase_timings", warm)
        rc.note("compile_cache", compile_cache_counts())
        compiles0 = compile_count()

        t0 = time.perf_counter()
        setup_s = t0 - rc.t_start
        trains, traced = [], {"reduced": None}
        while not trains or time.perf_counter() - t0 < rc.seconds:
            tracing = rc.trace and not trains
            with (harness.profiler_window() if tracing
                  else contextlib.nullcontext(traced)) as traced_now:
                t = time.perf_counter()
                row, phases = one_train()
                trains.append((time.perf_counter() - t, phases, row))
            if tracing:
                traced = traced_now
        window_s = time.perf_counter() - t0
    finally:
        rec.RecommendationDataSource._ratings = original

    rc.note("trains", len(trains))
    rc.note("train_seconds_each", [round(s, 4) for s, _, _ in trains])
    rc.note("phase_timings_last", trains[-1][1])
    rc.note("compiles_in_window", compile_count() - compiles0)
    peak = harness.device_memory_peak()
    rc.note("memory_peak_bytes", peak)
    rc.note("peak_rss_bytes", harness.peak_rss_bytes())

    # what the timed path produced: the model the last train persisted
    blob = registry.get_model_data_models().get(trains[-1][2].id).models
    model = loads(blob)[0]
    got = (np.asarray(model.user_factors), np.asarray(model.item_factors))
    del model, blob, columns, registry
    harness.free_device()

    t = time.perf_counter()
    ref = reference.als_reference(
        u, i, r, n_users, n_items, rank=int(cfg["rank"]),
        iterations=int(a["iterations"]), reg=float(a["lambda"]),
        seed=harness.als_seed(rc.seed))
    numbers = reference.compare_factors(got, ref, u, i, r, rc.seed)
    rc.note("reference_s", round(time.perf_counter() - t, 3))
    correct, compared = reference.verdict(numbers,
                                          rc.cell["correct"]["limits"])
    return {
        "correct": correct, "compared": compared,
        "attempted": len(trains), "failed": 0,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_s": window_s / len(trains),
                       "setup_s": setup_s},
        "facts": {"phase_timings": [p for _, p, _ in trains],
                  "train_seconds": [s for s, _, _ in trains],
                  "trace": traced["reduced"]},
    }
