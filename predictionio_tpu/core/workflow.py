"""Train/eval orchestration around the storage registries.

Parity targets:
  - `CoreWorkflow.runTrain` / `runEvaluation`
    (`core/.../workflow/CoreWorkflow.scala:45-160`)
  - engine factory reflection (`CreateWorkflow.scala:195-203`,
    `WorkflowUtils.getEngine`)
  - deploy-time model preparation (`Engine.prepareDeploy`,
    `controller/Engine.scala:199-269`)
"""

from __future__ import annotations

import importlib
import threading
from typing import Any, Dict, List, Optional, Tuple

from predictionio_tpu.core.engine import Engine, EngineFactory
from predictionio_tpu.core.params import EngineParams
from predictionio_tpu.core.persistence import (
    deserialize_models, serialize_models,
)
from predictionio_tpu.core.runtime import RuntimeContext
from predictionio_tpu.data.event import utcnow
from predictionio_tpu.data.storage.base import (
    EngineInstance, EngineInstanceStatus, Model,
)
from predictionio_tpu.obs import (
    get_logger, install_compile_probe, record_train_phases,
)

_log = get_logger("workflow")

# explicit registry complementing dotted-path import, so quickstart factories
# can register under short names (the classpath-reflection analog)
_ENGINE_FACTORIES = {}


def register_engine(name: str, factory) -> None:
    _ENGINE_FACTORIES[name] = factory


def resolve_engine(factory_name: str) -> Engine:
    """Resolve an engine factory by registered short name or dotted path
    'package.module.FactoryClass' (WorkflowUtils.getEngine analog)."""
    target = _ENGINE_FACTORIES.get(factory_name)
    if target is None and "." not in factory_name:
        # short names self-register on import: try the bundled templates
        mod_name = f"predictionio_tpu.models.{factory_name}"
        try:
            importlib.import_module(mod_name)
            target = _ENGINE_FACTORIES.get(factory_name)
        except ModuleNotFoundError as e:
            if e.name != mod_name:
                raise   # a real dependency failure inside the template

    if target is None:
        module_name, _, attr = factory_name.rpartition(".")
        if not module_name:
            raise ValueError(
                f"Unknown engine factory {factory_name!r}; registered: "
                f"{sorted(_ENGINE_FACTORIES)} (or use a dotted path)")
        mod = importlib.import_module(module_name)
        target = getattr(mod, attr)
    if isinstance(target, Engine):
        return target
    if isinstance(target, type) and issubclass(target, EngineFactory):
        return target.apply()
    if callable(target):
        result = target()
        if isinstance(result, Engine):
            return result
    raise TypeError(f"{factory_name!r} did not produce an Engine")


def _heartbeat_interval(registry) -> float:
    """`PIO_TRAIN_HEARTBEAT_S` (default 5s); <= 0 disables the beat."""
    cfg = getattr(registry, "config", {}) or {}
    try:
        return float(cfg.get("PIO_TRAIN_HEARTBEAT_S", 5.0))
    except (TypeError, ValueError):
        return 5.0


def _start_heartbeat(instances, instance_id: str, stop: threading.Event,
                     interval_s: float) -> Optional[threading.Thread]:
    if interval_s <= 0:
        return None

    def beat():
        while not stop.wait(interval_s):
            try:
                instances.record_heartbeat(instance_id)
            except Exception as e:
                # a failed beat must never kill the train; the janitor
                # threshold absorbs gaps far longer than one interval
                _log.warning("heartbeat_failed", instance_id=instance_id,
                             error=f"{type(e).__name__}: {e}")

    t = threading.Thread(target=beat, name=f"pio-heartbeat-{instance_id}",
                         daemon=True)
    t.start()
    return t


def _stop_heartbeat(stop: threading.Event,
                    thread: Optional[threading.Thread]) -> None:
    stop.set()
    if thread is not None and thread.is_alive():
        thread.join(timeout=10.0)


class CoreWorkflow:
    """Training orchestration with engine-instance lifecycle."""

    @staticmethod
    def run_train(engine: Engine, engine_params: EngineParams,
                  ctx: RuntimeContext, *,
                  engine_factory: str = "",
                  engine_variant: str = "",
                  verbose_save: bool = True,
                  persist: bool = True) -> EngineInstance:
        """Train, persist models, record the instance
        (CoreWorkflow.scala:45-101): insert INIT row, train, serialize
        models into the model repo, update status to COMPLETED; any failure
        leaves the row non-COMPLETED so deploy refuses it
        (commands/Engine.scala:235-236).

        `persist=False` runs the training computation but touches no
        storage — the non-coordinator processes of a multi-host run use
        it: they must participate in every collective, while only
        process 0 owns the metadata/model writes (the analog of Spark
        executors computing while the driver alone talks to storage)."""
        # per-phase wall times and XLA compile counts land in the
        # process-default metrics registry; the CLI renders its timing
        # report from there (obs.train_report)
        install_compile_probe()
        if not persist:
            engine.train(ctx, engine_params)
            record_train_phases(ctx.phase_timings)
            return EngineInstance(
                id="", status=EngineInstanceStatus.COMPLETED,
                start_time=utcnow(), end_time=utcnow(),
                engine_id="default", engine_version="default",
                engine_variant=engine_variant or "default",
                engine_factory=engine_factory)
        registry = ctx.registry
        instances = registry.get_meta_data_engine_instances()
        row = EngineInstance(
            id="", status=EngineInstanceStatus.INIT,
            start_time=utcnow(), end_time=utcnow(),
            engine_id="default", engine_version="default",
            engine_variant=engine_variant or "default",
            engine_factory=engine_factory,
            batch=ctx.workflow_params.batch,
            env={}, runtime_conf=dict(ctx.workflow_params.runtime_conf),
            data_source_params=_named_params_json(
                engine_params.data_source_params),
            preparator_params=_named_params_json(
                engine_params.preparator_params),
            algorithms_params=_algo_params_json(engine_params),
            serving_params=_named_params_json(engine_params.serving_params),
        )
        instance_id = instances.insert(row)
        row = row.with_(id=instance_id,
                        status=EngineInstanceStatus.TRAINING,
                        heartbeat=utcnow())
        instances.update(row)
        # liveness beats let the stale-instance janitor distinguish a
        # long-running train from one whose process died mid-run
        stop_beat = threading.Event()
        beat_thread = _start_heartbeat(
            instances, instance_id, stop_beat,
            interval_s=_heartbeat_interval(registry))
        try:
            models = engine.train(ctx, engine_params)
            record_train_phases(ctx.phase_timings)
            _, _, algos, _ = engine.make_components(engine_params)
            blob = serialize_models(instance_id, algos, models, ctx)
            registry.get_model_data_models().insert(Model(instance_id, blob))
            # the beat thread must be down BEFORE the terminal status
            # write: a concurrent get+update beat could resurrect the
            # TRAINING row after COMPLETED landed
            _stop_heartbeat(stop_beat, beat_thread)
            row = row.with_(
                status=EngineInstanceStatus.COMPLETED, end_time=utcnow(),
                # per-phase timings travel with the instance: `pio
                # status`/dashboard can show WHERE a train spent its
                # time, not just start/end
                runtime_conf={**row.runtime_conf,
                              "phase_timings": dict(ctx.phase_timings)})
            instances.update(row)
            return row
        except Exception as e:
            _stop_heartbeat(stop_beat, beat_thread)
            _log.exception("train_failed", instance_id=instance_id,
                           error=f"{type(e).__name__}: {e}")
            row = row.with_(status=EngineInstanceStatus.FAILED,
                            end_time=utcnow())
            instances.update(row)
            raise
        finally:
            _stop_heartbeat(stop_beat, beat_thread)

    @staticmethod
    def prepare_deploy(engine: Engine, instance: EngineInstance,
                       ctx: RuntimeContext,
                       engine_params: Optional[EngineParams] = None,
                       *, warm_batch_max: Optional[int] = None,
                       observed_sizes: Optional[Dict[int, int]] = None
                       ) -> Tuple[List[Any], List[Any], Any]:
        """Load (or retrain) the instance's models for serving; returns
        (algorithms, models, serving). (Engine.prepareDeploy +
        CreateServer.createServerActorWithEngine:186-244).

        `warm_batch_max` caps the batch buckets AOT-warmed through each
        algorithm's `warm_serving` hook (the server passes its
        micro-batcher `batch_max`); None skips warmup entirely.
        `observed_sizes` (pow2 batch size -> drain count, the
        micro-batcher's persisted histogram) narrows warmup to the
        shapes real traffic actually formed."""
        if engine_params is None:
            engine_params = engine_params_from_instance(engine, instance)
        from predictionio_tpu.core.engine import bind_serving_context
        from predictionio_tpu.resilience import faults
        faults().check("deploy.prepare")  # chaos seam: /reload rollback
        ds, prep, algos, serving = engine.make_components(engine_params)
        bind_serving_context(algos, ctx)
        blob_row = ctx.registry.get_model_data_models().get(instance.id)
        if blob_row is None:
            raise ValueError(f"No model blob for instance {instance.id}")

        def retrain(indices):
            # read/prepare once; train only the marker algorithms
            # (Engine.prepareDeploy retrains Unit models, Engine.scala:211-233)
            td = ds.read_training(ctx)
            pd = prep.prepare(ctx, td)
            return {i: algos[i].train(ctx, pd) for i in indices}

        models = deserialize_models(blob_row.models, instance.id, algos,
                                    ctx, retrain)
        if warm_batch_max is not None:
            # the serving mesh candidate: the engine-instance's recorded
            # runtime_conf (training's device layout) merged with the
            # server's own runtime_conf — a configured mesh in either
            # forces the sharded serve path; otherwise plans shard only
            # when the catalog exceeds one device's capacity
            from predictionio_tpu.ops.topk_sharded import (
                serve_mesh_from_conf,
            )
            conf = {**dict(getattr(instance, "runtime_conf", None) or {}),
                    **dict(ctx.workflow_params.runtime_conf or {})}
            warm_deploy(algos, models, warm_batch_max,
                        mesh=serve_mesh_from_conf(conf),
                        observed_sizes=observed_sizes)
        return algos, models, serving


def derive_warm_buckets(warm_batch_max: int,
                        observed_sizes: Optional[Dict[int, int]] = None
                        ) -> List[int]:
    """The batch shapes a deploy should AOT-compile.

    No observation history -> the full pow2 ladder 1..warm_batch_max
    (cold start must handle anything). With a recorded batch-size
    histogram, only the observed pow2 shapes (clamped to the ladder)
    plus bucket 1 — the single-query shape every dispatch can fall back
    to — get compiled, cutting deploy warmup time on workloads that
    never form the big batches."""
    cap = max(1, int(warm_batch_max))
    ladder: List[int] = []
    b = 1
    while b <= cap:
        ladder.append(b)
        b *= 2
    if not observed_sizes:
        return ladder
    wanted = {1}
    for size, count in observed_sizes.items():
        try:
            size, count = int(size), int(count)
        except (TypeError, ValueError):
            continue
        if count <= 0 or size < 1:
            continue
        # clamp outsized observations (batch_max shrank between runs)
        # onto the largest ladder shape
        wanted.add(max(s for s in ladder if s <= size))
    return [s for s in ladder if s in wanted]


def warm_deploy(algos: List[Any], models: List[Any],
                warm_batch_max: int, mesh=None,
                observed_sizes: Optional[Dict[int, int]] = None) -> int:
    """AOT-warm every algorithm's serve executables for the power-of-two
    batch buckets up to `warm_batch_max`, pinning model state device
    resident, so steady-state serving never recompiles. `mesh` (a
    `topk_sharded.ServeMesh` or None) is forwarded to every
    `warm_serving` override that accepts it, so plans can shard model
    state across the device mesh; legacy two-argument overrides keep
    working. Warmup cost/count land in the default metrics registry
    (`pio_serve_warmup_seconds`, `pio_serve_warmup_compiles_total`);
    `PIO_SERVE_WARMUP=off` disables (and leaves every query on the
    generic dispatch, host numpy below the crossover). A warm-up that
    raises fails the deploy: a server whose plans did not compile would
    answer 200 from a slower path and nobody would know. On `/reload`
    the same error rolls back to the last good deployment."""
    import inspect
    import os
    import time as _time
    if os.environ.get("PIO_SERVE_WARMUP", "on").lower() in (
            "off", "0", "false"):
        return 0
    # compiles during warmup must be attributed (and post-warmup drift
    # detectable), so the probe goes in before the first lowering
    install_compile_probe()
    buckets = derive_warm_buckets(warm_batch_max, observed_sizes)
    from predictionio_tpu.obs import get_registry
    reg = get_registry()
    t0 = _time.perf_counter()
    compiled = 0
    for algo, model in zip(algos, models):
        try:
            params = inspect.signature(algo.warm_serving).parameters
            takes_mesh = ("mesh" in params or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values()))
        except (TypeError, ValueError):
            takes_mesh = False
        n = (algo.warm_serving(model, buckets, mesh=mesh)
             if takes_mesh else algo.warm_serving(model, buckets))
        compiled += int(n or 0)
    reg.gauge("pio_serve_warmup_seconds",
              "Wall time of the last deploy serve warmup").set(
        _time.perf_counter() - t0)
    if compiled:
        reg.counter(
            "pio_serve_warmup_compiles_total",
            "Serve executables AOT-compiled at deploy warmup").inc(compiled)
    _log.info("serve_warmup", buckets=buckets, compiled=compiled,
              shards=(mesh.n_shards if mesh is not None else 0),
              seconds=round(_time.perf_counter() - t0, 3))
    return compiled


def engine_params_from_instance(engine: Engine,
                                instance: EngineInstance) -> EngineParams:
    """Rebuild EngineParams from the params JSON recorded on the instance
    (Engine.engineInstanceToEngineParams, Engine.scala:422-492)."""
    import json
    variant = {
        "datasource": json.loads(instance.data_source_params or "{}"),
        "preparator": json.loads(instance.preparator_params or "{}"),
        "algorithms": json.loads(instance.algorithms_params or "[]"),
        "serving": json.loads(instance.serving_params or "{}"),
    }
    return engine.engine_params_from_variant(variant)


def _named_params_json(name_params) -> str:
    import dataclasses
    import json
    name, p = name_params
    return json.dumps({"name": name, "params": dataclasses.asdict(p)})


def _algo_params_json(engine_params: EngineParams) -> str:
    import dataclasses
    import json
    return json.dumps([
        {"name": name, "params": dataclasses.asdict(p)}
        for name, p in engine_params.algorithm_params_list])
