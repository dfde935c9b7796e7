"""Command implementations shared by the CLI, admin API, and tests.

Parity: `tools/.../commands/{App,AccessKey,Engine,Management,Export,
Import}.scala`.
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

from predictionio_tpu.data.event import Event, format_time
from predictionio_tpu.data.storage import AccessKey, App, Channel


# ---------------------------------------------------------------------------
# app (commands/App.scala:31-360)
# ---------------------------------------------------------------------------

def app_new(registry, name: str, *, description: Optional[str] = None,
            access_key: str = "") -> Dict[str, Any]:
    apps = registry.get_meta_data_apps()
    if apps.get_by_name(name) is not None:
        raise ValueError(f"App {name} already exists. Aborting.")
    app_id = apps.insert(App(0, name, description))
    registry.get_events().init(app_id)
    key = registry.get_meta_data_access_keys().insert(
        AccessKey(access_key, app_id, ()))
    return {"name": name, "id": app_id, "accessKey": key}


def _require_app(registry, name: str) -> App:
    app = registry.get_meta_data_apps().get_by_name(name)
    if app is None:
        raise ValueError(f"App {name} does not exist. Aborting.")
    return app


def app_list(registry) -> List[Dict[str, Any]]:
    out = []
    for app in sorted(registry.get_meta_data_apps().get_all(),
                      key=lambda a: a.name):
        keys = registry.get_meta_data_access_keys().get_by_appid(app.id)
        out.append({"name": app.name, "id": app.id,
                    "accessKeys": [k.key for k in keys]})
    return out


def app_show(registry, name: str) -> Dict[str, Any]:
    app = _require_app(registry, name)
    keys = registry.get_meta_data_access_keys().get_by_appid(app.id)
    channels = registry.get_meta_data_channels().get_by_appid(app.id)
    return {
        "name": app.name, "id": app.id, "description": app.description,
        "accessKeys": [{"key": k.key,
                        "events": list(k.events) or "(all)"} for k in keys],
        "channels": [{"id": c.id, "name": c.name} for c in channels],
    }


def app_quota_set(registry, name: str, *,
                  rate: Optional[float] = None,
                  burst: Optional[float] = None,
                  concurrency: Optional[int] = None,
                  queue_max: Optional[int] = None,
                  weight: Optional[float] = None) -> Dict[str, Any]:
    """Persist a per-app admission override (serving tenancy). Only
    the fields given override the fleet-wide PIO_TENANT_* defaults;
    the rest stay None and keep inheriting. Running servers pick the
    change up within the admission TTL — no redeploy."""
    from predictionio_tpu.data.storage import TenantQuota
    app = _require_app(registry, name)
    quotas = registry.get_meta_data_tenant_quotas()
    existing = quotas.get(app.id)
    fields = dict(rate=rate, burst=burst, concurrency=concurrency,
                  queue_max=queue_max, weight=weight)
    if existing is not None:
        for k, v in list(fields.items()):
            if v is None:
                fields[k] = getattr(existing, k)
    quota = TenantQuota(appid=app.id, **fields)
    quotas.upsert(quota)
    return app_quota_show(registry, name)


def app_quota_show(registry, name: str) -> Dict[str, Any]:
    """The app's stored admission override (unset fields inherit the
    PIO_TENANT_* defaults at the serving tier)."""
    app = _require_app(registry, name)
    quota = registry.get_meta_data_tenant_quotas().get(app.id)
    row = {"rate": None, "burst": None, "concurrency": None,
           "queue_max": None, "weight": None}
    if quota is not None:
        row = {k: getattr(quota, k) for k in row}
    return {"name": app.name, "id": app.id, "quota": row,
            "note": "null fields inherit the PIO_TENANT_* defaults"}


def app_quota_delete(registry, name: str) -> None:
    """Drop the app's override; defaults apply again."""
    app = _require_app(registry, name)
    registry.get_meta_data_tenant_quotas().delete(app.id)


def app_delete(registry, name: str, *, force: bool = False) -> None:
    app = _require_app(registry, name)
    if not force:
        raise ValueError("Pass force=True (CLI: --force) to delete")
    events = registry.get_events()
    for ch in registry.get_meta_data_channels().get_by_appid(app.id):
        events.remove(app.id, ch.id)
        registry.get_meta_data_channels().delete(ch.id)
    events.remove(app.id)
    for k in registry.get_meta_data_access_keys().get_by_appid(app.id):
        registry.get_meta_data_access_keys().delete(k.key)
    registry.get_meta_data_apps().delete(app.id)


def app_data_delete(registry, name: str, *,
                    channel: Optional[str] = None,
                    all_channels: bool = False,
                    force: bool = False) -> None:
    app = _require_app(registry, name)
    if not force:
        raise ValueError("Pass force=True (CLI: --force) to delete data")
    events = registry.get_events()
    channels = registry.get_meta_data_channels().get_by_appid(app.id)
    if channel is not None:
        match = [c for c in channels if c.name == channel]
        if not match:
            raise ValueError(f"Channel {channel} does not exist. Aborting.")
        events.remove(app.id, match[0].id)
        events.init(app.id, match[0].id)
        return
    events.remove(app.id)
    events.init(app.id)
    if all_channels:
        for c in channels:
            events.remove(app.id, c.id)
            events.init(app.id, c.id)


def channel_new(registry, app_name: str, channel_name: str) -> Dict[str, Any]:
    app = _require_app(registry, app_name)
    channels = registry.get_meta_data_channels()
    if any(c.name == channel_name for c in channels.get_by_appid(app.id)):
        raise ValueError(f"Channel {channel_name} already exists. Aborting.")
    channel_id = channels.insert(Channel(0, channel_name, app.id))
    registry.get_events().init(app.id, channel_id)
    return {"app": app_name, "channel": channel_name, "id": channel_id}


def channel_delete(registry, app_name: str, channel_name: str, *,
                   force: bool = False) -> None:
    app = _require_app(registry, app_name)
    if not force:
        raise ValueError("Pass force=True (CLI: --force) to delete")
    channels = registry.get_meta_data_channels()
    match = [c for c in channels.get_by_appid(app.id)
             if c.name == channel_name]
    if not match:
        raise ValueError(f"Channel {channel_name} does not exist. Aborting.")
    registry.get_events().remove(app.id, match[0].id)
    channels.delete(match[0].id)


# ---------------------------------------------------------------------------
# accesskey (commands/AccessKey.scala)
# ---------------------------------------------------------------------------

def accesskey_new(registry, app_name: str, *, key: str = "",
                  events: Sequence[str] = ()) -> Dict[str, Any]:
    app = _require_app(registry, app_name)
    new_key = registry.get_meta_data_access_keys().insert(
        AccessKey(key, app.id, tuple(events)))
    return {"accessKey": new_key, "app": app_name, "events": list(events)}


def accesskey_list(registry, app_name: Optional[str] = None
                   ) -> List[Dict[str, Any]]:
    keys_dao = registry.get_meta_data_access_keys()
    if app_name is not None:
        app = _require_app(registry, app_name)
        keys = keys_dao.get_by_appid(app.id)
    else:
        keys = keys_dao.get_all()
    return [{"accessKey": k.key, "appid": k.appid,
             "events": list(k.events)} for k in keys]


def accesskey_delete(registry, key: str) -> None:
    dao = registry.get_meta_data_access_keys()
    if dao.get(key) is None:
        raise ValueError(f"Access key {key} does not exist. Aborting.")
    dao.delete(key)


# ---------------------------------------------------------------------------
# train / eval / deploy plumbing (commands/Engine.scala)
# ---------------------------------------------------------------------------

def load_variant(path: str) -> Dict[str, Any]:
    p = Path(path)
    if not p.is_file():
        raise ValueError(f"Engine variant file {path} not found")
    return json.loads(p.read_text())


def resolve_factory_name(variant: Dict[str, Any],
                         engine_factory: Optional[str],
                         engine_json: str) -> str:
    factory = engine_factory or variant.get("engineFactory")
    if not factory:
        raise ValueError(
            f"No engineFactory in {engine_json} and none given "
            "(--engine-factory)")
    return factory


def train(registry, *, engine_json: str = "engine.json",
          engine_factory: Optional[str] = None,
          batch: str = "", mesh: Optional[str] = None,
          skip_sanity_check: bool = False,
          stop_after_read: bool = False,
          stop_after_prepare: bool = False,
          coordinator: Optional[str] = None,
          num_processes: Optional[int] = None,
          process_id: Optional[int] = None,
          profile_dir: Optional[str] = None) -> Dict[str, Any]:
    """pio train (commands/Engine.scala:177-188 -> CreateWorkflow).

    `profile_dir` (or PIO_TPU_PROFILE_DIR) wraps the whole run in
    `jax.profiler.trace`: a TensorBoard-loadable device trace next to
    the per-phase wall-clock the EngineInstance always records.

    Multi-host: `--coordinator host:port --num-processes N --process-id K`
    (or the PIO_TPU_COORDINATOR / _NUM_PROCESSES / _PROCESS_ID env vars)
    initializes `jax.distributed` before the mesh is built; every process
    runs the sharded training computation, but only process 0 writes
    metadata and the model blob (the env-forwarding spark-submit analog,
    Runner.scala:213-215,298-305)."""
    from predictionio_tpu.core import RuntimeContext, WorkflowParams
    from predictionio_tpu.core.workflow import CoreWorkflow, resolve_engine
    from predictionio_tpu.obs import (
        compile_cache_counts, compile_count, install_compile_probe,
    )
    from predictionio_tpu.parallel import initialize_distributed
    from predictionio_tpu.utils.device import claim_device

    # flags override env inside initialize_distributed; nothing is
    # written back to os.environ (a later single-host train in the same
    # process must not inherit coordinator state)
    distributed = initialize_distributed(
        coordinator=coordinator, num_processes=num_processes,
        process_id=process_id)
    device, cache_dir = claim_device()

    variant = load_variant(engine_json)
    factory = resolve_factory_name(variant, engine_factory, engine_json)
    engine = resolve_engine(factory)
    engine_params = engine.engine_params_from_variant(variant)
    runtime_conf = {}
    if mesh:
        runtime_conf["mesh"] = mesh
    ctx = RuntimeContext(
        registry=registry,
        workflow_params=WorkflowParams(
            batch=batch, skip_sanity_check=skip_sanity_check,
            stop_after_read=stop_after_read,
            stop_after_prepare=stop_after_prepare,
            runtime_conf=runtime_conf))
    persist = True
    if distributed:
        import jax
        persist = jax.process_index() == 0

    import contextlib
    profile_dir = profile_dir or os.environ.get("PIO_TPU_PROFILE_DIR")
    if profile_dir:
        import jax
        prof_ctx = jax.profiler.trace(profile_dir)
    else:
        prof_ctx = contextlib.nullcontext()
    # probe installed before training so this run's XLA compiles are
    # counted; the delta (not the process total) is reported
    install_compile_probe()
    compiles_before = compile_count()
    cache_before = compile_cache_counts()
    with prof_ctx:
        row = CoreWorkflow.run_train(
            engine, engine_params, ctx,
            engine_factory=factory,
            engine_variant=variant.get("id", "default"),
            persist=persist)
    cache_after = compile_cache_counts()
    return {"engineInstanceId": row.id, "status": row.status,
            "startTime": format_time(row.start_time),
            "endTime": format_time(row.end_time),
            "phaseTimings": dict(ctx.phase_timings),
            "jaxCompiles": int(compile_count() - compiles_before),
            "compileCache": {
                "dir": cache_dir,
                **{k: cache_after[k] - cache_before[k]
                   for k in cache_after}},
            "device": device,
            "mesh": {str(k): int(v) for k, v in ctx.mesh.shape.items()},
            "distributed": distributed, "persisted": persist}


def run_eval(registry, evaluation_path: str,
             params_generator_path: Optional[str] = None,
             output_path: Optional[str] = None) -> Dict[str, Any]:
    """pio eval <Evaluation> [<EngineParamsGenerator>]
    (Console.scala eval command)."""
    import importlib

    from predictionio_tpu.core import (
        MetricEvaluator, RuntimeContext, run_evaluation,
    )

    from predictionio_tpu.utils.device import claim_device

    def resolve(dotted: str):
        module_name, _, attr = dotted.rpartition(".")
        obj = getattr(importlib.import_module(module_name), attr)
        return obj() if callable(obj) and not hasattr(obj, "engine") else obj

    claim_device()

    evaluation = resolve(evaluation_path)
    engine_params_list = None
    if params_generator_path:
        gen = resolve(params_generator_path)
        engine_params_list = gen.engine_params_list
    ctx = RuntimeContext(registry=registry)
    evaluator = MetricEvaluator(evaluation.metric, evaluation.other_metrics,
                                output_path=output_path)
    row, result = run_evaluation(
        evaluation, ctx, evaluation_class=evaluation_path,
        engine_params_list=engine_params_list, evaluator=evaluator)
    return {"evaluationInstanceId": row.id, "result": result.one_liner(),
            "bestScore": result.best_score.score}


def batchpredict(registry, *, engine_json: str = "engine.json",
                 engine_factory: Optional[str] = None,
                 input_path: str = "batchpredict-input.json",
                 output_path: str = "batchpredict-output.json",
                 chunk_size: int = 1024) -> Dict[str, Any]:
    """pio batchpredict (commands/Engine.scala:279-314)."""
    from predictionio_tpu.core import RuntimeContext
    from predictionio_tpu.core.batchpredict import run_batch_predict
    from predictionio_tpu.core.workflow import resolve_engine
    from predictionio_tpu.utils.device import claim_device

    claim_device()
    variant = load_variant(engine_json)
    factory = resolve_factory_name(variant, engine_factory, engine_json)
    engine = resolve_engine(factory)
    ctx = RuntimeContext(registry=registry)
    instance = _latest_completed(registry, variant.get("id", "default"))
    n = run_batch_predict(engine, instance, ctx, input_path=input_path,
                          output_path=output_path, chunk_size=chunk_size)
    return {"engineInstanceId": instance.id, "predictions": n,
            "output": output_path}


def _latest_completed(registry, variant_id: str):
    instances = registry.get_meta_data_engine_instances()
    inst = instances.get_latest_completed("default", "default", variant_id)
    if inst is None:
        raise ValueError(
            "No valid engine instance found for this engine. Try running "
            "'train' before 'deploy' (commands/Engine.scala:235-236)")
    return inst


def _post_server(ip: str, port: int, endpoint: str, access_key: str,
                 timeout: float) -> bool:
    """POST a lifecycle endpoint on a running prediction server. The
    server key travels as the Basic-auth username (KeyAuthentication
    accepts it there), not as a query param, so it never lands in
    proxy/access logs. 401 raises (key needed); unreachable/refused
    returns False."""
    import base64
    import urllib.error
    import urllib.request
    headers = {}
    if access_key:
        headers["Authorization"] = "Basic " + base64.b64encode(
            f"{access_key}:".encode()).decode()
    try:
        req = urllib.request.Request(f"http://{ip}:{port}{endpoint}",
                                     data=b"", method="POST",
                                     headers=headers)
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status == 200
    except urllib.error.HTTPError as e:
        if e.code == 401:
            raise ValueError(
                f"Unauthorized: the server's {endpoint} is "
                "key-protected; pass --accesskey with the server key"
            ) from e
        return False
    except OSError:
        return False


def reload_server(ip: str = "127.0.0.1", port: int = 8000,
                  access_key: str = "") -> bool:
    """POST /reload: hot-swap to the latest COMPLETED instance. The
    train-then-reload pair is the reference's cron redeploy recipe
    (examples/redeploy-script/redeploy.sh)."""
    return _post_server(ip, port, "/reload", access_key, timeout=30)


def undeploy(ip: str = "127.0.0.1", port: int = 8000,
             access_key: str = "") -> bool:
    """POST /stop to a running prediction server (Console undeploy)."""
    return _post_server(ip, port, "/stop", access_key, timeout=5)


# ---------------------------------------------------------------------------
# template scaffold (commands/Template.scala analog)
# ---------------------------------------------------------------------------

_SCAFFOLD_ENGINE = '''\
"""Custom engine scaffold. Wire your DASE components into `engine()` and
reference this module from engine.json's engineFactory
("my_engine.engine")."""

from predictionio_tpu.core import Engine, FirstServing, IdentityPreparator
from predictionio_tpu.models.{base} import (
    {ds_class} as DataSource,
    {algo_class} as Algorithm,
)


def engine() -> Engine:
    return Engine(
        data_source=DataSource,
        preparator=IdentityPreparator,
        algorithms={{"": Algorithm}},
        serving=FirstServing,
    )
'''

_SCAFFOLD_BASES = {
    "recommendation": ("RecommendationDataSource", "ALSAlgorithm"),
    "similarproduct": ("SimilarProductDataSource", "ALSAlgorithm"),
    "classification": ("ClassificationDataSource", "NaiveBayesAlgorithm"),
    "ecommerce": ("ECommDataSource", "ECommAlgorithm"),
    "twotower": ("TwoTowerDataSource", "TwoTowerAlgorithm"),
    "seqrec": ("SeqRecDataSource", "SeqRecAlgorithm"),
}


def template_new(directory: str, *, base: str = "recommendation") -> str:
    """Scaffold an engine dir with engine.json + my_engine.py."""
    if base not in _SCAFFOLD_BASES:
        raise ValueError(
            f"Unknown base template {base!r}; known: "
            f"{sorted(_SCAFFOLD_BASES)}")
    target = Path(directory)
    if target.exists() and any(target.iterdir()):
        raise ValueError(f"Directory {directory} exists and is not empty")
    target.mkdir(parents=True, exist_ok=True)
    ds_class, algo_class = _SCAFFOLD_BASES[base]
    (target / "my_engine.py").write_text(_SCAFFOLD_ENGINE.format(
        base=base, ds_class=ds_class, algo_class=algo_class))
    # bases whose ALGORITHM reads the event store at serve time carry
    # app_name in their algo params too — omitting it would make
    # serve-time reads silently target the 'default' app and return
    # empty predictions
    algo_params = ({"app_name": "myapp"}
                   if base in ("ecommerce", "seqrec") else {})
    (target / "engine.json").write_text(json.dumps({
        "id": "default",
        "description": f"scaffold based on the {base} template",
        "engineFactory": "my_engine.engine",
        "datasource": {"params": {"app_name": "myapp"}},
        "algorithms": [{"name": "", "params": algo_params}],
    }, indent=2) + "\n")
    return str(target)


# ---------------------------------------------------------------------------
# status (commands/Management.scala:99-181)
# ---------------------------------------------------------------------------

def _probe_device(timeout_s: float = 60.0) -> Dict[str, Any]:
    """`utils.device.device_info()` from a CHILD process: `status` itself
    must not take the chip (one process per chip), and when a server
    holds it the child fails or hangs — reported, never waited on past
    `timeout_s`."""
    import subprocess
    import sys
    code = ("import json; from predictionio_tpu.utils.device import "
            "device_info; print(json.dumps(device_info()))")
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"platform": f"unavailable: no answer in {timeout_s:.0f}s "
                            "(is another process holding the chip?)"}
    if out.returncode != 0:
        reason = (out.stderr.strip().splitlines() or ["no output"])[-1]
        return {"platform": f"unavailable: {reason}"}
    return json.loads(out.stdout.strip().splitlines()[-1])


def status(registry) -> Dict[str, Any]:
    import predictionio_tpu

    info: Dict[str, Any] = {
        "version": predictionio_tpu.__version__,
        "storageSources": {
            name: cfg.get("TYPE") for name, cfg in registry.sources.items()},
        "repositories": {
            repo: cfg.get("SOURCE")
            for repo, cfg in registry.repositories.items()},
    }
    try:
        registry.verify_all_data_objects()
        info["storage"] = "ok"
    except Exception as e:
        info["storage"] = f"error: {e}"
    info["device"] = _probe_device()
    from predictionio_tpu import native
    info["native"] = {"eventlog": native.load("eventlog") is not None}
    info["status"] = ("(sleeping)" if info["storage"] == "ok"
                      else "storage check failed")
    # the latest completed train with its per-phase timings (the
    # tracing record run_train persists into runtime_conf)
    try:
        latest = registry.get_meta_data_engine_instances() \
            .get_latest_completed("default", "default", "default")
        if latest is not None:
            info["latestTrainedInstance"] = {
                "id": latest.id,
                "startTime": format_time(latest.start_time),
                "endTime": format_time(latest.end_time),
                "phaseTimings": latest.runtime_conf.get(
                    "phase_timings", {}),
            }
    except Exception:   # status must never fail on metadata quirks
        pass
    return info


def doctor(registry, *, repair: bool = False,
           stale_after_s: Optional[float] = None) -> Dict[str, Any]:
    """`pio doctor`: store-wide fsck + stale-instance janitor report."""
    from predictionio_tpu.data import fsck
    return fsck.doctor(
        registry, repair=repair,
        stale_after_s=(stale_after_s if stale_after_s is not None
                       else fsck.DEFAULT_STALE_S))


# ---------------------------------------------------------------------------
# import / export (tools/.../{imprt,export})
# ---------------------------------------------------------------------------

def _require_pyarrow():
    try:
        import pyarrow  # noqa: F401
        import pyarrow.parquet  # noqa: F401
        return pyarrow
    except ImportError as e:  # pragma: no cover - env dependent
        raise ValueError(
            "format='parquet' requires pyarrow, which is not installed; "
            "use format='json'") from e


def import_events(registry, *, app_id: int, input_path: str,
                  channel_id: Optional[int] = None,
                  format: str = "json") -> int:
    """Events file -> event store (imprt/FileToEvents.scala:40-106).
    `format` is json (one API-JSON event per line) or parquet (the
    columnar schema written by `export_events`)."""
    store = registry.get_events()
    store.init(app_id, channel_id)
    n = 0
    batch: List[Event] = []

    def flush():
        nonlocal n, batch
        store.insert_batch(batch, app_id, channel_id)
        n += len(batch)
        batch = []

    if format == "parquet":
        pa = _require_pyarrow()
        # stream record batches: bounded memory for multi-GB files
        pf = pa.parquet.ParquetFile(input_path)
        for rb in pf.iter_batches(batch_size=500):
            for row in rb.to_pylist():
                payload = {k: v for k, v in row.items() if v is not None}
                if "properties" in payload:
                    payload["properties"] = json.loads(payload["properties"])
                batch.append(Event.from_api_json(payload))
                if len(batch) >= 500:
                    flush()
    elif format == "json":
        with open(input_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                batch.append(Event.from_api_json(json.loads(line)))
                if len(batch) >= 500:
                    flush()
    else:
        raise ValueError(f"Unknown import format {format!r} "
                         "(expected 'json' or 'parquet')")
    if batch:
        flush()
    return n


def export_events(registry, *, app_id: int, output_path: str,
                  channel_id: Optional[int] = None,
                  format: str = "json") -> int:
    """Event store -> file (export/EventsToFile.scala:40-108 supports
    text|parquet; so does this). The parquet schema is the API-JSON
    fields as columns, with `properties` as a JSON-encoded string column
    (schemaless property bags don't have a static arrow schema)."""
    events_iter = registry.get_events().find(app_id, channel_id)
    if format == "parquet":
        pa = _require_pyarrow()
        cols = ["eventId", "event", "entityType", "entityId",
                "targetEntityType", "targetEntityId", "properties",
                "eventTime", "tags", "prId", "creationTime"]
        schema = pa.schema(
            [(c, pa.list_(pa.string()) if c == "tags" else pa.string())
             for c in cols])
        n = 0
        writer = None
        try:
            chunk: List[dict] = []

            def write_chunk():
                nonlocal writer, n
                data = {c: [r.get(c) for r in chunk] for c in cols}
                table = pa.table(data, schema=schema)
                if writer is None:
                    writer = pa.parquet.ParquetWriter(output_path, schema)
                writer.write_table(table)
                n += len(chunk)

            for e in events_iter:
                d = e.to_api_json()
                if "properties" in d:
                    d["properties"] = json.dumps(d["properties"])
                chunk.append(d)
                if len(chunk) >= 5000:
                    write_chunk()
                    chunk = []
            if chunk or writer is None:
                write_chunk()
        finally:
            if writer is not None:
                writer.close()
        return n
    if format != "json":
        raise ValueError(f"Unknown export format {format!r} "
                         "(expected 'json' or 'parquet')")
    n = 0
    with open(output_path, "w") as f:
        for e in events_iter:
            f.write(json.dumps(e.to_api_json()) + "\n")
            n += 1
    return n
