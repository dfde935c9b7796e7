"""Background serve-path refresher: delta-scan -> fold-in -> hot swap.

A `Refresher` thread rides inside `PredictionServer` and ticks every
`refresh_interval_s` seconds: snapshot the ingest watermark, delta-scan
the journal tail, run each algorithm's `fold_in` hook, then COMMIT —
swap the updated item factors into the device-resident serve plans
(same shapes => the AOT executables keep serving, zero recompiles; only
the factor block crosses host->device) and publish a new deployment
object under the server's swap lock.

Failure policy (the PR-2 rollback discipline): all new models are
computed host-side BEFORE anything touches the serve path; the
`streaming.refresh.swap` fault seam fires between compute and commit;
any commit failure re-swaps the last-good factors and keeps the old
deployment — both factor sets are valid mid-swap, so in-flight client
requests never fail. `DeltaInvalidated` (deletes between snapshots,
new items, over-budget deltas, drivers with no delta path) falls back
to the full-scan path: an in-process retrain from the complete store
read, shape-matched plans hot-swapped, changed shapes re-warmed.

Freshness accounting: `pio_freshness_seconds` is the age of the newest
event reflected in the serving model, sampled at each successful tick
(0 when the store and model already agree). Events that landed between
the FULL train and the refresher's first watermark baseline ride the
next full retrain unless their user is touched again — fold-in
refetches a touched user's complete history, which heals most of that
gap for active users. Count-merge folds (cooccurrence, popularity) may
over-count events racing a full rebuild; the next full retrain is
ground truth.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Dict, Optional, Tuple

from predictionio_tpu.data.storage.base import DeltaInvalidated
from predictionio_tpu.obs import MetricsRegistry, get_logger, get_registry
from predictionio_tpu.obs import trace
from predictionio_tpu.resilience import faults
from predictionio_tpu.streaming.delta import Delta, scan_delta
from predictionio_tpu.streaming.updaters import FoldContext

_log = get_logger(__name__)


def locate_event_store(dep, registry) -> Optional[
        Tuple[object, int, object, dict]]:
    """events DAO + app/channel ids from a live deployment's data
    source params (the `{"name":..., "params": {...}}` shape the
    workflow persists). Shared by the refresher and the quality
    feedback joiner; None when the deployment has no locatable app."""
    from predictionio_tpu.data.store import app_name_to_id
    try:
        raw = json.loads(dep.instance.data_source_params or "{}")
    except ValueError:
        return None
    params = raw.get("params", {}) if isinstance(raw, dict) else {}
    app_name = params.get("app_name")
    if not app_name:
        return None
    try:
        app_id, channel_id = app_name_to_id(
            registry, app_name, params.get("channel"))
    except ValueError:
        return None
    return registry.get_events(), app_id, channel_id, params


def _metrics(reg: MetricsRegistry) -> dict:
    return {
        "freshness": reg.gauge(
            "pio_freshness_seconds",
            "age of the newest event reflected in the serving model, "
            "sampled at the last successful refresh tick"),
        "ticks": reg.counter(
            "pio_streaming_refresh_total",
            "refresh ticks by outcome", labels=("outcome",)),
        "tick_s": reg.histogram(
            "pio_streaming_refresh_seconds", "refresh tick duration"),
        "folded": reg.counter(
            "pio_streaming_fold_rows_total",
            "factor rows re-solved by fold-in", labels=("side",)),
    }


class Refresher:
    """One background freshness loop per PredictionServer."""

    def __init__(self, server, interval_s: float, *,
                 stagger_s: float = 0.0,
                 metrics: Optional[MetricsRegistry] = None):
        self.server = server
        self.interval_s = float(interval_s)
        self.stagger_s = float(stagger_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._wm: Optional[Dict[str, int]] = None
        self._m = _metrics(metrics if metrics is not None
                           else get_registry())
        self.last_outcome = ""          # test/introspection surface
        self.beat = None                # watchdog liveness stamp

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        if self.beat is None:
            from predictionio_tpu.resilience.watchdog import watchdog
            # budget: a tick may legitimately take a full-rebuild, so
            # give several intervals of slack before a stall verdict
            self.beat = watchdog().register(
                "refresher", budget_s=self.interval_s * 3.0 + 5.0,
                restart=self._spawn)
        self._spawn()

    def _spawn(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="pio-refresher", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        beat, self.beat = self.beat, None
        if beat is not None:
            beat.close()
        t = self._thread
        if t is not None:
            t.join(min(10.0, self.interval_s + 5.0))

    def _loop(self) -> None:
        beat = self.beat
        if beat is not None:
            beat.guard(self._loop_body)
        else:
            self._loop_body()

    def _loop_body(self) -> None:
        # fleet rolling variant: replicas start offset by stagger so at
        # most one folds at a time and a poisoned swap (rolled back)
        # never hits the whole fleet in the same instant
        beat = self.beat
        if self.stagger_s > 0 and self._stop.wait(self.stagger_s):
            return
        while not self._stop.is_set():
            if beat is not None:
                beat.tick()
            try:
                self.tick()
            except Exception:
                self.last_outcome = "failed"
                self._m["ticks"].labels(outcome="failed").inc()
                _log.exception("refresh_tick_failed")
            if self._stop.wait(self.interval_s):
                return

    # -- one tick -----------------------------------------------------------
    def tick(self) -> str:
        """One refresh pass; returns the outcome label (also recorded
        in `pio_streaming_refresh_total`). Safe to call directly from
        tests — the loop is just pacing around this."""
        t0 = time.perf_counter()
        # background span: each tick (and the fold/rebuild inside it)
        # lands in the trace ring as kind="background" when tracing is on
        with trace.background("refresh_tick"):
            outcome = self._tick_inner()
        self.last_outcome = outcome
        self._m["ticks"].labels(outcome=outcome).inc()
        self._m["tick_s"].observe(time.perf_counter() - t0)
        return outcome

    def _tick_inner(self) -> str:
        server = self.server
        dep = server._dep
        if dep is None:
            return "no_deployment"
        located = self._locate(dep)
        if located is None:
            return "no_app"
        events, app_id, channel_id, ds_params = located
        # PIO_INGEST_SERVICE reroutes the delta scans below through the
        # shared ingest tier (watermark + find stay on the local store)
        from predictionio_tpu.ingest.client import maybe_remote
        events = maybe_remote(events)
        wm_now = events.ingest_watermark(app_id, channel_id)
        if wm_now is None:
            return "no_watermark"       # driver can't delta: stay passive
        if self._wm is None:
            # deploy-time baseline; pre-deploy stragglers ride the next
            # full retrain (module docstring, "Freshness accounting")
            self._wm = wm_now
            self._m["freshness"].set(0.0)
            return "baseline"
        if wm_now == self._wm:
            self._m["freshness"].set(0.0)
            return "noop"
        try:
            delta = scan_delta(events, app_id, channel_id, self._wm,
                               wm_now)
            fctx = FoldContext(
                store=events, app_id=app_id, channel_id=channel_id,
                since=self._wm, upto=wm_now,
                mesh=getattr(dep, "mesh", None), ds_params=ds_params)
            outcome = self._fold_and_swap(dep, delta, fctx)
        except DeltaInvalidated as e:
            _log.warning("delta_invalidated", reason=str(e))
            self._full_rebuild(dep)
            outcome = "full_rebuild"
            self._m["freshness"].set(0.0)
        except Exception:
            # commit failed and was rolled back (or fold itself blew
            # up): last-good keeps serving; do NOT advance the
            # watermark — the same delta retries next tick
            _log.exception("refresh_swap_rolled_back")
            return "rolled_back"
        self._wm = wm_now
        return outcome

    def _locate(self, dep) -> Optional[Tuple[object, int, object, dict]]:
        return locate_event_store(dep, self.server.ctx.registry)

    # -- fold + commit ------------------------------------------------------
    def _fold_and_swap(self, dep, delta: Delta,
                       fctx: FoldContext) -> str:
        if delta.empty:
            self._m["freshness"].set(0.0)
            return "noop"
        with trace.background("refresh_fold"):
            return self._fold_and_swap_inner(dep, delta, fctx)

    def _fold_and_swap_inner(self, dep, delta: Delta,
                             fctx: FoldContext) -> str:
        # phase 1 — compute ALL updated models host-side (no serving
        # impact; a crash here changes nothing the client sees)
        new_models = list(dep.models)
        swaps = []                      # (plan, new_item_factors)
        folded = False
        for i, (algo, model) in enumerate(zip(dep.algos, dep.models)):
            hook = getattr(algo, "fold_in", None)
            if hook is None or model is None:
                continue
            new_model = hook(model, delta, fctx)
            if new_model is None:
                continue
            new_models[i] = new_model
            folded = True
            factors = getattr(new_model, "item_factors", None)
            if factors is not None:
                swaps += [(plan, factors) for plan in algo.serve_plans()]
        if not folded:
            return "no_hooks"
        self._m["folded"].labels(side="user").inc(
            len(delta.touched_users))
        # phase 2 — commit: device swap + deployment publish, with
        # rollback to last-good on ANY failure (chaos seam included)
        done = []                       # (plan, previous_host_factors)
        try:
            faults().check("streaming.refresh.swap")
            for plan, factors in swaps:
                done.append((plan, plan.swap_factors(factors)))
            new_dep = self.server._refresh_deployment(dep, new_models)
            with self.server._dep_lock:
                self.server._dep = new_dep
        except Exception:
            for plan, old in done:
                plan.swap_factors(old)
            raise
        self._m["freshness"].set(
            max(0.0, time.time() - delta.newest_us / 1e6))  # lint: ok
        return "folded"

    # -- the full-scan fallback ---------------------------------------------
    def _full_rebuild(self, dep) -> None:
        """`DeltaInvalidated` => retrain in process from the complete
        store read (the watermark-keyed prepared cache keeps the scan
        cheap), hot-swap plans whose shapes survived, re-warm the rest,
        and publish. The serve path never sees a half-built state."""
        from predictionio_tpu.core.workflow import (
            engine_params_from_instance, warm_deploy,
        )
        from predictionio_tpu.ops.topk_sharded import serve_mesh_from_conf
        server = self.server
        ctx = server.ctx
        engine_params = engine_params_from_instance(dep.engine,
                                                    dep.instance)
        ds, prep, _, _ = dep.engine.make_components(engine_params)
        td = ds.read_training(ctx)
        pd = prep.prepare(ctx, td)
        new_models = [algo.train(ctx, pd) for algo in dep.algos]
        done, rewarm = [], []
        try:
            for algo, model in zip(dep.algos, new_models):
                factors = getattr(model, "item_factors", None)
                if factors is None:
                    continue
                for plan in algo.serve_plans():
                    if factors.shape != (plan.n_items, plan.rank):
                        rewarm.append((algo, model))
                        break
                    done.append((plan, plan.swap_factors(factors)))
            if rewarm:
                # shape changed (catalog grew): recompile is unavoidable.
                # Same mesh derivation and batch buckets as deploy time
                # (CoreWorkflow.prepare_deploy).
                conf = {**dict(getattr(dep.instance, "runtime_conf",
                                       None) or {}),
                        **dict(ctx.workflow_params.runtime_conf or {})}
                wbm = (server.config.batch_max
                       if getattr(server, "_batcher", None) is not None
                       else 1)
                warm_deploy([a for a, _ in rewarm],
                            [m for _, m in rewarm], wbm,
                            mesh=serve_mesh_from_conf(conf))
            new_dep = server._refresh_deployment(dep, new_models)
            with server._dep_lock:
                server._dep = new_dep
        except Exception:
            for plan, old in done:
                plan.swap_factors(old)
            raise
