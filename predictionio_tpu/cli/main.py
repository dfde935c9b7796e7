"""Argparse front-end: the `pio` console.

Parity: `tools/.../console/Console.scala:134-824` (grammar + dispatch) and
`console/Pio.scala` (command wiring). Storage configuration comes from the
same layered config as everything else (env / pio-env file / zero-config
sqlite default) via the process-default registry.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Optional

from predictionio_tpu.cli import ops


def _registry():
    from predictionio_tpu.data.storage import storage
    return storage()


def _emit(obj) -> None:
    print(json.dumps(obj, indent=2, default=str))


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio-tpu",
        description="predictionio_tpu console (the `pio` analog)")
    sub = p.add_subparsers(dest="command", required=True)

    # app ------------------------------------------------------------------
    app = sub.add_parser("app", help="manage apps").add_subparsers(
        dest="app_command", required=True)
    x = app.add_parser("new")
    x.add_argument("name")
    x.add_argument("--description")
    x.add_argument("--access-key", default="")
    app.add_parser("list")
    x = app.add_parser("show")
    x.add_argument("name")
    x = app.add_parser("delete")
    x.add_argument("name")
    x.add_argument("--force", "-f", action="store_true")
    x = app.add_parser("data-delete")
    x.add_argument("name")
    x.add_argument("--channel")
    x.add_argument("--all", action="store_true")
    x.add_argument("--force", "-f", action="store_true")
    x = app.add_parser("channel-new")
    x.add_argument("app_name")
    x.add_argument("channel_name")
    x = app.add_parser("channel-delete")
    x.add_argument("app_name")
    x.add_argument("channel_name")
    x.add_argument("--force", "-f", action="store_true")
    x = app.add_parser(
        "quota-set",
        help="persist a per-app serving admission override (rate/"
             "burst/concurrency/queue/weight); unset fields inherit "
             "the PIO_TENANT_* defaults")
    x.add_argument("name")
    x.add_argument("--rate", type=float,
                   help="token-bucket refill, requests/second")
    x.add_argument("--burst", type=float,
                   help="token-bucket capacity, requests")
    x.add_argument("--concurrency", type=int,
                   help="in-flight cap (0 = unlimited)")
    x.add_argument("--queue-max", type=int,
                   help="per-tenant micro-batch pending cap")
    x.add_argument("--weight", type=float,
                   help="weighted-fair drain weight (default 1.0)")
    x = app.add_parser("quota-show")
    x.add_argument("name")
    x = app.add_parser("quota-delete")
    x.add_argument("name")

    # accesskey ------------------------------------------------------------
    ak = sub.add_parser("accesskey", help="manage access keys"
                        ).add_subparsers(dest="ak_command", required=True)
    x = ak.add_parser("new")
    x.add_argument("app_name")
    x.add_argument("--key", default="")
    x.add_argument("--events", nargs="*", default=[])
    x = ak.add_parser("list")
    x.add_argument("app_name", nargs="?")
    x = ak.add_parser("delete")
    x.add_argument("key")

    # build / train / eval / deploy ----------------------------------------
    x = sub.add_parser("build", help="validate the engine variant")
    x.add_argument("--engine-json", default="engine.json")
    x = sub.add_parser("train")
    x.add_argument("--engine-json", default="engine.json")
    x.add_argument("--engine-factory")
    x.add_argument("--batch", default="")
    x.add_argument("--mesh", help="mesh spec, e.g. data=8 or data=4,model=2")
    x.add_argument("--skip-sanity-check", action="store_true")
    x.add_argument("--stop-after-read", action="store_true")
    x.add_argument("--stop-after-prepare", action="store_true")
    x.add_argument("--coordinator",
                   help="host:port of process 0 for multi-host training "
                        "(jax.distributed); or set PIO_TPU_COORDINATOR")
    x.add_argument("--num-processes", type=int)
    x.add_argument("--process-id", type=int)
    x.add_argument("--profile-dir",
                   help="write a jax.profiler device trace here "
                        "(TensorBoard-loadable); or set "
                        "PIO_TPU_PROFILE_DIR")
    x = sub.add_parser("eval")
    x.add_argument("evaluation", help="dotted path to an Evaluation")
    x.add_argument("params_generator", nargs="?",
                   help="dotted path to an EngineParamsGenerator")
    x.add_argument("--output-path")
    x = sub.add_parser("deploy")
    x.add_argument("--engine-json", default="engine.json")
    x.add_argument("--engine-factory")
    x.add_argument("--ip", default="0.0.0.0")
    x.add_argument("--port", type=int, default=8000)
    x.add_argument("--feedback", action="store_true")
    x.add_argument("--event-server-ip", default="localhost")
    x.add_argument("--event-server-port", type=int, default=7070)
    x.add_argument("--accesskey")
    x.add_argument("--batch-window-ms", type=int, default=0)
    x.add_argument("--replicas", type=int, default=1,
                   help="serve replicas behind the fleet control plane "
                        "(>1 enables health-gated routing + rolling "
                        "/reload; 0 starts a router-only control plane "
                        "fed by --join replicas)")
    x.add_argument("--join",
                   help="comma-separated router URLs: start this server "
                        "as a standalone fleet replica that registers "
                        "with (and heartbeats) every listed router, e.g. "
                        "--join http://router:8000,http://standby:8000")
    x.add_argument("--supervised", type=int, default=0, metavar="N",
                   help="run N replicas as supervised CHILD PROCESSES "
                        "behind a router-only control plane: a replica "
                        "that crashes or is SIGKILLed is respawned with "
                        "jittered backoff (crash loops circuit-break), "
                        "re-registers through the membership path, and "
                        "SIGTERM gives every child a graceful drain")
    x.add_argument("--advertise",
                   help="host:port other fleet hosts reach this process "
                        "at (default 127.0.0.1:<port>; required for "
                        "real cross-host fleets)")
    x.add_argument("--standby", action="store_true",
                   help="start a standby router: no local replicas, "
                        "learns membership from replica heartbeats, and "
                        "takes over the leadership lease when the "
                        "current leader's lease expires")
    x.add_argument("--autoscale", choices=["on", "off"],
                   help="with --supervised: let the router's control "
                        "loop grow/retire replica child processes off "
                        "its own tsdb ring — scale up on sustained "
                        "p99/queue-delay/burn/shed breach, drain down "
                        "on sustained idle, with hysteresis, cooldown "
                        "and flap damping (PIO_AUTOSCALE; thresholds "
                        "via PIO_AUTOSCALE_* env knobs)")
    x.add_argument("--autoscale-min", type=int,
                   help="autoscaler floor on supervised children "
                        "(PIO_AUTOSCALE_MIN, default 1)")
    x.add_argument("--autoscale-max", type=int,
                   help="autoscaler ceiling on supervised children "
                        "(PIO_AUTOSCALE_MAX, default 4)")
    x.add_argument("--member-name",
                   help="with --join: stable member name this replica "
                        "reports in heartbeats (the autoscaler "
                        "addresses scaled children by it)")
    x.add_argument("--mesh",
                   help="serving mesh spec. `items=8` forces the "
                        "mesh-sharded serve plan — item factors "
                        "partitioned row-wise across the device mesh "
                        "with on-device partial top-k + allgather "
                        "merge. `items=N@fleet` (with --replicas or "
                        "remote --join members) runs a CROSS-HOST "
                        "mesh: each fleet member owns catalog shard "
                        "i of N and the router merge re-top-ks their "
                        "partial results")
    x.add_argument("--refresh-interval", type=float, default=0.0,
                   help="streaming freshness: seconds between "
                        "background delta-scan + fold-in + hot-swap "
                        "ticks (0 = disabled; PIO_REFRESH_INTERVAL_S "
                        "applies when unset). Replicas of a fleet "
                        "stagger their ticks automatically")
    x.add_argument("--tenancy", choices=["on", "off"],
                   help="multi-tenant admission on /queries.json: "
                        "authenticate app access keys, enforce per-app "
                        "rate/concurrency quotas (429 + Retry-After), "
                        "and drain the micro-batch queue weighted-fair "
                        "across apps (default: the PIO_TENANCY env/"
                        "config knob, off when unset)")
    x.add_argument("--tenant-rate", type=float,
                   help="default per-app rate quota, requests/second "
                        "(PIO_TENANT_RATE)")
    x.add_argument("--tenant-burst", type=float,
                   help="default per-app token-bucket burst "
                        "(PIO_TENANT_BURST)")
    x.add_argument("--tenant-concurrency", type=int,
                   help="default per-app in-flight cap, 0 = unlimited "
                        "(PIO_TENANT_CONCURRENCY)")
    x.add_argument("--tenant-queue-max", type=int,
                   help="default per-app micro-batch pending cap "
                        "(PIO_TENANT_QUEUE_MAX)")
    x.add_argument("--quality", choices=["on", "off"],
                   help="prediction-quality observatory: score "
                        "sketches + drift gauges on the serve path, "
                        "the feedback-join reward loop (with "
                        "--feedback), and the reload canary gate "
                        "(default: the PIO_QUALITY env knob, on when "
                        "unset)")
    x.add_argument("--attribution-s", type=float, default=0.0,
                   help="feedback-join attribution window, seconds "
                        "(PIO_ATTRIBUTION_S, default 300)")
    x.add_argument("--canary-sample", type=int, default=-1,
                   help="traced queries replayed old-vs-new on each "
                        "reload (PIO_CANARY_SAMPLE, default 16; 0 "
                        "disables the canary)")
    x.add_argument("--canary-min-overlap", type=float, default=-1.0,
                   help="abort a (rolling) reload when the replayed "
                        "top-k overlap falls below this "
                        "(PIO_CANARY_MIN_OVERLAP, default 0 = "
                        "report-only)")
    x = sub.add_parser("undeploy")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=8000)
    x.add_argument("--accesskey", default="",
                   help="server key when /stop is key-protected")
    x = sub.add_parser(
        "redeploy",
        help="train, then hot-reload the running prediction server "
             "(the cron recipe from examples/redeploy-script/"
             "redeploy.sh: put 'pio-tpu redeploy' in crontab)")
    x.add_argument("--engine-json", default="engine.json")
    x.add_argument("--engine-factory")
    x.add_argument("--mesh")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=8000)
    x.add_argument("--accesskey", default="",
                   help="server key when /reload is key-protected")
    x = sub.add_parser("batchpredict")
    x.add_argument("--engine-json", default="engine.json")
    x.add_argument("--engine-factory")
    x.add_argument("--input", default="batchpredict-input.json")
    x.add_argument("--output", default="batchpredict-output.json")
    x.add_argument("--query-partitions", type=int, default=1024,
                   help="device batch chunk size")

    # servers --------------------------------------------------------------
    x = sub.add_parser("eventserver")
    x.add_argument("--ip", default="0.0.0.0")
    x.add_argument("--port", type=int, default=7070)
    x.add_argument("--stats", action="store_true")
    x = sub.add_parser(
        "ingestd", help="disaggregated scan/prep service: owns the "
                        "columnar scan and streams CRC-framed column "
                        "blocks to trainers/refreshers")
    x.add_argument("--ip", default="0.0.0.0")
    x.add_argument("--port", type=int, default=7200)
    x.add_argument("--block-rows", type=int, default=0,
                   help="rows per streamed block "
                        "(default PIO_INGEST_BLOCK_ROWS or 65536)")
    x.add_argument("--workers", type=int, default=None,
                   help="scan worker pool width "
                        "(default PIO_INGEST_WORKERS)")
    x.add_argument("--join", default="",
                   help="comma-separated router URLs to register with "
                        "as a role=ingest fleet member")
    x.add_argument("--advertise", default="",
                   help="host:port other hosts reach this service at")
    x = sub.add_parser("dashboard")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=9000)
    x = sub.add_parser("adminserver")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=7071)
    x = sub.add_parser("top", help="terminal observatory view of a running "
                       "server (qps, p50/p99, shed, burn, RSS, top frames "
                       "from /tsdb.json + /profile.json)")
    x.add_argument("--host", default="127.0.0.1")
    x.add_argument("--port", type=int, default=8000)
    x.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                   help="redraw every N seconds (0 = one-shot)")

    # service ops (bin/pio-start-all, pio-stop-all, pio-daemon) ------------
    x = sub.add_parser("start-all", help="start event server + dashboard + "
                                         "admin server as daemons")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--event-server-port", type=int, default=7070)
    x.add_argument("--dashboard-port", type=int, default=9000)
    x.add_argument("--admin-port", type=int, default=7071)
    x.add_argument("--pid-dir")
    x.add_argument("--log-dir")
    x = sub.add_parser("stop-all", help="stop all pidfile-tracked services")
    x.add_argument("--pid-dir")
    x = sub.add_parser("daemon", help="run a pio-tpu subcommand detached "
                                      "with a pidfile (bin/pio-daemon)")
    x.add_argument("--name", required=True)
    x.add_argument("--pid-dir")
    x.add_argument("--log-dir")
    x.add_argument("daemon_argv", nargs=argparse.REMAINDER,
                   help="subcommand to run, e.g. -- eventserver --port 7070")

    # chaos ----------------------------------------------------------------
    x = sub.add_parser(
        "chaos",
        help="self-healing drills: timed fault scenarios (thread "
             "stall/death, lease failover, memory pressure, replica "
             "SIGKILL) against a real loopback topology, gated on "
             "invariants — non-zero exit on any violation")
    chaos = x.add_subparsers(dest="chaos_command", required=True)
    chaos.add_parser("list", help="list scenarios")
    y = chaos.add_parser("run", help="run one scenario (or 'all')")
    y.add_argument("scenario", help="scenario name, or 'all'")
    y.add_argument("--json", action="store_true",
                   help="machine-readable reports on stdout")

    # loadsim --------------------------------------------------------------
    x = sub.add_parser(
        "loadsim",
        help="trace-driven open-loop traffic generator: per-app "
             "non-homogeneous Poisson arrivals from declarative phases "
             "(diurnal sinusoid, flash crowd, hot-key pivot), Zipf "
             "user/item skew over millions of simulated users, mixed "
             "query shapes incl. binary frames — coordinated-omission "
             "safe, bench-format JSON results")
    x.add_argument("loadsim_argv", nargs=argparse.REMAINDER,
                   help="arguments for the simulator, e.g. -- "
                        "--scenario flash-crowd --port 8000 --scale 0.2 "
                        "(see `pio-tpu loadsim -- --help`)")

    # misc -----------------------------------------------------------------
    x = sub.add_parser(
        "doctor",
        help="durability check: fsck every bound store (corrupt model "
             "blobs, torn journal tails, stale indexes) + the stale-"
             "instance janitor; --repair to act")
    x.add_argument("--repair", action="store_true",
                   help="quarantine/truncate/rebuild/fail instead of "
                        "just reporting")
    x.add_argument("--stale-after", type=float, default=None,
                   help="seconds before an INIT/TRAINING instance with "
                        "no heartbeat counts as dead (default 900)")
    sub.add_parser("status")
    sub.add_parser("version")
    x = sub.add_parser("import")
    x.add_argument("--appid", type=int, required=True)
    x.add_argument("--channel", type=int, default=None)
    x.add_argument("--input", required=True)
    x.add_argument("--format", choices=["json", "parquet"], default="json")
    x = sub.add_parser("export")
    x.add_argument("--appid", type=int, required=True)
    x.add_argument("--channel", type=int, default=None)
    x.add_argument("--output", required=True)
    x.add_argument("--format", choices=["json", "parquet"], default="json")
    x = sub.add_parser("run", help="run a dotted-path function with storage "
                                   "configured (console run analog)")
    x.add_argument("target", help="module.function")
    sub.add_parser("shell",
                   help="interactive Python with the storage registry "
                        "and core API preloaded (bin/pio-shell analog)")
    x = sub.add_parser("template",
                       help="scaffold a new engine directory "
                            "(commands/Template.scala analog)")
    x.add_argument("template_command", choices=["new"])
    x.add_argument("directory")
    x.add_argument("--base", default="recommendation",
                   help="bundled template to base the scaffold on")
    return p


def _serve_forever(server) -> None:   # pragma: no cover - signal loop
    # SIGTERM/SIGINT route through the server's graceful stop() drain
    # (accepted requests finish, replicas deregister) — the supervisor
    # and `kill` both get a clean exit instead of a mid-request death
    from predictionio_tpu.serving.server import install_signal_handlers
    done = {"flag": False}

    def _on_stopped():
        done["flag"] = True

    install_signal_handlers(server, on_stopped=_on_stopped)
    try:
        while not done["flag"] and server.is_running():
            time.sleep(0.2)
    finally:
        if server.is_running():
            server.shutdown()


def main(argv: Optional[list] = None) -> int:
    args = build_parser().parse_args(argv)
    cmd = args.command
    try:
        if cmd == "app":
            return _app(args)
        if cmd == "accesskey":
            return _accesskey(args)
        if cmd == "build":
            variant = ops.load_variant(args.engine_json)
            from predictionio_tpu.core.workflow import resolve_engine
            factory = ops.resolve_factory_name(variant, None,
                                               args.engine_json)
            engine = resolve_engine(factory)
            engine.engine_params_from_variant(variant)
            _emit({"message": "Engine variant is valid",
                   "engineFactory": factory})
            return 0
        if cmd == "train":
            _emit(ops.train(
                _registry(), engine_json=args.engine_json,
                engine_factory=args.engine_factory, batch=args.batch,
                mesh=args.mesh, skip_sanity_check=args.skip_sanity_check,
                stop_after_read=args.stop_after_read,
                stop_after_prepare=args.stop_after_prepare,
                coordinator=args.coordinator,
                num_processes=args.num_processes,
                process_id=args.process_id,
                profile_dir=args.profile_dir))
            # the timing report goes to stderr: stdout stays pure JSON
            # for scripted callers parsing the result above
            from predictionio_tpu.obs import train_report
            print(train_report(), file=sys.stderr)
            return 0
        if cmd == "eval":
            _emit(ops.run_eval(_registry(), args.evaluation,
                               args.params_generator, args.output_path))
            return 0
        if cmd == "deploy":
            from predictionio_tpu.serving import (
                FleetServer, PredictionServer, ReplicaAgent, ServerConfig,
                fleet_config_from_env,
            )
            from predictionio_tpu.tenancy import TenancyConfig
            variant = ops.load_variant(args.engine_json)
            factory = ops.resolve_factory_name(variant, args.engine_factory,
                                               args.engine_json)
            registry = _registry()
            # layered: pio-env/env PIO_TENANCY + PIO_TENANT_* defaults,
            # explicit deploy flags win
            tenancy_overrides = {}
            if args.tenancy:
                tenancy_overrides["enabled"] = args.tenancy == "on"
            if args.tenant_rate is not None:
                tenancy_overrides["rate"] = args.tenant_rate
            if args.tenant_burst is not None:
                tenancy_overrides["burst"] = args.tenant_burst
            if args.tenant_concurrency is not None:
                tenancy_overrides["concurrency"] = args.tenant_concurrency
            if args.tenant_queue_max is not None:
                tenancy_overrides["queue_max"] = args.tenant_queue_max
            tenancy = TenancyConfig.from_env(registry.config,
                                             **tenancy_overrides)
            config = ServerConfig(
                ip=args.ip, port=args.port, engine_factory=factory,
                engine_variant=variant.get("id", "default"),
                feedback=args.feedback,
                event_server_ip=args.event_server_ip,
                event_server_port=args.event_server_port,
                access_key=args.accesskey,
                batch_window_ms=args.batch_window_ms,
                mesh=args.mesh or "",
                refresh_interval_s=args.refresh_interval,
                server_key=registry.config.get("PIO_SERVER_ACCESS_KEY", ""),
                tenancy=tenancy,
                quality=(args.quality == "on" if args.quality else None),
                attribution_s=args.attribution_s,
                canary_sample=args.canary_sample,
                canary_min_overlap=args.canary_min_overlap)
            if args.supervised > 0 and not args.join:
                # router-only control plane + N supervised replica child
                # processes: each child re-runs this CLI with the same
                # deploy flags, minus supervision/port, plus --join back
                # here on an ephemeral port
                from predictionio_tpu.serving.supervisor import (
                    ChildSpec, Supervisor, child_argv_from_parent,
                    require_chips,
                )
                from predictionio_tpu.utils.device import (
                    visible_chip_count,
                )
                # every child holds a chip; this router holds none.
                # More children than chips is refused before anything
                # starts, and caps the autoscaler below
                chips = visible_chip_count()
                require_chips(args.supervised, chips)
                server = FleetServer(
                    config, fleet_config_from_env(
                        registry.config, replicas=0,
                        advertise=args.advertise or ""),
                    registry=registry)
                port = server.start()
                parent_argv = list(argv) if argv is not None \
                    else sys.argv[1:]
                router_url = f"http://127.0.0.1:{port}"

                def _child_spec(name: str) -> ChildSpec:
                    return ChildSpec(name, child_argv_from_parent(
                        parent_argv, router_url,
                        extra=("--member-name", name)))

                sup = Supervisor(
                    [_child_spec(f"replica{i}")
                     for i in range(args.supervised)], chips=chips)
                sup.start()
                scaling = ""
                if args.autoscale == "on" or (
                        args.autoscale is None
                        and registry.config.get("PIO_AUTOSCALE", "")
                        in ("1", "true", "on")):
                    # the control loop rides the router's own scraper
                    # tick (FleetServer._autoscale_tick) — attaching
                    # the instance is all the wiring there is
                    from predictionio_tpu.serving.autoscaler import (
                        AutoscaleConfig, Autoscaler,
                    )
                    acfg = AutoscaleConfig.from_env()
                    acfg = dataclasses.replace(
                        acfg, enabled=True,
                        min_children=(args.autoscale_min
                                      if args.autoscale_min is not None
                                      else acfg.min_children),
                        max_children=(args.autoscale_max
                                      if args.autoscale_max is not None
                                      else acfg.max_children))
                    if chips is not None:
                        acfg = dataclasses.replace(
                            acfg, max_children=min(acfg.max_children,
                                                   chips))
                    server.autoscaler = Autoscaler(
                        acfg, supervisor=sup, fleet=server,
                        spec_factory=_child_spec)
                    scaling = (f", autoscale "
                               f"[{acfg.min_children}, "
                               f"{acfg.max_children}]")
                print(f"Fleet control plane started on {args.ip}:{port} "
                      f"({args.supervised} supervised replica "
                      f"processes{scaling})", flush=True)
                try:
                    _serve_forever(server)
                finally:
                    sup.stop()
                return 0
            if args.join:
                # standalone replica: serve locally, register with (and
                # heartbeat) every router listed. The joined routers are
                # the auth + quota boundary; this replica honors their
                # X-PIO-App assertion — verified against the shared
                # PIO_SERVER_ACCESS_KEY — and applies only the fairness
                # layer (no key on either side = header refused, key
                # auth re-runs here)
                config = dataclasses.replace(
                    config, tenancy=tenancy.replica_variant())
                server = PredictionServer(config, registry=registry)
                port = server.start()
                fc = fleet_config_from_env(registry.config)
                agent = ReplicaAgent(
                    server, args.join.split(","),
                    advertise=args.advertise or "",
                    server_key=config.server_key,
                    heartbeat_s=fc.heartbeat_s,
                    member_name=args.member_name or "")
                agent.start()
                print(f"Fleet replica started on {args.ip}:{port}, "
                      f"joined {args.join}", flush=True)
                try:
                    _serve_forever(server)
                finally:
                    agent.stop()
                return 0
            if args.replicas > 1 or args.replicas == 0 or args.standby:
                replicas = 0 if args.standby else args.replicas
                server = FleetServer(
                    config, fleet_config_from_env(
                        registry.config, replicas=replicas,
                        standby=args.standby,
                        advertise=args.advertise or ""),
                    registry=registry)
                port = server.start()
                role = "standby router" if args.standby else "control plane"
                print(f"Fleet {role} started on {args.ip}:{port} "
                      f"({replicas} local replicas)", flush=True)
            else:
                server = PredictionServer(config, registry=registry)
                port = server.start()
                print(f"Engine server started on {args.ip}:{port}",
                      flush=True)
            _serve_forever(server)
            return 0
        if cmd == "undeploy":
            ok = ops.undeploy(args.ip, args.port,
                              access_key=args.accesskey)
            print("Undeployed" if ok else "No server responded")
            return 0 if ok else 1
        if cmd == "redeploy":
            _emit(ops.train(
                _registry(), engine_json=args.engine_json,
                engine_factory=args.engine_factory, mesh=args.mesh))
            ok = ops.reload_server(args.ip, args.port,
                                   access_key=args.accesskey)
            print("Reloaded" if ok
                  else "Trained, but no server responded to /reload")
            return 0 if ok else 1
        if cmd == "batchpredict":
            _emit(ops.batchpredict(
                _registry(), engine_json=args.engine_json,
                engine_factory=args.engine_factory,
                input_path=args.input, output_path=args.output,
                chunk_size=args.query_partitions))
            return 0
        if cmd == "eventserver":
            from predictionio_tpu.data.eventserver import (
                EventServer, EventServerConfig,
            )
            server = EventServer(
                EventServerConfig(ip=args.ip, port=args.port,
                                  stats=args.stats), _registry())
            port = server.start()
            print(f"Event server started on {args.ip}:{port}", flush=True)
            _serve_forever(server)
            return 0
        if cmd == "ingestd":
            from predictionio_tpu.ingest.service import (
                IngestConfig, IngestService,
            )
            server = IngestService(
                IngestConfig(ip=args.ip, port=args.port,
                             block_rows=args.block_rows,
                             workers=args.workers), _registry())
            port = server.start()
            print(f"Ingest service started on {args.ip}:{port}",
                  flush=True)
            agent = None
            if args.join:
                from predictionio_tpu.serving.fleet import ReplicaAgent
                agent = ReplicaAgent(
                    server, args.join.split(","),
                    advertise=args.advertise or f"{args.ip}:{port}",
                    role="ingest")
                agent.start()
            try:
                _serve_forever(server)
            finally:
                if agent is not None:
                    agent.stop()
            return 0
        if cmd == "dashboard":
            from predictionio_tpu.tools.dashboard import (
                Dashboard, DashboardConfig,
            )
            server = Dashboard(DashboardConfig(ip=args.ip, port=args.port),
                               _registry())
            port = server.start()
            print(f"Dashboard started on {args.ip}:{port}", flush=True)
            _serve_forever(server)
            return 0
        if cmd == "adminserver":
            from predictionio_tpu.tools.admin import AdminConfig, AdminServer
            server = AdminServer(AdminConfig(ip=args.ip, port=args.port),
                                 _registry())
            port = server.start()
            print(f"Admin server started on {args.ip}:{port}", flush=True)
            _serve_forever(server)
            return 0
        if cmd == "top":
            from predictionio_tpu.tools.admin import run_top
            return run_top(args.host, args.port, watch_s=args.watch)
        if cmd == "status":
            _emit(ops.status(_registry()))
            return 0
        if cmd == "loadsim":
            from predictionio_tpu.tools import loadsim
            sim_argv = list(args.loadsim_argv)
            if sim_argv and sim_argv[0] == "--":
                sim_argv = sim_argv[1:]
            return loadsim.main(sim_argv)
        if cmd == "chaos":
            from predictionio_tpu.resilience import scenarios
            if args.chaos_command == "list":
                _emit([{"name": n,
                        "description": scenarios.get(n).description}
                       for n in scenarios.names()])
                return 0
            wanted = (scenarios.names() if args.scenario == "all"
                      else [args.scenario])
            unknown = [n for n in wanted if n not in scenarios.names()]
            if unknown:
                print(f"[ERROR] unknown scenario(s): "
                      f"{', '.join(unknown)}; have: "
                      f"{', '.join(scenarios.names())}", file=sys.stderr)
                return 2
            trained = scenarios.train_tiny()
            rc = 0
            reports = []
            for n in wanted:
                report = scenarios.run(n, trained=trained)
                reports.append(report.to_json())
                if not report.ok:
                    rc = 1
                if not args.json:
                    print(scenarios.format_report(report), flush=True)
            if args.json:
                _emit(reports)
            return rc
        if cmd == "doctor":
            report = ops.doctor(_registry(), repair=args.repair,
                                stale_after_s=args.stale_after)
            _emit(report)
            # rc 1 = damage found and not repaired (report-only mode or
            # a repair that could not act); clean or fully repaired = 0
            return 1 if report["unrepaired"] else 0
        if cmd == "start-all":
            from predictionio_tpu.cli import service
            _emit(service.start_all(
                ip=args.ip, event_server_port=args.event_server_port,
                dashboard_port=args.dashboard_port,
                admin_port=args.admin_port,
                pid_dir=args.pid_dir, log_dir=args.log_dir))
            return 0
        if cmd == "stop-all":
            from predictionio_tpu.cli import service
            _emit(service.stop_all(pid_dir=args.pid_dir))
            return 0
        if cmd == "daemon":
            from predictionio_tpu.cli import service
            argv_rest = list(args.daemon_argv)
            if argv_rest and argv_rest[0] == "--":   # only the separator
                argv_rest = argv_rest[1:]
            if not argv_rest:
                raise ValueError("daemon needs a subcommand after --")
            _emit(service.daemonize(argv_rest, name=args.name,
                                    pid_dir=args.pid_dir,
                                    log_dir=args.log_dir))
            return 0
        if cmd == "version":
            import predictionio_tpu
            print(predictionio_tpu.__version__)
            return 0
        if cmd == "import":
            n = ops.import_events(_registry(), app_id=args.appid,
                                  channel_id=args.channel,
                                  input_path=args.input,
                                  format=args.format)
            _emit({"imported": n})
            return 0
        if cmd == "export":
            n = ops.export_events(_registry(), app_id=args.appid,
                                  channel_id=args.channel,
                                  output_path=args.output,
                                  format=args.format)
            _emit({"exported": n})
            return 0
        if cmd == "template":
            path = ops.template_new(args.directory, base=args.base)
            _emit({"message": f"Engine scaffold created at {path}",
                   "next": "edit engine.json, then: pio-tpu build && "
                           "pio-tpu train"})
            return 0
        if cmd == "run":
            import importlib
            module_name, _, attr = args.target.rpartition(".")
            fn = getattr(importlib.import_module(module_name), attr)
            result = fn()
            if result is not None:
                _emit(result)
            return 0
        if cmd == "shell":
            # bin/pio-shell analog: a REPL with the storage registry
            # and core API in scope (the reference drops users into a
            # spark-shell with pio jars on the classpath)
            import code

            import predictionio_tpu
            from predictionio_tpu import core, data, models, ops as tops
            registry = _registry()
            ns = {"predictionio_tpu": predictionio_tpu, "core": core,
                  "data": data, "models": models, "ops": tops,
                  "registry": registry,
                  "events": registry.get_events()}
            banner = ("pio-tpu shell - preloaded: registry (storage "
                      "registry), events (event store), core, data, "
                      "models, ops")
            code.interact(banner=banner, local=ns)
            return 0
    except (ValueError, OSError) as e:
        print(f"[ERROR] {e}", file=sys.stderr)
        return 1
    print(f"Unknown command {cmd}", file=sys.stderr)
    return 1


def _app(args) -> int:
    registry = _registry()
    c = args.app_command
    if c == "new":
        _emit(ops.app_new(registry, args.name, description=args.description,
                          access_key=args.access_key))
    elif c == "list":
        _emit(ops.app_list(registry))
    elif c == "show":
        _emit(ops.app_show(registry, args.name))
    elif c == "delete":
        ops.app_delete(registry, args.name, force=args.force)
        _emit({"message": f"App {args.name} deleted"})
    elif c == "data-delete":
        ops.app_data_delete(registry, args.name, channel=args.channel,
                            all_channels=args.all, force=args.force)
        _emit({"message": f"App {args.name} data deleted"})
    elif c == "channel-new":
        _emit(ops.channel_new(registry, args.app_name, args.channel_name))
    elif c == "channel-delete":
        ops.channel_delete(registry, args.app_name, args.channel_name,
                           force=args.force)
        _emit({"message": f"Channel {args.channel_name} deleted"})
    elif c == "quota-set":
        _emit(ops.app_quota_set(
            registry, args.name, rate=args.rate, burst=args.burst,
            concurrency=args.concurrency, queue_max=args.queue_max,
            weight=args.weight))
    elif c == "quota-show":
        _emit(ops.app_quota_show(registry, args.name))
    elif c == "quota-delete":
        ops.app_quota_delete(registry, args.name)
        _emit({"message": f"Quota override for {args.name} deleted"})
    return 0


def _accesskey(args) -> int:
    registry = _registry()
    c = args.ak_command
    if c == "new":
        _emit(ops.accesskey_new(registry, args.app_name, key=args.key,
                                events=args.events))
    elif c == "list":
        _emit(ops.accesskey_list(registry, args.app_name))
    elif c == "delete":
        ops.accesskey_delete(registry, args.key)
        _emit({"message": "Deleted"})
    return 0


def entrypoint() -> None:   # pragma: no cover - console-script shim
    """`pio-tpu` console script (pyproject [project.scripts])."""
    sys.exit(main())


if __name__ == "__main__":   # pragma: no cover
    sys.exit(main())
