"""Fleet control plane: replica-set serving, cross-host membership,
lease-based leader handoff, zero-downtime rolling reloads.

`pio-tpu deploy --replicas N` puts N in-process `PredictionServer`
workers (each with its own micro-batcher, deployment, and loopback
port) behind this router. `pio-tpu deploy --join http://router:8000`
starts a STANDALONE replica anywhere on the network that registers
itself with the router(s) and heartbeats; in-process workers and
remote members live in the same membership table and are routed,
health-gated, and rolled identically. The control plane:

  - health-gates routing on heartbeat age + probe suspicion: a member
    serves traffic only while admitted. Remote members heartbeat
    `POST /fleet/heartbeat` (model id + readiness); the monitor thread
    probes `/ready` every `health_interval_s`. Ejection needs BOTH
    `eject_threshold` consecutive suspicions AND a stale heartbeat
    (probes alone can lie during a partition) — except data-path
    evidence (connection errors / 5xx seen while routing), which
    ejects on the threshold alone. First healthy probe or ready
    heartbeat re-admits.
  - routes `/queries.json` round-robin over admitted members and
    RETRIES connection-level failures on the next healthy member, so
    a member dying mid-request costs the client nothing; HTTP error
    responses (the member answered — a 503 shed, a 400 bad query)
    pass through untouched. A request whose deadline budget is
    already spent is shed with 504 BEFORE dialing
    (`pio_shed_total{surface="deadline"}`).
  - elects a LEADER through a TTL lease in the metadata store
    (`data.storage.base.Leases`): every router — including standbys
    started with `--standby` — runs the same acquire/renew loop, and
    the CAS in the store guarantees at most one holder. Non-leaders
    307-redirect `/queries.json` to the leader and refuse `/reload`,
    so at most one router ever rolls the fleet (split-brain safe even
    when routers can't see each other). When the leader dies, its
    lease expires and a standby takes over within ~`lease_ttl_s`,
    rebuilding membership from heartbeats (remote agents beat ALL
    routers) and the persisted member snapshot.
  - implements rolling `/reload` (leader-only): one member at a time
    is ejected from routing, drained, reloaded (the replica's own
    last-good rollback + warm_deploy apply inside its /reload),
    probed, and re-admitted before the next begins. Progress is
    journaled through the lease row, so a leader that dies mid-roll
    hands the remaining members to the next leader, which resumes the
    roll — a roll always completes or rolls back, never stalls
    half-applied. A member that DIES mid-reload is left ejected and
    the roll continues; a member whose load FAILS (HTTP 500, rolled
    back to last-good) is re-admitted on the old model and the roll
    ABORTS; a member that is partitioned away (ejected and
    unreachable) is SKIPPED — ejected from routing, not rolled.

Partition chaos seams (`resilience.faults`): `fleet.net.<member>.heartbeat`
drops probes and heartbeats for a member, `fleet.net.<member>.data`
drops its proxied query traffic; arming one or both simulates the
partition classes the membership logic must survive.

One fsck/janitor sweep runs per fleet (the control plane's; replicas
are built with `startup_check=False`), as does the single scheduled
background fsck thread (PIO_FSCK_INTERVAL_S).
"""

from __future__ import annotations

import base64
import dataclasses
import json
import re
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence

from predictionio_tpu.data.storage.base import Model, StorageError
from predictionio_tpu.obs import MetricsRegistry, get_logger
from predictionio_tpu.obs import trace
from predictionio_tpu.resilience import (
    DeadlineExceeded, OverloadedError, current_deadline, faults,
)
from predictionio_tpu.serving.server import PredictionServer, ServerConfig
from predictionio_tpu.utils.http import (
    HTTPError, HTTPServerBase, Request, Response,
)
from predictionio_tpu.utils.wire import (
    BIN_CONTENT_TYPE, HTTPConnectionPool, decode_bin_query,
)

_log = get_logger("serving.fleet")

# headers forwarded verbatim to the replica (deadline propagation,
# request-id correlation, auth, trace context — the router's OWN
# asserted X-PIO-Trace, layered via extra_headers, wins over a
# client-supplied one)
_FORWARD_HEADERS = ("X-PIO-Deadline-Ms", "X-Request-ID", "Authorization",
                    "Content-Type", "X-PIO-App", "X-PIO-Trace")

# reserved model-store id for the membership snapshot (per variant);
# fsck's divergence sweep reports but never deletes unknown ids, so the
# blob is safe alongside real model envelopes
_MEMBERS_BLOB_PREFIX = "__fleet_members__"


def measure_store_rtt(leases, holder: str, samples: int = 3) -> float:
    """Median CAS round-trip of the lease store, measured with a
    throwaway probe lease. The lease TTL and heartbeat cadence are only
    meaningful when they dwarf this RTT — a TTL within a few RTTs of
    the store flaps leadership on every storage hiccup."""
    name = f"__rtt_probe__{holder or 'fleet'}"
    times = []
    for _ in range(max(1, samples)):
        t0 = time.perf_counter()
        try:
            leases.acquire(name, holder, 1.0)
            leases.release(name, holder)
        except Exception:
            continue              # a failed probe measures nothing
        times.append(time.perf_counter() - t0)
    if not times:
        return 0.0
    times.sort()
    return times[len(times) // 2]


@dataclass
class FleetConfig:
    """Control-plane knobs (the ServerConfig carries everything the
    replicas themselves need)."""
    replicas: int = 3
    # /ready probe cadence for the health monitor
    health_interval_s: float = 1.0
    # consecutive suspicions (probe, connection, 5xx) before ejection
    # (env: PIO_FLEET_SUSPECT_N)
    eject_threshold: int = 3
    # per-attempt proxy timeout when the request carries no deadline
    proxy_timeout_s: float = 30.0
    # rolling reload: max wait for a replica's in-flight requests
    drain_timeout_s: float = 10.0
    # expected remote-heartbeat cadence; 0 = derive from
    # health_interval_s (env: PIO_FLEET_HEARTBEAT_S)
    heartbeat_s: float = 0.0
    # leadership lease TTL; a dead leader's lease expires after this
    # and a standby takes over (env: PIO_FLEET_LEASE_TTL_S)
    lease_ttl_s: float = 10.0
    # standby router: no local replicas, contends for the lease
    standby: bool = False
    # address other hosts reach this router at ("host:port");
    # default 127.0.0.1:<bound port> (single-host fleets)
    advertise: str = ""
    # per-member /reload call budget during a roll
    reload_timeout_s: float = 120.0

    def effective_heartbeat_s(self) -> float:
        return self.heartbeat_s if self.heartbeat_s > 0 \
            else self.health_interval_s


def fleet_config_from_env(cfg: Mapping[str, str], **overrides) -> FleetConfig:
    """FleetConfig from environment-style config (the CLI path). Env
    knobs: PIO_FLEET_LEASE_TTL_S, PIO_FLEET_HEARTBEAT_S,
    PIO_FLEET_SUSPECT_N; explicit `overrides` win."""
    kw: Dict[str, object] = {}
    try:
        if cfg.get("PIO_FLEET_LEASE_TTL_S"):
            kw["lease_ttl_s"] = float(cfg["PIO_FLEET_LEASE_TTL_S"])  # lint: ok — host str
        if cfg.get("PIO_FLEET_HEARTBEAT_S"):
            kw["heartbeat_s"] = float(cfg["PIO_FLEET_HEARTBEAT_S"])  # lint: ok — host str
        if cfg.get("PIO_FLEET_SUSPECT_N"):
            kw["eject_threshold"] = int(cfg["PIO_FLEET_SUSPECT_N"])  # lint: ok — host str
    except ValueError as e:
        raise ValueError(f"bad PIO_FLEET_* value: {e}") from e
    kw.update(overrides)
    return FleetConfig(**kw)


class _Replica:
    """One fleet member and its routing state — either a managed
    in-process PredictionServer worker (`server` set, loopback port) or
    a REMOTE replica that registered over HTTP (`server` is None; all
    the control plane knows is its address and its heartbeats)."""

    def __init__(self, index: int, server: Optional[PredictionServer] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self.index = index
        self.server = server
        self.host = host
        self.port = port
        self.lock = threading.Lock()
        self.admitted = False
        # serving|ejected|reloading|dead|retiring (retiring = graceful
        # scale-down drain: out of rotation, NOT suspicion)
        self.state = "starting"
        self.failures = 0         # consecutive probe/route suspicions
        self.inflight = 0
        self.last_beat = time.monotonic()
        self.ejected_at = 0.0     # monotonic stamp of last eject evidence
        self.model_id = ""
        self.name = ""            # supervisor child name, from heartbeats
        self.shard = ""           # mesh shard owned ("i/n"), "" = whole
        self.role = "serve"       # serve|ingest: only serve joins rotation

    @property
    def key(self) -> str:
        return f"{self.host}:{self.port}"

    @property
    def remote(self) -> bool:
        return self.server is None

    def beat(self, model_id: Optional[str] = None) -> None:
        with self.lock:
            self.last_beat = time.monotonic()
            if model_id is not None:
                self.model_id = model_id

    def beat_age(self) -> float:
        return time.monotonic() - self.last_beat

    def running(self) -> bool:
        """In-process: the server object knows. Remote: only probes and
        heartbeats do — a remote member is running unless marked dead."""
        if self.server is not None:
            return self.server.is_running()
        return self.state != "dead"

    def snapshot(self) -> dict:
        with self.lock:
            return {"replica": self.index, "port": self.port,
                    "member": f"{self.host}:{self.port}",
                    "remote": self.server is None,
                    "state": self.state, "admitted": self.admitted,
                    "failures": self.failures, "inflight": self.inflight,
                    "model": self.model_id, "name": self.name,
                    "shard": self.shard, "role": self.role,
                    "beat_age_s": round(time.monotonic() - self.last_beat, 3)}


class FleetServer(HTTPServerBase):
    """The tiny control plane in front of N PredictionServer members."""

    def __init__(self, config: ServerConfig,
                 fleet: Optional[FleetConfig] = None, registry=None,
                 plugins: Optional[Sequence] = None, engine=None,
                 metrics: Optional[MetricsRegistry] = None):
        super().__init__(host=config.ip, port=config.port, metrics=metrics,
                         default_deadline_ms=config.default_deadline_ms,
                         max_inflight=config.max_inflight)
        from predictionio_tpu.core import RuntimeContext
        from predictionio_tpu.utils.security import KeyAuthentication

        self.config = config
        self.fleet = fleet if fleet is not None else FleetConfig()
        # cross-host serve mesh: `--mesh items=N@fleet` makes this
        # router a MERGE point over N member-owned catalog shards
        # (in-process replicas are auto-assigned shard i%N; remote
        # members declare theirs via heartbeats). 0 = plain routing.
        from predictionio_tpu.parallel.mesh import parse_fleet_mesh
        parsed = parse_fleet_mesh(config.mesh)
        self._mesh_shards = (parsed[0]
                             if parsed is not None and parsed[1] is None
                             else 0)
        self.store_rtt_s = 0.0    # measured at start by _apply_rtt_floor
        if self.fleet.replicas < 0:
            raise ValueError(
                "replicas must be >= 0 (0 = router-only: --join feeds "
                "members, or --standby contends for the lease)")
        self.ctx = RuntimeContext(registry=registry)
        self.auth = KeyAuthentication(config.server_key or None)
        # multi-tenant admission: the ROUTER is the auth + quota
        # boundary of a fleet — it authenticates the app key and
        # charges rate/concurrency ONCE, then asserts the identity to
        # replicas via X-PIO-App (replicas run trust_header variants
        # and only re-apply per-tenant FAIRNESS, never a second charge)
        from predictionio_tpu.tenancy import (
            AdmissionController, TenancyConfig,
        )
        tcfg = (config.tenancy if config.tenancy is not None
                else TenancyConfig.from_env())
        if tcfg.enabled and not tcfg.header_key:
            # no operator-configured PIO_SERVER_ACCESS_KEY: mint an
            # ephemeral per-fleet secret so in-process replicas can
            # still VERIFY the router's X-PIO-App assertion instead of
            # trusting any client that dials them directly. Cross-host
            # (--join) replicas can't see this token — they need the
            # shared PIO_SERVER_ACCESS_KEY and warn otherwise.
            import secrets
            tcfg = dataclasses.replace(
                tcfg, header_key=secrets.token_hex(16))
        self.admission = AdmissionController(
            tcfg, registry=self.ctx.registry, metrics=self.metrics)
        self._engine_arg = engine
        self._plugins = plugins
        self._rr_lock = threading.Lock()
        self._rr_next = 0
        # persistent upstream connections for the data-path proxy: at
        # wire-path throughput a fresh dial per proxied request is the
        # dominant cost (utils/wire.HTTPConnectionPool)
        self._upstream = HTTPConnectionPool()
        self._reload_lock = threading.Lock()
        # the in-memory mirror of the lease journal's "roll" key: the
        # single `_journal_payload` builder merges it with the
        # admission bucket snapshot so the renewal tick and the roll
        # path never clobber each other's half of the journal doc
        self._roll_pending: List[str] = []
        # attached control loop (serving/autoscaler.py); ticked from
        # the tsdb scrape cycle when present
        self.autoscaler = None
        self._stopping = False
        self._monitor_stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        # watchdog liveness: the health monitor is restartable, the
        # lease loop is NOT — a dead lease loop forfeits leadership, so
        # the watchdog degrades this router's /ready instead and a
        # standby takes over on TTL expiry
        self._monitor_beat = None
        self._lease_beat = None
        self._fleet_obs = _fleet_metrics(self.metrics)
        # metrics federation: last-good member /metrics text by member
        # key (scraped over the upstream pool on the tsdb tick,
        # re-served at /federate with a `member` label) plus the
        # previous parsed sample per member for rate/p99 derivation
        self._federate_lock = threading.Lock()
        self._federated: Dict[str, str] = {}
        self._member_prom_last: Dict[str, tuple] = {}
        # leadership: holder identity is the advertised address; the
        # lease DAO lives in the store every router shares. Until the
        # first lease tick this router is NOT leader (no routing).
        self._members_lock = threading.Lock()
        self._advertise = self.fleet.advertise
        self._holder = self._advertise
        self._leases = None
        self._lease_name = (
            f"fleet-leader-{config.engine_variant or 'default'}")
        self._is_leader = False
        self._leader_hint = ""
        self._lease_stop = threading.Event()
        self._lease_thread: Optional[threading.Thread] = None
        # ONE recovery sweep + ONE scheduled-fsck thread per fleet
        from predictionio_tpu.data.fsck import (
            start_scheduled_fsck, startup_check,
        )
        startup_check(self.ctx.registry, log=_log.warning)
        self._fsck_sched = start_scheduled_fsck(
            self.ctx.registry, log=_log.warning)
        self._replicas: List[_Replica] = []
        self._routes()

    # -- lifecycle ----------------------------------------------------------
    def _replica_config(self, index: int = 0) -> ServerConfig:
        """Replicas bind loopback ephemeral ports, skip the per-process
        fsck sweep, and never probe/undeploy a port occupant (the fleet
        owns the public port; replica ports are fresh). Streaming
        refreshers get a per-replica stagger — replica i's first tick
        lands i/replicas of the way through the interval — so at most
        one replica of the fleet is folding at any instant and a
        poisoned swap (rolled back) never hits every replica at once
        (the rolling variant of the serve-path hot swap)."""
        stagger = 0.0
        if self.config.refresh_interval_s > 0 and self.fleet.replicas > 1:
            stagger = (index * self.config.refresh_interval_s
                       / self.fleet.replicas)
        # the router already authenticated and charged the quota;
        # replicas trust its X-PIO-App assertion and apply only the
        # weighted-fair batching layer (admission is absent only on
        # partially constructed servers in tests)
        admission = getattr(self, "admission", None)
        tenancy = (admission.config.replica_variant()
                   if admission is not None else None)
        # mesh mode: each in-process replica owns catalog shard i%N —
        # its warm_deploy sees `items=N@fleet:i` and builds a
        # ShardSliceTopK over its slice only
        mesh = self.config.mesh
        shards = getattr(self, "_mesh_shards", 0)
        if shards:
            mesh = f"items={shards}@fleet:{index % shards}"
        return dataclasses.replace(
            self.config, ip="127.0.0.1", port=0, startup_check=False,
            max_inflight=0, refresh_stagger_s=stagger,
            tenancy=tenancy, mesh=mesh)

    def start(self, background: bool = True) -> int:
        for i in range(self.fleet.replicas):
            server = PredictionServer(
                self._replica_config(i), registry=self.ctx.registry,
                plugins=self._plugins, engine=self._engine_arg,
                metrics=self.metrics)
            rep = _Replica(i, server)
            rep.port = server.start(background=True)
            rep.shard = server.shard_spec()
            self._replicas.append(rep)
            if self._probe(rep):
                rep.beat()
                self._admit(rep)
            _log.info("replica_started", replica=i, port=rep.port,
                      admitted=rep.admitted)
        # bind first so the advertised address (and lease holder id)
        # carries the real port even when config.port == 0
        port = super().start(background=True)
        if not self._advertise:
            self._advertise = f"127.0.0.1:{port}"
        self._holder = self._advertise
        self._resolve_leases()
        self._apply_rtt_floor()
        self._restore_members()
        # leadership settles before start() returns: a fresh single
        # router is leader immediately; a standby next to a live leader
        # observes the holder and stays passive
        self._lease_tick()
        from predictionio_tpu.resilience.watchdog import watchdog
        self._monitor_beat = watchdog().register(
            "health", budget_s=self.fleet.health_interval_s * 3.0 + 5.0,
            restart=self._spawn_monitor)
        self._lease_beat = watchdog().register(
            "lease", budget_s=self.fleet.lease_ttl_s + 5.0)
        self._spawn_monitor()
        self._spawn_lease()
        watchdog().ensure_started()
        if not background and self._thread is not None:
            self._thread.join()
        return port

    def stop(self) -> None:
        """Stop the fleet: replicas drain gracefully (their stop()
        finishes accepted work), the lease is RELEASED (a standby can
        take over immediately instead of waiting out the TTL), then
        the router socket closes."""
        with self._rr_lock:
            if self._stopping:
                return
            self._stopping = True
        self._monitor_stop.set()
        self._lease_stop.set()
        self._close_beats()
        for rep in list(self._replicas):
            with rep.lock:
                rep.admitted = False
                rep.state = "stopping"
            if rep.server is None:
                continue
            try:
                rep.server.stop()
            except Exception as e:
                _log.warning("replica_stop_failed", replica=rep.index,
                             error=f"{type(e).__name__}: {e}")
        if self._leases is not None and self._is_leader:
            try:
                self._leases.release(self._lease_name, self._holder)
            except Exception as e:
                _log.warning("lease_release_failed",
                             error=f"{type(e).__name__}: {e}")
        self._is_leader = False
        self._fleet_obs["leader"].set(0.0)
        if self._fsck_sched is not None:
            self._fsck_sched.stop()
        self._upstream.close()
        self.shutdown()

    def crash(self) -> None:
        """Chaos hook (tests/bench): die the way a SIGKILLed router
        does — no drain, no snapshot, and crucially NO lease release,
        so failover exercises the TTL-expiry path. In-process replicas
        are left running (use router-only fleets to model a real
        cross-host leader crash)."""
        with self._rr_lock:
            self._stopping = True
        self._monitor_stop.set()
        self._lease_stop.set()
        self._close_beats()
        if self._fsck_sched is not None:
            self._fsck_sched.stop()
        self.shutdown()

    def _close_beats(self) -> None:
        for beat in (self._monitor_beat, self._lease_beat):
            if beat is not None:
                beat.close()
        self._monitor_beat = None
        self._lease_beat = None

    def readiness(self):
        """/ready: the fleet serves while >=1 member is admitted AND
        no non-restartable control loop has been given up on — a dead
        lease loop cannot renew leadership, so this router must fail
        readiness and let a standby take over on TTL expiry."""
        admitted = [r.index for r in self._replicas
                    if r.admitted and r.running()]
        detail = {"replicas": len(self._replicas), "admitted": admitted,
                  "leader": self._is_leader}
        dead_loops = [b.role for b in (self._monitor_beat,
                                       self._lease_beat)
                      if b is not None and b.degraded]
        if dead_loops:
            detail["degradedLoops"] = dead_loops
            return (False, detail)
        # worst-case SLO burn across the in-process replicas, so the
        # router — the probe target operators actually watch — surfaces
        # degradation without walking members (remote members carry
        # their own /ready detail)
        slo: Dict[str, dict] = {}
        degraded = False
        for rep in self._replicas:
            if rep.server is None:
                continue
            for label, d in rep.server._slo.snapshot().items():
                cur = slo.get(label)
                if cur is None or d["burn_5m"] > cur["burn_5m"]:
                    slo[label] = d
            degraded = degraded or rep.server._slo.degraded()
        if slo:
            detail["slo"] = slo
            detail["sloDegraded"] = degraded
        return (bool(admitted), detail)

    # -- leadership ---------------------------------------------------------
    def is_leader(self) -> bool:
        return self._is_leader

    def _resolve_leases(self) -> None:
        try:
            self._leases = self.ctx.registry.get_leases()
        except StorageError as e:
            # store without a lease DAO: degrade to always-leader (the
            # pre-lease behavior — fine for a single router, unsafe
            # only if the operator runs two routers anyway)
            self._leases = None
            _log.warning("lease_dao_unavailable_always_leader", error=str(e))

    def _apply_rtt_floor(self) -> None:
        """Satellite guard: measure the lease store's CAS RTT once at
        start and CLAMP the lease TTL (and heartbeat cadence) to at
        least 10x it. An operator-tuned PIO_FLEET_LEASE_TTL_S that the
        store cannot physically renew in time would otherwise flap
        leadership on every slow CAS — warn loudly instead of flapping
        silently."""
        if self._leases is None:
            return
        rtt = measure_store_rtt(self._leases, self._holder)
        self.store_rtt_s = rtt
        self.metrics.gauge(
            "pio_fleet_store_rtt_seconds",
            "Median lease-store CAS round-trip measured at start").set(rtt)
        if rtt <= 0:
            return
        floor = 10.0 * rtt
        if self.fleet.lease_ttl_s < floor:
            _log.warning(
                "lease_ttl_below_rtt_floor_clamped",
                configured_ttl_s=self.fleet.lease_ttl_s,
                store_rtt_s=round(rtt, 4),
                clamped_ttl_s=round(floor, 3),
                hint="PIO_FLEET_LEASE_TTL_S must be >= 10x the lease "
                     "store's CAS RTT or leadership flaps on slow CAS")
            self.fleet.lease_ttl_s = floor
        hb_floor = floor / 3.0
        if 0 < self.fleet.heartbeat_s < hb_floor:
            _log.warning(
                "heartbeat_below_rtt_floor_clamped",
                configured_heartbeat_s=self.fleet.heartbeat_s,
                clamped_heartbeat_s=round(hb_floor, 3))
            self.fleet.heartbeat_s = hb_floor

    def _lease_tick(self) -> None:
        if self._leases is None:
            if not self._is_leader:
                self._become_leader(previous="", journal="")
            return
        try:
            cur = self._leases.get(self._lease_name)
            # a leader RENEWAL also journals its tenant-budget snapshot
            # (plus any mid-roll state); an ACQUISITION passes None so
            # the store preserves the dead leader's journal for
            # `_become_leader` to inherit — writing here would destroy
            # the very state a takeover needs to adopt
            journal = self._journal_payload() if self._is_leader else None
            got = self._leases.acquire(
                self._lease_name, self._holder, self.fleet.lease_ttl_s,
                journal=journal)
        except Exception as e:
            # storage flake: keep the current role; if we are leader
            # and stay cut off, the TTL expires us from everyone
            # else's point of view, which is the safe outcome
            _log.warning("lease_tick_failed",
                         error=f"{type(e).__name__}: {e}")
            return
        if got is not None:
            self._leader_hint = self._holder
            if not self._is_leader:
                prev = cur.holder if (cur is not None and
                                      cur.holder != self._holder) else ""
                self._become_leader(previous=prev, journal=got.journal)
        else:
            self._leader_hint = cur.holder if cur is not None else ""
            if self._is_leader:
                self._step_down()
            # continuously shadow the leader's journaled budgets: a
            # standby that serves during the handoff gap (leader dead,
            # lease not yet expired) charges buckets already synced to
            # the leader's spent state — adoption is clamp-down-only,
            # so the gap cannot mint a second per-tenant burst
            if cur is not None and cur.journal:
                try:
                    doc = json.loads(cur.journal) or {}
                except ValueError:
                    doc = {}
                if doc.get("buckets"):
                    self.admission.adopt_buckets(doc)

    def _become_leader(self, previous: str, journal: str) -> None:
        self._is_leader = True
        self._fleet_obs["leader"].set(1.0)
        if previous:
            self._fleet_obs["handoff"].inc()
            _log.warning("leader_takeover", holder=self._holder,
                         previous=previous)
        else:
            _log.info("leader_elected", holder=self._holder)
        # rebuild membership a dead leader knew about (heartbeats to
        # all routers usually made this a no-op already)
        self._restore_members()
        doc: dict = {}
        if journal:
            try:
                doc = json.loads(journal) or {}
            except ValueError:
                doc = {}
        # adopt the dead leader's spent tenant buckets BEFORE any
        # request admits here: a takeover must continue the previous
        # holder's budget, not mint a second burst per tenant
        adopted = self.admission.adopt_buckets(doc)
        if adopted:
            _log.info("tenant_budget_adopted", tenants=adopted,
                      previous=previous)
        pending = [str(k) for k in (doc.get("roll") or [])]
        # mirror immediately: a renewal tick before the resume thread
        # journals again must not drop the roll key from the doc
        self._roll_pending = list(pending)
        if pending:
            # the previous leader died mid-roll; finish what it started
            _log.warning("resuming_interrupted_roll", pending=pending)
            threading.Thread(target=self._resume_roll, args=(pending,),
                             name="pio-fleet-roll-resume",
                             daemon=True).start()

    def _step_down(self) -> None:
        self._is_leader = False
        self._fleet_obs["leader"].set(0.0)
        _log.warning("leader_stepped_down", holder=self._holder,
                     leader=self._leader_hint)

    def _spawn_lease(self) -> None:
        self._lease_thread = threading.Thread(
            target=self._lease_loop, name="pio-fleet-lease", daemon=True)
        self._lease_thread.start()

    def _lease_loop(self) -> None:
        beat = self._lease_beat
        if beat is not None:
            beat.guard(self._lease_body)
        else:
            self._lease_body()

    def _lease_body(self) -> None:
        beat = self._lease_beat
        interval = max(self.fleet.lease_ttl_s / 3.0, 0.02)
        while not self._lease_stop.wait(interval):
            if beat is not None:
                beat.tick()
            self._lease_tick()

    def _journal_payload(self) -> str:
        """The full journal doc a leader maintains: mid-roll progress
        plus the admission spent-bucket snapshot. ONE builder for both
        writers (the roll path and the renewal tick), so neither
        clobbers the other's half of the doc."""
        doc: dict = {}
        if self._roll_pending:
            doc["roll"] = list(self._roll_pending)
        try:
            snap = self.admission.export_buckets()
        except Exception as e:
            snap = {}
            _log.warning("bucket_export_failed",
                         error=f"{type(e).__name__}: {e}")
        if snap:
            doc["t"] = snap["t"]
            doc["buckets"] = snap["buckets"]
        return json.dumps(doc) if doc else ""

    def _journal_roll(self, pending: List[str]) -> None:
        """Record the members still to roll in the lease row (renewing
        the lease as a side effect); an empty list clears the roll key."""
        self._roll_pending = list(pending)
        if self._leases is None or not self._is_leader:
            return
        try:
            self._leases.acquire(self._lease_name, self._holder,
                                 self.fleet.lease_ttl_s,
                                 journal=self._journal_payload())
        except Exception as e:
            _log.warning("roll_journal_write_failed",
                         error=f"{type(e).__name__}: {e}")

    def _resume_roll(self, pending: List[str]) -> None:
        try:
            report = self.rolling_reload(only=pending)
            _log.info("roll_resumed", aborted=report["aborted"],
                      results=len(report["results"]))
        except HTTPError as e:
            # 409: an operator roll beat us; 503: lost the lease again
            _log.warning("roll_resume_not_run", error=e.message)

    # -- membership ---------------------------------------------------------
    def _find_member(self, key: str) -> Optional[_Replica]:
        for rep in list(self._replicas):
            if rep.key == key:
                return rep
        return None

    def _add_member(self, host: str, port: int) -> _Replica:
        with self._members_lock:
            for rep in self._replicas:
                if rep.host == host and rep.port == port:
                    return rep
            rep = _Replica(len(self._replicas), server=None,
                           host=host, port=port)
            self._replicas.append(rep)
        self._update_gauges()
        return rep

    def _members_blob_id(self) -> str:
        return _MEMBERS_BLOB_PREFIX + (self.config.engine_variant
                                       or "default")

    def _persist_members(self) -> None:
        """Snapshot the remote membership into the model store, so a
        restarted router re-admits remote replicas immediately instead
        of waiting a full re-registration interval."""
        remote = [{"member": r.key, "model": r.model_id,
                   "shard": r.shard, "role": r.role}
                  for r in list(self._replicas) if r.remote]
        try:
            self.ctx.registry.get_model_data_models().insert(Model(
                self._members_blob_id(),
                json.dumps({"members": remote}).encode()))
        except Exception as e:
            _log.warning("member_snapshot_write_failed",
                         error=f"{type(e).__name__}: {e}")

    def _restore_members(self) -> None:
        try:
            blob = self.ctx.registry.get_model_data_models().get(
                self._members_blob_id())
        except Exception as e:
            _log.warning("member_snapshot_read_failed",
                         error=f"{type(e).__name__}: {e}")
            return
        if blob is None:
            return
        try:
            entries = json.loads(bytes(blob.models)).get("members", [])
        except (ValueError, TypeError):
            return
        for entry in entries:
            member = str(entry.get("member", ""))
            host, sep, port_s = member.rpartition(":")
            if not sep or not host or not port_s.isdigit():
                continue
            if self._find_member(member) is not None:
                continue
            rep = self._add_member(host, int(port_s))  # lint: ok — host str
            rep.model_id = str(entry.get("model", ""))
            rep.shard = str(entry.get("shard", ""))
            rep.role = str(entry.get("role", "")) or "serve"
            if self._probe(rep):
                rep.beat()
                self._admit(rep)
            _log.info("member_restored", member=member,
                      admitted=rep.admitted)

    def _handle_beat(self, req: Request, register: bool) -> Response:
        try:
            body = req.json()
        except ValueError as e:
            raise HTTPError(400, str(e))
        member = str(body.get("member", ""))
        host, sep, port_s = member.rpartition(":")
        if not sep or not host or not port_s.isdigit():
            raise HTTPError(400, "member must be 'host:port'")
        # partition seam: an armed rule means this beat never arrived
        if faults().dropped(f"fleet.net.{member}.heartbeat"):
            raise HTTPError(503, "heartbeat dropped (injected partition)")
        rep = self._find_member(member)
        if rep is None:
            # /fleet/heartbeat auto-registers too: a router restarted
            # from scratch re-learns the fleet within one beat
            rep = self._add_member(host, int(port_s))  # lint: ok — host str
            self._fleet_obs["transitions"].labels(event="register").inc()
            _log.info("member_registered", member=member,
                      explicit=register)
            self._persist_members()
        rep.beat(model_id=str(body.get("model", "")))
        ready = bool(body.get("ready", True))
        with rep.lock:
            name = str(body.get("name", ""))
            if name:
                rep.name = name   # supervisor child name, for retirement
            shard = str(body.get("shard", ""))
            if shard != rep.shard:
                rep.shard = shard  # mesh shard this member declares
            role = str(body.get("role", "")) or "serve"
            if role != rep.role:
                rep.role = role   # ingest members never enter rotation
            # retiring members stay out of rotation but keep beating:
            # a drain-in-progress must not re-admit (nor eject) itself
            busy = rep.state in ("reloading", "stopping", "retiring")
            if rep.state == "dead":
                rep.state = "starting"
        if not busy:
            if ready:
                self._maybe_admit(rep)
            else:
                self._eject(rep, "member reported not ready")
        return Response.json({
            "member": member, "admitted": rep.admitted,
            "leader": self._leader_hint, "shard": rep.shard,
            "heartbeat_s": self.fleet.effective_heartbeat_s()})

    # -- health gating ------------------------------------------------------
    def _grace_s(self) -> float:
        # a member is only eject-stale once it has missed ~3 beats
        return 3.0 * self.fleet.effective_heartbeat_s()

    def _probe(self, rep: _Replica) -> bool:
        if faults().dropped(f"fleet.net.{rep.key}.heartbeat"):
            return False          # partition: the probe never lands
        try:
            req = urllib.request.Request(
                f"http://{rep.host}:{rep.port}/ready", method="GET")
            with urllib.request.urlopen(req, timeout=2) as resp:
                return resp.status == 200
        except urllib.error.HTTPError:
            return False          # answered but not ready
        except OSError:
            return False          # unreachable
        except Exception:
            return False

    def _admit(self, rep: _Replica) -> None:
        with rep.lock:
            was = rep.admitted
            rep.admitted = True
            rep.state = "serving"
            rep.failures = 0
        if not was:
            self._fleet_obs["transitions"].labels(event="admit").inc()
        self._update_gauges()

    def _maybe_admit(self, rep: _Replica) -> None:
        """Admit on positive health evidence (good probe, ready beat) —
        UNLESS the member is in post-eject quarantine. Without the
        quarantine a data-path-partitioned member would flap: its
        heartbeats and control-path probes look healthy, so every beat
        would re-admit what routing just ejected."""
        with rep.lock:
            quarantined = (rep.ejected_at > 0.0 and
                           time.monotonic() - rep.ejected_at
                           < self._grace_s())
        if not quarantined:
            self._admit(rep)

    def _eject(self, rep: _Replica, reason: str) -> None:
        with rep.lock:
            was = rep.admitted
            rep.admitted = False
            rep.ejected_at = time.monotonic()
            if rep.state == "serving":
                rep.state = "ejected"
        if was:
            self._fleet_obs["transitions"].labels(event="eject").inc()
            _log.warning("replica_ejected", replica=rep.index,
                         member=rep.key, reason=reason)
        self._update_gauges()

    def _record_failure(self, rep: _Replica, reason: str,
                        data_path: bool = False) -> None:
        """One suspicion. Data-path evidence (routing saw a connection
        error or 5xx) ejects at the threshold alone; probe-only
        suspicion additionally needs a stale heartbeat, so a member
        whose control path flaps while its beats keep arriving is not
        bounced out of rotation."""
        with rep.lock:
            rep.failures += 1
            over = rep.failures >= self.fleet.eject_threshold
            stale = (time.monotonic() - rep.last_beat) >= self._grace_s()
        if over and (data_path or stale):
            self._eject(rep, reason)

    def _spawn_monitor(self) -> None:
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="pio-fleet-health", daemon=True)
        self._monitor.start()

    def _monitor_loop(self) -> None:
        beat = self._monitor_beat
        if beat is not None:
            beat.guard(self._monitor_body)
        else:
            self._monitor_body()

    def _monitor_body(self) -> None:
        beat = self._monitor_beat
        while not self._monitor_stop.wait(self.fleet.health_interval_s):
            if beat is not None:
                beat.tick()
            for rep in list(self._replicas):
                with rep.lock:
                    skip = rep.state in ("reloading", "stopping",
                                         "retiring")
                self._fleet_obs["beat_age"].labels(
                    member=rep.key).set(rep.beat_age())
                if skip:
                    continue
                if self._probe(rep):
                    rep.beat()
                    self._maybe_admit(rep)
                else:
                    self._record_failure(rep, "readiness probe failed")

    def _update_gauges(self) -> None:
        members = list(self._replicas)
        admitted = sum(1 for r in members if r.admitted)
        self._fleet_obs["admitted"].set(float(admitted))  # lint: ok — host int
        self._fleet_obs["size"].set(float(len(members)))
        self._fleet_obs["members"].set(float(len(members)))
        for rep in members:
            if rep.shard:
                self._fleet_obs["shard_owner"].labels(
                    shard=rep.shard, member=rep.key).set(
                        1.0 if rep.admitted else 0.0)

    # -- elastic scale-down (drain != death) --------------------------------
    def member_by_name(self, name: str) -> Optional[_Replica]:
        """The member a supervisor child registered as: matched by the
        heartbeat-carried child name, falling back to the stub model-id
        convention (`stub-<name>`)."""
        for rep in list(self._replicas):
            if rep.name == name or rep.model_id == f"stub-{name}":
                return rep
        return None

    def retire_member_named(self, name: str) -> bool:
        rep = self.member_by_name(name)
        if rep is None:
            return False
        return self.retire_member(rep)

    def retire_member(self, rep: _Replica) -> bool:
        """Graceful scale-down of one member: out of rotation, drained
        to zero inflight, then forgotten. Counts as a `retire`
        transition — NEVER an eject, and it leaves the suspicion
        counters untouched (a retired child is a decision, not a
        failure). Returns whether the drain completed inside the
        drain-timeout budget."""
        with rep.lock:
            rep.admitted = False
            rep.state = "retiring"
        self._fleet_obs["transitions"].labels(event="retire").inc()
        self._update_gauges()
        _log.info("member_retiring", member=rep.key, name=rep.name)
        drained = self._await_drain(rep)
        if not drained:
            _log.warning("retire_drain_timeout", member=rep.key,
                         inflight=rep.inflight)
        return drained

    def forget_member(self, key: str) -> None:
        """Remove a retired member from the roster and the persisted
        snapshot; its later heartbeats (if the process lingers) would
        simply re-register it."""
        with self._members_lock:
            self._replicas = [r for r in self._replicas if r.key != key]
        self._persist_members()
        self._update_gauges()
        _log.info("member_forgotten", member=key)

    # -- metrics federation -------------------------------------------------
    def _obs_collectors(self):
        """The router's tsdb tick additionally scrapes every admitted
        member, so derived per-member gauges land in the router's own
        ring (one `/tsdb.json` holds the whole fleet's history)."""
        return super()._obs_collectors() + [self._scrape_members,
                                            self._autoscale_tick]

    def _autoscale_tick(self) -> None:
        """Drive the attached autoscaler (if any) once per tsdb scrape
        cycle — it reads the ring `_scrape_members` just refreshed.
        Attach-order-proof: the collector exists from construction and
        no-ops until `self.autoscaler` is set."""
        a = self.autoscaler
        if a is not None:
            a.tick()

    def _scrape_members(self) -> None:
        """Pull each admitted member's /metrics over the persistent
        upstream pool: cache the text for /federate and derive
        per-member qps/p99/burn/reactor-balance gauges. A failed
        scrape feeds the suspicion machinery (it is data-path-adjacent
        evidence, but a scrape is not a client request — so it counts
        as probe-grade suspicion, never a lone ejection cause) and
        keeps the member's last-good text serving."""
        for rep in list(self._replicas):
            if not rep.admitted:
                continue
            try:
                status, _rh, body = self._upstream.request(
                    rep.host, rep.port, "GET", "/metrics", None, {},
                    timeout=2.0)
                if status != 200:
                    raise OSError(f"scrape status {status}")
            except OSError as e:
                self._fleet_obs["scrapes"].labels(outcome="error").inc()
                self._record_failure(
                    rep, f"metrics scrape failed: {e}")
                continue
            text = body.decode("utf-8", "replace")
            with self._federate_lock:
                self._federated[rep.key] = text
            self._fleet_obs["scrapes"].labels(outcome="ok").inc()
            try:
                self._derive_member_gauges(rep.key, text)
            except (ValueError, KeyError, ZeroDivisionError):
                pass              # malformed exposition: text still federates

    def _derive_member_gauges(self, member: str, text: str) -> None:
        """Fold one member scrape into `pio_fleet_member_*` gauges.
        Counters need two sightings (rates are deltas over the scrape
        interval); gauges land immediately."""
        now = time.monotonic()
        parsed = _parse_prom(text)
        prev = self._member_prom_last.get(member)
        self._member_prom_last[member] = (now, parsed)
        obs = self._fleet_obs
        burn = 0.0
        for (name, labels), v in parsed.items():
            if (name == "pio_slo_burn_rate"
                    and dict(labels).get("window") == "5m"):
                burn = max(burn, v)
        obs["member_burn"].labels(member=member).set(burn)
        if prev is None:
            return
        pts, pparsed = prev
        dt = now - pts
        if dt <= 0:
            return

        def _sum(cur: Dict, name: str) -> float:
            return sum(v for (n, _l), v in cur.items() if n == name)

        dreq = (_sum(parsed, "pio_http_requests_total")
                - _sum(pparsed, "pio_http_requests_total"))
        if dreq >= 0:
            obs["member_qps"].labels(member=member).set(dreq / dt)
        obs["member_p99"].labels(member=member).set(
            _prom_hist_p99(parsed, pparsed,
                           "pio_http_request_duration_seconds_bucket"))
        # reactor balance: max/mean of per-reactor request deltas
        # (1.0 = perfectly balanced accept sharding)
        per_reactor: Dict[str, float] = {}
        for (name, labels), v in parsed.items():
            if name == "pio_wire_requests_total":
                r = dict(labels).get("reactor", "0")
                pv = pparsed.get((name, labels), 0.0)
                per_reactor[r] = per_reactor.get(r, 0.0) + (v - pv)
        deltas = [d for d in per_reactor.values() if d >= 0]
        if deltas and sum(deltas) > 0:
            mean = sum(deltas) / len(deltas)
            obs["member_balance"].labels(member=member).set(
                max(deltas) / mean if mean > 0 else 1.0)

    # -- routing ------------------------------------------------------------
    def _rotation(self) -> List[_Replica]:
        """Admitted members, round-robin rotated so consecutive
        requests spread; the non-admitted are excluded entirely."""
        admitted = [r for r in self._replicas
                    if r.admitted and r.role == "serve"]
        if not admitted:
            return []
        with self._rr_lock:
            start = self._rr_next % len(admitted)
            self._rr_next += 1
        return admitted[start:] + admitted[:start]

    def _proxy(self, rep: _Replica, req: Request, timeout: float,
               extra_headers: Optional[Dict[str, str]] = None
               ) -> Response:
        """Forward one request to one member. An HTTP error status is
        a RESPONSE (the member is alive and answered — pass it
        through); only transport-level failures raise OSError to the
        retry loop. `extra_headers` are router-asserted values (the
        authenticated tenant identity) layered over the forwarded set."""
        if faults().dropped(f"fleet.net.{rep.key}.data"):
            raise OSError(f"injected partition: fleet.net.{rep.key}.data")
        headers = {}
        for name in _FORWARD_HEADERS:
            v = req.header(name)
            if v:
                headers[name] = v
        if extra_headers:
            headers.update(extra_headers)
        path = req.path
        if req.query:
            from urllib.parse import urlencode
            path = f"{path}?{urlencode(dict(req.query))}"
        # pooled keep-alive upstream: error statuses come back as plain
        # (status, headers, body) responses, and ONLY transport-level
        # failures raise OSError — identical semantics to the old
        # urllib call, minus the per-request dial
        status, rheaders, body = self._upstream.request(
            rep.host, rep.port, req.method, path,
            req.body if req.method == "POST" else None, headers, timeout)
        return Response(
            status=status, body=body,
            content_type=rheaders.get("Content-Type", "application/json"))

    def _leader_gate(self, req: Request, p) -> None:
        """Non-leaders 307-redirect data traffic to the leader (503
        when no leader is elected yet) — shared by the plain route and
        the mesh merge path."""
        if self._is_leader:
            return
        leader = self._leader_hint
        if leader and leader != self._advertise:
            self._fleet_obs["routed"].labels(outcome="redirected").inc()
            hdrs = {"Location": f"http://{leader}{req.path}"}
            if p is not None:
                # attach our trace context to the redirect so a
                # trace-aware client re-asserts it at the leader and
                # the two hops stitch under one trace id
                trace.annotate_pending(p, kind="router")
                hdrs[trace.TRACE_HEADER] = trace.child_header(p)
            raise HTTPError(
                307, f"not the fleet leader; try {leader}",
                headers=hdrs)
        raise HTTPError(503, "no fleet leader elected",
                        headers={"Retry-After": "1"})

    def _route(self, req: Request,
               extra_headers: Optional[Dict[str, str]] = None) -> Response:
        """Route to an admitted member; connection-level failures are
        retried on the NEXT admitted member (zero failed client
        requests when a member dies), each failure feeding the
        ejection counter. Non-leaders redirect to the leader."""
        p = trace.current()
        self._leader_gate(req, p)
        deadline = current_deadline()
        rotation = self._rotation()
        if not rotation:
            self._fleet_obs["routed"].labels(outcome="no_replica").inc()
            raise HTTPError(503, "no healthy replica available",
                            headers={"Retry-After": "1"})
        last_err: Optional[Exception] = None
        for rep in rotation:
            timeout = self.fleet.proxy_timeout_s
            if deadline is not None:
                remaining = deadline.remaining()
                if remaining <= 0.005:
                    # the budget is spent: shed with 504 BEFORE dialing
                    # rather than burning a connection on a doomed call
                    self._shed_counter.labels(surface="deadline",
                                              app="").inc()
                    raise DeadlineExceeded(
                        "deadline budget exhausted before dialing a "
                        "replica")
                timeout = min(timeout, remaining)
            with rep.lock:
                rep.inflight += 1
            t_dial = time.perf_counter()
            try:
                resp = self._proxy(rep, req, timeout, extra_headers)
            except OSError as e:
                last_err = e
                trace.add_span(p, f"proxy_retry:{rep.key}", t_dial,
                               time.perf_counter())
                self._record_failure(
                    rep, f"route error: {type(e).__name__}: {e}",
                    data_path=True)
                self._fleet_obs["routed"].labels(outcome="retried").inc()
                continue
            finally:
                with rep.lock:
                    rep.inflight -= 1
            trace.add_span(p, f"proxy:{rep.key}", t_dial,
                           time.perf_counter())
            if resp.status >= 500:
                # the member answered; pass the response through but
                # feed the error threshold (a member shedding 503s or
                # erroring 500s should leave rotation until it recovers)
                self._record_failure(rep, f"HTTP {resp.status}",
                                     data_path=True)
            else:
                with rep.lock:
                    rep.failures = 0
            self._fleet_obs["routed"].labels(outcome="ok").inc()
            return resp
        self._fleet_obs["routed"].labels(outcome="exhausted").inc()
        raise HTTPError(
            503,
            f"every admitted replica unreachable "
            f"(last: {type(last_err).__name__ if last_err else 'n/a'})",
            headers={"Retry-After": "1"})

    def _route_mesh(self, req: Request,
                    extra_headers: Optional[Dict[str, str]] = None
                    ) -> Response:
        """Cross-host mesh merge: fan one query out to an admitted
        owner of EVERY catalog shard (`/shard/queries.json`, same
        persistent upstream pool), then re-top-k the returned (global
        id, score) candidates by (-score, gid) with gid dedupe —
        bit-identical to the single-device oracle whenever all shards
        answer. Transport failures retry the NEXT owner of the SAME
        shard (feeding the ejection counter); a shard with no live
        owner degrades the response (`partial: true`, the remaining
        shards still serve) — a missing member never costs the client
        a 500."""
        p = trace.current()
        self._leader_gate(req, p)
        deadline = current_deadline()
        n = self._mesh_shards
        shards = [f"{i}/{n}" for i in range(n)]
        owners: Dict[str, List[_Replica]] = {s: [] for s in shards}
        for rep in self._replicas:
            if rep.admitted and rep.shard in owners:
                owners[rep.shard].append(rep)
        if not any(owners.values()):
            # no member declares a shard (mixed/older fleet): the
            # mesh degrades to plain routing rather than 503ing
            return self._route(req, extra_headers=extra_headers)
        headers = {}
        for name in _FORWARD_HEADERS:
            v = req.header(name)
            if v:
                headers[name] = v
        if extra_headers:
            headers.update(extra_headers)
        body = req.body
        if (headers.get("Content-Type") or "").startswith(
                BIN_CONTENT_TYPE):
            # binary-framed wire queries decode HERE: members' shard
            # surface speaks JSON, and the frame only carries
            # (user, num) anyway
            decoded = decode_bin_query(body)
            if decoded is None:
                raise HTTPError(400, "malformed binary query frame")
            body = json.dumps({"user": decoded[0],
                               "num": decoded[1]}).encode()
            headers["Content-Type"] = "application/json"
        cands: List[tuple] = []
        num = 0
        degraded: List[str] = []
        for shard in shards:
            got = None
            for rep in owners[shard]:
                timeout = self.fleet.proxy_timeout_s
                if deadline is not None:
                    remaining = deadline.remaining()
                    if remaining <= 0.005:
                        self._shed_counter.labels(surface="deadline",
                                                  app="").inc()
                        raise DeadlineExceeded(
                            "deadline budget exhausted before dialing "
                            "a shard owner")
                    timeout = min(timeout, remaining)
                with rep.lock:
                    rep.inflight += 1
                t_dial = time.perf_counter()
                try:
                    if faults().dropped(f"fleet.net.{rep.key}.data"):
                        raise OSError(
                            f"injected partition: fleet.net.{rep.key}.data")
                    status, rheaders, rbody = self._upstream.request(
                        rep.host, rep.port, "POST",
                        "/shard/queries.json", body, headers, timeout)
                except OSError as e:
                    trace.add_span(p, f"shard_retry:{rep.key}", t_dial,
                                   time.perf_counter())
                    self._record_failure(
                        rep, f"shard route error: {type(e).__name__}: {e}",
                        data_path=True)
                    self._fleet_obs["routed"].labels(
                        outcome="retried").inc()
                    continue
                finally:
                    with rep.lock:
                        rep.inflight -= 1
                trace.add_span(p, f"shard:{shard}:{rep.key}", t_dial,
                               time.perf_counter())
                if status >= 500:
                    self._record_failure(rep, f"HTTP {status}",
                                         data_path=True)
                    continue
                if status >= 400:
                    # a CLIENT error (bad query, over quota): every
                    # shard would answer identically — pass it through
                    return Response(
                        status=status, body=rbody,
                        content_type=rheaders.get("Content-Type",
                                                  "application/json"))
                with rep.lock:
                    rep.failures = 0
                try:
                    got = json.loads(rbody)
                except ValueError:
                    self._record_failure(rep, "unparseable shard reply",
                                         data_path=True)
                    got = None
                    continue
                break
            if got is None:
                degraded.append(shard)
                continue
            num = max(num, int(got.get("num") or 0))  # lint: ok — host json
            for c in got.get("cands", ()):
                cands.append((int(c[0]), float(c[1]), c[2]))  # lint: ok — host json
        if not cands:
            self._fleet_obs["mesh"].labels(outcome="empty").inc()
            self._fleet_obs["routed"].labels(outcome="exhausted").inc()
            raise HTTPError(
                503, f"no mesh shard reachable ({len(degraded)}/{n} "
                     "degraded)", headers={"Retry-After": "1"})
        # exact merge re-top-k: stable (-score, global id) — the same
        # tie-break every plan layer uses — then gid dedupe, which also
        # collapses full-catalog answers from shard-less members
        cands.sort(key=lambda c: (-c[1], c[0]))
        seen = set()
        top: List[dict] = []
        for gid, score, name in cands:
            key = gid if gid >= 0 else f"name:{name}"
            if key in seen:
                continue
            seen.add(key)
            top.append({"item": name, "score": score})
            if num and len(top) >= num:
                break
        out: Dict[str, object] = {"itemScores": top}
        if degraded:
            out["partial"] = True
            out["degradedShards"] = degraded
            self._fleet_obs["mesh"].labels(outcome="partial").inc()
        else:
            self._fleet_obs["mesh"].labels(outcome="ok").inc()
        self._fleet_obs["routed"].labels(outcome="ok").inc()
        return Response.json(out)

    # -- rolling reload -----------------------------------------------------
    def _await_drain(self, rep: _Replica) -> bool:
        """Wait (bounded) for the router's in-flight requests to this
        replica to finish; new traffic is already diverted."""
        waiter = threading.Event()
        end = time.perf_counter() + self.fleet.drain_timeout_s
        while time.perf_counter() < end:
            with rep.lock:
                if rep.inflight == 0:
                    return True
            waiter.wait(0.02)
        with rep.lock:
            return rep.inflight == 0

    def _reload_replica(self, rep: _Replica) -> dict:
        """POST /reload on one member (its own last-good rollback and
        warm_deploy run inside). Transport failure -> 'died'. The call
        budget is reload_timeout_s, clamped to any remaining request
        deadline so an operator's bounded /reload stays bounded."""
        timeout = self.fleet.reload_timeout_s
        deadline = current_deadline()
        if deadline is not None:
            remaining = deadline.remaining()
            if remaining <= 0.005:
                return {"status": 0,
                        "detail": "deadline exhausted before reload dial"}
            timeout = min(timeout, remaining)
        headers = {}
        if self.config.server_key:
            headers["Authorization"] = "Basic " + base64.b64encode(
                f"{self.config.server_key}:".encode()).decode()
        req = urllib.request.Request(
            f"http://{rep.host}:{rep.port}/reload", data=b"",
            method="POST", headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return {"status": resp.status}
        except urllib.error.HTTPError as e:
            detail = ""
            try:
                detail = json.loads(e.read()).get("message", "")
            except Exception:
                pass
            return {"status": e.code, "detail": detail}
        except OSError as e:
            return {"status": 0, "detail": f"{type(e).__name__}: {e}"}

    def rolling_reload(self, only: Optional[List[str]] = None) -> dict:
        """One member at a time: eject -> drain -> reload -> probe ->
        re-admit -> next. Leader-only (the lease guarantees at most one
        roller fleet-wide); progress is journaled through the lease row
        so the next leader resumes an interrupted roll. See the module
        docstring for the failure policy (dead member: continue;
        unreachable member: skip; failed load: abort)."""
        if not self._is_leader:
            raise HTTPError(
                503, f"not the fleet leader "
                     f"(leader: {self._leader_hint or 'unknown'}); only "
                     f"the lease holder may run a rolling reload")
        if not self._reload_lock.acquire(blocking=False):
            raise HTTPError(409, "a rolling reload is already running")
        t_roll = time.perf_counter()
        try:
            members = list(self._replicas)
            if only is not None:
                wanted = set(only)
                members = [m for m in members if m.key in wanted]
            results: List[dict] = []
            aborted = False
            pending = [m.key for m in members]
            for rep in members:
                # journal BEFORE touching the member: a leader dying
                # here leaves `rep` pending, so the standby re-rolls it
                self._journal_roll(pending)
                if rep.server is not None and not rep.server.is_running():
                    results.append({"replica": rep.index,
                                    "outcome": "skipped_dead"})
                    pending.remove(rep.key)
                    continue
                if not rep.admitted and not self._probe(rep):
                    # partitioned-but-maybe-alive: it is already out of
                    # routing; do NOT roll what we cannot reach (its
                    # agent re-registers and the monitor re-admits it
                    # on heal, still on the model it last loaded)
                    results.append({"replica": rep.index,
                                    "member": rep.key,
                                    "outcome": "skipped_unreachable"})
                    pending.remove(rep.key)
                    continue
                with rep.lock:
                    rep.admitted = False
                    rep.state = "reloading"
                self._fleet_obs["transitions"].labels(
                    event="reload_start").inc()
                self._update_gauges()
                drained = self._await_drain(rep)
                outcome = self._reload_replica(rep)
                if outcome["status"] == 200:
                    ok = self._probe(rep)
                    if ok:
                        rep.beat()
                        self._admit(rep)
                    else:
                        with rep.lock:
                            rep.state = "ejected"
                    results.append({
                        "replica": rep.index,
                        "outcome": "reloaded" if ok else "reloaded_not_ready",
                        "drained": drained})
                elif outcome["status"] == 0:
                    # transport failure: the member died mid-reload.
                    # Leave it ejected — the monitor re-admits if it
                    # ever comes back — and keep rolling: N-1 members
                    # are still serving the old or new model.
                    with rep.lock:
                        rep.state = "dead"
                    self._update_gauges()
                    _log.warning("reload_replica_died", replica=rep.index,
                                 detail=outcome.get("detail", ""))
                    results.append({"replica": rep.index,
                                    "outcome": "died",
                                    "detail": outcome.get("detail", "")})
                else:
                    # the member answered non-200: the LOAD failed and
                    # its last-good rollback kept the old model serving.
                    # Re-admit it and ABORT — the new model is bad and
                    # would fail identically on every remaining member.
                    if self._probe(rep):
                        self._admit(rep)
                    results.append({"replica": rep.index,
                                    "outcome": "load_failed_rolled_back",
                                    "detail": outcome.get("detail", "")})
                    aborted = True
                    break
                pending.remove(rep.key)
            # roll finished (or deterministically aborted): clear the
            # journal so the next leader does not replay it
            self._journal_roll([])
            report = {"results": results, "aborted": aborted}
            self._fleet_obs["rolls"].labels(
                outcome="aborted" if aborted else "ok").inc()
            rec = trace.get_recorder()
            if rec.enabled:
                rec.record_background(
                    "rolling_reload", t_roll, time.perf_counter(),
                    error="aborted" if aborted else "")
            _log.info("rolling_reload_done", aborted=aborted,
                      results=len(results))
            return report
        finally:
            self._reload_lock.release()

    # -- routes -------------------------------------------------------------
    def _routes(self) -> None:
        r = self.router

        @r.post("/queries.json")
        def queries(req: Request) -> Response:
            # Admission is resolved AND charged before any routing
            # decision — a standby that 307-redirects has already spent
            # the rate token (the _AdmitGuard releases only the
            # concurrency slot), so N standbys cannot admit N x rate
            # during a handoff window. Locked by the regression test in
            # tests/test_tenancy.py. Bodies proxy as opaque bytes with
            # Content-Type forwarded, so binary-framed queries
            # (application/x-pio-bin) ride through unchanged.
            from predictionio_tpu.tenancy import TENANT_HEADER
            tenant = self.admission.resolve(req)
            try:
                guard = self.admission.admit(tenant)
            except OverloadedError as e:
                # shed at a standby: still tell the client where the
                # leader is, so handoff-window retries go to the node
                # that will actually serve them
                leader = self._leader_hint
                if (not self._is_leader and leader
                        and leader != self._advertise):
                    raise HTTPError(
                        e.status, e.message,
                        headers={
                            "Retry-After":
                                str(max(1, round(e.retry_after))),
                            "Location": f"http://{leader}{req.path}",
                        })
                raise
            with guard:
                # HMAC-signed assertion: replicas verify before
                # honoring, so only this router can mint identities
                extra = ({TENANT_HEADER: self.admission.signed_header(tenant)}
                         if tenant is not None else None)
                p = trace.current()
                if p is not None:
                    # the router's hop is kind=router (excluded from
                    # pio_serve_seconds — the replica's serve entry owns
                    # that observation) and asserts a signed child
                    # context so replica spans stitch under our id
                    trace.annotate_pending(
                        p, kind="router",
                        app=tenant.label if tenant is not None else "")
                    extra = dict(extra or ())
                    extra[trace.TRACE_HEADER] = trace.child_header(p)
                if self._mesh_shards:
                    return self._route_mesh(req, extra_headers=extra)
                return self._route(req, extra_headers=extra)

        @r.post("/fleet/register")
        def fleet_register(req: Request) -> Response:
            self.auth.check(req)
            return self._handle_beat(req, register=True)

        @r.post("/fleet/heartbeat")
        def fleet_heartbeat(req: Request) -> Response:
            self.auth.check(req)
            return self._handle_beat(req, register=False)

        @r.get("/status.json")
        def status(req: Request) -> Response:
            return Response.json({
                "status": "alive",
                "role": "fleet",
                "leader": self._is_leader,
                "leaderHint": self._leader_hint,
                "advertise": self._advertise,
                "replicas": [rep.snapshot() for rep in self._replicas],
            })

        @r.get("/")
        def index(req: Request) -> Response:
            rows = "".join(
                f"<tr><td>{s['replica']}</td><td>{s['member']}</td>"
                f"<td>{s['state']}</td><td>{s['failures']}</td></tr>"
                for s in (rep.snapshot() for rep in self._replicas))
            role = "leader" if self._is_leader else "standby"
            return Response.html(
                "<html><head><title>PredictionIO-TPU fleet</title></head>"
                f"<body><h1>Fleet control plane ({role})</h1>"
                "<table><tr><th>member</th><th>address</th><th>state</th>"
                f"<th>failures</th></tr>{rows}</table></body></html>")

        @r.post("/reload")
        def reload(req: Request) -> Response:
            self.auth.check(req)
            report = self.rolling_reload()
            status = 500 if report["aborted"] else 200
            return Response.json(report, status=status)

        @r.get("/quality.json")
        def quality_json(req: Request) -> Response:
            # per-member quality snapshots, fetched live from admitted
            # members; a member failing to answer is reported, never
            # fatal — the quality view degrades like /federate does
            members = {}
            for rep in self._replicas:
                if not rep.admitted:
                    continue
                try:
                    with urllib.request.urlopen(
                            f"http://{rep.host}:{rep.port}/quality.json",
                            timeout=2) as resp:
                        members[rep.key] = json.loads(
                            resp.read().decode("utf-8"))
                except (OSError, ValueError) as e:
                    members[rep.key] = {
                        "error": f"{type(e).__name__}: {e}"}
            return Response.json({"role": "fleet", "members": members})

        @r.get("/fleet.html")
        def fleet_html(req: Request) -> Response:
            from predictionio_tpu.tools.dashboard import _fleet_page
            return Response.html(_fleet_page(
                self.tsdb, [rep.snapshot() for rep in self._replicas]))

        @r.get("/federate")
        def federate(req: Request) -> Response:
            # every admitted member's last-good /metrics text with a
            # `member` label injected per sample — one scrape target
            # for the whole fleet. A dead member keeps serving its
            # last-good text until ejection removes it from scraping;
            # the endpoint itself never errors on member failures.
            with self._federate_lock:
                items = sorted(self._federated.items())
            out: List[str] = []
            for member, text in items:
                for line in text.splitlines():
                    if not line or line.startswith("#"):
                        continue
                    out.append(_federate_line(line, member))
            return Response.text(
                "\n".join(out) + ("\n" if out else ""),
                content_type="text/plain; version=0.0.4; charset=utf-8")

        @r.post("/stop")
        def stop(req: Request) -> Response:
            self.auth.check(req)
            threading.Thread(target=self.stop, daemon=True,
                             name="pio-fleet-stop").start()
            return Response.json({"message": "Fleet shutting down"})


class ReplicaAgent:
    """Sidecar loop for a standalone replica (`pio-tpu deploy --join
    http://router:8000[,http://standby:8000]`): registers the local
    PredictionServer with every router URL, then heartbeats
    {member, model, ready} each `heartbeat_s`. Beating ALL routers —
    leader and standbys alike — keeps every membership table warm, so
    a standby that wins the lease can route instantly. `/fleet/
    heartbeat` auto-registers, so a router restarted from scratch
    re-learns this replica within one beat."""

    def __init__(self, server: PredictionServer, routers: Sequence[str],
                 advertise: str = "", server_key: str = "",
                 heartbeat_s: float = 0.0, member_name: str = "",
                 role: str = "serve"):
        self.server = server
        self.routers = [u.rstrip("/") for u in routers if u]
        self.advertise = advertise
        self.server_key = server_key
        self.heartbeat_s = heartbeat_s
        # supervisor child name (--member-name): lets the router map a
        # member back to the child the autoscaler can retire
        self.member_name = member_name
        # role="ingest" rides the same membership/heartbeat machinery
        # (liveness, /fleet members, metrics federation) but is kept out
        # of the query rotation by the router
        self.role = role
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._router_down: Dict[str, bool] = {}
        self.beat = None                # watchdog liveness stamp

    def start(self) -> None:
        if not self.advertise:
            self.advertise = f"127.0.0.1:{self.server.port}"
        if self._beat_all("/fleet/register", first=True) == 0:
            _log.warning("fleet_register_failed_everywhere",
                         routers=",".join(self.routers))
        if self.heartbeat_s <= 0:
            self.heartbeat_s = 1.0
        if self.beat is None:
            from predictionio_tpu.resilience.watchdog import watchdog
            # a dead agent means missed heartbeats and eventual fleet
            # ejection of a healthy replica: restartable, tight budget
            self.beat = watchdog().register(
                "agent", budget_s=self.heartbeat_s * 3.0 + 5.0,
                restart=self._spawn)
        self._spawn()

    def _spawn(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="pio-replica-agent", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        beat, self.beat = self.beat, None
        if beat is not None:
            beat.close()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _payload(self) -> bytes:
        try:
            ready, _ = self.server.readiness()
        except Exception:
            ready = False
        # shard_spec is PredictionServer-only; stub replicas (the
        # supervisor's test double) and older server shapes have none
        shard = getattr(self.server, "shard_spec", lambda: "")()
        return json.dumps({"member": self.advertise,
                           "model": self.server.current_instance_id(),
                           "name": self.member_name,
                           "shard": shard, "role": self.role,
                           "ready": bool(ready)}).encode()

    def _post(self, url: str, data: bytes) -> dict:
        headers = {"Content-Type": "application/json"}
        if self.server_key:
            headers["Authorization"] = "Basic " + base64.b64encode(
                f"{self.server_key}:".encode()).decode()
        req = urllib.request.Request(url, data=data, method="POST",
                                     headers=headers)
        with urllib.request.urlopen(req, timeout=3) as resp:
            return json.loads(resp.read() or b"{}")

    def _beat_all(self, path: str, first: bool = False) -> int:
        data = self._payload()
        ok = 0
        for router in self.routers:
            try:
                out = self._post(router + path, data)
            except (OSError, ValueError) as e:
                # log edges, not every missed beat
                if not self._router_down.get(router):
                    _log.warning("fleet_router_unreachable", router=router,
                                 error=f"{type(e).__name__}: {e}")
                self._router_down[router] = True
                continue
            if self._router_down.get(router):
                _log.info("fleet_router_reachable_again", router=router)
            self._router_down[router] = False
            ok += 1
            if first and self.heartbeat_s <= 0:
                hb = float(out.get("heartbeat_s") or 0)  # lint: ok — host json scalar
                if hb > 0:
                    self.heartbeat_s = hb
        return ok

    def _loop(self) -> None:
        beat = self.beat
        if beat is not None:
            beat.guard(self._loop_body)
        else:
            self._loop_body()

    def _loop_body(self) -> None:
        beat = self.beat
        while not self._stop.wait(self.heartbeat_s):
            if beat is not None:
                beat.tick()
            self._beat_all("/fleet/heartbeat")


_PROM_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prom(text: str) -> Dict[tuple, float]:
    """Prometheus text exposition -> {(name, sorted-label-tuple):
    value}. Tolerant: unparseable lines are skipped (a member running
    a newer build must still federate)."""
    out: Dict[tuple, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        if not head:
            continue
        try:
            value = float(val)  # lint: ok — host str
        except ValueError:
            continue
        brace = head.find("{")
        if brace < 0:
            out[(head, ())] = value
        else:
            labels = tuple(sorted(_PROM_LABEL_RE.findall(head[brace:])))
            out[(head[:brace], labels)] = value
    return out


def _prom_hist_p99(parsed: Dict[tuple, float], prev: Dict[tuple, float],
                   bucket_name: str) -> float:
    """p99 over the delta histogram between two scrapes, aggregated
    across every series of `bucket_name` (in-bucket linear
    interpolation, the registry's own estimator). 0.0 when the
    interval saw no observations."""
    by_le: Dict[float, float] = {}
    for (name, labels), v in parsed.items():
        if name != bucket_name:
            continue
        le_s = dict(labels).get("le", "+Inf")
        le = float("inf") if le_s == "+Inf" else float(le_s)  # lint: ok — host str
        delta = v - prev.get((name, labels), 0.0)
        if delta > 0:
            by_le[le] = by_le.get(le, 0.0) + delta
    if not by_le:
        return 0.0
    bounds = sorted(by_le)
    total = by_le[bounds[-1]] if bounds[-1] == float("inf") else max(
        by_le.values())
    if total <= 0:
        return 0.0
    target = 0.99 * total
    lower = 0.0
    prev_cum = 0.0
    for le in bounds:
        cum = by_le[le]
        if cum >= target:
            if le == float("inf"):
                return lower
            span = cum - prev_cum
            frac = ((target - prev_cum) / span) if span > 0 else 1.0
            return lower + (le - lower) * frac
        prev_cum = cum
        lower = le if le != float("inf") else lower
    return lower


def _federate_line(line: str, member: str) -> str:
    """Inject `member=` into one exposition sample line."""
    head, _, val = line.rpartition(" ")
    if head.endswith("}"):
        return f'{head[:-1]},member="{member}"}} {val}'
    return f'{head}{{member="{member}"}} {val}'


def _fleet_metrics(metrics: MetricsRegistry):
    return {
        "scrapes": metrics.counter(
            "pio_fleet_metrics_scrapes_total",
            "Member /metrics federation scrapes by outcome",
            labels=("outcome",)),
        "member_qps": metrics.gauge(
            "pio_fleet_member_qps",
            "Per-member HTTP request rate derived from federation "
            "scrapes", labels=("member",)),
        "member_p99": metrics.gauge(
            "pio_fleet_member_p99_seconds",
            "Per-member request p99 over the last scrape interval",
            labels=("member",)),
        "member_burn": metrics.gauge(
            "pio_fleet_member_burn",
            "Per-member worst 5m SLO burn rate", labels=("member",)),
        "member_balance": metrics.gauge(
            "pio_fleet_member_reactor_balance",
            "Per-member max/mean reactor request skew (1.0 = balanced)",
            labels=("member",)),
        "routed": metrics.counter(
            "pio_fleet_routed_total",
            "Router outcomes (ok/retried/redirected/no_replica/exhausted)",
            labels=("outcome",)),
        "transitions": metrics.counter(
            "pio_fleet_transitions_total",
            "Member lifecycle events (admit/eject/register/reload_start)",
            labels=("event",)),
        "rolls": metrics.counter(
            "pio_fleet_rolling_reload_total",
            "Rolling reloads by outcome", labels=("outcome",)),
        "admitted": metrics.gauge(
            "pio_fleet_replicas_admitted",
            "Members currently admitted to routing"),
        "size": metrics.gauge(
            "pio_fleet_replicas_total", "Members managed by the fleet"),
        "members": metrics.gauge(
            "pio_fleet_members",
            "Members in the routing table (in-process + remote)"),
        "leader": metrics.gauge(
            "pio_fleet_leader",
            "1 while this router holds the fleet leadership lease"),
        "handoff": metrics.counter(
            "pio_fleet_handoff_total",
            "Leadership handoffs (lease taken over from a dead holder)"),
        "beat_age": metrics.gauge(
            "pio_fleet_heartbeat_age_seconds",
            "Seconds since each member's last heartbeat or healthy probe",
            labels=("member",)),
        "shard_owner": metrics.gauge(
            "pio_fleet_shard_owner",
            "Mesh shard ownership (1 = admitted owner of the shard)",
            labels=("shard", "member")),
        "mesh": metrics.counter(
            "pio_fleet_mesh_merged_total",
            "Cross-host mesh merges by outcome (ok/partial/empty)",
            labels=("outcome",)),
    }
