"""Storage SPI: DAO interfaces and metadata records.

Parity targets in the reference:
  - meta records/DAOs: `data/.../storage/{Apps,AccessKeys,Channels,
    EngineInstances,EvaluationInstances,Models}.scala`
  - event DAO: `data/.../storage/LEvents.scala:40-520` (the non-Spark event
    access used by servers and the CLI). The reference's separate `PEvents`
    (Spark RDD access) has no direct analog here: its role — bulk reads for
    training — is played by `predictionio_tpu.ingest`, which streams
    `EventStore.find` results into dense sharded jax.Arrays.

Drivers implement these ABCs and are discovered by the registry in
`predictionio_tpu.data.storage` (see `registry.py`) from layered config, the
analog of `Storage.scala:159-357`'s env-driven reflection.
"""

from __future__ import annotations

import abc
import base64
import secrets
import re
from dataclasses import dataclass, field, replace
from datetime import datetime
from typing import (
    Any, Callable, Dict, Iterator, List, Mapping, Optional, Sequence,
)

from predictionio_tpu.data.aggregate import aggregate_properties
from predictionio_tpu.data.event import Event, PropertyMap, utcnow


class StorageError(Exception):
    """Parity: StorageException (Storage.scala:88)."""


class StorageWriteError(StorageError):
    """A write rejected by the backend (duplicate key, constraint violation)."""


class StorageUnavailableError(StorageError):
    """A transient backend failure (connection refused, timeout, flaky
    remote). Drivers raise this — or a plain OSError — for conditions a
    retry can cure; the resilience proxy (`resilient.py`) retries these
    and trips the source's circuit breaker when they persist. Client
    errors (StorageWriteError, bad params) must NOT use this type."""


# what the storage resilience layer treats as retryable / breaker-tripping
# (ConnectionError and TimeoutError are OSError subclasses)
TRANSIENT_STORAGE_ERRORS = (StorageUnavailableError, OSError)


# ---------------------------------------------------------------------------
# Meta data records
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class App:
    """An application namespace for events (Apps.scala:25-35)."""
    id: int
    name: str
    description: Optional[str] = None


@dataclass(frozen=True)
class AccessKey:
    """An API access key; empty `events` list = all events allowed
    (AccessKeys.scala:25-38)."""
    key: str
    appid: int
    events: Sequence[str] = ()


CHANNEL_NAME_RE = re.compile(r"^[a-zA-Z0-9-]{1,16}$")
CHANNEL_NAME_CONSTRAINT = (
    "Only alphanumeric and - characters are allowed and max length is 16.")


@dataclass(frozen=True)
class Channel:
    """A named event channel within an app (Channels.scala:25-62)."""
    id: int
    name: str
    appid: int

    def __post_init__(self):
        if not self.is_valid_name(self.name):
            raise ValueError(
                f"Invalid channel name: {self.name}. {CHANNEL_NAME_CONSTRAINT}")

    @staticmethod
    def is_valid_name(s: str) -> bool:
        return bool(CHANNEL_NAME_RE.match(s))


class EngineInstanceStatus:
    INIT = "INIT"
    TRAINING = "TRAINING"
    COMPLETED = "COMPLETED"
    FAILED = "FAILED"


class EvaluationInstanceStatus:
    INIT = "EVALINIT"
    RUNNING = "EVALRUNNING"
    COMPLETED = "EVALCOMPLETED"


@dataclass(frozen=True)
class EngineInstance:
    """Metadata row for one train run (EngineInstances.scala:25-60).

    `runtime_conf` replaces the reference's `sparkConf`: it carries the
    JAX runtime configuration (mesh shape, platform, precision flags).
    """
    id: str = ""
    status: str = ""
    start_time: datetime = field(default_factory=utcnow)
    end_time: datetime = field(default_factory=utcnow)
    engine_id: str = ""
    engine_version: str = ""
    engine_variant: str = ""
    engine_factory: str = ""
    batch: str = ""
    env: Mapping[str, str] = field(default_factory=dict)
    runtime_conf: Mapping[str, Any] = field(default_factory=dict)
    data_source_params: str = ""
    preparator_params: str = ""
    algorithms_params: str = ""
    serving_params: str = ""
    # last liveness beat from the training process; the stale-instance
    # janitor fails INIT/TRAINING rows whose heartbeat (or, if never
    # beaten, start_time) is older than the staleness threshold
    heartbeat: Optional[datetime] = None

    def with_(self, **kw) -> "EngineInstance":
        return replace(self, **kw)


@dataclass(frozen=True)
class EvaluationInstance:
    """Metadata row for one eval run (EvaluationInstances.scala:25-56)."""
    id: str = ""
    status: str = ""
    start_time: datetime = field(default_factory=utcnow)
    end_time: datetime = field(default_factory=utcnow)
    evaluation_class: str = ""
    engine_params_generator_class: str = ""
    batch: str = ""
    env: Mapping[str, str] = field(default_factory=dict)
    runtime_conf: Mapping[str, Any] = field(default_factory=dict)
    evaluator_results: str = ""
    evaluator_results_html: str = ""
    evaluator_results_json: str = ""

    def with_(self, **kw) -> "EvaluationInstance":
        return replace(self, **kw)


@dataclass(frozen=True)
class Model:
    """Serialized model blob keyed by engine instance ID (Models.scala:25-33)."""
    id: str
    models: bytes


@dataclass(frozen=True)
class Lease:
    """A TTL lease row: the fleet control plane's leader-election
    primitive (no reference analog — the reference's CreateServer is a
    single actor system; cross-host leader handoff needs shared state).

    `journal` is an opaque payload the holder may update while the
    lease is held — the fleet writes its rolling-reload progress there
    so a standby taking over can detect a half-rolled fleet and finish
    or abort it instead of leaving it silently mixed."""
    name: str
    holder: str
    expires_at: datetime
    journal: str = ""

    def expired(self, now: Optional[datetime] = None) -> bool:
        return (now or utcnow()) >= self.expires_at


@dataclass(frozen=True)
class TenantQuota:
    """Per-app admission-control override row (no reference analog —
    the reference is multi-app on ingest only; serve-side quotas are
    this port's million-user follow-on). Every field except `appid` is
    Optional: None means 'inherit the server-wide default' so an
    operator can raise one knob without freezing the rest.

    `channel` scopes the row WITHIN an app: "" (the default) is the
    app-wide row; a non-empty channel names a sub-quota that inherits
    every unset field from the app-wide row, which in turn inherits
    from the server default — a three-level resolution chain the
    admission controller walks (channel.merged_over(app).merged_over(
    default)). The field is LAST so `TenantQuota(*row)` positional
    construction from pre-channel readers keeps working."""
    appid: int
    rate: Optional[float] = None         # token-bucket refill, req/s
    burst: Optional[float] = None        # bucket capacity, requests
    concurrency: Optional[int] = None    # in-flight cap (0 = unlimited)
    queue_max: Optional[int] = None      # micro-batch pending cap
    weight: Optional[float] = None       # DRR drain weight
    channel: str = ""                    # "" = app-wide row

    def merged_over(self, other: "TenantQuota") -> "TenantQuota":
        """This row's explicit fields over `other`'s (defaults)."""
        return TenantQuota(
            appid=self.appid,
            rate=self.rate if self.rate is not None else other.rate,
            burst=self.burst if self.burst is not None else other.burst,
            concurrency=(self.concurrency if self.concurrency is not None
                         else other.concurrency),
            queue_max=(self.queue_max if self.queue_max is not None
                       else other.queue_max),
            weight=self.weight if self.weight is not None else other.weight,
            channel=self.channel)


@dataclass(frozen=True)
class SLOObjective:
    """Per-app service-level objective override row (read by
    `obs/slo.py`'s burn-rate tracker, the serving-side counterpart of
    `TenantQuota`). None means 'inherit the server-wide default' from
    PIO_SLO_LATENCY_MS / PIO_SLO_TARGET."""
    appid: int
    latency_ms: Optional[float] = None   # good-event latency threshold
    target: Optional[float] = None       # availability objective, e.g. 0.999


# ---------------------------------------------------------------------------
# DAO interfaces
# ---------------------------------------------------------------------------

class Apps(abc.ABC):
    """App CRUD (Apps.scala:43-61)."""

    @abc.abstractmethod
    def insert(self, app: App) -> Optional[int]:
        """Insert; a 0 id means 'generate one'. Returns the effective id."""

    @abc.abstractmethod
    def get(self, app_id: int) -> Optional[App]: ...

    @abc.abstractmethod
    def get_by_name(self, name: str) -> Optional[App]: ...

    @abc.abstractmethod
    def get_all(self) -> List[App]: ...

    @abc.abstractmethod
    def update(self, app: App) -> None: ...

    @abc.abstractmethod
    def delete(self, app_id: int) -> None: ...


class AccessKeys(abc.ABC):
    """Access key CRUD + generation (AccessKeys.scala:46-77)."""

    @abc.abstractmethod
    def insert(self, k: AccessKey) -> Optional[str]:
        """Insert; empty key means 'generate one'. Returns the effective key."""

    @abc.abstractmethod
    def get(self, key: str) -> Optional[AccessKey]: ...

    @abc.abstractmethod
    def get_all(self) -> List[AccessKey]: ...

    @abc.abstractmethod
    def get_by_appid(self, appid: int) -> List[AccessKey]: ...

    @abc.abstractmethod
    def update(self, k: AccessKey) -> None: ...

    @abc.abstractmethod
    def delete(self, key: str) -> None: ...

    def generate_key(self) -> str:
        """URL-safe 48-byte random key, never starting with '-'
        (AccessKeys.scala:68-77)."""
        while True:
            key = base64.urlsafe_b64encode(secrets.token_bytes(48)).decode().rstrip("=")
            if not key.startswith("-"):
                return key


class Channels(abc.ABC):
    """Channel CRUD (Channels.scala:64-81)."""

    @abc.abstractmethod
    def insert(self, channel: Channel) -> Optional[int]:
        """Insert; a 0 id means 'generate one'. Returns the effective id."""

    @abc.abstractmethod
    def get(self, channel_id: int) -> Optional[Channel]: ...

    @abc.abstractmethod
    def get_by_appid(self, appid: int) -> List[Channel]: ...

    @abc.abstractmethod
    def delete(self, channel_id: int) -> None: ...


class EngineInstances(abc.ABC):
    """Engine instance registry (EngineInstances.scala:62-100)."""

    @abc.abstractmethod
    def insert(self, i: EngineInstance) -> str: ...

    @abc.abstractmethod
    def get(self, iid: str) -> Optional[EngineInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def get_latest_completed(self, engine_id: str, engine_version: str,
                             engine_variant: str) -> Optional[EngineInstance]:
        """Most recent COMPLETED instance for (id, version, variant) — the
        row `deploy` resolves (EngineInstances.scala getLatestCompleted)."""

    @abc.abstractmethod
    def get_completed(self, engine_id: str, engine_version: str,
                      engine_variant: str) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def update(self, i: EngineInstance) -> None: ...

    @abc.abstractmethod
    def delete(self, iid: str) -> None: ...

    def record_heartbeat(self, iid: str,
                         ts: Optional[datetime] = None) -> None:
        """Refresh the liveness beat on a row (default impl: get+update;
        drivers may override with a single-column write)."""
        row = self.get(iid)
        if row is not None:
            self.update(row.with_(heartbeat=ts or utcnow()))


class EvaluationInstances(abc.ABC):
    """Evaluation instance registry (EvaluationInstances.scala:58-84)."""

    @abc.abstractmethod
    def insert(self, i: EvaluationInstance) -> str: ...

    @abc.abstractmethod
    def get(self, iid: str) -> Optional[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_completed(self) -> List[EvaluationInstance]:
        """COMPLETED instances, reverse-sorted by start time."""

    @abc.abstractmethod
    def update(self, i: EvaluationInstance) -> None: ...

    @abc.abstractmethod
    def delete(self, iid: str) -> None: ...


class Models(abc.ABC):
    """Model blob store (Models.scala:36-45)."""

    @abc.abstractmethod
    def insert(self, m: Model) -> None: ...

    @abc.abstractmethod
    def get(self, mid: str) -> Optional[Model]: ...

    @abc.abstractmethod
    def delete(self, mid: str) -> None: ...

    def list_model_ids(self) -> List[str]:
        """Store-enumerable model ids, sorted. Default: the driver
        cannot enumerate (object stores without listing, etc.) — the
        fsck/doctor sweeps then fall back to metadata-derived ids
        alone. Drivers with lossy key escaping (localfs) return the
        ESCAPED names; instance ids are alphanumeric so the escape is
        the identity for every id the system itself writes."""
        return []


class Leases(abc.ABC):
    """TTL lease DAO — compare-and-swap leader election on shared
    storage. Acquire semantics (the only subtle part): `acquire`
    succeeds iff the row is absent, expired, or already held by the
    same holder (re-acquire == renew). Clocks are the metadata store's
    callers' — holders must pick TTLs that dominate their renewal
    jitter, not rely on sub-second fencing."""

    @abc.abstractmethod
    def acquire(self, name: str, holder: str, ttl_s: float,
                journal: Optional[str] = None) -> Optional[Lease]:
        """CAS-acquire/renew `name` for `holder` with a fresh TTL.
        Returns the new lease row on success, None when a different
        holder's unexpired lease exists. `journal=None` preserves the
        row's existing journal — even across a holder change, so a
        standby taking over an expired lease inherits the previous
        leader's roll journal atomically; a string replaces it (empty
        string clears it)."""

    @abc.abstractmethod
    def get(self, name: str) -> Optional[Lease]:
        """The current row, expired or not; None when absent. Callers
        decide what expiry means (`lease.expired()`)."""

    @abc.abstractmethod
    def release(self, name: str, holder: str) -> bool:
        """Delete the row iff `holder` still owns it. True when the
        row was deleted (a graceful step-down); False when someone
        else holds it or it is gone already."""


class TenantQuotas(abc.ABC):
    """Per-app quota-override CRUD on the metadata store, read by the
    serving admission controller (cached, so a write lands within its
    refresh interval, not instantly)."""

    @abc.abstractmethod
    def upsert(self, quota: TenantQuota) -> None:
        """Insert or fully replace the override row for
        `(quota.appid, quota.channel)`."""

    @abc.abstractmethod
    def get(self, appid: int,
            channel: str = "") -> Optional[TenantQuota]: ...

    @abc.abstractmethod
    def get_all(self) -> List[TenantQuota]: ...

    @abc.abstractmethod
    def delete(self, appid: int, channel: str = "") -> None: ...


class SLOObjectives(abc.ABC):
    """Per-app SLO-override CRUD on the metadata store, read by the
    serving SLO tracker (TTL-cached, like `TenantQuotas`)."""

    @abc.abstractmethod
    def upsert(self, slo: SLOObjective) -> None:
        """Insert or fully replace the override row for `slo.appid`."""

    @abc.abstractmethod
    def get(self, appid: int) -> Optional[SLOObjective]: ...

    @abc.abstractmethod
    def get_all(self) -> List[SLOObjective]: ...

    @abc.abstractmethod
    def delete(self, appid: int) -> None: ...


# ---------------------------------------------------------------------------
# Event store
# ---------------------------------------------------------------------------

_UNSET = object()  # sentinel distinguishing "no filter" from "filter == None"


class DeltaInvalidated(Exception):
    """A `scan_columns(since=...)` delta cannot be decoded safely — a
    delete/tombstone, external-id overwrite, journal rewrite, or
    over-budget delta landed between the two watermarks, or the driver
    has no delta path at all. Callers MUST fall back to a full scan
    (the watermark-keyed full `scan_columns`), which remains the ground
    truth. Deliberately NOT an OSError: the resilience layer must not
    retry it as a transient storage fault."""


def match_properties(e: Event, properties: Dict[str, object]) -> bool:
    """True iff every (name, value) filter pair appears verbatim in the
    event's properties (the ES field-value query role). Uses the
    PropertyMap's own `in`/`[]` — `.fields` copies the dict, which adds
    up on the per-event post-filter path."""
    pm = e.properties
    for k, v in properties.items():
        if k not in pm or pm[k] != v:
            return False
    return True


class EventStore(abc.ABC):
    """Event DAO, the analog of the reference's `LEvents` trait
    (LEvents.scala:40-520). All operations are scoped to an (app, channel);
    channel_id None = the app's default channel.

    Filter semantics of `find` match `LEvents.futureFind`:
      - start_time inclusive, until_time exclusive
      - event_names: any-of filter
      - target_entity_type/id use a three-state convention: leave the kwarg
        at its default for "no filter"; pass None to match events WITHOUT a
        target entity; pass a string to match it exactly (the reference's
        Option[Option[String]]).
    """

    @abc.abstractmethod
    def init(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Initialize storage for an (app, channel); idempotent."""

    @abc.abstractmethod
    def remove(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Drop all events of an (app, channel)."""

    @abc.abstractmethod
    def close(self) -> None: ...

    def insert(self, event: Event, app_id: int,
               channel_id: Optional[int] = None) -> str:
        """Insert one event (validated first); returns its id."""
        from predictionio_tpu.data.event import EventValidation
        EventValidation.validate(event)
        return self._insert(event, app_id, channel_id)

    def insert_batch(self, events: Sequence[Event], app_id: int,
                     channel_id: Optional[int] = None) -> List[str]:
        from predictionio_tpu.data.event import EventValidation
        for e in events:
            EventValidation.validate(e)
        return self._insert_batch(events, app_id, channel_id)

    @abc.abstractmethod
    def _insert(self, event: Event, app_id: int,
                channel_id: Optional[int] = None) -> str: ...

    def _insert_batch(self, events: Sequence[Event], app_id: int,
                      channel_id: Optional[int] = None) -> List[str]:
        return [self._insert(e, app_id, channel_id) for e in events]

    @abc.abstractmethod
    def get(self, event_id: str, app_id: int,
            channel_id: Optional[int] = None) -> Optional[Event]: ...

    @abc.abstractmethod
    def delete(self, event_id: str, app_id: int,
               channel_id: Optional[int] = None) -> bool: ...

    @abc.abstractmethod
    def find(self, app_id: int, channel_id: Optional[int] = None, *,
             start_time: Optional[datetime] = None,
             until_time: Optional[datetime] = None,
             entity_type: Optional[str] = None,
             entity_id: Optional[str] = None,
             event_names: Optional[Sequence[str]] = None,
             target_entity_type: object = _UNSET,
             target_entity_id: object = _UNSET,
             properties: Optional[Dict[str, object]] = None,
             limit: Optional[int] = None,
             reversed: bool = False) -> Iterator[Event]:
        """Find events; limit None = unlimited, limit <= 0 = unlimited
        (LEvents futureFind; the API layer applies its own default of 20).
        reversed=True requires entity_type+entity_id in the API layer; the
        store just sorts descending by event time.

        `properties` filters on exact property values: an event matches
        when every (name, value) pair appears in its properties — the
        arbitrary field-value query the reference delegates to
        Elasticsearch's query DSL (ESLEvents.scala:308). Every driver
        supports it (post-filter); PEVLOG additionally pushes it down to
        a per-segment property Bloom so non-matching segments are never
        replayed."""

    # -- derived operations --------------------------------------------------
    def scan_columns(self, app_id: int, channel_id: Optional[int] = None, *,
                     start_time: Optional[datetime] = None,
                     until_time: Optional[datetime] = None,
                     entity_type: Optional[str] = None,
                     entity_id: Optional[str] = None,
                     event_names: Optional[Sequence[str]] = None,
                     target_entity_type: object = _UNSET,
                     target_entity_id: object = _UNSET,
                     properties: Optional[Dict[str, object]] = None,
                     value_spec=None, require_target: bool = True,
                     workers: Optional[int] = None,
                     since: Optional[Dict[str, int]] = None,
                     upto: Optional[Dict[str, int]] = None):
        """Columnar training scan: `find` filter semantics, but the
        result is an `EventColumns` struct (interned int32 entity ids,
        float32 values per `value_spec`, int64 event times) instead of
        an Event iterator — the zero-object path template DataSources
        feed into `RatingColumns.from_store`/`PairColumns.from_store`.

        `since` is an `ingest_watermark()` snapshot: decode ONLY data
        appended after it (the streaming delta path), raising
        `DeltaInvalidated` whenever the delta cannot be produced exactly
        (deletes, rewrites, unsupported driver — this base
        implementation always raises, since `find` has no append-order
        lower bound). `upto` pins the delta's exclusive upper bound to a
        watermark the caller snapshotted BEFORE the scan, so the result
        provably corresponds to the `upto` fingerprint even while
        writers keep appending.

        This base implementation adapts `find()` (drivers keep their
        own pushdown); PEVLOG overrides it with a chunk-parallel
        raw-frame decode. `workers` is advisory — a driver without a
        parallel scan ignores it."""
        if since is not None:
            raise DeltaInvalidated(
                f"{type(self).__name__} has no delta scan path")
        del upto
        from predictionio_tpu.data.storage.columns import columns_from_events
        return columns_from_events(
            self.find(app_id, channel_id, start_time=start_time,
                      until_time=until_time, entity_type=entity_type,
                      entity_id=entity_id, event_names=event_names,
                      target_entity_type=target_entity_type,
                      target_entity_id=target_entity_id,
                      properties=properties),
            value_spec, require_target)

    def ingest_watermark(self, app_id: int,
                         channel_id: Optional[int] = None
                         ) -> Optional[Dict[str, int]]:
        """Monotone content fingerprint for the prepared-data cache:
        any insert/delete must change it. None (the default) disables
        caching for this driver."""
        return None

    def ingest_cache_dir(self, app_id: int,
                         channel_id: Optional[int] = None):
        """Directory for prepared-data cache blobs, or None when the
        driver has no natural on-disk home for them."""
        return None

    def aggregate_properties(self, app_id: int,
                             channel_id: Optional[int] = None, *,
                             entity_type: str,
                             start_time: Optional[datetime] = None,
                             until_time: Optional[datetime] = None,
                             required: Optional[Sequence[str]] = None,
                             ) -> Dict[str, PropertyMap]:
        """Replay $set/$unset/$delete into final per-entity properties
        (LEvents.futureAggregateProperties, LEvents.scala:393-440)."""
        events = self.find(
            app_id, channel_id,
            start_time=start_time, until_time=until_time,
            entity_type=entity_type,
            event_names=["$set", "$unset", "$delete"])
        result = aggregate_properties(events)
        if required:
            req = list(required)
            result = {k: v for k, v in result.items()
                      if all(r in v.fields for r in req)}
        return result

    def aggregate_properties_of_entity(
            self, app_id: int, channel_id: Optional[int] = None, *,
            entity_type: str, entity_id: str,
            start_time: Optional[datetime] = None,
            until_time: Optional[datetime] = None) -> Optional[PropertyMap]:
        from predictionio_tpu.data.aggregate import aggregate_properties_single
        events = self.find(
            app_id, channel_id,
            start_time=start_time, until_time=until_time,
            entity_type=entity_type, entity_id=entity_id,
            event_names=["$set", "$unset", "$delete"])
        return aggregate_properties_single(events)


def event_filter(*,
                 start_time: Optional[datetime] = None,
                 until_time: Optional[datetime] = None,
                 entity_type: Optional[str] = None,
                 entity_id: Optional[str] = None,
                 event_names: Optional[Sequence[str]] = None,
                 target_entity_type: object = _UNSET,
                 target_entity_id: object = _UNSET,
                 properties: Optional[Dict[str, object]] = None
                 ) -> Callable[[Event], bool]:
    """`find`'s filter semantics as one in-memory predicate, built once a
    read: it tests only the filters the call gives, and the event names
    as a set made here."""
    tests: List[Callable[[Event], bool]] = []
    if start_time is not None:
        lo = _aware(start_time)
        tests.append(lambda e: e.event_time >= lo)
    if until_time is not None:
        hi = _aware(until_time)
        tests.append(lambda e: e.event_time < hi)
    if entity_type is not None:
        tests.append(lambda e: e.entity_type == entity_type)
    if entity_id is not None:
        tests.append(lambda e: e.entity_id == entity_id)
    if event_names is not None:
        names = frozenset(event_names)
        tests.append(lambda e: e.event in names)
    if target_entity_type is not _UNSET:
        tests.append(lambda e: e.target_entity_type == target_entity_type)
    if target_entity_id is not _UNSET:
        tests.append(lambda e: e.target_entity_id == target_entity_id)
    if properties:
        tests.append(lambda e: match_properties(e, properties))
    if len(tests) == 1:
        return tests[0]

    def match(e: Event) -> bool:
        for test in tests:
            if not test(e):
                return False
        return True
    return match


def _aware(t: datetime) -> datetime:
    from datetime import timezone
    return t if t.tzinfo else t.replace(tzinfo=timezone.utc)
