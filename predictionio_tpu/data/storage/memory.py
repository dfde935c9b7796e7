"""In-memory storage driver ("MEM" type) — the test/default-free backend.

Serves the role of the reference's mocked storage in unit tests
(`data/.../storage/StorageMockContext.scala`) and doubles as a zero-setup
backend for quickstarts. Thread-safe via a single lock per client.
"""

from __future__ import annotations

import bisect
import itertools
import threading
import uuid
from datetime import datetime, timedelta, timezone
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from predictionio_tpu.data.event import Event, utcnow
from predictionio_tpu.data.storage import base
from predictionio_tpu.data.storage.base import (
    AccessKey, App, Channel, EngineInstance, EvaluationInstance, Lease, Model,
    SLOObjective, TenantQuota, _UNSET, event_filter,
)


class MemStorageClient:
    """Holds all tables for one 'source'; DAOs share it."""

    def __init__(self, config: Optional[dict] = None):
        self.config = config or {}
        self.lock = threading.RLock()
        self.apps: Dict[int, App] = {}
        self.access_keys: Dict[str, AccessKey] = {}
        self.channels: Dict[int, Channel] = {}
        self.engine_instances: Dict[str, EngineInstance] = {}
        self.evaluation_instances: Dict[str, EvaluationInstance] = {}
        self.models: Dict[str, Model] = {}
        self.leases: Dict[str, Lease] = {}
        # (appid, channel) -> row; channel "" is the app-wide row
        self.tenant_quotas: Dict[Tuple[int, str], TenantQuota] = {}
        self.slo_objectives: Dict[int, SLOObjective] = {}
        # (app_id, channel_id) -> event_id -> Event
        self.events: Dict[Tuple[int, Optional[int]], Dict[str, Event]] = {}
        # (app_id, channel_id) -> (entity_type, entity_id) -> the same
        # events by entity, in `_read_order` (MemEvents keeps it)
        self.events_by_entity: Dict[
            Tuple[int, Optional[int]],
            Dict[Tuple[str, str], List[Event]]] = {}
        self._app_seq = itertools.count(1)
        self._channel_seq = itertools.count(1)


class MemApps(base.Apps):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def insert(self, app: App) -> Optional[int]:
        with self.c.lock:
            if any(a.name == app.name for a in self.c.apps.values()):
                raise base.StorageWriteError(
                    f"App name {app.name!r} already exists")
            if app.id and app.id in self.c.apps:
                raise base.StorageWriteError(f"App id {app.id} already exists")
            app_id = app.id or next(self.c._app_seq)
            while app.id == 0 and app_id in self.c.apps:
                app_id = next(self.c._app_seq)
            self.c.apps[app_id] = App(app_id, app.name, app.description)
            return app_id

    def get(self, app_id: int) -> Optional[App]:
        return self.c.apps.get(app_id)

    def get_by_name(self, name: str) -> Optional[App]:
        with self.c.lock:
            for app in self.c.apps.values():
                if app.name == name:
                    return app
        return None

    def get_all(self) -> List[App]:
        return sorted(self.c.apps.values(), key=lambda a: a.id)

    def update(self, app: App) -> None:
        with self.c.lock:
            self.c.apps[app.id] = app

    def delete(self, app_id: int) -> None:
        with self.c.lock:
            self.c.apps.pop(app_id, None)


class MemAccessKeys(base.AccessKeys):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def insert(self, k: AccessKey) -> Optional[str]:
        with self.c.lock:
            key = k.key or self.generate_key()
            if key in self.c.access_keys:
                raise base.StorageWriteError(
                    f"Access key {key!r} already exists")
            self.c.access_keys[key] = AccessKey(key, k.appid, tuple(k.events))
            return key

    def get(self, key: str) -> Optional[AccessKey]:
        return self.c.access_keys.get(key)

    def get_all(self) -> List[AccessKey]:
        return list(self.c.access_keys.values())

    def get_by_appid(self, appid: int) -> List[AccessKey]:
        return [k for k in self.c.access_keys.values() if k.appid == appid]

    def update(self, k: AccessKey) -> None:
        with self.c.lock:
            self.c.access_keys[k.key] = k

    def delete(self, key: str) -> None:
        with self.c.lock:
            self.c.access_keys.pop(key, None)


class MemChannels(base.Channels):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def insert(self, channel: Channel) -> Optional[int]:
        with self.c.lock:
            if channel.id and channel.id in self.c.channels:
                raise base.StorageWriteError(
                    f"Channel id {channel.id} already exists")
            cid = channel.id or next(self.c._channel_seq)
            while channel.id == 0 and cid in self.c.channels:
                cid = next(self.c._channel_seq)
            self.c.channels[cid] = Channel(cid, channel.name, channel.appid)
            return cid

    def get(self, channel_id: int) -> Optional[Channel]:
        return self.c.channels.get(channel_id)

    def get_by_appid(self, appid: int) -> List[Channel]:
        return sorted((c for c in self.c.channels.values() if c.appid == appid),
                      key=lambda c: c.id)

    def delete(self, channel_id: int) -> None:
        with self.c.lock:
            self.c.channels.pop(channel_id, None)


class MemEngineInstances(base.EngineInstances):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def insert(self, i: EngineInstance) -> str:
        with self.c.lock:
            iid = i.id or uuid.uuid4().hex
            self.c.engine_instances[iid] = i.with_(id=iid)
            return iid

    def get(self, iid: str) -> Optional[EngineInstance]:
        return self.c.engine_instances.get(iid)

    def get_all(self) -> List[EngineInstance]:
        return list(self.c.engine_instances.values())

    def get_completed(self, engine_id, engine_version, engine_variant):
        with self.c.lock:
            rows = [i for i in self.c.engine_instances.values()
                    if i.status == base.EngineInstanceStatus.COMPLETED
                    and i.engine_id == engine_id
                    and i.engine_version == engine_version
                    and i.engine_variant == engine_variant]
        return sorted(rows, key=lambda i: i.start_time, reverse=True)

    def get_latest_completed(self, engine_id, engine_version, engine_variant):
        rows = self.get_completed(engine_id, engine_version, engine_variant)
        return rows[0] if rows else None

    def update(self, i: EngineInstance) -> None:
        with self.c.lock:
            self.c.engine_instances[i.id] = i

    def delete(self, iid: str) -> None:
        with self.c.lock:
            self.c.engine_instances.pop(iid, None)


class MemEvaluationInstances(base.EvaluationInstances):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def insert(self, i: EvaluationInstance) -> str:
        with self.c.lock:
            iid = i.id or uuid.uuid4().hex
            self.c.evaluation_instances[iid] = i.with_(id=iid)
            return iid

    def get(self, iid: str) -> Optional[EvaluationInstance]:
        return self.c.evaluation_instances.get(iid)

    def get_all(self) -> List[EvaluationInstance]:
        return list(self.c.evaluation_instances.values())

    def get_completed(self) -> List[EvaluationInstance]:
        rows = [i for i in self.c.evaluation_instances.values()
                if i.status == base.EvaluationInstanceStatus.COMPLETED]
        return sorted(rows, key=lambda i: i.start_time, reverse=True)

    def update(self, i: EvaluationInstance) -> None:
        with self.c.lock:
            self.c.evaluation_instances[i.id] = i

    def delete(self, iid: str) -> None:
        with self.c.lock:
            self.c.evaluation_instances.pop(iid, None)


class MemModels(base.Models):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def insert(self, m: Model) -> None:
        with self.c.lock:
            self.c.models[m.id] = m

    def get(self, mid: str) -> Optional[Model]:
        return self.c.models.get(mid)

    def delete(self, mid: str) -> None:
        with self.c.lock:
            self.c.models.pop(mid, None)

    def list_model_ids(self) -> List[str]:
        with self.c.lock:
            return sorted(self.c.models)


class MemTenantQuotas(base.TenantQuotas):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def upsert(self, quota: TenantQuota) -> None:
        with self.c.lock:
            self.c.tenant_quotas[(quota.appid, quota.channel)] = quota

    def get(self, appid: int, channel: str = "") -> Optional[TenantQuota]:
        with self.c.lock:
            return self.c.tenant_quotas.get((appid, channel))

    def get_all(self) -> List[TenantQuota]:
        with self.c.lock:
            return [self.c.tenant_quotas[k]
                    for k in sorted(self.c.tenant_quotas)]

    def delete(self, appid: int, channel: str = "") -> None:
        with self.c.lock:
            self.c.tenant_quotas.pop((appid, channel), None)


class MemSLOObjectives(base.SLOObjectives):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def upsert(self, slo: SLOObjective) -> None:
        with self.c.lock:
            self.c.slo_objectives[slo.appid] = slo

    def get(self, appid: int) -> Optional[SLOObjective]:
        with self.c.lock:
            return self.c.slo_objectives.get(appid)

    def get_all(self) -> List[SLOObjective]:
        with self.c.lock:
            return [self.c.slo_objectives[k]
                    for k in sorted(self.c.slo_objectives)]

    def delete(self, appid: int) -> None:
        with self.c.lock:
            self.c.slo_objectives.pop(appid, None)


class MemLeases(base.Leases):
    def __init__(self, client: MemStorageClient):
        self.c = client

    def acquire(self, name: str, holder: str, ttl_s: float,
                journal: Optional[str] = None) -> Optional[Lease]:
        with self.c.lock:
            now = utcnow()
            cur = self.c.leases.get(name)
            if cur is not None and cur.holder != holder \
                    and not cur.expired(now):
                return None
            # journal=None inherits the row's journal even across a
            # holder change — a standby taking over an expired lease
            # must not wipe the previous leader's roll journal
            keep = (cur.journal if cur is not None else "") \
                if journal is None else journal
            lease = Lease(name, holder, now + timedelta(seconds=ttl_s), keep)
            self.c.leases[name] = lease
            return lease

    def get(self, name: str) -> Optional[Lease]:
        return self.c.leases.get(name)

    def release(self, name: str, holder: str) -> bool:
        with self.c.lock:
            cur = self.c.leases.get(name)
            if cur is None or cur.holder != holder:
                return False
            del self.c.leases[name]
            return True


def _read_order(e: Event) -> Tuple[int, str]:
    """The order `find` returns events in: event time in epoch
    milliseconds, then event id. Computed where it is needed and never
    stored: the index holds the events alone."""
    return (e.event_time_millis, e.event_id or "")


def _not_before(prev: Event, e: Event) -> bool:
    """`_read_order(prev) <= _read_order(e)`, mostly without computing
    either key: the millisecond is monotone in the time, so UTC (or
    naive, read as UTC) times in order and ids in order settle it."""
    tz = e.event_time.tzinfo
    if (tz is timezone.utc or tz is None) and prev.event_time.tzinfo is tz \
            and prev.event_time <= e.event_time \
            and prev.event_id <= e.event_id:
        return True
    return _read_order(prev) <= _read_order(e)


_IN_ORDER = None


def _in_order():
    """`pio_mem_find_in_order`, in the process-default registry (lazy,
    like `ops/seqrec._seq_metrics`): one observation a `find`, 1 where
    it answered from an entity's events kept in `_read_order`, 0 where
    it scanned and sorted, so its mean is the share answered in order."""
    global _IN_ORDER
    if _IN_ORDER is None:
        from predictionio_tpu.obs import get_registry
        _IN_ORDER = get_registry().histogram(
            "pio_mem_find_in_order",
            "MEM event reads: 1 for one answered from an entity's events "
            "kept in time order, 0 for one that scanned and sorted",
            buckets=(0.0, 1.0))
    return _IN_ORDER


class MemEvents(base.EventStore):
    """Events of one (app, channel) in insertion order, and beside them
    an index by entity, kept on insert, delete and remove: each entity's
    events in `_read_order`. A `find` that names `entity_type` and
    `entity_id` (the serve-time history read) walks that entity's list
    from the end the call asks for and stops at `limit`: no event of
    another entity is looked at, and nothing is sorted. Same results,
    same order as the scan and sort of every other `find`."""

    def __init__(self, client: MemStorageClient):
        self.c = client

    def _table(self, app_id: int, channel_id: Optional[int]) -> Dict[str, Event]:
        return self.c.events.setdefault((app_id, channel_id), {})

    def _index(self, app_id: int, channel_id: Optional[int]
               ) -> Dict[Tuple[str, str], List[Event]]:
        return self.c.events_by_entity.setdefault((app_id, channel_id), {})

    def init(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        with self.c.lock:
            self._table(app_id, channel_id)
        return True

    def remove(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        with self.c.lock:
            self.c.events.pop((app_id, channel_id), None)
            self.c.events_by_entity.pop((app_id, channel_id), None)
        return True

    def close(self) -> None:
        pass

    def _insert(self, event: Event, app_id: int,
                channel_id: Optional[int] = None) -> str:
        with self.c.lock:
            e = event if event.event_id else event.with_id()
            table = self._table(app_id, channel_id)
            if e.event_id in table:
                raise base.StorageWriteError(
                    f"Duplicate event id {e.event_id}")
            table[e.event_id] = e
            index = self._index(app_id, channel_id)
            entity = (e.entity_type, e.entity_id)
            of_entity = index.get(entity)
            # events mostly arrive in time: one not before its entity's
            # last is appended
            if of_entity is None:
                index[entity] = [e]
            elif _not_before(of_entity[-1], e):
                of_entity.append(e)
            else:
                bisect.insort(of_entity, e, key=_read_order)
            return e.event_id

    def get(self, event_id: str, app_id: int,
            channel_id: Optional[int] = None) -> Optional[Event]:
        return self._table(app_id, channel_id).get(event_id)

    def delete(self, event_id: str, app_id: int,
               channel_id: Optional[int] = None) -> bool:
        with self.c.lock:
            e = self._table(app_id, channel_id).pop(event_id, None)
            if e is None:
                return False
            index = self._index(app_id, channel_id)
            of_entity = index[(e.entity_type, e.entity_id)]
            # ids are unique in the table, so the key finds `e` itself
            del of_entity[bisect.bisect_left(of_entity, _read_order(e),
                                             key=_read_order)]
            if not of_entity:
                del index[(e.entity_type, e.entity_id)]
            return True

    def find(self, app_id: int, channel_id: Optional[int] = None, *,
             start_time: Optional[datetime] = None,
             until_time: Optional[datetime] = None,
             entity_type: Optional[str] = None,
             entity_id: Optional[str] = None,
             event_names: Optional[Sequence[str]] = None,
             target_entity_type: object = _UNSET,
             target_entity_id: object = _UNSET,
             properties=None,
             limit: Optional[int] = None,
             reversed: bool = False) -> Iterator[Event]:
        in_order = entity_type is not None and entity_id is not None
        _in_order().observe(float(in_order))
        if not in_order:
            with self.c.lock:
                events = list(self._table(app_id, channel_id).values())
            match = event_filter(
                start_time=start_time, until_time=until_time,
                entity_type=entity_type, entity_id=entity_id,
                event_names=event_names,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id, properties=properties)
            events = [e for e in events if match(e)]
            events.sort(key=_read_order, reverse=reversed)
            if limit is not None and limit > 0:
                events = events[:limit]
            return iter(events)
        with self.c.lock:
            events = self._index(app_id, channel_id).get(
                (entity_type, entity_id), [])
            events = events[::-1] if reversed else events[:]
        # the index holds this entity's events alone
        found = filter(event_filter(
            start_time=start_time, until_time=until_time,
            event_names=event_names, target_entity_type=target_entity_type,
            target_entity_id=target_entity_id, properties=properties),
            events)
        if limit is not None and limit > 0:
            found = itertools.islice(found, limit)
        return iter(list(found))
