"""Device mesh + sharding utilities.

The TPU-native replacement for Spark's cluster-manager/executor topology
(reference: `tools/.../Runner.scala:185-307` spark-submit launching,
SURVEY.md §2.8). A `MeshSpec` is carried in engine-instance `runtime_conf`
(the slot the reference used for `sparkConf`) so training and serving agree
on the device layout.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Mapping, Optional

import numpy as np


@dataclass(frozen=True)
class MeshSpec:
    """A declarative mesh shape: axis name -> size; -1 means 'all remaining
    devices'. The default is pure data parallelism over every device, the
    analog of Spark defaulting to one partition per core."""
    axes: Mapping[str, int] = field(default_factory=lambda: {"data": -1})

    def resolve(self, n_devices: int) -> "Tuple[Tuple[str, ...], Tuple[int, ...]]":
        names = tuple(self.axes.keys())
        sizes = list(self.axes.values())
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError("At most one mesh axis may be -1")
        fixed = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[wild[0]] = n_devices // fixed
        total = int(np.prod(sizes)) if sizes else 1
        if total > n_devices:
            raise ValueError(
                f"Mesh {dict(zip(names, sizes))} needs {total} devices, "
                f"have {n_devices}")
        return names, tuple(int(s) for s in sizes)

    @staticmethod
    def from_conf(conf: Mapping[str, str]) -> "MeshSpec":
        """Parse 'mesh' key of runtime_conf, e.g. 'data=8' or
        'data=4,model=2'. Missing/empty -> default all-data mesh."""
        s = (conf or {}).get("mesh", "")
        if not s:
            return MeshSpec()
        axes = {}
        for part in s.split(","):
            k, _, v = part.partition("=")
            axes[k.strip()] = int(v)
        return MeshSpec(axes)


def parse_fleet_mesh(spec: str):
    """Parse a cross-host mesh spec: `items=N@fleet` (router side:
    merge over N member-owned shards) or `items=N@fleet:i` (member
    side: this process owns shard i). Returns (n_shards, index-or-None)
    or None when `spec` is not a fleet mesh."""
    import re
    m = re.match(r"\s*items\s*=\s*(\d+)\s*@\s*fleet(?::(\d+))?\s*$",
                 spec or "")
    if m is None:
        return None
    n = int(m.group(1))
    idx = int(m.group(2)) if m.group(2) is not None else None
    if n < 1 or (idx is not None and not 0 <= idx < n):
        raise ValueError(f"bad fleet mesh spec {spec!r}: need "
                         "items=N@fleet[:i] with 0 <= i < N")
    return n, idx


def make_mesh(spec: Optional[MeshSpec] = None, devices=None):
    """Build a `jax.sharding.Mesh` from a spec over the available devices.

    Uses only the largest prefix of devices that fills the mesh shape (so a
    7-device pool with data=-1 uses all 7; data=4 uses the first 4)."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    spec = spec or MeshSpec()
    names, sizes = spec.resolve(len(devices))
    n = int(np.prod(sizes)) if sizes else 1
    dev_array = np.array(devices[:n]).reshape(sizes)
    return Mesh(dev_array, names)


def batch_sharding(mesh, axis: str = "data", rank: int = 1):
    """NamedSharding that shards dim 0 over `axis`, replicates the rest."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(axis, *([None] * (rank - 1))))


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of m that is >= n (>= m so empty stays shardable)."""
    return max(((n + m - 1) // m) * m, m)


def pad_rows(a: np.ndarray, target: int, fill=0) -> np.ndarray:
    """Pad dim 0 of `a` to `target` rows with `fill`. Static-shape bucketing
    is how ragged event-derived data becomes XLA-friendly (SURVEY.md §7
    'Dynamic event queries → static shapes')."""
    if a.shape[0] == target:
        return a
    if a.shape[0] > target:
        raise ValueError(f"Cannot pad {a.shape[0]} rows down to {target}")
    pad_width = [(0, target - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad_width, constant_values=fill)


def shard_put(a: np.ndarray, mesh, axis: str = "data", fill=0):
    """Pad dim 0 to a multiple of the mesh axis size and device_put with a
    batch sharding. Returns (sharded jax.Array, original row count)."""
    import jax
    size = int(mesh.shape[axis])
    n = a.shape[0]
    a = pad_rows(a, pad_to_multiple(n, size), fill)
    return jax.device_put(a, batch_sharding(mesh, axis, a.ndim)), n


_distributed_initialized = False


def initialize_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> bool:
    """Initialize `jax.distributed` for multi-host training; no-op
    (False) when no coordinator is configured. Explicit arguments
    override the PIO_TPU_COORDINATOR / _NUM_PROCESSES / _PROCESS_ID env
    vars. Idempotent: a second call in the same process returns True
    without re-initializing. The analog of the reference forwarding
    PIO_* env through spark-submit to driver/executors
    (`Runner.scala:213-215,298-305`)."""
    global _distributed_initialized
    addr = coordinator or os.environ.get("PIO_TPU_COORDINATOR")
    if not addr:
        return False
    if _distributed_initialized:
        return True

    def setting(explicit, env_key, what):
        if explicit is not None:
            return int(explicit)
        val = os.environ.get(env_key)
        if val is None:
            raise ValueError(
                f"Multi-host init needs {what}: pass it explicitly "
                f"(--num-processes/--process-id) or set {env_key}")
        return int(val)

    n_proc = setting(num_processes, "PIO_TPU_NUM_PROCESSES",
                     "the process count")
    pid = setting(process_id, "PIO_TPU_PROCESS_ID", "this process's id")
    import jax
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=n_proc, process_id=pid)
    _distributed_initialized = True
    return True
