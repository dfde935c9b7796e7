"""Which device this process computes on, and who may touch it.

A TPU chip belongs to one process at a time: a process that initialises
a JAX backend holds the chip until it exits, and any other process that
needs it then fails or hangs. So the rule of this package is

  - processes that COMPUTE (`train`, `deploy`, `eval`, `batchpredict`,
    `redeploy`, the benchmark) call `claim_device()` once at start: it
    places the persistent compile cache, initialises the backend, and
    refuses a CPU backend nobody asked for;
  - everything else (event server, fleet router, ingest service,
    dashboard, admin server, `status`) never initialises a backend:
    their device sampling goes through `live_devices()`, which only
    reports what is already there.

This module imports nothing from jax at import time, so the servers
that must stay off the chip can import it freely.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

# <checkout>/.xla_cache (listed in .gitignore): a fixed path, because
# the path is part of the cache key and a directory that moves never
# hits
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".xla_cache"


def backend_initialized() -> bool:
    """Whether THIS process has already initialised a JAX backend (and
    so holds whatever chip it found). Never initialises one."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return bool(xla_bridge.backends_are_initialized())


def live_devices() -> List[Any]:
    """The devices of an already-initialised backend, or [] — the
    sampling entry point for code that may run in a process that does
    not compute."""
    if not backend_initialized():
        return []
    import jax
    return list(jax.devices())


def device_info() -> Dict[str, Any]:
    """`platform`, `device_kind` and device count as JAX reports them.
    Initialises the backend: call only from a process that computes."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def cpu_requested() -> bool:
    """Whether the CPU was asked for by name: it is the FIRST platform
    in `JAX_PLATFORMS` / `jax.config.jax_platforms` (the first one named
    is the default backend; a `tpu,cpu` list asks for the TPU)."""
    import jax
    asked = jax.config.jax_platforms or ""
    return asked.split(",")[0].strip().lower() == "cpu"


def compile_cache_dir() -> str:
    """Place JAX's persistent compile cache and return its path. Where
    `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and nothing
    is touched; otherwise the cache goes to `<checkout>/.xla_cache`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def claim_device() -> Tuple[Dict[str, Any], str]:
    """Start-up of a process that computes: place the compile cache,
    initialise the backend and report both as `(device_info(), cache
    directory)`. With no platform named, JAX falls back to the CPU
    without a word when the accelerator cannot be opened (another
    process holds the chip, no driver); that fallback is refused here —
    the CPU is used only when asked for by name."""
    cache = compile_cache_dir()
    info = device_info()
    if info["platform"] == "cpu" and not cpu_requested():
        raise RuntimeError(
            "no accelerator: JAX fell back to the CPU backend. Either "
            "another process holds the chip (one process per chip), or "
            "there is none; to compute on the CPU say so with "
            "JAX_PLATFORMS=cpu")
    return info, cache


def visible_chip_count() -> Optional[int]:
    """How many accelerator chips this host can hand to child processes
    — read WITHOUT initialising a backend (the supervising router must
    never hold a chip its children need): the TPU device nodes are
    counted. None when there are none (no accelerator host: nothing to
    ration)."""
    nodes = list(Path("/dev").glob("accel[0-9]*"))
    nodes += list(Path("/dev/vfio").glob("[0-9]*"))
    return len(nodes) or None


def chip_env(index: int) -> Dict[str, str]:
    """Environment that pins a child process to chip `index` of a
    multi-chip host (libtpu's per-process chip selection): the child
    sees one device and leaves the other chips to its siblings."""
    port = 8476 + index
    return {"TPU_VISIBLE_CHIPS": str(index),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
            "TPU_MESH_CONTROLLER_PORT": str(port)}
