"""What every driver shares: the run's context, the look for the chip, the
readings taken from the process (device memory, RSS, the metrics
registry, compile counts) and the profiler window.
"""

from __future__ import annotations

import contextlib
import json
import resource
import shutil
import tempfile
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Iterator

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# a failed or never-answered request is slower than any limit; JSON has
# no infinity, so a latency that is infinite is printed as this many ms
SLOWER_THAN_ANY_LIMIT_MS = 1e9


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class RunContext:
    cell: Dict[str, Any]               # workloads/<cell>.json
    config: Dict[str, Any]             # configs/<config>.json
    seed: int
    seconds: float
    trace: bool
    t_start: float                     # perf_counter at process start

    def note(self, key: str, value: Any) -> None:
        """A line for the run's earlier output (`# key: value`)."""
        shown = value if isinstance(value, str) else json.dumps(value)
        print(f"# {key}: {shown}", flush=True)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def claim_chips(chips: int, rehearse_cpu: bool) -> Dict[str, Any]:
    """Initialise the backend through the program's own `claim_device`
    (which also places the compile cache where the program keeps it) and
    insist on an accelerator with at least `chips` devices. A CPU is
    accepted only for a rehearsal cell that BENCHMARK.json does not
    list."""
    from predictionio_tpu.utils.device import claim_device
    try:
        info, cache_dir = claim_device()
    except RuntimeError as e:
        raise NoChip(str(e)) from e
    if info["platform"] == "cpu" and not rehearse_cpu:
        raise NoChip("JAX found no accelerator (platform cpu)")
    if info["device_count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{info['device_count']}")
    info["compile_cache_dir"] = cache_dir
    return info


def device_memory_peak() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it
    (0 where it reports nothing, as the CPU backend does)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def free_device() -> None:
    """Delete every live device array: the program's state has to be gone
    before the reference runs beside nothing."""
    import gc

    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def peak_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def registry_snapshot(registry) -> Dict[str, Dict[str, Any]]:
    """Every series of the program's metrics registry: a histogram as
    {bounds, buckets, sum, count}, a counter or gauge as {value}. Keyed
    `family` or `family{label=value,...}`."""
    snap: Dict[str, Dict[str, Any]] = {}
    for fam in registry._families_snapshot():
        for key, child in fam._items():
            labels = ",".join(f"{k}={v}" for k, v in zip(fam.labelnames, key))
            name = f"{fam.name}{{{labels}}}" if labels else fam.name
            if hasattr(child, "bucket_counts"):
                with child._lock:
                    snap[name] = {"bounds": list(child.bounds),
                                  "buckets": list(child.bucket_counts),
                                  "sum": child.sum, "count": child.count}
            else:
                snap[name] = {"value": child.value}
    return snap


def snapshot_delta(before: Dict, after: Dict) -> Dict[str, Dict[str, Any]]:
    """after - before, series by series (a series born inside the window
    counts from zero)."""
    out: Dict[str, Dict[str, Any]] = {}
    for name, a in after.items():
        b = before.get(name)
        if "buckets" in a:
            bb = b["buckets"] if b else [0] * len(a["buckets"])
            out[name] = {"bounds": a["bounds"],
                         "buckets": [x - y for x, y in zip(a["buckets"], bb)],
                         "sum": a["sum"] - (b["sum"] if b else 0.0),
                         "count": a["count"] - (b["count"] if b else 0)}
        else:
            out[name] = {"value": a["value"] - (b["value"] if b else 0.0)}
    return out


@contextlib.contextmanager
def profiler_window() -> Iterator[Dict[str, Any]]:
    """Trace what runs inside into a temporary directory under TMPDIR,
    reduce it, remove it. Yields a dict whose `reduced` is filled at
    exit. Host Python frames are not traced (they would be most of the
    file and slow the server); TraceMe spans and the device are."""
    import jax

    from trace_reduce import reduce_dir
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    out: Dict[str, Any] = {"reduced": None}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench:traced"):
                yield out
        finally:
            jax.profiler.stop_trace()
        out["reduced"] = reduce_dir(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def mem_registry():
    """An in-memory storage registry, as `bench.py:_train_registry`
    builds one."""
    from predictionio_tpu.data.storage import StorageRegistry
    return StorageRegistry({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEM",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })


def als_seed(seed: int) -> int:
    """--seed may exceed 32 signed bits; the program's PRNGKey may not."""
    return int(seed) % (2**31 - 1)
