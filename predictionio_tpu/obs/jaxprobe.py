"""JAX compile-cache-miss probe.

Every jit cache miss that reaches the XLA compiler emits the
`/jax/core/compile/backend_compile_duration` event on jax.monitoring's
duration stream (jax/_src/dispatch.py BACKEND_COMPILE_EVENT). Counting
those events counts real backend compilations — recompiles from shape
churn or cache invalidation show up here long before they show up as
mystery latency. `pio train` reports the per-run delta next to its phase
timings (the tf.data-service-style "where did the time go" telemetry).

jax.monitoring listeners are process-global and cannot be removed
individually, so installation is once-per-process into the
process-default registry; `install_compile_probe` is idempotent.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional

from predictionio_tpu.obs.metrics import MetricsRegistry, get_registry

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# persistent compile cache (jax/_src/compiler.py, compilation_cache.py):
# a hit loads the executable instead of compiling it; a miss is counted
# when the freshly compiled entry is written
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                   30.0, 60.0, 120.0)

_install_lock = threading.Lock()
_installed = False


def _instruments(registry: MetricsRegistry):
    counter = registry.counter(
        "pio_jax_backend_compiles_total",
        "XLA backend compilations (jit compile-cache misses)")
    hist = registry.histogram(
        "pio_jax_backend_compile_seconds",
        "XLA backend compile wall time per compilation",
        buckets=COMPILE_BUCKETS)
    return counter, hist


def _cache_counter(registry: MetricsRegistry):
    return registry.counter(
        "pio_jax_compile_cache_total",
        "Persistent compile-cache lookups by result (hit: executable "
        "loaded from the cache directory; miss: compiled and written)",
        labels=("result",))


def install_compile_probe(
        registry: Optional[MetricsRegistry] = None) -> None:
    """Register the jax.monitoring listener (once per process). Counts
    land in `registry` (default: the process-default registry)."""
    global _installed
    counter, hist = _instruments(registry or get_registry())
    cache = _cache_counter(registry or get_registry())
    with _install_lock:
        if _installed:
            return
        from jax import monitoring   # lazy: obs must import without jax

        def _on_duration(event: str, duration: float, **kwargs) -> None:
            if event == BACKEND_COMPILE_EVENT:
                counter.inc()
                hist.observe(duration)

        def _on_event(event: str, **kwargs) -> None:
            if event == CACHE_HIT_EVENT:
                cache.labels(result="hit").inc()
            elif event == CACHE_MISS_EVENT:
                cache.labels(result="miss").inc()

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def compile_count(registry: Optional[MetricsRegistry] = None) -> int:
    """Current backend-compile count (0 before the probe ever fired)."""
    counter, _ = _instruments(registry or get_registry())
    return int(counter.value)


def compile_cache_counts(
        registry: Optional[MetricsRegistry] = None) -> dict:
    """Persistent compile-cache {"hit": n, "miss": n} so far."""
    cache = _cache_counter(registry or get_registry())
    return {r: int(cache.labels(result=r).value) for r in ("hit", "miss")}


class _CompileWatch:
    """Result object of `compile_watch`; `.count` is live inside the
    block and frozen at exit."""

    def __init__(self, registry: Optional[MetricsRegistry]):
        self._registry = registry
        self._before = compile_count(registry)
        self._final: Optional[int] = None

    @property
    def count(self) -> int:
        if self._final is not None:
            return self._final
        return compile_count(self._registry) - self._before


@contextmanager
def compile_watch(registry: Optional[MetricsRegistry] = None):
    """Count backend compiles across a block::

        with compile_watch() as w:
            serve_a_lot()
        assert w.count == 0   # steady state must not recompile

    Installs the probe on entry (idempotent), so the first use in a
    process is also correct."""
    install_compile_probe(registry)
    watch = _CompileWatch(registry)
    try:
        yield watch
    finally:
        watch._final = compile_count(registry) - watch._before
