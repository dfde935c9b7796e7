"""Unified observability layer: metrics, structured logging, tracing.

The standard instrumentation surface for every layer of the stack
(`pio_*` metric families). Servers expose the process-default registry
on `GET /metrics` (Prometheus text format); the HTTP middleware in
`utils.http` emits one structured JSON log line per request with a
propagated request id; the serve chain, event ingestion, and the train
workflow all record into the same registry. Future perf PRs report
through this package instead of ad-hoc prints and time.time() — the
lint gate (`tools.lint`) enforces it in serving/, data/, and core/.
"""

from predictionio_tpu.obs.metrics import (  # noqa: F401
    DEFAULT_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry,
    get_registry,
)
from predictionio_tpu.obs.logs import (  # noqa: F401
    StructuredLogger, get_logger, new_request_id,
)
from predictionio_tpu.obs.jaxprobe import (  # noqa: F401
    compile_cache_counts, compile_count, compile_watch,
    install_compile_probe,
)
from predictionio_tpu.obs.report import (  # noqa: F401
    record_train_phases, train_report,
)
from predictionio_tpu.obs.trace import (  # noqa: F401
    TRACE_HEADER, PendingTrace, TraceRecorder, get_recorder,
)
from predictionio_tpu.obs.slo import (  # noqa: F401
    SLOTracker, dao_overrides_loader,
)
from predictionio_tpu.obs.quality import (  # noqa: F401
    CanaryGate, CanaryVeto, QualityJoiner, QualityStats,
    QuantileSketch, js_divergence, psi, quality_enabled,
)
from predictionio_tpu.obs.profiler import (  # noqa: F401
    HostSampler, SamplingProfiler, ensure_started, get_profiler,
    install_gc_callbacks, role_of, sample_device_memory,
)
from predictionio_tpu.obs.tsdb import (  # noqa: F401
    Scraper, TSDB, series_key,
)
