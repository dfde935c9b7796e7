"""Driver `http_open`: as `http_closed`, with arrivals on a schedule
whatever the replies do (traffic `loop: "open"`, `rate_qps`, optional
bursts). Latency is timed from the instant a request was due; how late
the sender ran is in the facts (`gen_late_ms_p99`) for a per-layer
metric to read (`gen_late_ms_p99.serve`). Cell
`amazon23q-serve-open-80qps` uses it.
"""

from __future__ import annotations

from typing import Any, Dict

import harness
from drivers.http_closed import run_serve


def run(rc: harness.RunContext) -> Dict[str, Any]:
    return run_serve(rc, "open")
