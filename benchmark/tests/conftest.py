"""Tests of the benchmark's own code, run on the CPU:
`JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q`. Not part of
`tests/`, so the repository's tier-1 count does not change."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
