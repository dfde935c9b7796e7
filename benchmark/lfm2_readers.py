"""Readers of the per-layer metrics PR 33 brought. Each returns None,
never 0, where it finds nothing to read (an untraced run, a CPU, a
program without the kernel)."""

from __future__ import annotations

import re
from typing import Any, Dict, Optional


def kernel_share(facts: Dict[str, Any], ops: str,
                 module: str) -> Optional[float]:
    """Device time of the operations whose name matches `ops` over the
    device time of the runs of the programs whose name matches
    `module`, both over the traced seconds: how much of the program is
    the kernel, and so how much is around it."""
    tr = facts.get("trace")
    if tr is None:
        return None
    rx = re.compile(ops)
    spent = sum(s for name, s in tr.ops.items() if rx.search(name))
    whole = sum(tr.module_seconds(module))
    if spent <= 0 or whole <= 0:
        return None
    return 100.0 * spent / whole
