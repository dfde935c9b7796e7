"""Driver `lfm2_http_closed`: `seq_http_closed`'s run, whole, over a
sequence-recommender instance whose backbone is an LFM2 configuration.

What differs between the two sequence cells is which modules draw the
weights, give the plain reference and count the required work; the
set-up, the window, the trace, the sample and the comparison that
decides `correct` are `seq_http_closed.run`'s own lines. That function
names its three modules as module globals, so this driver stands
`lfm2_datagen`, `lfm2_reference` and `lfm2_opcount` (which keep the
names `run` calls) in their place for the length of one run and puts
them back. A `benchmark` PR that lets `run` take its modules from the
configuration makes this file a line of the cell's JSON (PERF.md,
Open questions).

The program's new mixer is imported FIRST: a program without it (the
parent of PR 33) fails here, at once, before any weight is drawn and
any child exists.
"""

from __future__ import annotations

from typing import Any, Dict

import harness
import lfm2_datagen
import lfm2_opcount
import lfm2_reference
from drivers import seq_http_closed as base

_STAND_INS = {"seq_datagen": lfm2_datagen, "seq_opcount": lfm2_opcount,
              "seq_reference": lfm2_reference}


def run(rc: harness.RunContext) -> Dict[str, Any]:
    from predictionio_tpu.ops.backbone import conv_block    # noqa: F401
    saved = {name: getattr(base, name) for name in _STAND_INS}
    for name, module in _STAND_INS.items():
        setattr(base, name, module)
    try:
        return base.run(rc)
    finally:
        for name, module in saved.items():
            setattr(base, name, module)
