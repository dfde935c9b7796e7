"""Driver `nemotron_http_closed`: `seq_http_closed`'s run, whole, over a
sequence-recommender instance whose backbone is a Nemotron-H
configuration.

As `lfm2_http_closed`: what differs between the sequence cells is which
modules draw the weights, give the plain reference and count the
required work, and `seq_http_closed.run` names its three as module
globals, so this driver stands `nemotron_datagen`, `nemotron_reference`
and `nemotron_opcount` (which keep the names `run` calls) in their
place for the length of one run and puts them back. What it adds to the
run's facts is `seq.ssm`: the scans' required FLOPs and bytes over the
window's live tokens and calls (`nemotron_opcount.ssm_work`), which
`seq_ssm_roofline` reads.

The program's new mixer is imported FIRST: a program without it (the
parent of PR 36) fails here, at once, before any weight is drawn and
any child exists.
"""

from __future__ import annotations

from typing import Any, Dict

import harness
import nemotron_datagen
import nemotron_opcount
import nemotron_reference
from drivers import seq_http_closed as base

_STAND_INS = {"seq_datagen": nemotron_datagen,
              "seq_opcount": nemotron_opcount,
              "seq_reference": nemotron_reference}


def run(rc: harness.RunContext) -> Dict[str, Any]:
    from predictionio_tpu.ops.backbone import ssm_block     # noqa: F401
    saved = {name: getattr(base, name) for name in _STAND_INS}
    for name, module in _STAND_INS.items():
        setattr(base, name, module)
    try:
        res = base.run(rc)
    finally:
        for name, module in saved.items():
            setattr(base, name, module)
    seq = res["facts"]["seq"]
    seq["ssm"] = nemotron_opcount.ssm_work(
        nemotron_reference.arch(rc.config), seq["tokens"], seq["calls"])
    return res
