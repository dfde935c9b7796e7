"""Driver `brumby_http_closed`: `seq_http_closed`'s run, whole, over a
sequence-recommender instance whose backbone is a Brumby configuration.

As `nemotron_http_closed`: `seq_http_closed.run` names the modules
that draw the weights, give the plain reference and count the required
work as module globals, so this driver stands `brumby_datagen`,
`brumby_reference` and a counter over `brumby_opcount` in their place
for the length of one run and puts them back. The stack has no expert
layer, so the run counts no (token, expert) pairs and leaves the whole
step's FLOPs to this driver: it keeps the lengths of the completed
queries that the run hands `attention_work`, and adds to the facts
`seq.serve_flops` (`brumby_opcount.serve_flops`) and `seq.retention`,
the retention kernels' required FLOPs and bytes
(`brumby_opcount.retention_work`), which `seq_retention_roofline`
reads; both over the window, as the run scales its own counts.

The program's new mixer is imported FIRST: a program without it fails
here, at once, before any weight is drawn and any child exists.
"""

from __future__ import annotations

from typing import Any, Dict, List

import brumby_datagen
import brumby_opcount
import brumby_reference
import harness
from drivers import seq_http_closed as base


class _Counts:
    """`brumby_opcount` as the run calls it, keeping the lengths of the
    completed queries it is handed."""

    def __init__(self):
        self.lengths: List[int] = []

    def attention_work(self, a, lengths):
        self.lengths = [int(n) for n in lengths]
        return brumby_opcount.attention_work(a, self.lengths)

    def __getattr__(self, name):
        return getattr(brumby_opcount, name)


def run(rc: harness.RunContext) -> Dict[str, Any]:
    from predictionio_tpu.ops.backbone import retention_block  # noqa: F401
    counts = _Counts()
    stand_ins = {"seq_datagen": brumby_datagen, "seq_opcount": counts,
                 "seq_reference": brumby_reference}
    saved = {name: getattr(base, name) for name in stand_ins}
    for name, module in stand_ins.items():
        setattr(base, name, module)
    try:
        res = base.run(rc)
    finally:
        for name, module in saved.items():
            setattr(base, name, module)
    facts, a = res["facts"], brumby_reference.arch(rc.config)
    if counts.lengths:
        scale = facts["completed"] / len(counts.lengths)
        seq = facts["seq"]
        seq["serve_flops"] = scale * brumby_opcount.serve_flops(
            a, counts.lengths)
        seq["retention"] = tuple(
            scale * x for x in brumby_opcount.retention_work(
                a, counts.lengths))
    return res
