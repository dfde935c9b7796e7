"""BENCHMARK.json as the harness reads it, and the rules it must keep.

`check()` runs at the start of every run, before the device is claimed,
and in `tests/test_manifest.py`. The rule that refused PR 22: a
per-layer metric may be reported only in cells that also report the
end-to-end metric it `moves`. So every metric carries its own list of
cells, and a run prints exactly the metrics whose list names its cell.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Any, Dict, List

from harness import BENCH_DIR, ROOT, load_json

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class ManifestError(ValueError):
    pass


class Manifest:
    def __init__(self, doc: Dict[str, Any]):
        self.doc = doc
        self.cells = {w["name"]: w for w in doc["workloads"]}
        self.configs = {c["name"]: c for c in doc["configs"]}

    @classmethod
    def load(cls, path: Path = ROOT / "BENCHMARK.json",
             queued: bool = False) -> "Manifest":
        """BENCHMARK.json; with `queued` also the entries of
        `benchmark/queued.json` (cells that are not in the benchmark yet:
        rehearsals and tests only, never a run on the chip)."""
        doc = load_json(path)
        if queued:
            extra = load_json(BENCH_DIR / "queued.json")
            for m in doc["end_to_end"] + doc["per_layer"]:
                if m["name"] in extra["also_reported"]:
                    m["workloads"] = (m["workloads"]
                                      + extra["also_reported"][m["name"]])
            for key in ("configs", "workloads", "end_to_end", "per_layer"):
                doc[key] = doc[key] + extra[key]
        return cls(doc)

    def _cells_of(self, metric: Dict[str, Any]) -> List[str]:
        return list(metric.get("workloads", self.cells))

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.doc["end_to_end"]
                if cell in self._cells_of(m)]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.doc["per_layer"]
                if cell in self._cells_of(m)]

    def check(self) -> None:
        d = self.doc
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            raise ManifestError("end_to_end lacks setup_s")
        seen = set()
        for m in d["end_to_end"] + d["per_layer"]:
            if not _NAME.match(m["name"]):
                raise ManifestError(f"bad metric name {m['name']!r}")
            if not _UNIT.match(m["unit"]):
                raise ManifestError(f"bad unit {m['unit']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"{m['name']}: better is lower|higher")
            if m["source"] not in _SOURCES:
                raise ManifestError(f"{m['name']}: source {m['source']!r}")
            if m["name"] in seen:
                raise ManifestError(f"metric {m['name']} appears twice")
            seen.add(m["name"])
            for c in m.get("workloads", ()):
                if c not in self.cells:
                    raise ManifestError(f"{m['name']} lists unknown cell {c}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"{m['name']}: an end-to-end metric is "
                                    "taken by the benchmark itself")
        for m in d["per_layer"]:
            moved = e2e.get(m["moves"])
            if moved is None:
                raise ManifestError(f"{m['name']} moves unknown "
                                    f"{m['moves']!r}")
            for c in self._cells_of(m):
                if c not in self._cells_of(moved):
                    raise ManifestError(
                        f"per_layer metric {m['name']} is reported on "
                        f"workload {c}, where {m['moves']}, which it "
                        "should move, is not")
            if not (BENCH_DIR / "layer_metrics" / f"{m['name']}.json").exists():
                raise ManifestError(f"no layer_metrics/{m['name']}.json")
        for name, w in self.cells.items():
            if not _NAME.match(name) or not _NAME.match(w["traffic"]):
                raise ManifestError(f"bad cell name {name!r}")
            if not 1 <= len(w["why"]) <= 200:
                raise ManifestError(f"{name}: why has {len(w['why'])} "
                                    "characters, 1 to 200 allowed")
            if w["config"] not in self.configs:
                raise ManifestError(f"{name}: unknown config {w['config']}")
            if len(self.end_to_end(name)) < 2 or not self.per_layer(name):
                raise ManifestError(f"{name} needs setup_s, another "
                                    "end-to-end metric and a per-layer one")
            if not (BENCH_DIR / "workloads" / f"{name}.json").exists():
                raise ManifestError(f"no workloads/{name}.json")
        for name, c in self.configs.items():
            if not _NAME.match(name) or not (ROOT / c["file"]).exists():
                raise ManifestError(f"config {name}: bad name or no file")
