"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration's file is the ``file`` of its entry under ``configs``;
* a traffic mix ``T`` is ``bench/traffic/T.json``;
* a metric ``M`` is read by ``bench/metrics/M.py``, or, for a name split
  by the cells it serves (``batch_size_mean.open``), by the reader of the
  part before the last dot (``bench/metrics/batch_size_mean.py``) when
  the split has no reader of its own.

A cell reports an end-to-end metric unless the metric lists other cells
under ``workloads``.  It reports a per-layer metric that lists it under
``workloads``, or, where the metric has no such list, one whose
end-to-end metric (``moves``) it reports.
"""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import List

__all__ = ["NAME", "UNIT", "Cell", "load", "cell", "reader_path",
           "reports"]

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def reports(manifest: dict, metric: dict, cell_name: str) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    if metric in manifest["end_to_end"]:
        return True
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == metric["moves"])
    return reports(manifest, moved, cell_name)


def reader_path(metric_name: str, bench: Path = BENCH) -> Path:
    own = bench / "metrics" / f"{metric_name}.py"
    if own.is_file() or "." not in metric_name:
        return own
    return bench / "metrics" / f"{metric_name.rsplit('.', 1)[0]}.py"


def cell(manifest: dict, name: str, root: Path,
         bench: Path = BENCH) -> Cell:
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = next(c for c in manifest["configs"] if c["name"] == w["config"])
    traffic_file = bench / "traffic" / f"{w['traffic']}.json"
    return Cell(
        name=name, chips=int(w["chips"]), config_name=conf["name"],
        config=json.loads((Path(root) / conf["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(traffic_file.read_text()),
        end_to_end=[m for m in manifest["end_to_end"]
                    if reports(manifest, m, name)],
        per_layer=[m for m in manifest["per_layer"]
                   if reports(manifest, m, name)])
