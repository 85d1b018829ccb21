"""What ``BENCHMARK.json`` names, found by name in files of their own.

A cell (an entry of ``workloads``) pairs a configuration,
``configs/<config>.json``, with a traffic mix, ``traffic/<traffic>.json``,
and the limits of its output check, ``limits/<cell>.json``.  Each
metric is read by ``metrics/<metric>.py``.  Adding a cell or a metric
means adding files and entries; no file here names one.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def limits(cell: str) -> dict:
    return _json(BENCH / "limits" / f"{cell}.json")


def peaks() -> dict:
    return _json(BENCH / "peaks.json")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict


def cell(name: str, bm: dict | None = None) -> Cell:
    bm = bm or benchmark()
    for w in bm["workloads"]:
        if w["name"] == name:
            return Cell(name, int(w["chips"]), config(w["config"]),
                        traffic(w["traffic"]), limits(name))
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def end_to_end(cell_name: str, bm: dict | None = None) -> List[dict]:
    """The end-to-end metrics this cell reports."""
    bm = bm or benchmark()
    return [m for m in bm["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(cell_name: str, bm: dict | None = None) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose ``moves`` metric the cell reports."""
    bm = bm or benchmark()
    reported = {m["name"] for m in end_to_end(cell_name, bm)}
    return [m for m in bm["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def reader(metric: str):
    """The ``metrics/<metric>.py`` module."""
    path = BENCH / "metrics" / f"{metric}.py"
    mod_name = "bench_metric_" + re.sub(r"\W", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
