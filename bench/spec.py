"""What ``BENCHMARK.json`` names, found by name in files of their own.

A cell (an entry of ``workloads``) pairs a configuration,
``configs/<config>.json``, with a traffic mix, ``traffic/<traffic>.json``,
and the limits of its output check, ``limits/<cell>.json``.  The
configuration names its model family, ``families/<family>.py``: all
that the harness, the check and the readers need to know of the model
(see ``family``).  Each metric is read by ``metrics/<metric>.py``.
Adding a configuration, a cell or a metric means adding files and
entries; no file here names one.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List, Optional

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


def _module(kind: str, name: str) -> ModuleType:
    path = BENCH / kind / f"{name}.py"
    mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str) -> ModuleType:
    """The ``families/<name>.py`` module.  A family gives:

      QUALITY             the name of its quality numbers; the check
                          compares ``<QUALITY>_init_gap`` and
                          ``<QUALITY>_final_gap``
      CONTROL, WITNESS    keywords of ``reference_rounds`` and
                          ``quality`` for the reference one precision
                          below the configuration's, and at the
                          program's own precision
      FAULTS              planted fault name -> ``"rounds"`` or
                          ``"quality"``, the part of the reference it
                          is planted in (as ``fault=<name>``)
      check_config(config)  raises ``ValueError`` on a configuration it
                          cannot run
      data(config, seed)  the host data, made once before the warm-up
      job(cell, seed, data)  one whole job through the program's entry
                          point; returns its ``RunResult``
      init_leaves(config, seed), final_leaves(result)  the initial and
                          the job's final parameters, float64 leaves in
                          wire order
      reference_rounds(cell, data, seed, rounds, **kw)
                          ``{"uploads", "bytes"}``: per round of the
                          reference, each participant's upload as
                          leaves, and the round's wire bytes
      quality(cell, data, seed, final, fault=None, **kw)
                          ``{"init", "final"}``: the reference's quality
                          tuple of the initial model and of ``final``
      recorded_quality(result)  the same, as the program recorded it
      job_flops(config, records)  required training operations of the
                          rounds in ``records``
      shrink(cell, **over)  the cell at a size a CPU test holds
    """
    return _module("families", name)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    family: Optional[ModuleType] = None

    def __post_init__(self):
        if self.family is None:
            self.family = family(self.config["family"])


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
    return _module("metrics", metric)
