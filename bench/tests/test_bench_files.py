"""BENCHMARK.json and the files it names keep to the benchmark's
contract: every file loads, names and units use the allowed characters,
each metric's reader agrees with its entry, and every per-layer metric
is reported by cells that report the metric it moves."""
import re

import pytest

from bench import spec

BM = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]
METRICS = BM["end_to_end"] + BM["per_layer"]


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][1].startswith(BM["paths"][0] + "/")
    assert 1 <= BM["run_seconds"] <= 51


def test_names_and_units():
    names = [m["name"] for m in METRICS] + CELLS \
        + [c["name"] for c in BM["configs"]] \
        + [w["traffic"] for w in BM["workloads"]] \
        + [k for c in BM["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len(set(CELLS)) == len(CELLS)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m
    lines = [w["why"] for w in BM["workloads"]] \
        + [c["source"] for c in BM["configs"]] \
        + [m["layer"] for m in BM["per_layer"]] + BM["command"]
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in lines), lines
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    c = spec.cell(cell, BM)
    assert c.chips in (1, 4)
    assert c.config["features"][0] == c.config["cohort"]["medicines"]
    assert int(c.limits["rounds"]) >= 1
    assert c.limits["limits"]
    entry = next(x for x in BM["configs"]
                 if x["name"] == c.config["name"])
    assert entry["file"] == f"bench/configs/{c.config['name']}.json"
    assert entry["source"] == c.config["source"]
    assert entry["reduced"] == c.config["reduced"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_reader_agrees_with_entry(metric):
    mod = spec.reader(metric["name"])
    assert callable(mod.read)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE) == (
        metric["unit"], metric["better"], metric["source"])
    if metric in BM["per_layer"]:
        assert (mod.LAYER, mod.MOVES) == (metric["layer"], metric["moves"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    e2e = [m["name"] for m in spec.end_to_end(cell, BM)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.per_layer(cell, BM)
    assert layer
    for m in layer:
        assert m["moves"] in e2e


def test_listed_cells_exist():
    for m in METRICS:
        assert set(m.get("workloads", [])) <= set(CELLS)


def test_check_fits_in_the_budget():
    runs = 2 + 14 * 24
    need = runs * (BM["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert need <= 43200
