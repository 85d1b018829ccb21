"""A configuration is files alone: a copy of the benchmark, given a
second model family with its configuration, traffic, limits and cell
and nothing else, runs that cell through the harness unchanged; its
check passes on the program as it is and comes out false under a fault
planted in the timed path.  The check's wire decoder takes any tree."""
import dataclasses
import io
import json
import shutil
import time

import jax
import numpy as np
import pytest

from bench import harness, spec, wire
from repro.comm import wire as program_wire
from bench.tests.test_bench_run import SEED, _stuck

# The MLP family under configuration keys of its own: ``widths`` for the
# layer widths, ``records`` for the cohort's parameters.
FAMILY = '''"""The MLP, configured by ``widths`` and ``records``."""
import dataclasses

from bench import spec

_mlp = spec.family("mlp")
QUALITY, CONTROL, WITNESS = _mlp.QUALITY, _mlp.CONTROL, _mlp.WITNESS
FAULTS = _mlp.FAULTS
final_leaves, recorded_quality = _mlp.final_leaves, _mlp.recorded_quality


def _config(config):
    own = {k: v for k, v in config.items() if k not in ("widths", "records")}
    return dict(own, features=config["widths"], cohort=config["records"])


def _cell(cell):
    return dataclasses.replace(cell, config=_config(cell.config))


def check_config(config):
    _mlp.check_config(_config(config))


def data(config, seed):
    return _mlp.data(_config(config), seed)


def job(cell, seed, data):
    return _mlp.job(_cell(cell), seed, data)


def init_leaves(config, seed):
    return _mlp.init_leaves(_config(config), seed)


def reference_rounds(cell, data, seed, rounds, **kw):
    return _mlp.reference_rounds(_cell(cell), data, seed, rounds, **kw)


def quality(cell, data, seed, final, **kw):
    return _mlp.quality(_cell(cell), data, seed, final, **kw)


def job_flops(config, records):
    return _mlp.job_flops(_config(config), records)


def shrink(cell, **over):
    return dataclasses.replace(cell, config=dict(cell.config, **over))
'''
CELL = "alias-scbf"


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of the benchmark with the second family's files and entries
    added, and ``spec`` pointed at it."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    bench = root / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    (bench / "families" / "mlp_alias.py").write_text(FAMILY)
    # the silo5 configuration and traffic at the tiny size of the MLP's
    # ``shrink``, under the alias's keys
    silo5 = spec.cell("silo5-scbf")
    tiny = silo5.family.shrink(silo5)
    config = {k: v for k, v in tiny.config.items()
              if k not in ("features", "cohort")}
    config.update(name="alias-tiny", family="mlp_alias",
                  widths=tiny.config["features"],
                  records=tiny.config["cohort"])
    files = {"configs/alias-tiny.json": config,
             "traffic/scbf-fused3-job6.json": tiny.traffic,
             f"limits/{CELL}.json": tiny.limits}
    for path, obj in files.items():
        (bench / path).write_text(json.dumps(obj))
    bm = spec.benchmark()
    bm["configs"].append({"name": "alias-tiny", "source": silo5.config[
        "source"], "file": "bench/configs/alias-tiny.json", "reduced": [],
        "why": "the MLP under another family's name and keys"})
    bm["workloads"].append({"name": CELL, "config": "alias-tiny",
                            "traffic": "scbf-fused3-job6", "chips": 1,
                            "why": "a second family, added as files"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    real = spec.BENCH
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCH", bench)
    jax.clear_caches()
    yield root
    jax.clear_caches()
    assert not (real / "families" / "mlp_alias.py").exists()


def _run():
    return harness.run(CELL, SEED, 0.0, False, time.perf_counter(),
                       require_chip=False, log=io.StringIO())


def test_second_family_is_files_alone(bench_copy):
    cell = spec.cell(CELL)
    assert cell.family.__file__ == str(bench_copy / "bench" / "families"
                                       / "mlp_alias.py")
    assert "features" not in cell.config and "cohort" not in cell.config
    cell.family.check_config(cell.config)
    out = _run()
    assert out["correct"] is True
    assert set(cell.limits["limits"]) <= set(out["checks"])
    assert out["metrics"]["rounds_per_s"]["value"] > 0


def test_second_family_catches_a_planted_fault(bench_copy, monkeypatch):
    _stuck(monkeypatch)
    out = _run()
    assert out["correct"] is False
    assert out["checks"]["aggregate_gap"]["value"] \
        > out["checks"]["aggregate_gap"]["limit"]


def test_wire_decode_takes_leaves_of_any_rank():
    """The decoder gives back every leaf of a payload in the order the
    tree flattens, whatever the tree and the leaves' ranks, and counts a
    payload that lost a leaf as a wire fault."""
    import dataclasses

    import numpy as np

    from bench import wire
    from repro.comm import wire as program_wire

    rng = np.random.default_rng(7)

    def sparse(*shape):
        a = rng.normal(size=shape).astype(np.float32)
        return np.where(rng.random(shape) < 0.2, a, 0).astype(np.float32)

    tree = {"experts": sparse(4, 8, 6), "attn": [sparse(8, 8), sparse(8)],
            "router": sparse(8, 4), "scale": np.zeros(3, np.float32)}
    payload = program_wire.encode(tree)
    leaves, bad = wire.decode(payload)
    assert bad == 0
    want = jax.tree_util.tree_leaves(tree)
    assert [a.shape for a in leaves] == [a.shape for a in want]
    assert all(np.array_equal(a, b) for a, b in zip(leaves, want))
    assert wire.upload_bytes(leaves) == sum(lp.nbytes
                                            for lp in payload.layers)
    short = dataclasses.replace(payload, layers=payload.layers[:-1])
    assert wire.decode(short)[1] == 1
