"""The cross-device cell, ``device100-scbf``, on the CPU at a tiny size
that keeps its partial participation and padding: 12 clients of 20 rows,
half sampled, 6 clients in 8 slots a round.  Its check comes out false
where padded slots leak into the aggregate, and ``valid_slot_share``
reads 6 of 8 slots.  Agreement with the reference and the faults every
cell shares are in ``test_bench_run.py``, parametrized over the cells."""
import io
import time

import numpy as np
import pytest

from bench import harness, spec
from bench.tests import tiny
from bench.tests.test_bench_run import SEED, fresh_programs  # noqa: F401

CELL = "device100-scbf"


def _cell():
    return tiny.cell(CELL, clients=12, local_batch_size=4)


def _leaky_padding(monkeypatch):
    """Fused plans whose validity is all true: the padded slots (slot 0's
    client trained again under filler keys) enter the aggregate."""
    from repro.fed import engine
    orig = engine.horizon_slot_plan

    def plan(participants, num_slots, horizon):
        part_idx, valid = orig(participants, num_slots, horizon)
        return part_idx, np.ones_like(valid)

    monkeypatch.setattr(engine, "horizon_slot_plan", plan)


def test_leaky_padding_is_not_correct(monkeypatch, fresh_programs):
    c = _cell().config
    assert (c["clients"], c["sample_fraction"]) == (12, 0.5)
    _leaky_padding(monkeypatch)
    out = harness.run(CELL, SEED, 0.0, False, time.perf_counter(),
                      require_chip=False, cell=_cell(), log=io.StringIO())
    assert out["correct"] is False
    assert out["checks"]["aggregate_gap"]["value"] \
        > out["checks"]["aggregate_gap"]["limit"]


def test_valid_slot_share_reads_the_records():
    records = harness.Job(_cell(), SEED)().records
    assert [(r.num_participants, r.slots) for r in records] == [(6, 8)] * 6
    reader = spec.reader("valid_slot_share")
    assert reader.read({"records": records}) == pytest.approx(75.0)
    # the records of a program that does not count its slots
    bare = [type("R", (), {"num_participants": r.num_participants})()
            for r in records]
    assert reader.read({"records": bare}) is None
    assert reader.read({"records": []}) is None
