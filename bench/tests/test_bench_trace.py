"""The trace reduction, on a hand-made trace and on a window of a trace
recorded on a TPU v5e (the end of a silo5-scbf fused chunk, the wire
emission gap and the evaluation that follows); the operation counts of
the metric readers, against hand counts at the paper's shapes; and the
other readers on a hand-made context."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import spec, tracereduce as T

DATA = Path(__file__).parent / "data"


def _trace(ops, modules=(), host=()):
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": [list(e) for e in ops]},
                   {"name": "XLA Modules",
                    "events": [list(e) for e in modules]}]},
        {"name": "/host:CPU",
         "lines": [{"name": "main", "events": [list(e) for e in host]}]}]}


def test_hand_trace():
    ops = [("%while.1", 0, 10), ("%fusion.2", 5, 10), ("%copy.3", 20, 10),
           ("%fusion.4", 35, 10)]
    mods = [("jit__fused_scbf_rounds(7)", 0, 16), ("jit_eval(9)", 20, 30)]
    tr = _trace(ops, mods, [("bench_job", 0, 40), ("fused_chunk", 0, 18)])
    plane = T.device_planes(tr)[0]
    window = T.host_events(tr, "bench_job")[0]
    assert window == (0, 40)
    busy = T.busy(plane, window)
    assert busy == [(0, 15), (20, 30), (35, 40)]
    assert T.total(busy) == 30
    assert T.gaps(busy, window) == [(15, 20), (30, 35)]
    assert T.op_time(plane, window) == 30
    assert T.op_time(plane, window, "_fused_scbf_rounds") == 15
    assert T.op_time(plane, window, "jit_eval") == 15
    assert T.top_ops(plane, window) == [
        ("jit__fused_scbf_rounds/%while.1", 10e-9),
        ("jit__fused_scbf_rounds/%fusion.2", 10e-9),
        ("jit_eval/%copy.3", 10e-9), ("jit_eval/%fusion.4", 5e-9)]
    spans = [("fused_chunk", 0, 18), ("encode", 14, 17), ("eval", 31, 50)]
    assert T.name_gaps(T.gaps(busy, window), spans) == [
        ("eval", 4e-9), ("encode", 2e-9), ("none", 2e-9),
        ("fused_chunk", 1e-9), ("none", 1e-9)]
    assert T.align([1.0, 5.0], [11.0, 15.0]) == 10.0
    assert T.align([1.0], []) is None


@pytest.fixture(scope="module")
def chip_trace():
    with open(DATA / "trace_v5e_silo5.json") as fh:
        return json.load(fh)


def _grid(intervals, window):
    """Busy nanoseconds counted on a 1 ns grid, independently of the
    interval algebra."""
    a, b = int(window[0]), int(window[1])
    mask = np.zeros(b - a, bool)
    for s, e in intervals:
        mask[max(int(s), a) - a:max(min(int(e), b) - a, 0)] = True
    return mask


def test_recorded_trace(chip_trace):
    plane = T.device_planes(chip_trace)[0]
    window = T.host_events(chip_trace, "bench_job")[0]
    ops = [(s, s + d) for _, s, d in plane["lines"][1]["events"]]
    grid = _grid(ops, window)
    busy = T.busy(plane, window)
    assert T.total(busy) == pytest.approx(grid.sum(), abs=len(ops))
    assert T.total(busy) + T.total(T.gaps(busy, window)) == pytest.approx(
        window[1] - window[0])
    fused = [(s, s + d) for n, s, d in plane["lines"][0]["events"]
             if "_fused_scbf_rounds" in n]
    both = grid & _grid(fused, window)
    assert T.op_time(plane, window, "_fused_scbf_rounds") == pytest.approx(
        both.sum(), abs=len(ops))
    assert 0 < T.op_time(plane, window, "_fused_scbf_rounds") \
        < T.total(busy)
    top = T.top_ops(plane, window)
    assert top[0][0].startswith("jit__fused_scbf_rounds/%while")
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    chunk = T.host_events(chip_trace, "fused_chunk")
    gaps = T.name_gaps(T.gaps(busy, window),
                       [("fused_chunk",) + c for c in chunk])
    assert gaps[0][0] == "fused_chunk" and gaps[0][1] > 0.4


def test_example_flops_by_hand():
    mfu = spec.reader("train_mfu")
    # forward 2*(2917*256 + 256*64 + 64*1), the same again for the
    # weight gradients, and the activation gradients of layers 2 and 3
    fwd = 2 * (2917 * 256 + 256 * 64 + 64)
    assert mfu.example_flops((2917, 256, 64, 1)) \
        == 2 * fwd + 2 * (256 * 64 + 64) == 3_085_696


class _Rec:
    def __init__(self, participants, hidden=()):
        self.num_participants = participants
        self.hidden_sizes = hidden


@pytest.mark.parametrize("over,examples", [
    # 18456 training rows / 5 = 3691 per hospital: 14 batches of 256,
    # 2 epochs
    ({}, 14 * 256 * 2),
    # 18456 / 100 = 184 per client: 18 batches of 10, 5 epochs
    ({"clients": 100, "sample_fraction": 0.5, "local_batch_size": 10,
      "local_epochs": 5}, 18 * 10 * 5),
])
def test_job_flops_by_hand(over, examples):
    mfu = spec.reader("train_mfu")
    c = dict(spec.cell("silo5-scbf").config, **over)
    per = round(c["sample_fraction"] * c["clients"])
    recs = [_Rec(per), _Rec(per)]
    assert mfu.job_flops(c, recs) == 2 * per * examples * 3_085_696
    pruned = [_Rec(per, (128, 32))]
    small = 2 * (2 * (2917 * 128 + 128 * 32 + 32)) + 2 * (128 * 32 + 32)
    assert mfu.job_flops(c, pruned) == per * examples * small


def _ctx(**kw):
    ctx = {"planes": [{}], "window_s": 2.0, "busy_s": 0.5, "rounds": 4,
           "span_s": {"encode": 0.2, "eval": 0.04},
           "records": [type("R", (), {"sparse_bytes": b})()
                       for b in (100, 300, 200, 400)],
           "setup_s": 30.0, "jobs": 2}
    ctx.update(kw)
    return ctx


@pytest.mark.parametrize("metric,want", [
    ("device_idle_share", 75.0),
    ("emit_ms_per_round", 50.0),
    ("eval_ms_per_round", 10.0),
    ("upload_bytes_per_round", 250.0),
    ("rounds_per_s", 2.0),
    ("setup_s", 30.0),
])
def test_reader_by_hand(metric, want):
    assert spec.reader(metric).read(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["device_idle_share", "emit_ms_per_round",
                                    "eval_ms_per_round",
                                    "upload_bytes_per_round"])
def test_reader_with_nothing_to_read(metric):
    ctx = _ctx(planes=[], span_s={}, rounds=0)
    assert spec.reader(metric).read(ctx) is None
