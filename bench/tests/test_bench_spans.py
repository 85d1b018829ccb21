"""The readers of the program's host spans that split a job's time:
job set-up, chunk planning and the three parts of wire emission, on a
hand-made context, and silent where the span is missing."""
import pytest

from bench import spec

READERS = {
    "emit_wait_ms_per_round": "emit_wait",
    "emit_pull_ms_per_round": "emit_pull",
    "wire_encode_ms_per_round": "wire_encode",
    "job_setup_ms_per_round": "job_setup",
    "plan_ms_per_round": "plan",
}


def _ctx(**kw):
    ctx = {"rounds": 30,
           "span_s": {"encode": 1.8, "emit_wait": 0.15, "emit_pull": 0.3,
                      "wire_encode": 1.35, "job_setup": 0.36,
                      "plan": 0.012, "cohort_put": 0.3, "records": 0.2}}
    ctx.update(kw)
    return ctx


@pytest.mark.parametrize("metric,want", [
    ("emit_wait_ms_per_round", 5.0),
    ("emit_pull_ms_per_round", 10.0),
    ("wire_encode_ms_per_round", 45.0),
    ("job_setup_ms_per_round", 12.0),
    ("plan_ms_per_round", 0.4),
])
def test_span_reader_by_hand(metric, want):
    assert spec.reader(metric).read(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_span_reader_with_nothing_to_read(metric):
    assert spec.reader(metric).read(_ctx(span_s={})) is None
    assert spec.reader(metric).read(_ctx(rounds=0)) is None


@pytest.mark.parametrize("metric,span", sorted(READERS.items()))
def test_span_reader_reads_its_own_span(metric, span):
    only = _ctx(span_s={span: 0.3})
    assert spec.reader(metric).read(only) == pytest.approx(10.0)
    others = {k: v for k, v in _ctx()["span_s"].items() if k != span}
    assert spec.reader(metric).read(_ctx(span_s=others)) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_span_reader_is_listed(metric):
    """Listed with no ``workloads``: every cell that reports
    ``rounds_per_s`` reports it (``test_reader_agrees_with_entry`` holds
    the rest of the entry to the reader)."""
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == metric)
    assert "workloads" not in entry
