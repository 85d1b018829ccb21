"""The flight recorder (repro.obs): device metrics, spans, run reports.

The PR-7 acceptance bars, in test form:

* telemetry changes NOTHING about training — the fused trajectory with
  device metrics on is bit-identical to the plain one, and the loss
  instrumentation (``with_loss``) leaves the parameter stream untouched;
* the fused hot loop stays clean with collect on — zero host transfers
  inside a chunk (the one offload happens at the boundary) and <= 2
  fused compiles;
* the on-device byte/loss/codec metrics agree exactly with the wire
  (repro.comm) ground truth they mirror;
* the events.jsonl schema is a golden contract, and the report CLI
  renders/refuses it correctly.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from _trace_guards import assert_compiles, assert_no_transfers
from repro.comm import wire
from repro.config import FedConfig, ObsConfig, ScbfConfig, TrainConfig
from repro.core.scbf import run_federated
from repro.data.medical import generate_cohort
from repro.fed.engine import make_engine
from repro.fed.scheduler import make_scheduler
from repro.models.mlp_net import init_mlp
from repro.obs import (EVENT_SCHEMA, Recorder, get_recorder, metrics as obsm,
                       recording, span, to_chrome_trace, trace as obstrace)
from repro.obs import report as obs_report


@pytest.fixture(scope="module")
def cohort():
    return generate_cohort(num_admissions=800, num_medicines=40,
                           num_risk_medicines=15, num_interactions=4, seed=0)


FEATS = (40, 16, 4, 1)


def _tcfg(fuse: int, loops: int = 4, K: int = 5, obs=None, **scbf_kw):
    return TrainConfig(
        learning_rate=0.05, global_loops=loops, local_batch_size=64,
        local_epochs=1, eval_every=1,
        obs=obs or ObsConfig(),
        scbf=ScbfConfig(upload_rate=0.1, num_clients=K, **scbf_kw),
        fed=FedConfig(fuse_rounds=fuse))


def _params_bitwise_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# trace primitives
# ---------------------------------------------------------------------------

def test_span_measures_without_recorder():
    assert get_recorder() is None
    with span("anything", foo=1) as sp:
        sum(range(1000))
    assert sp.elapsed > 0.0          # the one wall-clock source always works


def test_recorder_event_log_and_counters(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with recording(path) as rec:
        assert get_recorder() is rec
        obstrace.event("custom", value=3)
        with span("work", n=2):
            pass
        obstrace.count("host_offloads")
    assert get_recorder() is None
    assert rec.counters["events"] == 2          # custom + the span event
    assert rec.counters["spans"] == 1
    assert rec.counters["host_offloads"] == 1
    events = obs_report.read_events(path)
    assert events[0]["ev"] == "meta"
    assert events[0]["schema"] == EVENT_SCHEMA
    kinds = [e["ev"] for e in events]
    assert kinds == ["meta", "custom", "span"]
    assert all(e["ts"] >= 0 for e in events)


def test_events_noop_without_recorder():
    before = Recorder()                  # unrelated, inactive
    obstrace.event("dropped")
    obstrace.count("dropped")
    assert len(before.events) == 1       # only its own meta


def test_chrome_trace_export():
    rec = Recorder()
    rec.event("round", loop=0)
    with rec.span("chunk", rounds=2):
        pass
    trace = to_chrome_trace(rec.events)
    phases = {e["name"]: e["ph"] for e in trace["traceEvents"]}
    assert phases == {"round": "i", "chunk": "X"}
    slice_ = next(e for e in trace["traceEvents"] if e["ph"] == "X")
    assert slice_["ts"] >= 0 and slice_["dur"] >= 0
    assert slice_["args"]["rounds"] == 2


def test_roundplan_telemetry_fields():
    sched = make_scheduler(FedConfig(mode="sync"), num_clients=8, seed=0)
    t = sched.plan(0).telemetry()
    assert set(t) == {"sampled", "dropped", "stragglers",
                      "staleness_mean", "staleness_max"}
    assert t["staleness_mean"] == 0.0 and t["staleness_max"] == 0


def test_codec_breakdown_stable_keys():
    out = wire.codec_breakdown([])
    assert set(out) == set(wire.CODECS)
    assert all(v == 0 for v in out.values())


# ---------------------------------------------------------------------------
# device metrics vs wire ground truth
# ---------------------------------------------------------------------------

def _tiny_engine(K=6, n=32, d=12, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    clients = [((rng.random((n, d)) < 0.3).astype(np.float32),
                (rng.random(n) < 0.5).astype(np.float32))
               for _ in range(K)]
    eng = make_engine("batched", clients, batch, epochs=1)
    params = init_mlp((d, 8, 4, 1), jax.random.PRNGKey(1))
    return eng, params, ScbfConfig(upload_rate=0.25, num_clients=K)


def _keys(key, p):
    key, kc, ks, kd = jax.random.split(key, 4)
    return key, tuple(jax.random.split(k, p) for k in (kc, ks, kd))


def test_device_metrics_match_wire_truth():
    """The on-device byte accounting IS the wire accounting: sparse
    bytes, per-codec breakdown, and participant count must agree with
    the encoded payloads exactly, not approximately."""
    K = 6
    eng, params, cfg = _tiny_engine(K=K)
    _, (ck, sk, dk) = _keys(jax.random.PRNGKey(0), K)
    payloads, stats, dm = eng.scbf_round(params, np.arange(K), 0.05,
                                         ck, sk, dk, cfg, collect=True)
    assert dm["participants"] == len(payloads) == K
    assert dm["sparse_bytes"] == sum(p.nbytes for p in payloads)
    assert dm["codec_bytes"] == wire.codec_breakdown(payloads)
    assert sum(dm["codec_bytes"].values()) == dm["sparse_bytes"]
    assert dm["train_loss"] > 0.0
    assert len(dm["selected"]) == len(params) and \
        all(s >= 0 for s in dm["selected"])


def test_empty_round_collect_shape():
    eng, params, cfg = _tiny_engine()
    out = eng.scbf_round(params, np.array([], np.int64), 0.05,
                         (), (), (), cfg, collect=True)
    assert out == ([], [], None)


def test_with_loss_leaves_params_bitwise_identical():
    """value_and_grad instrumentation must not perturb training: the
    same round with collect on/off produces the same payload bytes."""
    K = 4
    eng, params, cfg = _tiny_engine(K=K)
    _, (ck, sk, dk) = _keys(jax.random.PRNGKey(3), K)
    plain, _ = eng.scbf_round(params, np.arange(K), 0.05, ck, sk, dk, cfg)
    collected, _, dm = eng.scbf_round(params, np.arange(K), 0.05,
                                      ck, sk, dk, cfg, collect=True)
    assert dm["train_loss"] > 0.0
    for a, b in zip(plain, collected):
        assert a.nbytes == b.nbytes
        for la, lb in zip(a.layers, b.layers):
            assert la.codec == lb.codec
            assert np.array_equal(la.values, lb.values)


# ---------------------------------------------------------------------------
# fused-path hygiene: zero in-chunk transfers, bounded compiles
# ---------------------------------------------------------------------------

def test_fused_collect_chunk_transfer_clean_and_two_compiles():
    """With collect on, a warmed fused chunk still crosses the host
    boundary zero times — the (S,)-stacked MetricsCarry rides the scan
    and offloads ONCE at the chunk boundary — and the whole exercise
    stays <= 2 fused compiles."""
    K, S = 6, 3
    eng, params, cfg = _tiny_engine(K=K)
    B = eng.fused_num_slots(K)
    key = jax.random.PRNGKey(0)
    rows = []
    for _ in range(2 * S):
        key, r = _keys(key, K)
        rows.append(r)

    def plan_for(rows):
        return eng.prepare_fused_plan(
            [np.arange(K)] * S, [0.05] * S, [r[0] for r in rows],
            [r[1] for r in rows], [r[2] for r in rows],
            horizon=S, num_slots=B)

    with assert_compiles(2):
        p1, masked, masks, met = eng.fused_scbf_chunk(
            tuple(params), plan_for(rows[:S]), cfg, collect=True)
        jax.block_until_ready(p1)                       # warmup chunk
        plan2 = plan_for(rows[S:])                      # host→device here
        with assert_no_transfers():
            out = eng.fused_scbf_chunk(p1, plan2, cfg, collect=True)
            jax.block_until_ready(out)
        # ONE offload for the whole chunk, at the boundary
        rec = Recorder()
        with recording(recorder=rec):
            dms = obsm.offload(out[3], rounds=plan2.rounds)
    assert rec.counters["host_offloads"] == 1
    assert len(dms) == S
    # boundary-offloaded metrics still match the wire exactly
    per_round = eng.emit_fused_payloads(out[1], out[2], plan2)
    for dm, (payloads, _) in zip(dms, per_round):
        assert dm["sparse_bytes"] == sum(p.nbytes for p in payloads)
        assert dm["codec_bytes"] == wire.codec_breakdown(payloads)
        assert dm["participants"] == K


# ---------------------------------------------------------------------------
# driver-level: telemetry-on parity, records, run telemetry
# ---------------------------------------------------------------------------

def test_telemetry_does_not_change_fused_trajectory(cohort):
    """The headline invariant: turning the flight recorder on changes
    no training bit — params, bytes, ε all identical."""
    plain = run_federated(cohort, _tcfg(3, loops=5), method="scbf",
                          mlp_features=FEATS)
    cfg = dataclasses.replace(_tcfg(3, loops=5),
                              obs=ObsConfig(device_metrics=True))
    with_obs = run_federated(cohort, cfg, method="scbf",
                             mlp_features=FEATS)
    assert _params_bitwise_equal(plain.final_params, with_obs.final_params)
    for ra, rb in zip(plain.records, with_obs.records):
        assert ra.sparse_bytes == rb.sparse_bytes
        assert ra.upload_fraction == rb.upload_fraction
        assert ra.epsilon == rb.epsilon
        assert ra.train_loss is None          # collect was off
        assert rb.train_loss is not None and rb.train_loss > 0


def test_fused_wall_is_amortized_flag(cohort):
    fused = run_federated(cohort, _tcfg(3, loops=6), method="scbf",
                          mlp_features=FEATS)
    per_round = run_federated(cohort, _tcfg(1, loops=3), method="scbf",
                              mlp_features=FEATS)
    assert all(r.wall_is_amortized for r in fused.records)
    assert not any(r.wall_is_amortized for r in per_round.records)
    # within one chunk every round reports the same chunk-wall/S share
    walls = [r.wall_time for r in fused.records]
    assert walls[0] == walls[1] == walls[2]
    assert all(w > 0 for w in walls)


def test_fused_loss_matches_per_round_loss(cohort):
    obs = ObsConfig(device_metrics=True)
    a = run_federated(cohort, _tcfg(1, loops=4, obs=obs), method="scbf",
                      mlp_features=FEATS)
    b = run_federated(cohort, _tcfg(2, loops=4, obs=obs), method="scbf",
                      mlp_features=FEATS)
    for ra, rb in zip(a.records, b.records):
        assert ra.train_loss == pytest.approx(rb.train_loss, rel=1e-6)


def test_fedavg_collect_round_loss(cohort):
    obs = ObsConfig(device_metrics=True)
    res = run_federated(cohort, _tcfg(2, loops=4, obs=obs),
                        method="fedavg", mlp_features=FEATS)
    assert all(r.train_loss is not None and r.train_loss > 0
               for r in res.records)


# ---------------------------------------------------------------------------
# the events.jsonl golden schema + run telemetry watchdogs
# ---------------------------------------------------------------------------

# Required fields per event kind — the schema-1 contract
# docs/OBSERVABILITY.md documents.  Extending an event with NEW fields
# is fine; removing/renaming one of these requires an EVENT_SCHEMA bump.
REQUIRED_FIELDS = {
    "meta": {"schema", "emitter"},
    "run_start": {"method", "loops", "clients", "engine", "fuse_rounds",
                  "mode"},
    "round": {"loop", "participants", "upload_fraction", "sparse_bytes",
              "dense_bytes", "wall", "wall_is_amortized", "hidden",
              "evaluated", "sampled", "dropped", "stragglers",
              "staleness_mean", "staleness_max", "train_loss",
              "selected", "codec_bytes"},
    "span": {"name", "dur"},
    "run_end": set(),
}


@pytest.fixture(scope="module")
def golden_run(cohort, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("obs") / "events.jsonl")
    # device metrics are the config's switch: the recorder alone keeps
    # the host event log only
    obs = ObsConfig(device_metrics=True)
    with recording(path):
        res = run_federated(cohort, _tcfg(2, loops=4, obs=obs),
                            method="scbf", mlp_features=FEATS)
    return path, res


def test_events_jsonl_golden_schema(golden_run):
    path, _ = golden_run
    events = obs_report.read_events(path)
    assert events[0]["ev"] == "meta"
    kinds = [e["ev"] for e in events]
    assert kinds.count("run_start") == 1 and kinds.count("run_end") == 1
    assert kinds.count("round") == 4
    assert kinds.index("run_start") < kinds.index("round")
    for e in events:
        missing = REQUIRED_FIELDS.get(e["ev"], set()) - set(e)
        assert not missing, f"{e['ev']} event missing {missing}"
    spans = {e["name"] for e in events if e["ev"] == "span"}
    assert {"fused_chunk", "encode", "eval"} <= spans


def test_run_telemetry_watchdogs(golden_run):
    _, res = golden_run
    tel = res.telemetry
    assert tel is not None
    assert tel["fused_compiles"] <= 2          # the PR-4/5 bar holds
    assert tel["host_offloads"] == 2           # one per chunk (4 loops / 2)
    assert tel["events"] > 0 and tel["spans"] > 0


def test_recording_off_leaves_no_telemetry(cohort):
    res = run_federated(cohort, _tcfg(2, loops=2), method="scbf",
                        mlp_features=FEATS)
    assert res.telemetry is None


# ---------------------------------------------------------------------------
# host spans: every stretch of a job named, on the profiler's clock
# ---------------------------------------------------------------------------

# rounding of the event log (ts and dur to the microsecond)
_TOL = 3e-6


def _span_intervals(events):
    """{name: [(start_s, end_s, event), ...]} of a recorder's spans."""
    out = {}
    for e in events:
        if e["ev"] == "span":
            out.setdefault(e["name"], []).append(
                (e["ts"] - e["dur"], e["ts"], e))
    return out


def _inside(inner, outer):
    return outer[0] - _TOL <= inner[0] and inner[1] <= outer[1] + _TOL


def test_recorded_fused_job_names_every_host_stretch(cohort):
    """Job set-up (with the cohort's copy), each chunk's planning, the
    three parts of wire emission and the per-round records are spans,
    nested as the layers are: wait, pull and encoding lie inside their
    ``encode`` and add up to no more than it."""
    rec = Recorder()
    with recording(recorder=rec):
        run_federated(cohort, _tcfg(2, loops=4), method="scbf",
                      mlp_features=FEATS)
    sp = _span_intervals(rec.events)
    assert {"job_setup", "cohort_put", "plan", "emit_wait", "emit_pull",
            "wire_encode", "records", "encode", "fused_chunk",
            "eval"} <= set(sp)
    (setup,), (put,) = sp["job_setup"], sp["cohort_put"]
    assert _inside(put, setup)
    chunks = sp["fused_chunk"]
    assert len(chunks) == len(sp["plan"]) == len(sp["encode"]) \
        == len(sp["records"]) == 2
    assert setup[1] <= chunks[0][0] + _TOL
    for chunk, plan, enc, recs in zip(chunks, sp["plan"], sp["encode"],
                                      sp["records"]):
        assert _inside(plan, chunk) and _inside(enc, chunk)
        assert plan[1] <= enc[0] + _TOL
        # the records follow their chunk, outside it
        assert chunk[1] <= recs[0] + _TOL
        parts = [next(p for p in sp[name] if _inside(p, enc))
                 for name in ("emit_wait", "emit_pull", "wire_encode")]
        for a, b in zip(parts, parts[1:]):
            assert a[1] <= b[0] + _TOL
        assert sum(p[2]["dur"] for p in parts) <= enc[2]["dur"] + _TOL
        assert parts[1][2]["bytes"] > 0
        assert parts[2][2]["slots"] == 2 * 5
        # the chunk boundary's evaluation is nested in the records
        assert any(_inside(ev, recs) for ev in sp["eval"])
    for name in ("emit_wait", "emit_pull", "wire_encode"):
        assert len(sp[name]) == 2


def test_recorder_alone_runs_the_timed_program(cohort):
    """An active recorder turns on the host event log and nothing else:
    the recorded job is bitwise the unrecorded one, collects no device
    metrics, and compiles no fused program the unrecorded job did not
    already compile."""
    cfg = _tcfg(4, loops=4)
    plain = run_federated(cohort, cfg, method="scbf", mlp_features=FEATS)
    with recording(recorder=Recorder()):
        recd = run_federated(cohort, cfg, method="scbf",
                             mlp_features=FEATS)
    assert _params_bitwise_equal(plain.final_params, recd.final_params)
    assert [r.sparse_bytes for r in plain.records] == \
        [r.sparse_bytes for r in recd.records]
    assert all(r.train_loss is None for r in recd.records)
    assert recd.telemetry["fused_compiles"] == 0
    assert recd.telemetry["host_offloads"] == 0


def test_every_span_on_the_profiler_clock(cohort, tmp_path):
    """While recording, every span is a profiler host event of the same
    name: after aligning the recorder's clock on ``fused_chunk`` (the
    benchmark's ``bench.tracereduce.align``), each span starts within
    1 ms of its profiler event."""
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import tracereduce

    cfg = _tcfg(2, loops=4)
    run_federated(cohort, cfg, method="scbf", mlp_features=FEATS)  # warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    rec = Recorder()
    with recording(recorder=rec):
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            run_federated(cohort, cfg, method="scbf", mlp_features=FEATS)
    trace = tracereduce.load_dir(str(tmp_path))
    starts = {name: sorted(s * 1e9 for s, _, _ in iv)
              for name, iv in _span_intervals(rec.events).items()}
    prof = {name: [s for s, _ in tracereduce.host_events(trace, name)]
            for name in starts}
    off = tracereduce.align(starts["fused_chunk"], prof["fused_chunk"])
    assert off is not None
    for name, ours in starts.items():
        assert len(prof[name]) == len(ours), name
        for r, p in zip(ours, prof[name]):
            assert abs(r + off - p) < 1e6, (name, (r + off - p) / 1e6)


# ---------------------------------------------------------------------------
# the report pipeline
# ---------------------------------------------------------------------------

def test_report_cli_end_to_end(golden_run, tmp_path, capsys):
    path, res = golden_run
    json_out = str(tmp_path / "report.json")
    trace_out = str(tmp_path / "trace.json")
    assert obs_report.main([path, "--json-out", json_out,
                            "--trace-out", trace_out]) == 0
    table = capsys.readouterr().out
    assert "loop" in table and "~" in table    # amortized marker shown
    summary = json.load(open(json_out))
    assert summary["schema"] == EVENT_SCHEMA
    assert summary["rounds"] == 4
    assert summary["total_sparse_bytes"] == \
        sum(r.sparse_bytes for r in res.records)
    assert summary["final_train_loss"] == res.records[-1].train_loss
    assert summary["wall_is_amortized"] is True
    assert summary["compiles"]["fused_compiles"] <= 2
    trace = json.load(open(trace_out))
    assert any(e["ph"] == "X" for e in trace["traceEvents"])


def test_report_refuses_schema_mismatch(tmp_path, capsys):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"ev": "meta", "ts": 0.0, "schema": 99,
                               "emitter": "repro.obs/99"}) + "\n")
    with pytest.raises(ValueError, match="schema 99"):
        obs_report.read_events(str(bad))
    assert obs_report.main([str(bad)]) == 1
    assert "schema" in capsys.readouterr().err


def test_report_refuses_non_event_file(tmp_path):
    f = tmp_path / "x.jsonl"
    f.write_text('{"ev": "round"}\n')
    with pytest.raises(ValueError, match="meta"):
        obs_report.read_events(str(f))
