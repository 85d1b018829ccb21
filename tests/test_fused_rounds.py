"""The fused round loop: whole SCBF rounds as one device program.

Covers the PR-4 acceptance bars: fused-vs-per-round bit-parity at full
participation and under varying bucketed P, the prune/fedbuff fallback
boundary, a transfer-guard proof that the fused hot loop never crosses
the host, the <= 2-compiles property on a varying-P trace, and the
eval_every / evaluated-flag record semantics.
"""
import dataclasses

import jax
import numpy as np
import pytest

from _trace_guards import assert_compiles, assert_no_transfers
from repro.config import (FaultConfig, FedConfig, ObsConfig, ScbfConfig,
                          TrainConfig)
from repro.core.scbf import run_federated
from repro.data.medical import generate_cohort
from repro.fed.cohort import bucket_size
from repro.fed.engine import BatchedEngine, make_engine
from repro.fed.scheduler import make_scheduler
from repro.models.mlp_net import init_mlp
from repro.obs import trace as obstrace


@pytest.fixture(scope="module")
def cohort():
    return generate_cohort(num_admissions=800, num_medicines=40,
                           num_risk_medicines=15, num_interactions=4, seed=0)


FEATS = (40, 16, 4, 1)


def _tcfg(fuse: int, loops: int = 4, K: int = 5, eval_every: int = 1,
          batch: int = 64, scbf_kw=None, **fed_kw):
    # K=8 splits the 480 train rows into 60-row shards, so those tests
    # must pass batch=32 — at batch 64 every client trains ZERO batches
    # and the whole run is a (legitimate, but vacuous) no-op
    return TrainConfig(
        learning_rate=0.05, global_loops=loops, local_batch_size=batch,
        local_epochs=1, eval_every=eval_every,
        scbf=ScbfConfig(upload_rate=0.1, num_clients=K, **(scbf_kw or {})),
        fed=FedConfig(fuse_rounds=fuse, **fed_kw))


def _params_bitwise_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def _assert_trajectories_match(per_round, fused, bitwise_params=True):
    """Everything the fused path owes the per-round path: identical
    participation, byte accounting, ε spend, and final model."""
    assert len(per_round.records) == len(fused.records)
    for ra, rb in zip(per_round.records, fused.records):
        assert ra.loop == rb.loop
        assert ra.num_participants == rb.num_participants
        assert ra.sparse_bytes == rb.sparse_bytes
        assert ra.dense_bytes == rb.dense_bytes
        assert ra.upload_fraction == rb.upload_fraction
        assert ra.epsilon == rb.epsilon
        assert ra.hidden_sizes == rb.hidden_sizes
        assert ra.flops_proxy == rb.flops_proxy
    if bitwise_params:
        assert _params_bitwise_equal(per_round.final_params,
                                     fused.final_params)


def _recorded(cohort, cfg, method="scbf"):
    rec = obstrace.Recorder()
    with obstrace.recording(recorder=rec):
        res = run_federated(cohort, cfg, method=method, mlp_features=FEATS)
    return res, rec


# ---------------------------------------------------------------------------
# parity: the tentpole acceptance criterion
# ---------------------------------------------------------------------------

def test_fused_matches_per_round_full_participation(cohort):
    """fuse_rounds=S is bit-identical to fuse_rounds=1 at K=5 full
    participation: params, masks (via byte accounting), upload bytes,
    and ε all agree; the final evaluated AUC agrees exactly because the
    models are the same bits."""
    a = run_federated(cohort, _tcfg(1, loops=5), method="scbf",
                      mlp_features=FEATS)
    b = run_federated(cohort, _tcfg(3, loops=5), method="scbf",
                      mlp_features=FEATS)
    _assert_trajectories_match(a, b)
    assert b.records[-1].evaluated
    assert a.final.auc_roc == b.final.auc_roc
    assert a.final.auc_pr == b.final.auc_pr


def test_fused_matches_per_round_with_dp(cohort):
    """DP noise runs inside the fused scan; the ε ledger and the noised
    trajectory must both match the per-round path bit-for-bit."""
    kw = dict(scbf_kw=dict(dp_noise_multiplier=1.0, dp_clip_norm=1.0))
    a = run_federated(cohort, _tcfg(1, **kw), method="scbf",
                      mlp_features=FEATS)
    b = run_federated(cohort, _tcfg(4, **kw), method="scbf",
                      mlp_features=FEATS)
    _assert_trajectories_match(a, b)
    assert all(r.epsilon is not None for r in b.records)


@pytest.mark.parametrize("loops,fuse", [(7, 3), (25, 10)],
                         ids=["7x3", "25x10"])
def test_fused_matches_per_round_varying_bucketed_p(cohort, loops, fuse):
    """Sampling + dropout make P vary across bucket boundaries; the
    fused plan pads every round to one run-constant slot count, and the
    real slots must stay bit-identical to the per-round bucketed
    engine.  Every chunk but the first is dispatched before its
    predecessor's emission (25 rounds at S=10: three chunks, the last
    one padded)."""
    kw = dict(loops=loops, K=8, batch=32, sample_fraction=0.5,
              dropout_rate=0.25)
    a = run_federated(cohort, _tcfg(1, **kw), method="scbf",
                      mlp_features=FEATS)
    b, _ = _recorded(cohort, _tcfg(fuse, **kw))
    ps = [r.num_participants for r in a.records]
    assert len({p for p in ps if p}) > 1      # P actually varies
    # guard against a vacuous pass: real training, real uploads
    assert sum(r.sparse_bytes for r in a.records) > 0
    _assert_trajectories_match(a, b)
    chunks = -(-loops // fuse)
    assert b.telemetry["fused_dispatch_ahead"] == chunks - 1
    assert [r.evaluated for r in b.records].count(True) == chunks


@pytest.mark.parametrize("K,fraction,slots", [(5, 1.0, 5), (12, 0.5, 8)],
                         ids=["5of5", "6of12"])
def test_records_count_the_slots_trained(cohort, K, fraction, slots):
    """``LoopRecord.slots`` is the bucket the round's program trained, on
    the fused and the per-round path alike; it is an observation only,
    and the two paths still agree bit for bit on everything else."""
    kw = dict(loops=4, K=K, batch=16, sample_fraction=fraction)
    a = run_federated(cohort, _tcfg(1, **kw), method="scbf",
                      mlp_features=FEATS)
    b = run_federated(cohort, _tcfg(2, **kw), method="scbf",
                      mlp_features=FEATS)
    P = round(fraction * K)
    assert bucket_size(P, K, "pow2", 1) == slots
    for res in (a, b):
        assert [(r.num_participants, r.slots) for r in res.records] \
            == [(P, slots)] * 4
    assert sum(r.sparse_bytes for r in a.records) > 0
    _assert_trajectories_match(a, b)
    seq = run_federated(cohort, _tcfg(1, **kw), method="scbf",
                        mlp_features=FEATS, engine="sequential")
    assert [r.slots for r in seq.records] == [P] * 4


def test_fused_slots_are_the_largest_cohorts_bucket(cohort):
    """Under dropout P varies: the fused chunk trains the run-constant
    bucket of the scheduler's largest cohort every round, the per-round
    engine each round's own bucket (none for an empty round)."""
    kw = dict(loops=7, K=8, batch=32, sample_fraction=0.5,
              dropout_rate=0.25)
    a = run_federated(cohort, _tcfg(1, **kw), method="scbf",
                      mlp_features=FEATS)
    b = run_federated(cohort, _tcfg(3, **kw), method="scbf",
                      mlp_features=FEATS)
    ps = [r.num_participants for r in a.records]
    assert len(set(ps)) > 1
    assert [r.slots for r in a.records] == [bucket_size(p, 8, "pow2", 1)
                                            for p in ps]
    assert {r.slots for r in b.records} == {bucket_size(4, 8, "pow2", 1)}


def test_fused_fedavg_matches_per_round(cohort):
    """Fused FedAvg aggregates on device too.  XLA contracts the
    weight-multiply-accumulate inside the fused program (FMA), so
    parity here is allclose-tight rather than bitwise — the scbf path
    (pure adds, nothing to contract) is the bitwise one.  Its chunks
    (no emission) go through the same pipeline: the second is
    dispatched ahead."""
    a = run_federated(cohort, _tcfg(1, loops=5), method="fedavg",
                      mlp_features=FEATS)
    b, _ = _recorded(cohort, _tcfg(3, loops=5), method="fedavg")
    assert b.telemetry["fused_dispatch_ahead"] == 1
    for la, lb in zip(jax.tree_util.tree_leaves(a.final_params),
                      jax.tree_util.tree_leaves(b.final_params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-6, rtol=1e-5)
    assert a.final.auc_roc == pytest.approx(b.final.auc_roc, abs=1e-6)


# ---------------------------------------------------------------------------
# fallback boundary: prune / fedbuff / sequential run per-round
# ---------------------------------------------------------------------------

def test_fused_prune_falls_back_to_per_round(cohort):
    """Pruning reshapes the model mid-run, which a fixed-shape scan
    cannot express: fuse_rounds>1 + prune must take the per-round path
    — every loop evaluated (no chunk coarsening) and the trajectory
    identical to an explicit fuse_rounds=1 run."""
    kw = dict(loops=4, scbf_kw=dict(prune=True, prune_rate=0.2,
                                    prune_total=0.4))
    a = run_federated(cohort, _tcfg(1, **kw), method="scbf",
                      mlp_features=FEATS)
    b = run_federated(cohort, _tcfg(4, **kw), method="scbf",
                      mlp_features=FEATS)
    assert all(r.evaluated for r in b.records)      # per-round cadence
    assert [r.hidden_sizes for r in a.records] == \
        [r.hidden_sizes for r in b.records]
    assert [r.auc_roc for r in a.records] == [r.auc_roc for r in b.records]
    _assert_trajectories_match(a, b)


def test_fused_fedbuff_falls_back_to_per_round(cohort):
    """FedBuff needs per-round server-version feedback (staleness), so
    fuse_rounds>1 falls back rather than fabricating a horizon."""
    kw = dict(loops=3, K=8, batch=32, mode="fedbuff", buffer_size=4,
              concurrency=6, straggler_rate=0.3)
    a = run_federated(cohort, _tcfg(1, **kw), method="scbf",
                      mlp_features=FEATS)
    b = run_federated(cohort, _tcfg(4, **kw), method="scbf",
                      mlp_features=FEATS)
    assert all(r.evaluated for r in b.records)
    _assert_trajectories_match(a, b)


def test_fused_sequential_engine_falls_back(cohort):
    """There is no sequential program to fuse: the reference engine
    keeps its per-client loop under fuse_rounds>1."""
    a = run_federated(cohort, _tcfg(1, loops=3), method="scbf",
                      mlp_features=FEATS, engine="sequential")
    b = run_federated(cohort, _tcfg(3, loops=3), method="scbf",
                      mlp_features=FEATS, engine="sequential")
    assert all(r.evaluated for r in b.records)
    _assert_trajectories_match(a, b)


def test_fuse_rounds_validation(cohort):
    with pytest.raises(ValueError):
        run_federated(cohort, _tcfg(0), method="scbf", mlp_features=FEATS)


@pytest.mark.parametrize("fed_kw,scbf_kw,message", [
    (dict(max_update_norm=1.0), {}, "host-side norm gate"),
    ({}, dict(dp_noise_multiplier=-1.0), "must be >= 0"),
    (dict(sample_fraction=0.4, min_valid_participants=3), {},
     "can never be met"),
], ids=["fused", "dp", "quorum"])
def test_refusals_come_before_the_cohort_reaches_the_device(
        cohort, monkeypatch, fed_kw, scbf_kw, message):
    """Every refusal is decided from the configuration alone: none of
    them waits for the engine to copy the cohort to the device."""
    def _no_engine(*a, **k):
        raise AssertionError("make_engine ran before the refusal")
    monkeypatch.setattr("repro.fed.engine.make_engine", _no_engine)
    with pytest.raises(ValueError, match=message):
        run_federated(cohort, _tcfg(3, scbf_kw=scbf_kw, **fed_kw),
                      method="scbf", mlp_features=FEATS)


# the event envelope's timestamp, and the fields where the two loops
# differ by design: the fused loop amortises one chunk's wall over its
# rounds, trains a run-constant slot count and evaluates at chunk
# boundaries only
_LOOP_SHAPED = ("ts", "wall", "wall_is_amortized", "slots", "evaluated")


# fused FedAvg's model is allclose to the per-round one, not bitwise
# (test_fused_fedavg_matches_per_round), so its train loss differs in
# the last bits from the second round on
@pytest.mark.parametrize("method,kw,fuse,inexact", [
    ("scbf", dict(loops=7, K=8, batch=32, sample_fraction=0.5,
                  dropout_rate=0.25,
                  scbf_kw=dict(dp_noise_multiplier=1.0, dp_clip_norm=1.0)),
     3, ()),
    ("scbf", dict(loops=8, scbf_kw=dict(prune=True, prune_impl="mask",
                                        prune_rate=0.2, prune_total=0.5)),
     4, ()),
    ("fedavg", dict(loops=5), 3, ("train_loss",)),
], ids=["scbf-dp-sampled", "scbfwp-mask", "fedavg"])
def test_round_events_match_across_loops(cohort, method, kw, fuse, inexact):
    """The per-round and the fused loop send the same ``round`` event for
    every round (docs/OBSERVABILITY.md), device metrics included."""
    def rounds(S):
        cfg = dataclasses.replace(_tcfg(S, **kw),
                                  obs=ObsConfig(device_metrics=True))
        _, rec = _recorded(cohort, cfg, method)
        return [{k: v for k, v in e.items()
                 if k not in _LOOP_SHAPED + inexact}
                for e in rec.events if e["ev"] == "round"]
    per_round, fused = rounds(1), rounds(fuse)
    assert len(per_round) == len(fused) == kw["loops"]
    for a, b in zip(per_round, fused):
        assert a == b


# ---------------------------------------------------------------------------
# the hot loop is host-transfer-free, and compiles once
# ---------------------------------------------------------------------------

def _engine_fixture(K=5, n=24, d=12, seed=0):
    rng = np.random.default_rng(seed)
    clients = [(rng.random((n, d)).astype(np.float32),
                (rng.random(n) < 0.5).astype(np.float32))
               for _ in range(K)]
    params = init_mlp((d, 8, 1), jax.random.PRNGKey(1))
    return make_engine("batched", clients, 8, 1), params


def _round_key_rows(parts, seed=0):
    key = jax.random.PRNGKey(seed)
    cks, sks, dks = [], [], []
    for part in parts:
        p = int(np.asarray(part).size)
        key, kc, ks, kd = jax.random.split(key, 4)
        if p:
            cks.append(np.asarray(jax.random.split(kc, p)))
            sks.append(np.asarray(jax.random.split(ks, p)))
            dks.append(np.asarray(jax.random.split(kd, p)))
        else:
            empty = np.zeros((0, 2), np.uint32)
            cks.append(empty)
            sks.append(empty)
            dks.append(empty)
    return cks, sks, dks


def test_fused_chunk_runs_under_transfer_guard():
    """The scan body performs zero host transfers: after the one-time
    compile, a whole chunk dispatches and returns device arrays under
    ``jax.transfer_guard("disallow")`` — the proof that planning
    (prepare_fused_plan) really hoisted every transfer out of the hot
    loop.  Emission then runs outside the guard, as designed."""
    eng, params = _engine_fixture()
    cfg = ScbfConfig(upload_rate=0.25, num_clients=5)
    parts = [np.arange(5), np.array([0, 2, 4]),
             np.array([], dtype=np.int64)]
    cks, sks, dks = _round_key_rows(parts)
    plan = eng.prepare_fused_plan(parts, [0.1, 0.1, 0.1], cks, sks, dks,
                                  horizon=4,
                                  num_slots=eng.fused_num_slots(5))
    # every chunk call gets its own copy: the call donates its params
    # buffers on backends where donation is real, so `params` itself
    # must never be handed to a chunk and then reused
    warm = jax.tree_util.tree_map(lambda a: a + 0, tuple(params))
    eng.fused_scbf_chunk(warm, plan, cfg)          # compile outside guard
    fresh = jax.tree_util.tree_map(lambda a: a + 0, tuple(params))
    with assert_no_transfers():
        new_p, masked, masks = eng.fused_scbf_chunk(fresh, plan, cfg)
    emitted = eng.emit_fused_payloads(masked, masks, plan)
    assert [len(p) for p, _ in emitted] == [5, 3, 0]
    assert all(np.asarray(leaf).dtype == np.float32
               for leaf in jax.tree_util.tree_leaves(new_p))


def test_fused_compiles_once_across_varying_p(cohort):
    """The (S, B) plan is padded to a run-constant shape — short tail
    chunks and every distinct P included — so a whole varying-P run
    costs at most 2 fused compiles (expected: exactly 1)."""
    kw = dict(loops=10, K=8, batch=32, sample_fraction=0.5,
              dropout_rate=0.25)
    with assert_compiles(2):
        res = run_federated(cohort, _tcfg(4, **kw), method="scbf",
                            mlp_features=FEATS)
    ps = {r.num_participants for r in res.records if r.num_participants}
    assert len(ps) > 1
    assert sum(r.sparse_bytes for r in res.records) > 0


# ---------------------------------------------------------------------------
# eval_every / evaluated-flag record semantics
# ---------------------------------------------------------------------------

def test_eval_every_per_round_records(cohort):
    res = run_federated(cohort, _tcfg(1, loops=5, eval_every=2),
                        method="scbf", mlp_features=FEATS)
    assert [r.evaluated for r in res.records] == \
        [False, True, False, True, True]
    # non-evaluated loops carry the last-known metrics
    assert res.records[2].auc_roc == res.records[1].auc_roc
    assert res.records[2].auc_pr == res.records[1].auc_pr
    # loop 0 predates any evaluation: it carries the initial model's
    # metrics, still finite and well-defined
    assert np.isfinite(res.records[0].auc_roc)
    ref = run_federated(cohort, _tcfg(1, loops=5), method="scbf",
                        mlp_features=FEATS)
    assert res.final.auc_roc == ref.final.auc_roc   # training unchanged


def test_fused_evaluates_at_chunk_boundaries(cohort):
    """Fused execution coarsens evaluation to chunk boundaries; the
    final loop is always evaluated."""
    res = run_federated(cohort, _tcfg(3, loops=6), method="scbf",
                        mlp_features=FEATS)
    assert [r.evaluated for r in res.records] == \
        [False, False, True, False, False, True]
    for i in (0, 1):                      # pre-first-eval: initial model
        assert res.records[i].auc_roc == res.records[0].auc_roc
    for i in (3, 4):                      # carried from the loop-2 eval
        assert res.records[i].auc_roc == res.records[2].auc_roc
    assert res.final.evaluated


# ---------------------------------------------------------------------------
# the chunk pipeline: chunk k+1 trains while chunk k is emitted
# ---------------------------------------------------------------------------

# 25 rounds at S=10 with varying bucketed P: three chunks, the last one
# 5 real rounds padded to the run-constant horizon
_PIPE = dict(loops=25, K=8, batch=32, sample_fraction=0.5,
             dropout_rate=0.25)


def _assert_same_records(a, b):
    """Every field of every record but the wall clock, and the model."""
    assert len(a.records) == len(b.records)
    for ra, rb in zip(a.records, b.records):
        for name in ("loop", "num_participants", "slots", "sparse_bytes",
                     "dense_bytes", "upload_fraction", "epsilon",
                     "auc_roc", "auc_pr", "evaluated", "hidden_sizes"):
            assert getattr(ra, name) == getattr(rb, name), name
    assert _params_bitwise_equal(a.final_params, b.final_params)


def test_fused_dispatches_next_chunk_before_emission(cohort, monkeypatch):
    """Chunk k+1 is dispatched before chunk k's deltas are pulled: the
    device trains the next chunk while the host emits this one."""
    orig = BatchedEngine.fused_scbf_chunk

    def chunk(self, *args, **kw):
        out = orig(self, *args, **kw)
        obstrace.event("dispatched")
        return out

    monkeypatch.setattr(BatchedEngine, "fused_scbf_chunk", chunk)
    _, rec = _recorded(cohort, _tcfg(10, **_PIPE))
    sent = [e["ts"] for e in rec.events if e["ev"] == "dispatched"]
    pulls = [e["ts"] - e["dur"] for e in rec.events
             if e["ev"] == "span" and e["name"] == "emit_pull"]
    assert len(sent) == len(pulls) == 3
    for k in range(2):
        assert sent[k + 1] <= pulls[k]


def _donating(monkeypatch):
    """Make donation real on the CPU: every chunk call deletes the
    params it was given once it has been dispatched, as a backend that
    donates them would, so any later read of them raises."""
    orig = BatchedEngine.fused_scbf_chunk

    def chunk(self, params, *args, **kw):
        out = orig(self, params, *args, **kw)
        jax.block_until_ready(out)
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
        return out

    monkeypatch.setattr(BatchedEngine, "fused_scbf_chunk", chunk)


_STORM = FaultConfig(enabled=True, seed=7, crash_rate=0.15,
                     net_fail_rate=0.15, duplicate_rate=0.2,
                     bitflip_rate=0.15, nan_rate=0.15, poison_rate=0.15)


@pytest.mark.parametrize("faults", [False, True], ids=["plain", "faults"])
def test_fused_pipeline_reads_no_donated_model(cohort, monkeypatch,
                                               faults):
    """With the chunk's input model deleted at dispatch, a multi-chunk
    job with boundary evaluations — and one whose records run the fault
    pipeline and the admission gate — finishes with the records the
    undeleted job has: nothing reads a model after the next chunk
    took it."""
    kw = dict(_PIPE, loops=12, eval_every=1)
    if faults:
        kw.update(K=5, batch=64, sample_fraction=1.0, dropout_rate=0.0,
                  faults=_STORM, max_update_norm=100.0)
    cfg = _tcfg(5, **kw)
    plain = run_federated(cohort, cfg, method="scbf", mlp_features=FEATS)
    _donating(monkeypatch)
    donated, rec = _recorded(cohort, cfg)
    _assert_same_records(plain, donated)
    assert donated.telemetry["fused_dispatch_ahead"] == 2
    if faults:
        assert donated.telemetry.get("payloads_rejected", 0) > 0


def test_fused_pipeline_stays_off_while_pruning(cohort):
    """SCBFwP's prune step after a chunk sets the next chunk's
    keep-masks, so no prune-phase chunk runs ahead; the chunks after
    pruning do, and the records stay the per-round mask run's."""
    kw = dict(loops=10, scbf_kw=dict(prune=True, prune_impl="mask",
                                     prune_rate=0.2, prune_total=0.4))
    a = run_federated(cohort, _tcfg(1, **kw), method="scbf",
                      mlp_features=FEATS)
    b, rec = _recorded(cohort, _tcfg(3, **kw))
    pruned = sum(1 for e in rec.events if e["ev"] == "prune")
    chunks = sum(1 for e in rec.events
                 if e["ev"] == "span" and e["name"] == "fused_chunk")
    assert pruned >= 1 and chunks - pruned >= 2
    # one-round prune chunks, then S-round chunks: only the latter run
    # ahead, each into the next
    assert b.telemetry["fused_dispatch_ahead"] == chunks - pruned - 1
    _assert_trajectories_match(a, b)
    assert [r.hidden_sizes for r in a.records] == \
        [r.hidden_sizes for r in b.records]


@pytest.mark.parametrize("method", ["scbf", "fedavg"])
def test_fused_single_chunk_job_runs_nothing_ahead(cohort, method):
    """A job of one chunk has no next chunk to dispatch early."""
    res, _ = _recorded(cohort, _tcfg(5, loops=4), method=method)
    assert res.telemetry["fused_dispatch_ahead"] == 0
    assert len(res.records) == 4


# ---------------------------------------------------------------------------
# horizon planning
# ---------------------------------------------------------------------------

def test_sync_plan_horizon_matches_per_round_plans():
    cfg = FedConfig(sample_fraction=0.5, dropout_rate=0.2)
    a = make_scheduler(cfg, 16, seed=3)
    b = make_scheduler(cfg, 16, seed=3)
    horizon = a.plan_horizon(0, 6)
    singles = [b.plan(i) for i in range(6)]
    for pa, pb in zip(horizon, singles):
        np.testing.assert_array_equal(pa.participants, pb.participants)
        np.testing.assert_array_equal(pa.sampled, pb.sampled)
        np.testing.assert_array_equal(pa.dropped, pb.dropped)
    assert a.max_participants == 8
    with pytest.raises(ValueError):
        a.plan_horizon(0, 0)


def test_fedbuff_plan_horizon_refuses_multi_round():
    sched = make_scheduler(FedConfig(mode="fedbuff"), 8, seed=0)
    with pytest.raises(ValueError):
        sched.plan_horizon(0, 2)
    assert len(sched.plan_horizon(0, 1)) == 1


# ---------------------------------------------------------------------------
# pod-axis sharding composes with fused chunks
# ---------------------------------------------------------------------------

_FUSED_POD_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import numpy as np
import jax
from repro.comm import wire
from repro.config import ScbfConfig
from repro.fed.engine import make_engine
from repro.models.mlp_net import init_mlp

assert len(jax.devices()) == 4
rng = np.random.default_rng(0)
clients = [(rng.random((16, 8)).astype(np.float32),
            (rng.random(16) < .5).astype(np.float32)) for _ in range(4)]
params = init_mlp((8, 6, 1), jax.random.PRNGKey(1))
cfg = ScbfConfig(upload_rate=0.25, num_clients=4)
parts = [np.arange(4), np.array([0, 2]), np.array([], dtype=np.int64)]

def rows(seed):
    key = jax.random.PRNGKey(seed)
    cks, sks, dks = [], [], []
    for p in parts:
        key, kc, ks, kd = jax.random.split(key, 4)
        n = p.size
        if n:
            cks.append(np.asarray(jax.random.split(kc, n)))
            sks.append(np.asarray(jax.random.split(ks, n)))
            dks.append(np.asarray(jax.random.split(kd, n)))
        else:
            e = np.zeros((0, 2), np.uint32)
            cks.append(e); sks.append(e); dks.append(e)
    return cks, sks, dks

out = {}
for pods in (1, 4):
    eng = make_engine("batched", clients, 8, 1, pods=pods)
    cks, sks, dks = rows(0)
    plan = eng.prepare_fused_plan(parts, [0.1] * 3, cks, sks, dks,
                                  horizon=4,
                                  num_slots=eng.fused_num_slots(4))
    # fresh copy per engine: the chunk call donates its params buffers
    # where the backend supports donation
    p = jax.tree_util.tree_map(lambda a: a + 0, tuple(params))
    _, m, k = eng.fused_scbf_chunk(p, plan, cfg)
    out[pods] = eng.emit_fused_payloads(m, k, plan)
for (p1, _), (p4, _) in zip(out[1], out[4]):
    assert [a.nbytes for a in p1] == [a.nbytes for a in p4]
    for a, b in zip(p1, p4):
        for la, lb in zip(wire.decode(a), wire.decode(b)):
            for kk in la:
                np.testing.assert_array_equal(np.asarray(la[kk]),
                                              np.asarray(lb[kk]))
print("FUSED_POD_PARITY_OK")
"""


@pytest.mark.slow
def test_fused_chunk_pod_sharded_matches_single_device():
    """A fused chunk sharded over a 4-device pod mesh (slot axis on
    ``pod``, scan carry replicated) ships bit-identical uploads to the
    single-device chunk — including a bucket-padded round and an empty
    round.  Fresh process: the device count locks at first jax import."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _FUSED_POD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FUSED_POD_PARITY_OK" in out.stdout


# ---------------------------------------------------------------------------
# CI bench regression guard
# ---------------------------------------------------------------------------

def _load_checker():
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "check_fed_regression.py")
    spec = importlib.util.spec_from_file_location("check_fed_regression",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_regression_checker_logic():
    """The CI gate: a >25% fused-throughput-ratio drop and ANY
    compile-count growth fail; k_scaling speedup jitter passes (those
    rows are informational — only their presence is required)."""
    chk = _load_checker()
    baseline = {
        "schema": 3,
        "k_scaling": [{"K": 5, "speedup": 8.0}, {"K": 500, "speedup": 10.0}],
        "compile_counts": {"pow2": {"compiles": 1},
                           "exact": {"compiles": 7}},
        "fused": {"speedup": 4.0, "compile_trace": {"compiles": 1},
                  "telemetry": {"overhead": 0.03}},
        "prune": {"speedup": 2.0, "compiles": 2,
                  "steady": {"time_saving": 0.4}},
        "chaos": {"overhead": 0.08, "compiles": 1,
                  "chaos": {"reasons": {"checksum": 8, "nonfinite": 12}}},
    }
    same = {
        "schema": 3,
        "k_scaling": [{"K": 5, "speedup": 2.0},    # jitter: not gated
                      {"K": 500, "speedup": 5.0}],  # jitter: not gated
        "compile_counts": {"pow2": {"compiles": 1},
                           "exact": {"compiles": 7}},
        "fused": {"speedup": 3.5, "compile_trace": {"compiles": 1},
                  "telemetry": {"overhead": 0.10}},  # jitter: <= 25% passes
        "prune": {"speedup": 1.8, "compiles": 2,
                  "steady": {"time_saving": 0.1}},   # jitter: sign-gated
        "chaos": {"overhead": 0.15, "compiles": 1,   # jitter: <= 25% passes
                  "chaos": {"reasons": {"checksum": 3, "nonfinite": 5}}},
    }
    assert chk.compare(same, baseline) == []
    # schema handshake: a mismatched blob on EITHER side is refused
    # outright with a regenerate instruction, never field-compared
    old_fresh = {k: v for k, v in same.items() if k != "schema"}
    msgs = chk.compare(old_fresh, baseline)
    assert len(msgs) == 1 and "schema" in msgs[0] and "fresh" in msgs[0]
    old_base = {**baseline, "schema": 1}
    msgs = chk.compare(same, old_base)
    assert len(msgs) == 1 and "schema" in msgs[0] and "baseline" in msgs[0]
    retrace = {**same, "compile_counts": {"pow2": {"compiles": 3},
                                          "exact": {"compiles": 7}}}
    assert any("compile_counts" in m for m in chk.compare(retrace, baseline))
    fused_slow = {**same, "fused": {**same["fused"], "speedup": 2.0}}
    assert any("fused" in m for m in chk.compare(fused_slow, baseline))
    fused_retrace = {**same, "fused": {**same["fused"], "speedup": 4.0,
                                       "compile_trace": {"compiles": 2}}}
    assert any("compile trace" in m
               for m in chk.compare(fused_retrace, baseline))
    # flight-recorder cost: > 25% overhead fails, a dropped telemetry
    # section fails (schema >= 2 always records one)
    slow_telem = {**same, "fused": {**same["fused"],
                                    "telemetry": {"overhead": 0.40}}}
    assert any("telemetry overhead" in m
               for m in chk.compare(slow_telem, baseline))
    no_telem = {**same, "fused": {k: v for k, v in same["fused"].items()
                                  if k != "telemetry"}}
    assert any("telemetry" in m and "missing" in m
               for m in chk.compare(no_telem, baseline))
    missing = {k: v for k, v in same.items() if k != "fused"}
    assert any("missing" in m for m in chk.compare(missing, baseline))
    # the fused-SCBFwP section: ratio drop, compile growth, a negative
    # pruning time saving, and a silently-dropped section all fail
    prune_slow = {**same, "prune": {"speedup": 1.0, "compiles": 2,
                                    "steady": {"time_saving": 0.1}}}
    assert any("prune" in m and "speedup" in m
               for m in chk.compare(prune_slow, baseline))
    prune_retrace = {**same, "prune": {"speedup": 1.8, "compiles": 3,
                                       "steady": {"time_saving": 0.1}}}
    assert any("prune" in m and "compiles" in m
               for m in chk.compare(prune_retrace, baseline))
    prune_slower_than_unpruned = {
        **same, "prune": {"speedup": 1.8, "compiles": 2,
                          "steady": {"time_saving": -0.05}}}
    assert any("time saving" in m
               for m in chk.compare(prune_slower_than_unpruned, baseline))
    no_prune = {k: v for k, v in same.items() if k != "prune"}
    assert any("prune" in m and "missing" in m
               for m in chk.compare(no_prune, baseline))
    # the chaos section: fault-free resilience tax, armed compile
    # growth, an admission gate gone inert, and a dropped section all fail
    chaos_slow = {**same, "chaos": {**same["chaos"], "overhead": 0.40}}
    assert any("chaos" in m and "overhead" in m
               for m in chk.compare(chaos_slow, baseline))
    chaos_retrace = {**same, "chaos": {**same["chaos"], "compiles": 2}}
    assert any("chaos" in m and "compiles" in m
               for m in chk.compare(chaos_retrace, baseline))
    chaos_inert = {**same, "chaos": {**same["chaos"],
                                     "chaos": {"reasons": {"checksum": 3}}}}
    assert any("inert" in m and "nonfinite" in m
               for m in chk.compare(chaos_inert, baseline))
    no_chaos = {k: v for k, v in same.items() if k != "chaos"}
    assert any("chaos" in m and "missing" in m
               for m in chk.compare(no_chaos, baseline))
    # dropping a guarded section must fail, never vacuously pass
    no_counts = {k: v for k, v in same.items() if k != "compile_counts"}
    assert any("compile_counts" in m and "missing" in m
               for m in chk.compare(no_counts, baseline))
    no_k500 = {**same, "k_scaling": [{"K": 5, "speedup": 2.0}]}
    assert any("k_scaling" in m and "missing" in m
               for m in chk.compare(no_k500, baseline))
    # the committed baseline itself stays parseable and self-consistent
    import json
    import pathlib
    bl_path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
               / "baselines" / "fed_engine.json")
    committed = json.loads(bl_path.read_text())
    assert chk.compare(committed, committed) == []
    assert committed["schema"] == 3
    assert committed["fused"]["speedup"] >= 2.0   # the acceptance bar
    assert committed["fused"]["compile_trace"]["compiles"] <= 2
    # the flight recorder stays cheap (the <5% target lives in
    # docs/OBSERVABILITY.md; the committed number must meet the CI bound)
    assert committed["fused"]["telemetry"]["overhead"] <= 0.25
    assert committed["prune"]["compiles"] <= 2    # the PR 5 bar
    assert committed["prune"]["steady"]["time_saving"] > 0
    # the armed-but-idle fault model stays off the hot path, and the
    # committed storm exercises every admission-gate rejection reason
    assert committed["chaos"]["overhead"] <= 0.25
    assert committed["chaos"]["compiles"] <= 2
    assert set(committed["chaos"]["chaos"]["reasons"]) == {
        "malformed", "checksum", "duplicate", "nonfinite", "norm"}
