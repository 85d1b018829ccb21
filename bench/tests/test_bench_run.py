"""The harness end to end on the CPU at a tiny size: it refuses to run
without a TPU, its check passes on the program as it is, and comes out
false for the control and for each fault a one-chip training cell can
have, planted under the timed path.  Mask-mode SCBFwP, which no cell
runs yet, agrees with the reference too."""
import dataclasses
import io
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import numpy as np

from bench import check, cohort, harness, reference, spec
from bench.tests import tiny

SEED = 2147483659
CELLS = [w["name"] for w in spec.benchmark()["workloads"]
         if w["chips"] == 1]


def _results(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            out.append(obj)
    return out


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    r = _command(spec.ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not _results(r.stdout)


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    r = _command(tmp_path)
    assert r.returncode != 0
    assert not _results(r.stdout)


def _run(name):
    return harness.run(name, SEED, 0.0, False, time.perf_counter(),
                       require_chip=False, cell=tiny.cell(name),
                       log=io.StringIO())


@pytest.mark.parametrize("name", CELLS)
def test_program_agrees_with_reference(name):
    out = _run(name)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(tiny.cell(name).limits["limits"]) <= set(out["checks"])
    for k, v in out["checks"].items():
        assert v["value"] <= (0.0 if k == "wire_faults" else 1e-4), k
    assert out["metrics"]["rounds_per_s"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny.cell(name)
    job = harness.Job(cell, SEED)
    res = job()
    ref = harness.reference_side(cell, job, SEED, res.final_params)
    ctl = check.numbers(harness.reference_side(
        cell, job, SEED, res.final_params, dtype="bfloat16",
        precision="default"), ref, int(cell.limits["rounds"]))
    limits = cell.limits["limits"]
    assert any(v > limits.get(k, float("inf"))
               for k, v in ctl.items()), ctl


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _stuck(monkeypatch):
    """A fused chunk that returns the server state unchanged."""
    from repro.fed.engine import BatchedEngine
    orig = BatchedEngine.fused_scbf_chunk

    def chunk(self, params, plan, cfg, nmasks=None, collect=False):
        out = orig(self, params, plan, cfg, nmasks=nmasks, collect=collect)
        return (tuple(params),) + tuple(out[1:])

    monkeypatch.setattr(BatchedEngine, "fused_scbf_chunk", chunk)


def _half_batch(monkeypatch):
    """Local steps that take the mean over half of each batch."""
    from repro.core import client
    orig = client.bce_loss

    def loss(params, xb, yb, neuron_masks=None):
        h = xb.shape[0] // 2
        return orig(params, xb[:h], yb[:h], neuron_masks)

    monkeypatch.setattr(client, "bce_loss", loss)


def _altered(monkeypatch):
    """Uploads altered where they are encoded: one value moved."""
    from repro.comm import wire
    orig = wire.encode

    def encode(tree, codec="auto"):
        p = orig(tree, codec)
        layers = list(p.layers)
        i = next(i for i, lp in enumerate(layers) if lp.nnz)
        values = layers[i].values.copy()
        values[0] += 1.0
        layers[i] = dataclasses.replace(layers[i], values=values)
        return dataclasses.replace(p, layers=tuple(layers))

    monkeypatch.setattr(wire, "encode", encode)


def _eval_half(monkeypatch):
    """Evaluations that score the first half of the test rows only."""
    from repro.core import scbf
    orig = scbf._evaluate

    def evaluate(params, x, y, *args, **kwargs):
        return orig(params, x[:x.shape[0] // 2], y[:y.shape[0] // 2],
                    *args, **kwargs)

    monkeypatch.setattr(scbf, "_evaluate", evaluate)


@pytest.mark.parametrize("fault", [_stuck, _half_batch, _altered,
                                   _eval_half],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch, fresh_programs):
    fault(monkeypatch)
    assert _run(name)["correct"] is False


def test_calibrate_reads_program_control_and_faults():
    from bench import calibrate
    cell = tiny.cell(CELLS[0])
    row, = calibrate.calibrate(cell.name, [SEED], require_chip=False,
                               cell=cell)
    limits = cell.limits["limits"]
    assert check.verdict(row["program"], limits)
    for side in ("control", "half_batch", "double", "eval_half",
                 "eval_stale"):
        assert any(v > limits.get(k, float("inf"))
                   for k, v in row[side].items()), side
    assert set(row["dropped"]) == {"update1_gap", "change3_gap"}


def test_masked_pruning_agrees_with_reference():
    """Mask-mode SCBFwP (APoZ pruning, then one compaction), run fused
    through ``run_federated``: every round's kept widths, upload bytes and
    the compacted final parameters agree with the reference's."""
    cell = tiny.shrink(spec.Cell(
        "silo5-scbfwp", 1, spec.config("mlp-medical-silo5"),
        spec.traffic("scbfwp-mask-fused10-job30"), {}))
    job = harness.Job(cell, SEED)
    res = job()
    c = cell.config
    ref = reference.job_rounds(
        job.cohort.x_train, job.cohort.y_train, features=job.features,
        num_clients=c["clients"], fraction=c["sample_fraction"],
        lr=c["learning_rate"], batch=c["local_batch_size"],
        epochs=c["local_epochs"], upload_rate=c["upload_rate"],
        selection=c["selection"], seed=SEED, rounds=len(res.records),
        prune=cell.traffic["prune"], x_val=job.cohort.x_val)
    widths = [tuple(int(k.sum()) for k in keep) for keep in ref["keeps"]]
    assert [tuple(r.hidden_sizes) for r in res.records] == widths
    assert widths[0] != widths[-1] != tuple(c["features"][1:-1])
    assert [r.sparse_bytes for r in res.records] == ref["bytes"]
    final = [np.asarray(a, np.float64) for layer in res.final_params
             for a in (layer["w"], layer["b"])]
    want = reference.effective(ref["params"], ref["keeps"][-1])
    assert [a.shape for a in final] == [a.shape for a in check._flat(want)]
    assert check.gap_of_difference(final, check._flat(want)) < 1e-4


@pytest.mark.parametrize("scores,labels,roc,ap", [
    ([3, 2, 1, 0], [1, 1, 0, 0], 1.0, 1.0),
    ([1, 1, 1, 1], [1, 0, 1, 0], 0.5, 0.5),
    # positives above 2 of 2 and 1 of 2 negatives; precision 1 at recall
    # 1/2, then 2/3 at recall 1
    ([0.9, 0.8, 0.7, 0.6], [1, 0, 1, 0], 0.75, 0.5 + 0.5 * 2 / 3),
    # a positive tied with a negative counts half a pair
    ([2, 1, 1, 0], [1, 1, 0, 0], 0.875, 0.5 + 0.5 * 2 / 3),
])
def test_reference_aucs_by_hand(scores, labels, roc, ap):
    got = reference.aucs(np.asarray(scores, np.float64),
                         np.asarray(labels, np.float64))
    assert got == pytest.approx((roc, ap))


def test_reference_relu_passes_no_gradient_at_zero():
    """An input row of zeros meets every hidden neuron at 0 while the
    biases are 0: the reference's step then moves no first-layer bias
    (the ReLU's derivative at 0 is 0, as the program's is)."""
    params = [(jnp.ones((3, 4)), jnp.zeros(4)), (jnp.ones((4, 1)),
                                                 jnp.zeros(1))]
    x, y = jnp.zeros((2, 3)), jnp.array([1.0, 0.0])
    grads = jax.grad(reference._loss)(params, x, y, None,
                                      jax.lax.Precision.HIGHEST, False)
    assert float(jnp.max(jnp.abs(grads[0][1]))) == 0.0
    assert float(jnp.max(jnp.abs(grads[1][1]))) > 0.0


def test_every_seed_draws_the_same_popularities():
    """The cohort's medicine popularities are one set in another order
    for every seed, so every seed's cohort holds about ``mean_meds``
    medicines an admission."""
    cfg = dict(spec.config("mlp-medical-silo5")["cohort"],
               admissions=2000)
    means = []
    for seed in (1675539252, 19, 2147483659):
        x = np.concatenate(cohort.generate(**cfg, seed=seed)[::2])
        means.append(float(x.sum(axis=1).mean()))
    assert max(means) - min(means) < 0.3
    assert all(abs(m - cfg["mean_meds"]) < 0.5 for m in means)
