"""The paper's model family: a ReLU MLP with one logit over a synthetic
medical cohort (Shao et al. 2019, arXiv 1910.11160, section 2.2),
trained by ``repro.core.scbf.run_federated`` with ``mlp_features``.

Its configuration's keys: ``features`` (the widths, input first),
``cohort`` (``cohort.generate``'s parameters), ``clients``,
``sample_fraction``, ``local_batch_size``, ``local_epochs``,
``learning_rate``, ``upload_rate`` and ``selection``.  Its plain
reference is ``bench/reference.py``; its quality is the (AUC-ROC,
AUC-PR) of the model on the whole test split.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import List

import jax
import numpy as np

from bench import cohort, reference

QUALITY = "auc"
# the reference one precision below the configuration's float32, in the
# program's place
CONTROL = {"dtype": "bfloat16", "precision": "default"}
# no fault: the reference at the program's own matmul precision
WITNESS = {"precision": "default"}
# planted faults, each in the reference's rounds or in its evaluation:
# each step's mean over half its batch, the first upload counted twice,
# an evaluation of the first half of the test rows only, the initial
# model evaluated in place of the final one
FAULTS = {"half_batch": "rounds", "double": "rounds",
          "eval_half": "quality", "eval_stale": "quality"}
# Numbers the cells print but do not compare (on the chip no fault reads
# them far above a sound run): at ``shrink``'s size on the CPU a sound
# run reads under 1e-4 on each, so these limits let the tests see the
# check catch faults.
UNSET = {"auc_init_gap": 1e-3}


def check_config(config: dict) -> None:
    """Raises ``ValueError`` where the configuration is not one this
    family runs: the input width has to be the cohort's medicines."""
    if config["features"][0] != config["cohort"]["medicines"]:
        raise ValueError(f"input width {config['features'][0]} is not the "
                         f"{config['cohort']['medicines']} medicines")


def data(config: dict, seed: int):
    """The seed's cohort, as the program's ``MedicalCohort``."""
    from repro.data.medical import MedicalCohort
    return MedicalCohort(*cohort.generate(**config["cohort"], seed=seed))


def train_config(cell, seed: int):
    from repro.config import FedConfig, ScbfConfig, TrainConfig
    c, t = cell.config, cell.traffic
    prune = t.get("prune")
    scbf = ScbfConfig(
        upload_rate=c["upload_rate"], selection=c["selection"],
        num_clients=c["clients"], prune=prune is not None,
        prune_impl="mask" if prune else "reshape",
        prune_rate=prune["rate"] if prune else 0.1,
        prune_total=prune["total"] if prune else 0.47,
        prune_compact=prune["compact"] if prune else True)
    fed = FedConfig(fuse_rounds=t["fuse_rounds"], pods=cell.chips,
                    sample_fraction=c["sample_fraction"], partition="iid")
    return TrainConfig(learning_rate=c["learning_rate"],
                       global_loops=t["loops_per_job"],
                       eval_every=t["eval_every"],
                       local_epochs=c["local_epochs"],
                       local_batch_size=c["local_batch_size"],
                       seed=seed, scbf=scbf, fed=fed)


def job(cell, seed: int, data):
    """One federated training job of the cell on ``data``."""
    from repro.core.scbf import run_federated
    return run_federated(data, train_config(cell, seed),
                         method=cell.traffic["method"],
                         mlp_features=tuple(cell.config["features"]))


def leaves(pairs) -> List[np.ndarray]:
    """``[(w, b), ...]`` as float64 leaves in wire order: the order in
    which the program's layers, ``{"w", "b"}`` dicts, flatten."""
    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(
        [{"w": w, "b": b} for w, b in pairs])]


def pairs(flat) -> list:
    """Leaves in wire order as ``[(w, b), ...]``."""
    tree = jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(
        [{"w": 0, "b": 0}] * (len(flat) // 2)), list(flat))
    return [(layer["w"], layer["b"]) for layer in tree]


def init_leaves(config: dict, seed: int) -> List[np.ndarray]:
    """The seed's initial model, as the reference builds it."""
    return leaves(reference.init_params(config["features"], seed))


def final_leaves(result) -> List[np.ndarray]:
    """The job's final server parameters."""
    return [np.asarray(a, np.float64)
            for a in jax.tree_util.tree_leaves(result.final_params)]


def reference_rounds(cell, data, seed: int, rounds: int, **kw) -> dict:
    """The job's first ``rounds`` rounds as ``reference.job_rounds`` runs
    them: per round each participant's upload as leaves, and the round's
    wire bytes (``kw``: the arithmetic, ``dtype`` and ``precision``, or
    a planted ``fault``)."""
    c = cell.config
    out = reference.job_rounds(
        data.x_train, data.y_train, features=tuple(c["features"]),
        num_clients=c["clients"], fraction=c["sample_fraction"],
        lr=c["learning_rate"], batch=c["local_batch_size"],
        epochs=c["local_epochs"], upload_rate=c["upload_rate"],
        selection=c["selection"], seed=seed, rounds=rounds,
        prune=cell.traffic.get("prune"), x_val=data.x_val, **kw)
    return {"uploads": [[leaves(up) for up in ups]
                        for ups in out["uploads"]],
            "bytes": out["bytes"]}


def quality(cell, data, seed: int, final, fault=None, **kw) -> dict:
    """(AUC-ROC, AUC-PR) of the seed's initial model and of ``final``
    (leaves) by ``reference.evaluate`` (``kw``: its arithmetic).
    ``fault`` plants an evaluation fault: ``"eval_half"`` scores the
    first half of the test rows only, ``"eval_stale"`` scores the
    initial model in place of the final one."""
    init = reference.init_params(tuple(cell.config["features"]), seed)
    final = pairs(final)
    x, y = data.x_test, data.y_test
    if fault == "eval_half":
        x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
    if fault == "eval_stale":
        final = init
    return {"init": reference.evaluate(init, x, y, **kw),
            "final": reference.evaluate(final, x, y, **kw)}


def recorded_quality(result) -> dict:
    """The program's own evaluations of the initial and the final model:
    its first and last records."""
    first, last = result.records[0], result.records[-1]
    return {"init": (first.auc_roc, first.auc_pr),
            "final": (last.auc_roc, last.auc_pr)}


def example_flops(features) -> int:
    """Matrix-product operations of one SGD example through the MLP:
    the forward pass (2 a b for each a x b weight), the weight gradients
    (2 a b each) and the activation gradients of every layer but the
    first (2 a b each; nothing needs the gradient of the input).
    Biases, activations and the loss are left out."""
    dims = list(zip(features[:-1], features[1:]))
    mm = [2 * a * b for a, b in dims]
    return 2 * sum(mm) + sum(mm[1:])


def job_flops(config: dict, records) -> int:
    """Required training operations of the rounds in ``records``, for
    real clients in real rounds only (padded slots and rounds do not
    count).  Examples are the full batches of each local epoch; pruned
    rounds count the effective (kept) widths."""
    c = config
    n_train = int(c["cohort"]["split"][0] * c["cohort"]["admissions"])
    per_client = n_train // c["clients"]
    examples = (per_client // c["local_batch_size"]) \
        * c["local_batch_size"] * c["local_epochs"]
    total = 0
    for r in records:
        hidden = list(r.hidden_sizes) or list(c["features"][1:-1])
        feats = [c["features"][0]] + hidden + [c["features"][-1]]
        total += r.num_participants * examples * example_flops(feats)
    return total


def shrink(cell, **over):
    """``cell`` with the cohort, the widths and the jobs made small enough
    for a CPU test run; ``over`` replaces config keys."""
    cfg = copy.deepcopy(cell.config)
    cfg["cohort"].update(admissions=400, medicines=64, risk_medicines=15,
                         interactions=4)
    cfg.update(features=[64, 16, 8, 1], local_batch_size=16, local_epochs=1)
    cfg.update(over)
    traffic = dict(cell.traffic, loops_per_job=6, fuse_rounds=3)
    if traffic.get("prune"):
        # two pruning rounds, then compaction, at 24 hidden neurons
        traffic["prune"] = dict(traffic["prune"], rate=0.3)
    limits = dict(cell.limits)
    limits["limits"] = {**UNSET, **cell.limits.get("limits", {})}
    return dataclasses.replace(cell, config=cfg, traffic=traffic,
                               limits=limits)
