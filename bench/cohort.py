"""The benchmark's traffic generator: a synthetic medical cohort.

Written after the program's cohort generator and kept here so that a
change to the program cannot change the benchmark's inputs; it departs
from it in one place, the medicines' popularities (below).  The shape
is the paper's (Shao et al. 2019, arXiv 1910.11160, section 2.2):
admissions x distinct medicines, binary "took medicine m" features,
binary mortality labels, split 60/10/30 into train, validation and
test.

Medicine popularity follows a power law (mean ~7 medicines per
admission): every seed draws the same set of popularities, the quantiles
of a Pareto(1.2) law, in its own order, so that every seed's cohort asks
the same work of training.  Mortality comes from a planted sparse
logistic model with pairwise interactions and label noise, so a small
MLP reaches the paper's AUC-ROC regime.  Every array is a pure function
of the seed and the configuration's ``cohort`` parameters.
"""
from __future__ import annotations

import numpy as np


def generate(admissions: int, medicines: int, risk_medicines: int,
             interactions: int, mean_meds: float, label_noise: float,
             signal_scale: float, split, seed: int):
    """(x_train, y_train, x_val, y_val, x_test, y_test), float32."""
    rng = np.random.default_rng(seed)

    # every seed has the same popularities, the Pareto(1.2) quantiles,
    # in an order of its own: a cohort drawn at random from that law may
    # hold one medicine an admission in place of ``mean_meds`` and a
    # fifth of admissions with none, work of another kind
    u = (np.arange(medicines) + 0.5) / medicines
    pop = (1.0 - u) ** (-1.0 / 1.2) - 1.0 + 1e-3
    pop = rng.permutation(pop) / pop.sum() * mean_meds
    pop = np.clip(pop, 0.0, 0.6)

    x = (rng.random((admissions, medicines)) < pop[None, :])
    x = x.astype(np.float32)

    risk_medicines = min(risk_medicines, medicines // 2)
    risk_p = pop / pop.sum()
    risk_idx = rng.choice(medicines, size=risk_medicines, replace=False,
                          p=risk_p)
    w = np.zeros(medicines, dtype=np.float32)
    w[risk_idx] = rng.normal(0.0, 2.5, size=risk_medicines)

    logits = x @ w
    for _ in range(interactions):
        i, j = rng.choice(risk_idx, size=2, replace=False)
        coef = rng.normal(0.0, 3.0)
        logits += coef * x[:, i] * x[:, j]
    logits += rng.normal(0.0, 0.3, size=admissions)
    logits -= np.median(logits)
    logits *= signal_scale
    p = 1.0 / (1.0 + np.exp(-logits))
    y = (rng.random(admissions) < p).astype(np.float32)
    flip = rng.random(admissions) < label_noise
    y = np.where(flip, 1.0 - y, y)

    perm = rng.permutation(admissions)
    n_train = int(split[0] * admissions)
    n_val = int(split[1] * admissions)
    tr, va, te = np.split(perm, [n_train, n_train + n_val])
    return x[tr], y[tr], x[va], y[va], x[te], y[te]
