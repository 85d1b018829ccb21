"""Plain reference of the MLP family's SCBF job: its first rounds and its
evaluation.

Written from the paper's description (Shao et al. 2019, arXiv
1910.11160, section 2.1) and the repository's documented contracts, in
straightforward ``jax.numpy``; it imports nothing of the program.

One round, for each participating client k:

  1. local SGD on the client's shard, ``epochs`` passes of shuffled
     minibatches (the tail that does not fill a batch is dropped), mean
     binary cross-entropy of a ReLU MLP with one logit (the ReLU's
     derivative at 0 taken as 0);
  2. the delta G_k = W_after - W_before;
  3. channel selection: a channel is a path through one neuron of every
     layer, its score the sum over layers of the squared norm of the
     gradient entries feeding that neuron (incoming weights and bias);
     the threshold is the (1 - alpha) quantile of all channel scores
     (linear interpolation), and an entry of G_k is uploaded iff it
     lies on some channel scoring above the threshold;
  4. the server adds every upload: W <- W + sum_k masked G_k;
  5. with pruning (SCBFwP, section 2.1 "Pruning Process"): while the
     pruned share of the original hidden neurons is under theta_total,
     the server scores each kept hidden neuron by APoZ, the share of
     validation rows on which its activation is exactly zero, and
     removes theta of the remaining neurons, highest APoZ first (ties
     to the earlier layer and lower index), never emptying a layer and
     never passing theta_total.  A removed neuron's activation is
     multiplied by 0 from then on, its channels score -inf and are not
     counted in the quantile, and uploads are sent in the effective
     geometry of the kept neurons.

Randomness follows the program's documented key-stream contract so the
reference trains on the same batches: ``key = PRNGKey(seed)``; one
split gives the init key; each round splits ``(key, train, select,
dp)``, the training key splits once per client id, and each client's
key splits once per epoch into the permutation key of that epoch.
Clients are dealt an equal IID split of the training rows by
``default_rng(seed + 1)``, and each round samples
``round(fraction * K)`` clients without replacement from
``default_rng(seed)`` (the sync scheduler's draw, which also consumes
two uniform vectors of that length for dropout and stragglers).

Evaluation scores the test split with the model's logits and reports
the areas under the ROC curve (trapezoids between the points where the
score changes) and under the precision-recall curve (average precision:
each recall step times the precision there), as scikit-learn defines
them.

``dtype`` and ``precision`` select the arithmetic: float32 at
``HIGHEST`` is the reference; bfloat16 is the control, the reference
computed one precision below what the configuration states.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import wire

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "default": jax.lax.Precision.DEFAULT}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


# ---------------------------------------------------------------------------
# who trains on what
# ---------------------------------------------------------------------------

def client_rows(num_train: int, num_clients: int, seed: int):
    """Row indices of each client's shard (equal IID split)."""
    rng = np.random.default_rng(seed + 1)
    perm = rng.permutation(num_train)
    n = (num_train // num_clients) * num_clients
    return np.split(perm[:n], num_clients)


def participants(num_clients: int, fraction: float, seed: int,
                 rounds: int) -> List[np.ndarray]:
    """Sorted client ids of each of the first ``rounds`` rounds."""
    rng = np.random.default_rng(seed)
    m = min(max(1, int(round(fraction * num_clients))), num_clients)
    out = []
    for _ in range(rounds):
        out.append(np.sort(rng.choice(num_clients, size=m, replace=False)))
        rng.random(m)
        rng.random(m)
    return out


def init_params(features: Sequence[int], seed: int, dtype=jnp.float32):
    """He-normal weights and zero biases from the seed's init key."""
    _, init_key = jax.random.split(jax.random.PRNGKey(seed))
    keys = jax.random.split(init_key, len(features) - 1)
    out = []
    for k, fin, fout in zip(keys, features[:-1], features[1:]):
        w = jax.random.normal(k, (fin, fout), jnp.float32) \
            * jnp.sqrt(2.0 / fin)
        out.append((w.astype(dtype), jnp.zeros((fout,), dtype)))
    return out


# ---------------------------------------------------------------------------
# local training, selection, aggregation
# ---------------------------------------------------------------------------

def _hidden(params, x, precision, masks):
    """Post-ReLU activations of every hidden layer, and the logits.  The
    ReLU's derivative at 0 is 0, the common convention (``jnp.maximum``
    would pass half the gradient there: every hidden neuron of an
    admission with no medicine sits at 0 while the biases are 0)."""
    h, acts = x, []
    for i, (w, b) in enumerate(params):
        h = jnp.dot(h, w, precision=precision) + b
        if i < len(params) - 1:
            h = jnp.where(h > 0, h, 0)
            if masks is not None:
                h = h * masks[i]
            acts.append(h)
    return acts, h[:, 0]


def _loss(params, x, y, masks, precision, half):
    if half:
        x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
    _, z = _hidden(params, x, precision, masks)
    per = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return jnp.mean(per)


@partial(jax.jit, static_argnames=("batch", "epochs", "precision", "half"))
def local_train(params, x, y, key, lr, masks=None, *, batch: int,
                epochs: int, precision, half: bool = False):
    """``epochs`` passes of minibatch SGD; returns the trained params.

    ``half`` is a planted fault: each step's loss is the mean over the
    first half of its batch only.
    """
    n = x.shape[0]
    n_use = (n // batch) * batch
    grad = jax.grad(_loss)

    def epoch(p, k):
        perm = jax.random.permutation(k, n)[:n_use]
        xb = x[perm].reshape(-1, batch, x.shape[1])
        yb = y[perm].reshape(-1, batch)

        def step(p, b):
            g = grad(p, b[0], b[1], masks, precision, half)
            return [(w - lr * gw, c - lr * gc)
                    for (w, c), (gw, gc) in zip(p, g)], None

        p, _ = jax.lax.scan(step, p, (xb, yb))
        return p, None

    params, _ = jax.lax.scan(epoch, params, jax.random.split(key, epochs))
    return params


@partial(jax.jit, static_argnames=("upload_rate", "selection"))
def select(delta, masks=None, *, upload_rate: float, selection: str):
    """The delta restricted to entries on above-threshold channels;
    channels through a removed neuron (``masks``) are never selected
    and the quantile runs over the others."""
    scores = [jnp.sum(w.astype(jnp.float32) ** 2, axis=0)
              + b.astype(jnp.float32) ** 2 for w, b in delta]
    if masks is not None:
        scores = [jnp.where(masks[l] > 0, s, -jnp.inf)
                  if l < len(masks) else s for l, s in enumerate(scores)]
    L = len(scores)
    t = jnp.zeros((1,) * L, jnp.float32)
    for l, s in enumerate(scores):
        shape = [1] * L
        shape[l] = s.shape[0]
        t = t + s.reshape(shape)
    q = 1.0 - upload_rate if selection == "positive" else upload_rate
    vals = jnp.sort(t.reshape(-1))
    n = vals.shape[0]
    n_ok = jnp.sum(jnp.isfinite(vals).astype(jnp.int32))
    pos = (n - n_ok) + q * (n_ok - 1)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.ceil(pos).astype(jnp.int32)
    thr = vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)
    hot = t > thr

    def through(*layers):
        """Neuron (tuples) that some selected channel passes through."""
        axes = tuple(a for a in range(L) if a not in layers)
        return jnp.any(hot, axis=axes) if axes else hot

    out = []
    for l, (w, b) in enumerate(delta):
        if l == 0:
            on_b = through(0)
            on_w = jnp.broadcast_to(on_b[None, :], w.shape)
        else:
            on_w = through(l - 1, l)
            on_b = through(l)
        out.append((jnp.where(on_w, w, 0), jnp.where(on_b, b, 0)))
    return out


@partial(jax.jit, static_argnames=("precision",))
def _zero_share(params, x, masks, *, precision):
    acts, _ = _hidden(params, x, precision, masks)
    return [jnp.mean((a == 0).astype(jnp.float32), axis=0) for a in acts]


def apoz_prune(params, x_val, keep, *, rate: float, total: float,
               precision):
    """One pruning step: the new boolean keep-masks of the hidden
    layers (unchanged once ``total`` of them are gone)."""
    masks = [jnp.asarray(k, params[0][0].dtype) for k in keep]
    apoz = [np.asarray(a, np.float64) for a in jax.device_get(
        _zero_share(params, jnp.asarray(x_val, params[0][0].dtype), masks,
                    precision=precision))]
    keep = [k.copy() for k in keep]
    full = sum(k.size for k in keep)
    gone = full - sum(int(k.sum()) for k in keep)
    budget = max(0, min(int(rate * (full - gone)),
                        int(total * full) - gone))
    flat = np.concatenate([np.where(k, a, -np.inf)
                           for a, k in zip(apoz, keep)])
    owner = np.concatenate([np.full(k.size, l) for l, k in enumerate(keep)])
    start = np.cumsum([0] + [k.size for k in keep])
    removed = 0
    for i in np.argsort(-flat, kind="stable"):
        if removed >= budget or not np.isfinite(flat[i]):
            break
        l = owner[i]
        if keep[l].sum() > 1:
            keep[l][i - start[l]] = False
            removed += 1
    return keep


def effective(upload, keep):
    """An upload sliced to the kept neurons (input and output widths
    stay)."""
    if keep is None:
        return upload
    idx = [np.flatnonzero(k) for k in keep]
    out = []
    for l, (w, b) in enumerate(upload):
        if l > 0:
            w = w[idx[l - 1]]
        if l < len(upload) - 1:
            w, b = w[:, idx[l]], b[idx[l]]
        out.append((w, b))
    return out


def job_rounds(x_train, y_train, *, features, num_clients: int,
               fraction: float, lr: float, batch: int, epochs: int,
               upload_rate: float, selection: str, seed: int,
               rounds: int, dtype: str = "float32",
               precision: str = "highest", fault: str | None = None,
               prune: dict | None = None, x_val=None) -> dict:
    """The first ``rounds`` rounds of a job, as the reference runs them.

    Returns per round: ``uploads``, per participant (in increasing id)
    its upload as float64 host arrays ``[(w, b), ...]`` in the full
    geometry; ``bytes``, the round's wire bytes (uploads in the effective
    geometry, cheapest codec per leaf); with ``prune`` (``{"rate",
    "total"}``) ``keeps``, the boolean keep-masks after the round's
    pruning step; and ``params``, the server parameters after the last
    round (full geometry, float64).  ``fault`` plants one of the faults
    a training cell can have: ``"half_batch"`` (each step's mean over
    half its batch) or ``"double"`` (the first client's first upload
    counted twice).
    """
    dt = DTYPES[dtype]
    prec = PRECISIONS[precision]
    rows = client_rows(x_train.shape[0], num_clients, seed)
    parts = participants(num_clients, fraction, seed, rounds)
    params = init_params(features, seed, dt)
    keep = [np.ones(h, bool) for h in features[1:-1]] if prune else None
    key, _ = jax.random.split(jax.random.PRNGKey(seed))
    lr_d = jnp.asarray(lr, jnp.float32).astype(dt)
    out = {"uploads": [], "bytes": [], "keeps": []}
    for r in range(rounds):
        masks = None if keep is None else [jnp.asarray(k, dt) for k in keep]
        key, k_train, _, _ = jax.random.split(key, 4)
        ckeys = jax.random.split(k_train, num_clients)
        ups, sent = [], 0
        total = [(jnp.zeros_like(w), jnp.zeros_like(b)) for w, b in params]
        for i, k in enumerate(parts[r]):
            x = jnp.asarray(x_train[rows[k]], dt)
            y = jnp.asarray(y_train[rows[k]], dt)
            new = local_train(params, x, y, ckeys[k], lr_d, masks,
                              batch=batch, epochs=epochs, precision=prec,
                              half=fault == "half_batch")
            delta = [(w1 - w0, b1 - b0)
                     for (w1, b1), (w0, b0) in zip(new, params)]
            up = select(delta, masks, upload_rate=upload_rate,
                        selection=selection)
            if fault == "double" and r == 0 and i == 0:
                up = [(2 * w, 2 * b) for w, b in up]
            total = [(tw + w, tb + b) for (tw, tb), (w, b) in zip(total, up)]
            host = [(np.asarray(w, np.float64), np.asarray(b, np.float64))
                    for w, b in jax.device_get(up)]
            ups.append(host)
            sent += wire.upload_bytes(
                [a for pair in effective(host, keep) for a in pair])
        params = [(w + tw, b + tb) for (w, b), (tw, tb) in zip(params, total)]
        out["uploads"].append(ups)
        out["bytes"].append(sent)
        if keep is not None:
            keep = apoz_prune(params, x_val, keep, rate=prune["rate"],
                              total=prune["total"], precision=prec)
            out["keeps"].append([k.copy() for k in keep])
    out["params"] = [(np.asarray(w, np.float64), np.asarray(b, np.float64))
                     for w, b in jax.device_get(params)]
    return out


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("precision",))
def _logits(params, x, *, precision):
    return _hidden(params, x, precision, None)[1]


def logits(params, x, *, dtype: str = "float32", precision: str = "highest",
           block: int = 4096) -> np.ndarray:
    """The model's logit for every row of ``x``, in blocks of rows."""
    dt = DTYPES[dtype]
    params = [(jnp.asarray(w, dt), jnp.asarray(b, dt)) for w, b in params]
    return np.concatenate([
        np.asarray(_logits(params, jnp.asarray(x[s:s + block], dt),
                           precision=PRECISIONS[precision]), np.float64)
        for s in range(0, x.shape[0], block)])


def aucs(scores: np.ndarray, labels: np.ndarray):
    """(area under the ROC curve, average precision) of ``scores``."""
    order = np.argsort(-scores, kind="stable")
    s, y = scores[order], labels[order].astype(np.float64)
    end = np.append(s[1:] != s[:-1], True)
    tp = np.concatenate([[0.0], np.cumsum(y)[end]])
    fp = np.concatenate([[0.0], np.cumsum(1.0 - y)[end]])
    tpr, fpr = tp / max(tp[-1], 1.0), fp / max(fp[-1], 1.0)
    roc = float(np.sum(np.diff(fpr) * (tpr[1:] + tpr[:-1]) / 2))
    ap = float(np.sum(np.diff(tpr) * tp[1:] / (tp[1:] + fp[1:])))
    return roc, ap


def evaluate(params, x, y, **kw):
    """(AUC-ROC, AUC-PR) of the model on ``(x, y)``."""
    return aucs(logits(params, x, **kw), np.asarray(y))
