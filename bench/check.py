"""The numbers that decide ``correct``, each compared with its limit.

The program's side is what the timed job produced: every payload it
encoded (read where the program encodes it, ``comm.wire.encode``, the
documented boundary every upload passes) read back by the reference
decoder, its per-round records (participant counts, upload bytes, the
AUCs of its evaluations) and its final server parameters.  The
reference side is ``reference.job_rounds`` and ``reference.evaluate``
(or, for the control and the planted faults, those computed otherwise
and put in the program's place).

Training numbers follow the first ``R`` rounds (``limits["rounds"]``)
and are gaps of norms taken by the worst leaf: for each parameter leaf
``|norm(program) - norm(reference)| / max(norm(reference), median leaf
norm)``, leaving out leaves whose reference norm is under a thousandth
of the median leaf's (nought to rounding).

  update1_gap     the server update of round 1 (the sum of its uploads)
  client1_gap     each client's round-1 upload, the worst client
  change3_gap     the parameters' change over rounds 1..R
  bytes3_gap      upload bytes over rounds 1..R, relative gap

Evaluation numbers: the largest gap of AUC-ROC or AUC-PR between the
program's records and a plain evaluation on the whole test split.

  auc_init_gap    the first record (the initial model, which the
                  reference rebuilds from the seed)
  auc_final_gap   the last record (the final server parameters, which
                  ``aggregate_gap`` ties to the checked uploads)

Checks of the program against the reference applied to its own uploads:

  aggregate_gap   final server parameters minus the initial ones, against
                  the sum of every upload of the job read back from the
                  wire: worst leaf norm of the difference over the
                  reference's leaf norm (or the median leaf's)
  wire_faults     payload leaves that break the wire format, rounds whose
                  recorded bytes differ from the bytes of their payloads,
                  payloads not accounted to a round, and rounds 1..R whose
                  number of uploads differs from the reference's
"""
from __future__ import annotations

from dataclasses import dataclass, field
from statistics import median
from typing import Dict, List, Optional

import numpy as np

from bench import reference

RULE = 1e-3          # leaves under this share of the median leaf norm


def _flat(upload) -> List[np.ndarray]:
    return [a for pair in upload for a in pair]


def _sum(uploads) -> Optional[List[np.ndarray]]:
    """The leafwise sum of the uploads; None for no upload."""
    out = None
    for up in uploads:
        leaves = _flat(up)
        out = leaves if out is None else [a + b for a, b in zip(out, leaves)]
    return out


def gap_of_norms(prog: List[np.ndarray], ref: List[np.ndarray]) -> float:
    if prog is None or ref is None:
        return float("inf")
    n_p = [float(np.linalg.norm(a)) for a in prog]
    n_r = [float(np.linalg.norm(a)) for a in ref]
    med = median(n_r)
    return max(abs(p - r) / max(r, med)
               for p, r in zip(n_p, n_r) if r >= RULE * med)


def gap_of_difference(prog: List[np.ndarray], ref: List[np.ndarray]
                      ) -> float:
    if prog is None or ref is None:
        return float("inf")
    n_r = [float(np.linalg.norm(a)) for a in ref]
    med = median(n_r)
    return max(float(np.linalg.norm(p - r)) / max(nr, med)
               for p, r, nr in zip(prog, ref, n_r) if nr >= RULE * med)


@dataclass
class Side:
    """One side of the comparison: per round its uploads (``[(w, b),
    ...]`` float64 per client) and its upload bytes, and ``auc``, the
    (AUC-ROC, AUC-PR) of the initial and of the final model.  The
    program's side also holds its wire faults, its parameters' change
    over the job and the sum of all its uploads."""

    uploads: List[list]
    bytes: List[int]
    auc: Dict[str, tuple] = field(default_factory=dict)
    wire_faults: int = 0
    final_change: Optional[List[np.ndarray]] = None
    final_sum: Optional[List[np.ndarray]] = None


def program_side(result, payloads, init, rounds: int) -> Side:
    """The timed job's outputs.  ``payloads`` holds every payload the job
    encoded, in order: round by round, each round's participants in
    turn, as many as its record counts; ``init`` is the reference's
    initial parameters (the same seed).  Uploads are kept one by one for
    the first ``rounds`` rounds, and summed for all."""
    recorded = [int(r.sparse_bytes) for r in result.records]
    counts = [int(r.num_participants) for r in result.records]
    faults = int(sum(counts) != len(payloads))
    uploads, every, at = [], None, 0
    for r, (rec, n) in enumerate(zip(recorded, counts)):
        ups, sent = [], 0
        for p in payloads[at:at + n]:
            up, bad = reference.decode(p)
            sent += reference.upload_bytes(up)
            faults += bad
            leaves = _flat(up)
            every = leaves if every is None else [
                a + b for a, b in zip(every, leaves)]
            if r < rounds:
                ups.append(up)
        at += n
        faults += int(sent != rec)
        uploads.append(ups)
    start = [np.asarray(a, np.float64) for w, b in init for a in (w, b)]
    final = [np.asarray(a, np.float64)
             for layer in result.final_params
             for a in (layer["w"], layer["b"])]
    first, last = result.records[0], result.records[-1]
    auc = {"init": (first.auc_roc, first.auc_pr),
           "final": (last.auc_roc, last.auc_pr)}
    return Side(uploads, recorded, auc, faults,
                [f - s for f, s in zip(final, start)], every)


def numbers(prog: Side, ref: Side, rounds: int) -> Dict[str, float]:
    """Every compared number of ``prog`` against ``ref``."""
    out = {
        "update1_gap": gap_of_norms(_sum(prog.uploads[0]),
                                    _sum(ref.uploads[0])),
        "client1_gap": max((gap_of_norms(_flat(p), _flat(r)) for p, r
                            in zip(prog.uploads[0], ref.uploads[0])),
                           default=float("inf")),
        "change3_gap": gap_of_norms(
            _sum([u for r in range(rounds) for u in prog.uploads[r]]),
            _sum([u for r in range(rounds) for u in ref.uploads[r]])),
        "bytes3_gap": abs(sum(prog.bytes[:rounds]) - sum(ref.bytes[:rounds]))
        / max(sum(ref.bytes[:rounds]), 1),
    }
    for when in ("init", "final"):
        out[f"auc_{when}_gap"] = max(abs(float(p) - float(r)) for p, r
                                     in zip(prog.auc[when], ref.auc[when]))
    if prog.final_change is not None:
        out["aggregate_gap"] = gap_of_difference(prog.final_change,
                                                 prog.final_sum)
        uneven = sum(len(prog.uploads[r]) != len(ref.uploads[r])
                     for r in range(rounds))
        out["wire_faults"] = float(prog.wire_faults + uneven)
    return out


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True iff every number is present, finite and within its limit."""
    return all(name in values and np.isfinite(values[name])
               and values[name] <= lim for name, lim in limits.items())
