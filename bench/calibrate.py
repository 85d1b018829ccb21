"""Readings that the limits of a cell's check are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--out f]

For each seed, in one process: one job of the program (the timed path,
warm after the first seed's compile), and the cell's compared numbers
for

  program      the program against the float32 reference at HIGHEST
  control      the reference computed in bfloat16, in the program's place
  witness      the reference at the program's own matmul precision
               (float32, default), in the program's place: no fault, a
               second witness of what rounding alone moves
  half_batch   the reference with each step's mean over half its batch
  double       the reference with its first upload counted twice
  eval_half    the evaluation of the first half of the test rows only
  eval_stale   the initial model evaluated in place of the final one

and, under ``dropped``, the leaves (``w, b`` of each layer in turn) that
the rule on the reference's norms leaves out of ``update1_gap`` and
``change3_gap``.  One JSON line per seed goes to stdout (and to
``--out``).  The lower reading of a number is the largest the program
gives over the seeds; its upper reading the smallest the control or a
fault gives (PERF.md, section 2).  The benchmark's own runs do not run
this.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from statistics import median

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def dropped(leaves) -> list:
    """Indices of the leaves under ``check.RULE`` of the median norm."""
    from bench import check
    norms = [float(np.linalg.norm(a)) for a in leaves]
    return [i for i, n in enumerate(norms)
            if n < check.RULE * median(norms)]


def calibrate(cell_name: str, seeds, out=None, require_chip=True,
              cell=None):
    from bench import check, harness, reference, spec
    cell = cell or spec.cell(cell_name)
    if require_chip:
        harness.device_check(cell.chips)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        job = harness.Job(cell, seed)
        job.capture.install()
        try:
            res = job()
        finally:
            job.capture.uninstall()
        t_job = time.perf_counter() - t0
        rounds = int(cell.limits["rounds"])
        t1 = time.perf_counter()
        ref = harness.reference_side(cell, job, seed, res.final_params)
        init = reference.init_params(job.features, seed)
        prog = check.program_side(res, job.capture.payloads, init, rounds)
        row = {"seed": seed, "cell": cell.name,
               "program": check.numbers(prog, ref, rounds)}
        t_check = time.perf_counter() - t1
        planted = {"control": {"dtype": "bfloat16", "precision": "default"},
                   "witness": {"precision": "default"},
                   "half_batch": {"fault": "half_batch"},
                   "double": {"fault": "double"}}
        for name, kw in planted.items():
            row[name] = check.numbers(harness.reference_side(
                cell, job, seed, res.final_params, **kw), ref, rounds)
        for name in ("eval_half", "eval_stale"):
            row[name] = check.numbers(dataclasses.replace(
                ref, auc=harness.evaluation(job, seed, res.final_params,
                                            fault=name)), ref, rounds)
        row["dropped"] = {
            "update1_gap": dropped(check._sum(ref.uploads[0])),
            "change3_gap": dropped(check._sum(
                [u for r in range(rounds) for u in ref.uploads[r]]))}
        row["seconds"] = {"job_with_compile": t_job, "check": t_check}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            with open(out, "a") as fh:
                fh.write(line + "\n")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import harness
    try:
        calibrate(args.workload, [int(s) for s in args.seeds.split(",")],
                  args.out)
    except harness.ChipMissing as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
