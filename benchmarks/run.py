"""Benchmark runner — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run          # quick (CI-sized)
    PYTHONPATH=src python -m benchmarks.run --full   # paper-scale

Prints ``name,us_per_call,derived`` CSV sections plus the paper-claim
comparisons.  The roofline section reads pre-computed dry-run records if
``experiments/dryrun`` exists (the dry-run itself needs 512 virtual
devices and runs as its own process: ``python -m repro.launch.dryrun``).
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args, _ = ap.parse_known_args()

    print("# === fig2: SCBF vs FedAvg (AUC, ±pruning) ===", flush=True)
    from benchmarks.fig2_scbf_vs_fa import run as fig2
    t0 = time.time()
    results, summary = fig2(quick=not args.full,
                            out="experiments/fig2_summary.json")
    for m, s in summary.items():
        print(f"{m},{1e6*(time.time()-t0)/max(len(s['curve_auc_roc']),1):.0f},"
              f"best_roc={s['best_auc_roc']:.4f};best_pr={s['best_auc_pr']:.4f};"
              f"upload_mb={s['total_upload_mb']:.1f}")

    print("# === paper-claim checks ===")
    scbf, fa = summary.get("scbf"), summary.get("fedavg")
    if scbf and fa:
        print(f"claim_scbf_beats_fa,0,"
              f"scbf_roc={scbf['best_auc_roc']:.4f};"
              f"fa_roc={fa['best_auc_roc']:.4f};"
              f"holds={scbf['best_auc_roc'] > fa['best_auc_roc']}")
    wp = summary.get("scbfwp")
    if scbf and wp:
        droc = scbf["best_auc_roc"] - wp["best_auc_roc"]
        print(f"claim_pruning_cheap,0,d_auc_roc={droc:.4f};"
              f"paper_reports=0.0047")
        tsave = 1 - wp["total_time_s"] / max(scbf["total_time_s"], 1e-9)
        print(f"claim_pruning_saves_time,0,wall_saving={tsave:.2%};"
              f"paper_reports=57%")
    if wp and fa:
        csave = 1 - wp["total_upload_mb"] / max(fa["total_upload_mb"], 1e-9)
        print(f"claim_scbfwp_saves_comm,0,saving={csave:.2%};"
              f"paper_reports=85%")

    print("# === communication table ===")
    from benchmarks.table_communication import run as comm
    for name, rate, frac, enc, dense, _codecs in comm(quick=not args.full):
        print(f"{name}_a{rate},0,param_fraction={frac:.4f};"
              f"encoded_bytes={enc};dense_bytes={dense}")

    print("# === kernel ubenches ===")
    sys.argv = ["bench_kernels"]
    from benchmarks.bench_kernels import main as bk
    bk()

    print("# === roofline (from dry-run records, if present) ===")
    if os.path.isdir("experiments/dryrun"):
        from benchmarks.roofline_report import load
        recs = load("experiments/dryrun")
        ok = sum(1 for r in recs if r["ok"])
        print(f"dryrun_records,0,ok={ok}/{len(recs)}")
        for r in recs:
            if r["ok"]:
                t = r["terms"]
                print(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']},0,"
                      f"dom={t['dominant']};compute={t['compute_s']:.4f};"
                      f"mem={t['memory_s']:.4f};coll={t['collective_s']:.4f}")
    else:
        print("dryrun_records,0,missing (run python -m repro.launch.dryrun --all)")


if __name__ == "__main__":
    main()
