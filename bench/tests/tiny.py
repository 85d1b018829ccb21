"""Cells of the benchmark cut to a size a CPU test run holds: the same
files, with the cohort, the widths and the jobs made small."""
import copy

from bench import spec

# Numbers the cells print but do not compare (on the chip no fault reads
# them far above a sound run): at the tiny size on the CPU a sound run
# reads under 1e-4 on each, so these limits let the tests see the check
# catch faults.
UNSET = {"auc_init_gap": 1e-3}


def cell(name: str, **over) -> spec.Cell:
    """The named cell of BENCHMARK.json at a tiny size; ``over``
    replaces config keys."""
    return shrink(spec.cell(name), **over)


def shrink(c: spec.Cell, **over) -> spec.Cell:
    """``c`` at a tiny size; ``over`` replaces config keys."""
    cfg = copy.deepcopy(c.config)
    cfg["cohort"].update(admissions=400, medicines=64, risk_medicines=15,
                         interactions=4)
    cfg.update(features=[64, 16, 8, 1], local_batch_size=16, local_epochs=1)
    cfg.update(over)
    traffic = dict(c.traffic, loops_per_job=6, fuse_rounds=3)
    if traffic.get("prune"):
        # two pruning rounds, then compaction, at 24 hidden neurons
        traffic["prune"] = dict(traffic["prune"], rate=0.3)
    limits = dict(c.limits)
    limits["limits"] = {**UNSET, **c.limits.get("limits", {})}
    return spec.Cell(c.name, c.chips, cfg, traffic, limits)
