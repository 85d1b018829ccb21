"""fused_device_ms_per_round: device time of the fused chunk program
(fed/engine ``_fused_scbf_rounds``: local training, selection and the
on-device aggregation of S rounds) per round of the traced job."""
from bench import tracereduce

LAYER = "fused chunk program"
UNIT = "ms/round"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "rounds_per_s"
PROGRAM = "_fused_scbf_rounds"


def read(ctx):
    times = [tracereduce.op_time(p, ctx["window"], PROGRAM)
             for p in ctx["planes"]]
    if not times or max(times) == 0.0 or not ctx["rounds"]:
        return None
    return sum(times) / len(times) / 1e6 / ctx["rounds"]
