"""train_mfu: the local training's required operations over what the
chips' bf16 peak could do in the traced job.

Required operations are counted by the cell's family from the
configuration and the job's per-round records (``job_flops``): the
matrix products of local training, for real clients in real rounds only
(padded slots and rounds do not count), with no recomputation.
"""
LAYER = "device"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def read(ctx):
    if not ctx["planes"] or ctx["window_s"] <= 0:
        return None
    cell = ctx["cell"]
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * cell.family.job_flops(cell.config, ctx["records"]) \
        / (ctx["window_s"] * peak)
