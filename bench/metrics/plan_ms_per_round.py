"""plan_ms_per_round: the program's ``plan`` span (core/scbf ``_run_fused``:
round planning, key derivation and ``prepare_fused_plan``'s copy of the
chunk's plan to the device) per round."""
LAYER = "planning, host→device"
UNIT = "ms/round"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "rounds_per_s"


def read(ctx):
    s = ctx["span_s"].get("plan")
    if not s or not ctx["rounds"]:
        return None
    return 1000.0 * s / ctx["rounds"]
