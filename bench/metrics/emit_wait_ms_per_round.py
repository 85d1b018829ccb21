"""emit_wait_ms_per_round: the program's ``emit_wait`` span (fed/engine
``_pull_and_encode``: the host blocked on the fused chunk's device work
before its deltas are pulled) per round."""
LAYER = "fused chunk program"
UNIT = "ms/round"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "rounds_per_s"


def read(ctx):
    s = ctx["span_s"].get("emit_wait")
    if not s or not ctx["rounds"]:
        return None
    return 1000.0 * s / ctx["rounds"]
