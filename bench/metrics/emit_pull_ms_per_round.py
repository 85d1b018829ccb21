"""emit_pull_ms_per_round: the program's ``emit_pull`` span (fed/engine
``_pull_and_encode``: the device-to-host copy of the chunk's masked
deltas and masks) per round."""
LAYER = "wire emission"
UNIT = "ms/round"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "rounds_per_s"


def read(ctx):
    s = ctx["span_s"].get("emit_pull")
    if not s or not ctx["rounds"]:
        return None
    return 1000.0 * s / ctx["rounds"]
