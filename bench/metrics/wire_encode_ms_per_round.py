"""wire_encode_ms_per_round: the program's ``wire_encode`` span (fed/engine
``_pull_and_encode``: the NumPy wire encoding of every real slot) per
round."""
LAYER = "wire emission"
UNIT = "ms/round"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "rounds_per_s"


def read(ctx):
    s = ctx["span_s"].get("wire_encode")
    if not s or not ctx["rounds"]:
        return None
    return 1000.0 * s / ctx["rounds"]
