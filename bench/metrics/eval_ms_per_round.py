"""eval_ms_per_round: the program's ``eval`` span (core/scbf
``_evaluate``: the test-set forward pass and its quality numbers) per
round."""
LAYER = "evaluation"
UNIT = "ms/round"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "rounds_per_s"


def read(ctx):
    s = ctx["span_s"].get("eval")
    if not s or not ctx["rounds"]:
        return None
    return 1000.0 * s / ctx["rounds"]
