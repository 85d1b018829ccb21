"""emit_ms_per_round: the program's ``encode`` span (fed/engine
``emit_fused_payloads``: the wait for the chunk, the device-to-host pull
of its deltas and the NumPy wire encoding) per round."""
LAYER = "wire emission"
UNIT = "ms/round"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "rounds_per_s"


def read(ctx):
    s = ctx["span_s"].get("encode")
    if not s or not ctx["rounds"]:
        return None
    return 1000.0 * s / ctx["rounds"]
