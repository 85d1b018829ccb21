"""job_setup_ms_per_round: the program's ``job_setup`` span (core/scbf
``run_federated``: model initialisation, partition, engine build with
the training data's copy to the device, scheduler, strategy and lr
table) per round."""
LAYER = "planning, host→device"
UNIT = "ms/round"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "rounds_per_s"


def read(ctx):
    s = ctx["span_s"].get("job_setup")
    if not s or not ctx["rounds"]:
        return None
    return 1000.0 * s / ctx["rounds"]
