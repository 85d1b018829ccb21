"""rounds_per_s: federated rounds completed in the window over the
window's whole wall time, job set-up, evaluations and the last job's
overrun included."""
UNIT = "rounds/s"
BETTER = "higher"
SOURCE = "host_clock"


def read(ctx):
    return ctx["rounds"] / ctx["window_s"]
