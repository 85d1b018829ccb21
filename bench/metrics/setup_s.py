"""setup_s: seconds from process start to the first window job (JAX
start, the cohort generated from the seed, one warm-up job)."""
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(ctx):
    return ctx["setup_s"]
