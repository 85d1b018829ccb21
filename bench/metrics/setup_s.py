"""setup_s: seconds from process start to the first window job (JAX
start, the family's data made from the seed, one warm-up job)."""
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(ctx):
    return ctx["setup_s"]
