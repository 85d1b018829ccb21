"""device_idle_share: share of the traced job in which no operation ran
on the device (1 - busy / window, busy averaged over the chips)."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def read(ctx):
    if not ctx["planes"] or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
