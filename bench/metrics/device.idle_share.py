"""device.idle_share: 1 - (union of op intervals on the chip) / window, for
the most idle of the cell's chips, from the profiler trace."""
LAYER, UNIT, MOVES = "device", "%", "rounds_per_s"


def read(ctx):
    busy = min(ctx["busy_by_chip"])
    return 100.0 * (1.0 - busy / ctx["window_s"])
