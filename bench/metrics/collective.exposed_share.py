"""collective.exposed_share: share of the window in which a collective
(all-gather, all-reduce, ...) runs on a chip and no other op does, for the
most exposed chip."""
import devtrace

LAYER, UNIT, MOVES = "client mesh", "%", "rounds_per_s"


def read(ctx):
    if ctx["chips"] < 2:
        return None
    exposed = max(devtrace.exposed_collective_s(d) for d in ctx["devices"])
    return 100.0 * exposed / ctx["window_s"]
