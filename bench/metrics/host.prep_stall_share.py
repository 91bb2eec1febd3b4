"""host.prep_stall_share: share of the window in which the program's driver
waited for the next chunk's host-side preparation (its `prep_stall` spans:
control trace and batch staging in `core/engine.ChunkPrefetcher`)."""
LAYER, UNIT, MOVES = "host driver", "%", "rounds_per_s"


def read(ctx):
    return 100.0 * ctx["prep_stall_s"] / ctx["window_s"]
