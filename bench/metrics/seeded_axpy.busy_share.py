"""seeded_axpy.busy_share: the θ update kernel's device time over the
chips' busy time."""
import devtrace

LAYER, UNIT, MOVES = "theta update kernel", "%", "rounds_per_s"
NAME = "seeded_axpy_pallas"


def read(ctx):
    kernel_s = devtrace.op_seconds(ctx["devices"],
                                   lambda k: k == NAME)
    if kernel_s <= 0.0:
        return None
    return 100.0 * kernel_s / sum(ctx["busy_by_chip"])
