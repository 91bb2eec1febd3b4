"""seeded_axpy_roofline: the θ update kernel's least time (8 bytes per
parameter per call over HBM bandwidth, three calls per leaf per direction)
over the device time of its events (op kind `seeded_axpy_pallas`)."""
import counts
import devtrace

LAYER, UNIT, MOVES = "theta update kernel", "%", "rounds_per_s"
NAME = "seeded_axpy_pallas"


def read(ctx):
    kernel_s = devtrace.op_seconds(ctx["devices"],
                                   lambda k: k == NAME)
    if kernel_s <= 0.0:
        return None
    least = counts.axpy_bytes(ctx["n_params"], ctx["cell"].traffic) \
        * ctx["rounds"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
