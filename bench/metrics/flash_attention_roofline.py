"""flash_attention_roofline: the attention kernel's least time, the larger
of its causal FLOPs over the bf16 peak and its q/k/v/o bytes over HBM
bandwidth, both at the model's own head dim, over the device time of its
events (op kind `flash_attention_pallas`)."""
import counts
import devtrace

LAYER, UNIT, MOVES = "attention kernel", "%", "rounds_per_s"
NAME = "flash_attention_pallas"


def read(ctx):
    kernel_s = devtrace.op_seconds(ctx["devices"],
                                   lambda k: k == NAME)
    if kernel_s <= 0.0:
        return None
    m, t, pk = ctx["dims"], ctx["cell"].traffic, ctx["peaks"]
    least = max(counts.attention_flops(m, t) / pk["bf16_flops_per_s"],
                counts.attention_bytes(m, t) / pk["hbm_bytes_per_s"])
    return 100.0 * least * ctx["rounds"] / kernel_s
