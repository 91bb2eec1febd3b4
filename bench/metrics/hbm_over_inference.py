"""hbm_over_inference: the fullest chip's peak after the training window
over its peak after one plain jitted `loss_per_client` forward at the same
θ and batch, measured first in the same process (a ratio, in x)."""
LAYER, UNIT, MOVES = "round step memory", "x", "peak_hbm_gb"


def read(ctx):
    if not ctx["inference_peak"]:
        return None
    return ctx["memory_peak"] / ctx["inference_peak"]
