"""mfu: model FLOPs of the window's rounds (bench/counts.round_flops:
forward only, 2·n_perturb rollouts, matmuls, causal attention at the model's
head dim, LM head at scored positions) over window × chips × bf16 peak."""
import counts

LAYER, UNIT, MOVES = "round step", "%", "rounds_per_s"


def read(ctx):
    flops = counts.round_flops(ctx["dims"], ctx["cell"].traffic) \
        * ctx["rounds"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (ctx["window_s"] * peak)
