"""lm_head.busy_share: device self time of the chunk program's ops in the
LM head and loss (named scope `lm_head_ce`: logits, cross entropy and the
per-client mean) over the chips' busy time."""
import devtrace
import lanes

LAYER, UNIT, MOVES = "lm head", "%", "rounds_per_s"


def read(ctx):
    scopes = ctx.get("op_scopes")
    if not scopes:
        return None
    ns = sum(s for d in ctx["devices"] for n, s in devtrace.self_times(d)
             if scopes.get(lanes.instruction(n)) == "lm_head_ce")
    if ns <= 0:
        return None
    return 100.0 * ns * 1e-9 / sum(ctx["busy_by_chip"])
