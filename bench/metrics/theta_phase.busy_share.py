"""theta_phase.busy_share: device self time of the chunk program's ops in
the θ passes (named scopes `theta_plus`, `theta_minus`, `theta_update`:
the seeded_axpy kernel and each leaf's pads, reshapes, slices and copies
around it) over the chips' busy time. Scopes come from the compiled chunk
program's metadata (`repro.obs.hlo.op_scopes`)."""
import devtrace
import lanes

LAYER, UNIT, MOVES = "theta update kernel", "%", "rounds_per_s"


def read(ctx):
    scopes = ctx.get("op_scopes")
    if not scopes:
        return None
    ns = sum(s for d in ctx["devices"] for n, s in devtrace.self_times(d)
             if (scopes.get(lanes.instruction(n)) or "").startswith("theta_"))
    if ns <= 0:
        return None
    return 100.0 * ns * 1e-9 / sum(ctx["busy_by_chip"])
