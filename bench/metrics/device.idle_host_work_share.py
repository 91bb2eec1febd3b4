"""device.idle_host_work_share: share of the window in which the most idle
chip ran no op while some host lane of the program was working: its
innermost open span is not a wait (`WAITS`). The rest of the idle time the
host spent waiting, on the device or on the other lane."""
import devtrace
import lanes

LAYER, UNIT, MOVES = "device", "%", "rounds_per_s"
WAITS = ("prep_stall", "prep_device_wait")


def read(ctx):
    program = lanes.program_lanes(ctx.get("host_lanes") or [])
    if not program:
        return None
    win = tuple(ctx["window_ns"])
    chip = min(ctx["devices"], key=lambda d: devtrace.busy_s(d, win))
    work = [(a, b) for lane in program
            for name, a, b in lanes.innermost(lane, win)
            if name not in WAITS]
    idle = devtrace.idle_gaps(chip, win)
    return 100.0 * lanes.length(lanes.intersect(idle, work)) * 1e-9 \
        / ctx["window_s"]
