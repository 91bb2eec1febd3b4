"""host.prep_device_wait_share: share of the window in which the prefetch
worker, preparing the next chunk, waited for the device (its
`prep_device_wait` spans: the eager round-seed and noise-key ops in
`core/engine.build_trace`, the staging slot in `BatchStager`), read from
the program's spans on the profiler's host plane."""
import lanes

LAYER, UNIT, MOVES = "host driver", "%", "rounds_per_s"


def read(ctx):
    worker = lanes.worker_lane(ctx.get("host_lanes") or [])
    if worker is None:
        return None
    w0, w1 = ctx["window_ns"]
    waits = [(max(e.start, w0), min(e.end, w1))
             for e in worker.spans("prep_device_wait")
             if min(e.end, w1) > max(e.start, w0)]
    return 100.0 * lanes.length(waits) * 1e-9 / ctx["window_s"]
