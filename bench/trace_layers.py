"""bench/run.py with the program's own spans and scopes in its readers'
context, and the readers that need them.

    python3 bench/trace_layers.py --workload opt-125m.sst2-k5 --seed 7 \
        --seconds 10 --trace 1
    python3 bench/trace_layers.py --workload opt-125m.sst2-k5 --seed 7 \
        --seconds 10 --trace 0 --tracer 1

`--trace 1` is run.py's traced run, whose reader context also holds
`host_lanes` (`lanes.host_lanes` of the window's profiler trace: the
program's spans, per thread, on the device planes' clock) and `op_scopes`
(`repro.obs.hlo.op_scopes` of the chunk program the run dispatched,
compiled again after the peak is read, from the compile cache). The
result line's `breakdown` gains `layers`: the readers of `NEW_METRICS`,
the share of the chunk program's device self time that maps to an
instruction of `op_scopes` and to a named scope, the clock check (no
chunk's program starts on the chip before its `dispatch` span opens), and
the idle time of the most idle chip by the innermost span open on the
prefetch worker's lane.

`--trace 0 --tracer 1` is run.py's untraced run with a recording tracer
and no profiler: its `rounds_per_s` against a plain `--trace 0` run is
what the tracer's spans and annotations cost.

bench/run.py builds neither key, so these readers are not yet metrics of
BENCHMARK.json: once `build_context` adds the two keys as `with_layers`
does, each reader is listed there under its file's name.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import os
import re
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import lanes  # noqa: E402
import run  # noqa: E402

NEW_METRICS = ("host.prep_device_wait_share", "device.idle_host_work_share",
               "theta_phase.busy_share", "lm_head.busy_share")


def record_first_dispatch(state: Dict[str, Any]) -> None:
    """Keep the scan executor and the argument specs of the run's first
    chunk dispatch (taken before the carry is donated)."""
    from repro.core import engine
    from repro.obs import hlo
    real = engine.ScanExecutor.run

    def first_run(self, carry, ctl_stack, batch_stack):
        if "executor" not in state:
            state["executor"] = self
            state["specs"] = (hlo.specs_of(carry), hlo.specs_of(ctl_stack),
                              hlo.specs_of(batch_stack))
        return real(self, carry, ctl_stack, batch_stack)
    engine.ScanExecutor.run = first_run


def chunk_scopes(state: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """op_scopes of the chunk program the run dispatched."""
    if "executor" not in state:
        return None
    from repro.obs import hlo
    compiled = state["executor"].aot_compiled(*state["specs"])
    return hlo.op_scopes(compiled.as_text())


def chunk_ops(dev: devtrace.DeviceOps, runs: List[lanes.Interval],
              window) -> devtrace.DeviceOps:
    """The chip's ops that ran inside an execution of the chunk program
    that started in the window."""
    runs = [r for r in runs if window[0] <= r[0] < window[1]]
    starts = [a for a, _ in runs]
    kept = []
    for op in dev.ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] < runs[i][1]:
            kept.append(op)
    return devtrace.DeviceOps(dev.device, kept)


def coverage(ctx, runs) -> Optional[Dict[str, Any]]:
    """Shares of the chunk program's device self time in the window that
    map to an instruction of op_scopes, and to one of its named scopes."""
    scopes = ctx.get("op_scopes")
    if not scopes or not runs:
        return None
    total = mapped = scoped = 0
    by_scope: Dict[str, int] = {}
    unscoped: Dict[str, int] = {}
    for dev in ctx["devices"]:
        ops = chunk_ops(dev, runs.get(dev.device, []), ctx["window_ns"])
        for name, ns in devtrace.self_times(ops):
            total += ns
            inst = lanes.instruction(name)
            if inst not in scopes:
                continue
            mapped += ns
            scope = scopes[inst]
            if scope is None:
                key = devtrace.kind(name)
                unscoped[key] = unscoped.get(key, 0) + ns
                continue
            scoped += ns
            by_scope[scope] = by_scope.get(scope, 0) + ns
    if total <= 0:
        return None
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:8]
    return {"chunk_self_s": total * 1e-9,
            "mapped_share": 100.0 * mapped / total,
            "scoped_share": 100.0 * scoped / total,
            "by_scope_s": {k: v * 1e-9 for k, v in sorted(by_scope.items())},
            "top_unscoped_s": [[k, v * 1e-9] for k, v in top]}


# the runtime's own launch of an executable, on the host plane
LAUNCH = re.compile(r"LoadedExecutable.*Execute")


def clock_check(ctx, runs) -> Optional[Dict[str, Any]]:
    """Pairs the window's `dispatch` spans on the driver's lane with the
    chunk program's executions on each chip, in order; every execution
    has to start after its dispatch span opened. `launch_lead_ms` times
    each execution from the runtime's own launch event inside the span
    instead: a lead below 0 there too is the profiler's host-to-device
    clock offset, not the program's spans."""
    host = ctx.get("host_lanes") or []
    driver = lanes.driver_lane(host)
    if driver is None or not runs:
        return None
    w0, w1 = ctx["window_ns"]
    spans = sorted((e.start, e.end) for e in driver.spans("dispatch")
                   if w0 <= e.start < w1)
    launches = sorted(e.start for ln in host for e in ln.events
                      if LAUNCH.search(e.name) and w0 <= e.start < w1)
    first_launch = []
    for a, b in spans:
        i = bisect.bisect_left(launches, a)
        first_launch.append(launches[i] if i < len(launches)
                            and launches[i] <= b else None)
    out = {"dispatches": len(spans), "chips": {}}
    ok = True
    for chip, chip_runs in sorted(runs.items()):
        starts = sorted(a for a, _ in chip_runs if w0 <= a < w1)
        leads = [(r - d[0]) * 1e-6 for d, r in zip(spans, starts)]
        launch = [(r - e) * 1e-6 for e, r in zip(first_launch, starts)
                  if e is not None]
        ok &= bool(leads) and min(leads) >= 0.0 \
            and len(starts) == len(spans)
        out["chips"][chip] = {
            "runs": len(starts),
            "min_lead_ms": min(leads) if leads else None,
            "max_lead_ms": max(leads) if leads else None,
            "launch_lead_ms": [min(launch), max(launch)] if launch
            else None}
    out["ok"] = ok
    return out


def idle_by_span(ctx, lane: Optional[lanes.Lane]) -> Optional[Dict]:
    """Idle time of the most idle chip in the window by the lane's
    innermost program span open then (`none`: no span open)."""
    if lane is None:
        return None
    win = tuple(ctx["window_ns"])
    chip = min(ctx["devices"], key=lambda d: devtrace.busy_s(d, win))
    idle = devtrace.idle_gaps(chip, win)
    out: Dict[str, float] = {}
    covered = 0
    for name, a, b in lanes.innermost(lane, win):
        ns = lanes.length(lanes.intersect(idle, [(a, b)]))
        if ns:
            out[name] = out.get(name, 0.0) + ns * 1e-9
            covered += ns
    rest = lanes.length(idle) - covered
    if rest:
        out["none"] = rest * 1e-9
    gaps = sorted(g for g in ((b - a) * 1e-6 for a, b in idle) if g >= 1.0)
    return {"idle_s": lanes.length(idle) * 1e-9, "by_span_s": out,
            "gaps_over_1ms": len(gaps),
            "median_gap_ms": gaps[len(gaps) // 2] if gaps else None}


def _reading(fn, *args):
    """fn(*args), or the error it raised: one block that cannot be read
    leaves the run's other readings standing."""
    try:
        return fn(*args)
    except Exception as exc:  # noqa: BLE001 - report, keep the run
        return {"error": f"{type(exc).__name__}: {exc}"}


def with_layers(build_context, state):
    """run.build_context, adding `host_lanes` and `op_scopes` to the
    context and the `layers` block to its breakdown."""
    def build(*args, **kw):
        ctx = build_context(*args, **kw)
        profile_dir = args[4]
        path = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        ctx["host_lanes"] = lanes.host_lanes(path)
        try:
            ctx["op_scopes"] = chunk_scopes(state)
            scopes = len(ctx["op_scopes"] or {})
        except Exception as exc:  # noqa: BLE001 - report, keep the run
            ctx["op_scopes"] = None
            scopes = {"error": f"{type(exc).__name__}: {exc}"}
        runs = lanes.module_runs(path)
        host = ctx["host_lanes"]
        ctx["breakdown"]["layers"] = {
            "metrics": {name: _reading(run.load_reader(name).read, ctx)
                        for name in NEW_METRICS},
            "op_scopes": scopes,
            "coverage": _reading(coverage, ctx, runs),
            "clock": _reading(clock_check, ctx, runs),
            "idle_by_worker_span": _reading(idle_by_span, ctx,
                                            lanes.worker_lane(host)),
        }
        return ctx
    return build


def install(tracer_only: bool) -> None:
    """Patch run.py's flow for one of the two modes."""
    if tracer_only:
        from repro import obs
        obs.Telemetry.off = classmethod(
            lambda cls: cls(tracer=obs.Tracer()))
        return
    state: Dict[str, Any] = {}
    record_first_dispatch(state)
    run.build_context = with_layers(run.build_context, state)


def main(argv=None) -> int:
    # no abbreviations: run.py's own --trace must not read as --tracer
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=0)
    ours, rest = ap.parse_known_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    install(bool(ours.tracer))
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
