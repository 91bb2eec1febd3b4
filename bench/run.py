"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload opt-125m.sst2-k5 --seed 7 \
        --seconds 30 --trace 0

The window drives the program's main path, `fedsim.Experiment.run` on the
scan engine with the default chained dual forward and the analog OTA
transport, fed by the benchmark's own seeded traffic and weights. Set-up
(imports, weights, compilation and two warm chunks) ends at a chunk
boundary with the device idle; the window then runs whole chunks until
`--seconds` have passed and ends when the last chunk is done on the device.

After the window: the fullest chip's peak memory is read, the program's
state is freed, and a plain reference (bench/reference.py) follows the
run's first chunk from the same weights and traffic. The numbers compared
and their limits (bench/limits/<workload>.json) are printed as the last
lines of standard error and under `checks` in the result, which is the
last line of standard output. `--trace 1` adds a profiler trace of the
window and reports the cell's per-layer metrics instead of the end-to-end
ones.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with status 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import cell as cell_mod  # noqa: E402
import compare  # noqa: E402
import counts  # noqa: E402
import traffic_gen  # noqa: E402
import weights  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
WARM_CHUNKS = 3          # chunks run in set-up: the first compiles
EXIT_NO_DEVICE = 2


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def configure_environment() -> None:
    """JAX's persistent compile cache inside the checkout, at the fixed
    path the program's `launch/compile_cache` uses, holding every program
    however fast it compiled (so a run after the first compiles nothing);
    runtime logs under this run's TMPDIR."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(cell_mod.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, str(cell_mod.ROOT / "src"))


def require_devices(chips: int):
    """The first `chips` TPU devices; exits when there are none or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX's devices are {devs[0].platform}")
        sys.exit(EXIT_NO_DEVICE)
    if len(devs) < chips:
        log(f"{len(devs)} TPU devices, the cell needs {chips}")
        sys.exit(EXIT_NO_DEVICE)
    return devs[:chips]


class CompileCounter:
    """Backend compilations (and their seconds) from JAX's monitoring
    events; `window` counts those that fall inside the measured window."""

    def __init__(self):
        import jax
        self.total = 0
        self.seconds = 0.0
        self.window = 0
        self.in_window = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == BACKEND_COMPILE:
            self.total += 1
            self.seconds += duration
            if self.in_window:
                self.window += 1


def peak_bytes(devices) -> int:
    """HBM the runtime has held at its peak on the fullest of the cell's
    chips: buffers (`peak_bytes_in_use`) plus the region it reserves for
    programs' temporaries (`peak_bytes_reserved`), which the buffer count
    leaves out."""
    def held(stats):
        return int(stats["peak_bytes_in_use"]) \
            + int(stats["peak_bytes_reserved"])
    return max(held(d.memory_stats()) for d in devices)


def load_reader(name: str):
    """bench/metrics/<name>.py: a per-layer metric's reader."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FirstChunk:
    """A round hook (duck-typed `fedsim.RoundHook`) that records what the
    comparison needs of the run's first chunk: per-round `loss`, `p_hat`
    and `p_clients`, and at the chunk's boundary a host copy of θ's
    leaves (`stats_s` seconds, left out of the set-up time)."""
    cadence = 0

    def __init__(self, chunk: int):
        self.chunk = chunk
        self.first: Dict[str, Any] = {"loss": [], "p_hat": [],
                                      "p_clients": []}
        self.stats_s = 0.0
        self.losses: Dict[int, float] = {}

    def on_start(self, exp) -> None:
        pass

    def on_round(self, t, metrics) -> None:
        if t < self.chunk:
            self.first["loss"].append(float(metrics["loss"]))
            self.first["p_hat"].append(float(metrics["p_hat"]))
            self.first["p_clients"].append(
                [float(x) for x in metrics["p_clients"]])
        self.losses[t] = float(metrics["loss"])

    def on_boundary(self, t_done, exp) -> None:
        if t_done == self.chunk:
            t0 = time.perf_counter()
            self.first["leaves"] = compare.host_copy(exp.params)
            self.stats_s = time.perf_counter() - t0

    def close(self, exp) -> None:
        pass


class WindowHook(FirstChunk):
    """FirstChunk, and the window: it opens after the warm chunks with the
    device idle and closes at the first boundary past `seconds`, ending
    the run at chunk granularity through the program's `HealthAbort`."""

    def __init__(self, chunk: int, *, seconds: float,
                 counter: CompileCounter, on_open, on_close):
        super().__init__(chunk)
        self.seconds = seconds
        self.counter = counter
        self.on_open, self.on_close = on_open, on_close
        self.t_open = self.t_close = None
        self.r_open = self.r_close = None

    def on_boundary(self, t_done, exp) -> None:
        import jax
        from repro.obs import HealthAbort
        super().on_boundary(t_done, exp)
        if t_done == WARM_CHUNKS * self.chunk:
            jax.block_until_ready(exp.params)
            self.on_open()
            self.r_open = t_done
            self.t_open = time.perf_counter()
            self.counter.in_window = True
        elif self.t_open is not None \
                and time.perf_counter() - self.t_open >= self.seconds:
            self.end_window(exp, t_done)
            raise HealthAbort(t_done, "benchmark window closed")

    def end_window(self, exp, t_done) -> None:
        import jax
        jax.block_until_ready(exp.params)
        self.t_close = time.perf_counter()
        self.r_close = t_done
        self.counter.in_window = False
        self.on_close()


def build(cell, seed: int, devices) -> Dict[str, Any]:
    """The program's configurations for the cell, its client mesh, the
    weights made from the seed on the cell's chips, and the traffic."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.models import registry
    from repro.runtime import sharding as shd

    cfg, pz = cell_mod.program_configs(cell, seed)
    m = weights.dims(cell.config)
    want = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype),
                                  weights.shapes(m))
    got = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype),
                                 registry.abstract_params(cfg, jnp.float32))
    if want != got:
        raise SystemExit(f"the program's parameter tree {got} is not the "
                         f"benchmark's layout {want}")
    t = cell.traffic
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_client_mesh
        mesh = make_client_mesh(str(cell.chips), n_clients=int(t["clients"]))
        shardings = shd.params_sharding(mesh, weights.shapes(m))
    else:
        one = SingleDeviceSharding(devices[0])
        shardings = jax.tree_util.tree_map(lambda _: one, weights.shapes(m))
    return {"cfg": cfg, "pz": pz, "dims": m, "mesh": mesh,
            "params": weights.make(seed, m, shardings),
            "pipe": traffic_gen.Pipeline(t, m["vocab"], seed)}


def main(argv=None) -> int:
    args = cell_mod.parse_args(argv)
    cell = cell_mod.load(args.workload)
    configure_environment()
    import jax
    import jax.numpy as jnp
    devices = require_devices(cell.chips)
    counter = CompileCounter()
    log(f"set-up: devices at {time.perf_counter() - T_START!r} s")

    from repro.core import fedsim
    from repro.models import registry
    from repro.obs import Telemetry, Tracer

    built = build(cell, args.seed, devices)
    cfg, pz, m, mesh, params, pipe = (built[k] for k in (
        "cfg", "pz", "dims", "mesh", "params", "pipe"))
    t = cell.traffic
    chunk = int(t["chunk_rounds"])
    n_params = cell.config["params"]
    jax.block_until_ready(params)
    log(f"set-up: weights at {time.perf_counter() - T_START!r} s")

    inference_peak = None
    if args.trace and any(m["name"] == "hbm_over_inference"
                          for m in cell.per_layer):
        # the paper's memory claim: the training peak against one plain
        # forward at the same θ and batch, measured first
        b0 = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()
              if k != "labels"}
        mod = registry.get_module(cfg)
        fwd = jax.jit(lambda p, b: mod.loss_per_client(p, cfg, b))
        jax.block_until_ready(fwd(params, b0))
        inference_peak = peak_bytes(devices)
        del b0, fwd

    norms = compare.LeafNorms(m)
    tracer = Tracer() if args.trace else None
    profile_dir = tempfile.mkdtemp(prefix="bench_profile_") \
        if args.trace else None
    anchor: Dict[str, float] = {}

    def on_open():
        if args.trace:
            # no Python call tracer: it floods the host buffer, and the
            # bounded buffer then drops device events
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            anchor["perf"] = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_window_open"):
                pass

    def on_close():
        if args.trace:
            jax.profiler.stop_trace()

    hook = WindowHook(chunk, seconds=args.seconds, counter=counter,
                      on_open=on_open, on_close=on_close)
    telemetry = Telemetry(tracer=tracer) if tracer is not None else None
    exp = fedsim.Experiment(cfg, pz, pipe, int(t["horizon_rounds"]),
                            engine="scan", chunk_rounds=chunk, hooks=[hook],
                            params=params, mesh=mesh, telemetry=telemetry,
                            dtype=jnp.float32)
    del params
    res = exp.run()
    log(f"set-up: the first chunk's host copy took {hook.stats_s!r} s "
        f"(not set-up)")
    if hook.t_open is None:
        raise SystemExit("the horizon ended before the window opened")
    if hook.t_close is None:          # the horizon ended inside the window
        hook.end_window(exp, int(t["horizon_rounds"]))
    setup_s = hook.t_open - T_START - hook.stats_s
    setup_compiles = counter.total - counter.window
    window_s = hook.t_close - hook.t_open
    rounds = hook.r_close - hook.r_open
    memory_peak = peak_bytes(devices)
    window_losses = [v for r, v in hook.losses.items()
                     if hook.r_open <= r < hook.r_close]
    failed = sum(1 for v in window_losses if not math.isfinite(v))
    log(f"window: {rounds} rounds in {window_s!r} s; set-up {setup_s!r} s "
        f"with {setup_compiles} backend compiles "
        f"({counter.seconds!r} s compiling in all)")
    log(f"window_compiles={counter.window}")
    print(f"window_compiles={counter.window}", flush=True)

    spans = tracer.spans() if tracer is not None else []
    tracer_epoch = tracer.epoch if tracer is not None else 0.0
    first = hook.first
    del exp, res, hook, telemetry, tracer
    gc.collect()

    # the reference follows the first chunk from the same weights
    t_ref = time.perf_counter()
    checks = compare.check(cell, m, args.seed, first, pipe,
                           device=devices[0], pz=pz, norms=norms)
    del first
    correct = checks.pop("_correct")
    log(f"reference and comparison: {time.perf_counter() - t_ref!r} s")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {"correct": correct, "attempted": rounds,
                              "failed": failed, "device": device,
                              "window_compiles": counter.window}
    if args.trace:
        ctx = build_context(cell, m, n_params, devices, profile_dir, spans,
                            tracer_epoch, anchor, window_s=window_s,
                            rounds=rounds, memory_peak=memory_peak,
                            inference_peak=inference_peak)
        shutil.rmtree(profile_dir, ignore_errors=True)
        metrics = {}
        for entry in cell.per_layer:
            value = load_reader(entry["name"]).read(ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = ctx["busy_s"]
        device["window_s"] = ctx["window_s"]
        result["breakdown"] = ctx["breakdown"]
    else:
        values = {"rounds_per_s": rounds / window_s,
                  "peak_hbm_gb": memory_peak / 1e9, "setup_s": setup_s}
        result["metrics"] = {e["name"]: {"value": values[e["name"]],
                                         "unit": e["unit"]}
                             for e in cell.end_to_end}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


def build_context(cell, m, n_params, devices, profile_dir, spans,
                  tracer_epoch, anchor, *, window_s, rounds, memory_peak,
                  inference_peak) -> Dict[str, Any]:
    """What the per-layer readers read: the reduced trace, the program's
    spans in the window, the counts from shapes and the chip's peaks."""
    import devtrace as tr
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise SystemExit(f"the profiler wrote no trace under {profile_dir}")
    dev_ops, host = tr.load(paths[0])
    dev_ops = [d for d in dev_ops if d.device < len(devices)]
    open_ns = next((a for n, a, _ in host if n == "bench_window_open"), None)
    win_ns = (open_ns, open_ns + int(window_s * 1e9)) \
        if open_ns is not None else tr.window_of(dev_ops)
    if not any(d.ops for d in dev_ops):
        raise SystemExit("the trace holds no operation on the device")
    busy = [tr.busy_s(d, win_ns) for d in dev_ops]
    # program spans on the profiler's clock, through the window anchor
    to_ns = lambda perf: win_ns[0] + int((perf - anchor["perf"]) * 1e9)
    main_tid = next((s.get("tid") for s in spans if s["name"] == "chunk"),
                    None)
    lane = [(s["name"], to_ns(tracer_epoch + s["ts"]),
             to_ns(tracer_epoch + s["ts"] + s["dur"])) for s in spans
            if s.get("tid") == main_tid and s.get("dur") is not None]
    gaps = []
    for d in dev_ops:
        for a, b in tr.idle_gaps(d, win_ns):
            mid = (a + b) // 2
            open_spans = [x for x in lane if x[1] <= mid < x[2]]
            name = min(open_spans, key=lambda x: x[2] - x[1])[0] \
                if open_spans else "no_span"
            gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    per_chip = lambda ops: [(n, s / len(dev_ops)) for n, s in ops]
    stall = [(to_ns(tracer_epoch + s["ts"]), s["dur"]) for s in spans
             if s["name"] == "prep_stall" and s.get("dur") is not None]
    return {
        "cell": cell, "dims": m, "n_params": n_params,
        "chips": len(devices), "rounds": rounds, "window_s": window_s,
        "window_ns": win_ns, "devices": dev_ops,
        "busy_s": sum(busy) / len(busy), "busy_by_chip": busy,
        "peaks": counts.peaks(devices[0].device_kind),
        "prep_stall_s": sum(d for a, d in stall
                            if win_ns[0] <= a < win_ns[1]),
        "memory_peak": memory_peak, "inference_peak": inference_peak,
        "breakdown": {
            "device_ops": [list(x) for x in per_chip(tr.top_ops(dev_ops))],
            "idle_gaps": [list(g) for g in gaps[:10]]},
    }


if __name__ == "__main__":
    sys.exit(main())
