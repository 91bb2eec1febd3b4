"""Smoke run of the federated ZO trainer on a TPU, at full OPT-125M width.

    python chip_smoke.py              # one chip: phases (a) to (f)
    python chip_smoke.py --chips 4    # four chips: client mesh vs one device

Everything runs in this one process, which holds the chip for its whole
life and starts no other process. The model is the registered `opt-125m`
(12 layers, d 768, d_ff 3072, vocabulary 50,272: 190,483,200 float32
parameters) with random weights from the CLI's seed.

Phases of the one-chip run:

  (a) device: a TPU is present and the kernels dispatch to Pallas; the
      z stream drawn by the Mosaic kernel matches the XLA path;
  (b) main path: `repro.launch.train.main` trains 8 rounds on the scan
      engine with checkpoints at rounds 4 and 8;
  (c) resume: only the round-4 checkpoint of (b) is copied to a fresh
      directory, and the same command resumes from it;
  (d) reference: the same 8 rounds through `Experiment` on the XLA path at
      `highest` matmul precision;
  (e) fused: 2 rounds with the fused dual forward (`perturbed_matmul`);
  (f) loop engine against scan, bitwise (recorded, not gated).

Any failed phase exits non-zero and prints no `ok` line. Compile seconds
and the rounds/s of (b)'s second chunk are smoke figures, not a benchmark.
The last line of standard output is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.

The compile cache lives where `JAX_COMPILATION_CACHE_DIR` says, else in
`.jax_cache/` at the checkout root; a second run reuses it. Checkpoints
and traces go to `.smoke_work/` at the checkout root, removed at exit.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Sequence

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".smoke_work"
# largest relative loss difference admitted against the reference: the
# chip's default f32 matmul precision is bf16-based, the reference's is not
LOSS_RTOL = 1e-2
# Mosaic and XLA evaluate log/cos of the z stream separately; a wrong
# stream differs by O(1), rounding by a few ulp of values below ~6
Z_ATOL = 1e-4
ROUNDS, CHUNK = 8, 4
# `--lr 5e-7` is the paper's analog learning rate (ZOConfig's default);
# the CLI's 5e-3 is sized for the reduced CPU models
TRAIN_ARGS = ["--arch", "opt-125m", "--engine", "scan",
              "--chunk-rounds", str(CHUNK), "--rounds", str(ROUNDS),
              "--clients", "5", "--batch", "8", "--seq-len", "64",
              "--n-perturb", "1", "--lr", "5e-7", "--eval-every", "0"]
OPT_125M_PARAMS = 190_483_200
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A phase did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is timed as its retrieval)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_: Any) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def rel_diff(a: Sequence[float], b: Sequence[float]) -> float:
    return max(abs(x - y) / max(abs(y), 1e-30) for x, y in zip(a, b))


def sign_agreement(a: Sequence[float], b: Sequence[float]) -> str:
    same = sum(math.copysign(1.0, x) == math.copysign(1.0, y)
               for x, y in zip(a, b))
    return f"{same}/{len(a)}"


def check_losses(name: str, losses: List[float], n: int) -> None:
    check(len(losses) == n, f"{name}: {len(losses)} losses, want {n}")
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss in {losses}")


def train(argv: List[str]) -> Dict[str, Any]:
    from repro.launch import train as train_cli
    out = train_cli.main(argv)
    cost = out.get("cost_stats")
    if cost is not None:
        check("error" not in cost, f"cost analysis failed: {cost}")
    return out


def chunk_seconds(trace_path: Path) -> Dict[int, float]:
    """End time (s on the tracer clock) of each chunk's metric flush, by
    the chunk's first round. A flush returns once its chunk has finished
    on the device, so consecutive ends bound one chunk's execution."""
    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    return {int(e["args"]["t0"]): (e["ts"] + e["dur"]) * 1e-6
            for e in events if e.get("name") == "metrics_flush"}


def peak_bytes(device) -> int:
    stats = device.memory_stats()
    check(bool(stats) and "peak_bytes_in_use" in stats,
          f"{device} reports no peak_bytes_in_use")
    return int(stats["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> Dict[str, Any]:
    """(a) a TPU is attached and the kernels dispatch to Pallas."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    check(platform == "tpu", f"no TPU: JAX's devices are {platform}")
    check(len(devs) >= chips, f"{len(devs)} devices, want {chips}")
    from repro.kernels import ops
    check(ops._default_impl() == "pallas",
          f"kernels dispatch to {ops._default_impl()!r}, want 'pallas'")
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"(a) device kind={device['kind']} count={device['count']} "
        f"dispatch={ops._default_impl()}")
    return device


def phase_z_stream() -> None:
    """(a) the Mosaic seeded_axpy draws the XLA path's z stream."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    for shape in ((768, 3072), (50272, 768), (768,)):
        w = jnp.zeros(shape, jnp.float32)
        z_pl = np.asarray(ops.seeded_axpy(w, 12345, 1.0, impl="pallas"))
        z_x = np.asarray(ops.seeded_axpy(w, 12345, 1.0, impl="xla"))
        err = float(np.max(np.abs(z_pl - z_x)))
        log(f"(a) z stream {shape}: max|pallas - xla| = {err!r} "
            f"bitwise={bool(np.array_equal(z_pl, z_x))}")
        check(np.all(np.isfinite(z_pl)), f"non-finite z at {shape}")
        check(err <= Z_ATOL, f"z stream {shape} differs by {err}")
        std = float(np.std(z_pl))
        check(abs(std - 1.0) < 0.05, f"z stream {shape} has std {std}")


def phase_main(clock: CompileClock) -> Dict[str, Any]:
    """(b) the CLI trains 8 rounds at full width on the scan engine."""
    import jax
    from repro.models import registry
    cfg = registry.get_arch("opt-125m")
    n_params = cfg.param_count()
    log(f"(b) opt-125m: layers={cfg.n_layers} d={cfg.d_model} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} params={n_params:,}")
    check(n_params == OPT_125M_PARAMS, f"param count {n_params}")
    trace = WORK / "trace_b.json"
    argv = TRAIN_ARGS + ["--checkpoint-dir", str(WORK / "ckpt_b"),
                         "--checkpoint-every", str(CHUNK),
                         "--trace-out", str(trace)]
    before = clock.snapshot()
    t0 = time.perf_counter()
    out = train(argv)
    wall = time.perf_counter() - t0
    comp = clock.since(before)
    check_losses("(b)", out["losses"], ROUNDS)
    ends = chunk_seconds(trace)
    steady = ends[CHUNK] - ends[0]
    peak = peak_bytes(jax.devices()[0])
    customs = out["cost_stats"]["custom_calls"].get("tpu_custom_call", 0)
    log(f"(b) wall {wall!r} s; backend compile {comp['compile_s']!r} s "
        f"(persistent cache hits {comp['cache_hits']}, misses "
        f"{comp['cache_misses']}); first chunk ends {ends[0]!r} s into "
        "the run")
    log(f"(b) smoke figure, not a benchmark: second chunk {steady!r} s = "
        f"{CHUNK / steady!r} rounds/s")
    log(f"(b) peak_bytes_in_use {peak:,}")
    for t, (loss, p_hat) in enumerate(zip(out["losses"], out["p_hats"])):
        log(f"(b) round {t} loss {loss!r} p_hat {p_hat!r}")
    log(f"(b) chunk program: {customs} tpu_custom_call, custom calls "
        f"{out['cost_stats']['custom_calls']}")
    check(customs > 0, "the compiled chunk program has no Pallas kernel")
    return out


def phase_resume(main_out: Dict[str, Any]) -> None:
    """(c) resume from (b)'s round-4 checkpoint alone."""
    src = WORK / "ckpt_b" / f"step_{CHUNK:08d}"
    check(src.is_dir(), f"(b) wrote no {src.name}")
    dst = WORK / "ckpt_c"
    shutil.copytree(src, dst / src.name)
    out = train(TRAIN_ARGS + ["--checkpoint-dir", str(dst),
                              "--checkpoint-every", str(CHUNK),
                              "--trace-out", str(WORK / "trace_c.json")])
    log(f"(c) resumed_from {out['resumed_from']} final_loss "
        f"{out['final_loss']!r} (b) {main_out['final_loss']!r}")
    check(out["resumed_from"] == CHUNK,
          f"resumed from {out['resumed_from']}, want {CHUNK}")
    check(out["losses"] == main_out["losses"][CHUNK:],
          f"resumed losses {out['losses']} != "
          f"{main_out['losses'][CHUNK:]}")


def _experiment_inputs(extra: Sequence[str] = ()):
    from repro.launch import train as train_cli
    args = train_cli.build_parser().parse_args(TRAIN_ARGS + list(extra))
    return train_cli.make_configs(args)


def phase_reference(main_out: Dict[str, Any]) -> None:
    """(d) the XLA path at highest matmul precision."""
    import jax
    from repro.core import fedsim
    cfg, pz, pipe = _experiment_inputs()
    with jax.default_matmul_precision("highest"):
        res = fedsim.Experiment(cfg, pz, pipe, ROUNDS, engine="scan",
                                chunk_rounds=CHUNK, impl="xla").run()
    check_losses("(d)", res.losses, ROUNDS)
    err = rel_diff(main_out["losses"], res.losses)
    for t, (loss, p_hat) in enumerate(zip(res.losses, res.p_hats)):
        log(f"(d) round {t} reference loss {loss!r} p_hat {p_hat!r}")
    log(f"(d) max relative loss difference {err!r} (tolerance "
        f"{LOSS_RTOL}); p_hat sign agreement "
        f"{sign_agreement(main_out['p_hats'], res.p_hats)}")
    check(err <= LOSS_RTOL, f"loss differs from the reference by {err}")


def phase_fused(main_out: Dict[str, Any]) -> None:
    """(e) the fused dual forward, the path that runs perturbed_matmul."""
    from repro.core import fedsim
    cfg, pz, pipe = _experiment_inputs()
    pz = dataclasses.replace(pz, fused_perturbation=True)
    res = fedsim.Experiment(cfg, pz, pipe, 2, engine="scan",
                            chunk_rounds=2).run()
    check_losses("(e)", res.losses, 2)
    err = rel_diff(res.losses, main_out["losses"][:2])
    log(f"(e) fused losses {res.losses} p_hats {res.p_hats}; max "
        f"relative difference to (b) {err!r}")
    check(err <= LOSS_RTOL, f"fused losses differ from (b) by {err}")


def phase_loop(main_out: Dict[str, Any]) -> None:
    """(f) the loop engine against scan (recorded, not gated)."""
    out = train(TRAIN_ARGS + ["--engine", "loop"])
    check_losses("(f)", out["losses"], ROUNDS)
    log(f"(f) loop == scan bitwise: {out['losses'] == main_out['losses']}; "
        f"max relative difference "
        f"{rel_diff(out['losses'], main_out['losses'])!r}")


def phase_mesh(clock: CompileClock) -> None:
    """Four chips: `--mesh 4 --clients 8` against the same on one device."""
    import jax
    clients = ["--clients", "8"]
    before = clock.snapshot()
    mesh = train(TRAIN_ARGS + clients + [
        "--mesh", "4", "--checkpoint-dir", str(WORK / "ckpt_mesh"),
        "--checkpoint-every", str(CHUNK)])
    log(f"(mesh) backend compile {clock.since(before)['compile_s']!r} s")
    check_losses("(mesh)", mesh["losses"], ROUNDS)
    check(mesh["mesh"] == {"data": 4}, f"mesh {mesh['mesh']}")
    for d in jax.devices()[:4]:
        log(f"(mesh) device {d.id} peak_bytes_in_use {peak_bytes(d):,}")
    single = train(TRAIN_ARGS + clients)
    check_losses("(single)", single["losses"], ROUNDS)
    for t, (a, b) in enumerate(zip(mesh["losses"], single["losses"])):
        log(f"(mesh) round {t} mesh loss {a!r} single-device loss {b!r}")
    err = rel_diff(mesh["losses"], single["losses"])
    log(f"(mesh) mesh == single device bitwise: "
        f"{mesh['losses'] == single['losses']}; max relative difference "
        f"{err!r}; p_hat sign agreement "
        f"{sign_agreement(mesh['p_hats'], single['p_hats'])}")
    check(err <= LOSS_RTOL, f"mesh losses differ by {err}")


def run(chips: int) -> Dict[str, Any]:
    sys.path.insert(0, str(ROOT / "src"))
    device = phase_device(chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir()
    if chips == 4:
        phase_mesh(clock)
        return device
    phase_z_stream()
    main_out = phase_main(clock)
    phase_resume(main_out)
    phase_reference(main_out)
    phase_fused(main_out)
    phase_loop(main_out)
    log(f"all phases: backend compile {clock.seconds!r} s, persistent "
        f"cache hits {clock.cache_hits}, misses {clock.cache_misses}")
    return device


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the client-mesh comparison, on 4 chips")
    opts = ap.parse_args(argv)
    try:
        device = run(opts.chips)
    except Exception:  # noqa: BLE001 - any failure ends the smoke run
        traceback.print_exc()
        print("[chip_smoke] FAILED", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
