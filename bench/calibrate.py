"""Readings that set a cell's limits: the program against the reference on
many seeds, and the control and the planted faults on a few.

    python3 bench/calibrate.py --workload opt-125m.sst2-k5 \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3

All in one process on the cell's chips, so the programs compile once.
For each seed the program runs its first two chunks through the window's
path (`fedsim.Experiment` on the scan engine, the benchmark's weights and
traffic) and the reference follows the first chunk. On the control seeds
the reference is also put in the program's place:

  control     the reference in bfloat16 (the configuration states float32)
  half_batch  each client's loss over half of its rows
  token       one token of client 0's first row altered in every round
  exchange    (client mesh) only the first chip's clients superpose

and each is compared with the float32 reference like the program is. A
state left unchanged reads 1 on `dtheta` by construction and needs no run.
`--plant <fault>` (bench/faults.py) breaks the program itself instead, and
its readings are reported under the fault's name. One JSON line per
reading goes to standard output.

"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell as cell_mod  # noqa: E402
import compare  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import weights  # noqa: E402


def program_first_chunk(cell, seed, devices):
    """The program's first chunk for `seed`: its metrics and θ_C's host
    copy, read the way a benchmark run reads them."""
    import jax.numpy as jnp

    from repro.core import fedsim
    from repro.obs import HealthAbort

    built = run.build(cell, seed, devices)
    chunk = int(cell.traffic["chunk_rounds"])

    class UntilFirstChunkRead(run.FirstChunk):
        def on_boundary(self, t_done, exp):
            super().on_boundary(t_done, exp)
            if t_done == 2 * chunk:       # the first chunk's metrics are in
                raise HealthAbort(t_done, "first chunk read")

    hook = UntilFirstChunkRead(chunk)
    exp = fedsim.Experiment(built["cfg"], built["pz"], built["pipe"],
                            int(cell.traffic["horizon_rounds"]),
                            engine="scan", chunk_rounds=chunk, hooks=[hook],
                            params=built.pop("params"), mesh=built["mesh"],
                            dtype=jnp.float32)
    exp.run()
    del exp
    return hook.first, built


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--plant", default=None,
                    help="a fault of bench/faults.py to plant in the program")
    args = ap.parse_args(argv)
    cell = cell_mod.load(args.workload)
    run.configure_environment()
    import jax.numpy as jnp
    devices = run.require_devices(cell.chips)
    if args.plant:
        import faults
        faults.plant(args.plant)
    t = cell.traffic
    chunk, gamma = int(t["chunk_rounds"]), float(t["gamma"])
    m = weights.dims(cell.config)
    refs = {}
    norms = compare.LeafNorms(m)
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    def emit(kind, seed, values, t0):
        print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                          "seconds": time.perf_counter() - t0, **values}),
              flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        prog, built = program_first_chunk(cell, seed, devices)
        if not refs:
            channel = compare.channel_of(built["pz"])
            refs["f32"] = reference.Reference(m, t, channel)
            refs["bf16"] = reference.Reference(
                m, t, channel, dtype=jnp.bfloat16, precision=None)
        pseed = cell_mod.program_seed(seed)
        batches = compare.first_batches(built["pipe"], chunk)
        ref = compare.reference_run(refs["f32"], norms, seed, pseed,
                                    batches, devices[0])
        ref_leaves = ref.pop("leaves")
        gaps = norms.gaps(prog.pop("leaves"), ref_leaves)
        emit(args.plant or "program", seed, compare.readings(
            prog, ref, gaps, compare.decoded_of(refs["f32"], prog, pseed),
            gamma), t0)
        del prog
        if seed not in control_seeds:
            del ref_leaves
            continue
        ref_host = compare.host_copy(ref_leaves)
        del ref_leaves
        runs = {"control": (refs["bf16"], batches, 0),
                "half_batch": (refs["f32"], [
                    {k: v[:, :v.shape[1] // 2] for k, v in b.items()}
                    for b in batches], 0),
                "token": (refs["f32"], [_alter_token(b, m["vocab"])
                                        for b in batches], 0)}
        if cell.chips > 1:
            runs["exchange"] = (refs["f32"], batches,
                                int(t["clients"]) // cell.chips)
        for kind, (r, bs, heard) in runs.items():
            t0 = time.perf_counter()
            out = compare.reference_run(r, norms, seed, pseed, bs,
                                        devices[0], heard=heard)
            gaps = norms.gaps(ref_host, out.pop("leaves"))
            emit(kind, seed, compare.readings(
                out, ref, gaps, compare.decoded_of(refs["f32"], out, pseed),
                gamma), t0)
        del ref_host
    return 0


def _alter_token(batch, vocab):
    tokens = batch["tokens"].copy()
    tokens[0, 0, -1] = (tokens[0, 0, -1] + 1) % vocab
    return dict(batch, tokens=tokens)


if __name__ == "__main__":
    sys.exit(main())
