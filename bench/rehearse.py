"""Compile a cell's chunk program for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload opt-125m.sst2-k5

Builds the step and scan executor the way `fedsim.Experiment` builds them
for the cell, hands them abstract shapes placed on the devices of a
described `v5e:2x2` (one device, or a client mesh over the cell's chips),
compiles, and prints the compiler's memory analysis and collective census
as one JSON line. Nothing runs: these are compile-time byte counts, not
measurements.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import cell as cell_mod  # noqa: E402


def rehearse(workload: str) -> dict:
    sys.path.insert(0, str(cell_mod.ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec
    from jax.sharding import SingleDeviceSharding

    from repro import byzantine as byz
    from repro.core import engine as eng
    from repro.core import pairzero
    from repro.core import transport as tp
    from repro.kernels import ops
    from repro.models import registry
    from repro.obs import hlo
    from repro.runtime import sharding as shd

    # the described chip is not the backend JAX sees, so name the kernel
    # path the chip would take
    ops._default_impl = lambda: "pallas"
    cell = cell_mod.load(workload)
    cfg, pz = cell_mod.program_configs(cell, seed=0)
    t = cell.traffic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = None
    if cell.chips > 1:
        mesh = Mesh(np.asarray(topo.devices[:cell.chips]), ("data",),
                    axis_types=(AxisType.Auto,))
    transport = tp.resolve(pz)
    step = pairzero.make_zo_step(cfg, pz, transport=transport, mesh=mesh,
                                 behavior=byz.resolve_behavior(pz),
                                 defense=byz.resolve_defense(pz))
    executor = eng.ScanExecutor(step)
    params = registry.abstract_params(cfg, jnp.float32)
    if mesh is None:
        one = SingleDeviceSharding(topo.devices[0])
        place = lambda tree: jax.tree_util.tree_map(lambda _: one, tree)
        p_shard = place(params)
    else:
        p_shard = shd.params_sharding(mesh, params)
        place = lambda tree: jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, PartitionSpec()), tree)
    spec = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    carry = jax.tree_util.tree_map(spec, params, p_shard)
    c = int(t["chunk_rounds"])
    k, b, s = int(t["clients"]), int(t["batch"]), int(t["seq_len"])
    ctl = {n: jax.ShapeDtypeStruct((c,) + v.shape, v.dtype)
           for n, v in pairzero.control_spec(k, transport).items()}
    ctl = jax.tree_util.tree_map(spec, ctl, place(ctl))
    batch = {"tokens": jax.ShapeDtypeStruct((c, k, b, s), jnp.int32),
             "targets": jax.ShapeDtypeStruct((c, k, b, s), jnp.int32),
             "mask": jax.ShapeDtypeStruct((c, k, b, s), jnp.float32)}
    if mesh is None:
        b_shard = place(batch)
    else:
        b_shard = shd.chunk_batch_sharding(mesh, batch)
    batch = jax.tree_util.tree_map(spec, batch, b_shard)
    t0 = time.perf_counter()
    compiled = executor.aot_compiled(carry, ctl, batch)
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    out = {
        "workload": workload, "chips": cell.chips, "chunk_rounds": c,
        "params": cfg.param_count(), "compile_s": compile_s,
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "collectives": hlo.collective_census(text),
        "custom_calls": hlo.custom_call_census(text),
    }
    out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                         - out["alias_bytes"] + out["temp_bytes"])
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    print(json.dumps(rehearse(ap.parse_args().workload)), flush=True)
