"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh).

THE proof that the distribution config is coherent without real hardware:
for each of the 40 assigned cells this compiles the *actual* step the system
would run (the ZO pAirZero train step for train shapes; serve prefill/decode
for inference shapes) against the production mesh — (16,16) single-pod and
(2,16,16) multi-pod — using ShapeDtypeStruct stand-ins (zero allocation).

Per cell it records: compile success, per-device memory analysis, raw
cost_analysis, the collective schedule (parsed from compiled HLO), and —
single-pod only — the probe-derived roofline terms (launch/roofline.py).

Usage:
    python -m repro.launch.dryrun                         # everything
    python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    python -m repro.launch.dryrun --mesh multi            # multi-pod only
    python -m repro.launch.dryrun --variant fo            # FO baseline cells
    python -m repro.launch.dryrun --shard-clients         # shard_map'd step
    python -m repro.launch.dryrun --audit                 # capture variant
Results append incrementally to --out (default results/dryrun.json).
"""
# The VERY FIRST lines, before ANY other import (jax locks the platform and
# the device count on first init). This is a fake-device CPU rehearsal: it
# must never take a TPU, also when another program starts it as a child.
import os  # noqa: E402

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse        # noqa: E402
import dataclasses     # noqa: E402
import json            # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, SHAPES_BY_NAME  # noqa: E402
from repro.configs.base import (ModelConfig, PairZeroConfig, ShapeConfig,
                                ZOConfig)  # noqa: E402
from repro.core import pairzero  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_production_mesh, n_clients  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.optim import fo as fo_opt  # noqa: E402
from repro.runtime import sharding as shd  # noqa: E402

DTYPE = jnp.bfloat16


def audit_applies(shape_name: str, variant: str, audit: bool) -> bool:
    """The eavesdropper-capture variant exists only for the ZO train step."""
    return (audit and SHAPES_BY_NAME[shape_name].kind == "train"
            and variant == "zo")


def make_cell_id(arch: str, shape_name: str, mesh_name: str, variant: str,
                 *, bf16_reduce: bool = False, shard_clients: bool = False,
                 audit: bool = False) -> str:
    """The one cell-id spelling, shared by run_cell and the done-skip
    resume in main — a suffix added in only one place would make resume
    recompute finished cells or skip cells whose variant never lowered.
    `|audit` marks only cells that actually compile the capture variant."""
    return (f"{arch}|{shape_name}|{mesh_name}|{variant}"
            + ("|bf16r" if bf16_reduce else "")
            + ("|smap" if shard_clients else "")
            + ("|audit" if audit_applies(shape_name, variant, audit)
               else ""))


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return ("long_500k requires sub-quadratic decode state; "
                f"{cfg.name} is full-attention (see DESIGN.md skip list)")
    return None


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(arch: str, shape_name: str, mesh, *,
                variant: str = "zo") -> Tuple[Dict, Dict]:
    """(kwargs-for-step, meta). Every leaf is an abstract, sharded,
    weak-type-correct ShapeDtypeStruct — no device allocation anywhere."""
    cfg = registry.get_arch(arch)
    shape = SHAPES_BY_NAME[shape_name]
    k = n_clients(mesh)
    abs_params = registry.abstract_params(cfg, DTYPE)
    # decode cells use the serve-time EP-resident expert layout (§Perf)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_params, shd.params_sharding(mesh, abs_params,
                                        serve=shape.kind == "decode"))
    meta = {"cfg": cfg, "shape": shape, "k": k}

    if shape.kind == "train":
        batch_like = registry.train_batch_shapes(cfg, shape, k)
        batch = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            batch_like, shd.batch_sharding(mesh, batch_like))
        ctl_like = pairzero.control_spec(k)
        ctl = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            ctl_like, shd.control_sharding(mesh, ctl_like))
        return {"params": params, "batch": batch, "ctl": ctl}, meta

    b = shape.global_batch
    if shape.kind == "prefill":
        toks = jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32)
        tokens = jax.ShapeDtypeStruct(
            toks.shape, toks.dtype,
            sharding=shd.serve_batch_sharding(mesh, toks))
        spec = {"params": params, "tokens": tokens}
        if cfg.frontend.kind != "none":
            fr = jax.ShapeDtypeStruct(
                (b, cfg.frontend.n_frontend_tokens, cfg.d_model), DTYPE)
            spec["frontend"] = jax.ShapeDtypeStruct(
                fr.shape, fr.dtype,
                sharding=shd.serve_batch_sharding(mesh, fr))
        return spec, meta

    # decode: one new token against a seq_len-deep cache/state
    toks = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    tokens = jax.ShapeDtypeStruct(
        toks.shape, toks.dtype,
        sharding=shd.serve_batch_sharding(mesh, toks))
    cache_like = registry.serve_cache_shapes(cfg, b, shape.seq_len, DTYPE)
    cache = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        cache_like, shd.cache_sharding(mesh, cache_like))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return {"params": params, "cache": cache, "tokens": tokens,
            "pos": pos}, meta


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_step(cfg: ModelConfig, shape: ShapeConfig, k: int,
               variant: str = "zo", shard_clients_mesh=None,
               audit: bool = False):
    """Returns (fn, donate_argnums) for this cell.

    `shard_clients_mesh` compiles the shard_map'd ZO step instead: clients
    manual over (pod, data), 'model' under GSPMD auto — the dry-run proof
    that the cross-device psum aggregate lowers on the production mesh.
    `audit` compiles the eavesdropper-capture variant (repro.privacy): the
    step additionally emits the adversary's obs_* metrics — the dry-run
    proof that observation capture lowers at production scale too."""
    mod = registry.get_module(cfg)
    if shape.kind == "train":
        if variant == "zo":
            adversary = None
            if audit:
                from repro.privacy import Adversary
                adversary = Adversary()
            pz = PairZeroConfig(variant="analog", n_clients=k,
                                zo=ZOConfig(mu=1e-3, lr=5e-7,
                                            clip_gamma=100.0))
            step = pairzero.make_zo_step(cfg, pz, impl="xla",
                                         scheme="solution",
                                         mesh=shard_clients_mesh,
                                         adversary=adversary)
            return (lambda params, batch, ctl: step(params, batch, ctl)), (0,)
        if variant in ("fo", "fo_sgd"):
            opt = fo_opt.SGD(lr=1e-3) if variant == "fo_sgd" \
                else fo_opt.Adam(lr=1e-4)
            fostep = pairzero.make_fo_step(cfg, opt, impl="xla")

            def fo_with_init(params, batch, ctl):
                opt_state = opt.init(params)
                return fostep(params, opt_state, batch, ctl)

            return fo_with_init, (0,)
        raise ValueError(variant)

    if shape.kind == "prefill":
        if cfg.family == "audio":
            return (lambda params, tokens, frontend:
                    mod.prefill(params, cfg, tokens, frontend,
                                impl="xla")), ()
        if cfg.family == "vlm":
            return (lambda params, tokens, frontend:
                    mod.prefill(params, cfg, tokens,
                                prefix_embeds=frontend, impl="xla")), ()
        return (lambda params, tokens:
                mod.prefill(params, cfg, tokens, impl="xla")), ()

    # decode
    return (lambda params, cache, tokens, pos:
            mod.decode_step(params, cfg, cache, tokens, pos,
                            impl="xla")), (1,)


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             variant: str = "zo", with_roofline: bool = True,
             bf16_reduce: bool = False, shard_clients: bool = False,
             audit: bool = False, cost: bool = False) -> Dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    audit = audit_applies(shape_name, variant, audit)
    cell_id = make_cell_id(arch, shape_name, mesh_name, variant,
                           bf16_reduce=bf16_reduce,
                           shard_clients=shard_clients, audit=audit)
    cfg = registry.get_arch(arch)
    shape = SHAPES_BY_NAME[shape_name]
    out: Dict = {"cell": cell_id, "arch": arch, "shape": shape_name,
                 "mesh": mesh_name, "variant": variant,
                 "params_b": registry.count_params(cfg),
                 "active_params_b": registry.count_params(cfg, True)}

    reason = skip_reason(cfg, shape)
    if reason:
        out["status"] = "skipped"
        out["reason"] = reason
        return out

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        if shard_clients and shape.kind == "train" and variant == "zo":
            # jax-0.4.x workaround: partial-auto (manual clients + auto TP)
            # aborts XLA on large TP-sharded models, so the shard_map cell
            # compiles on the client-axes submesh (see mesh.client_submesh)
            from repro.launch.mesh import client_submesh
            mesh = client_submesh(mesh)
            out["client_submesh"] = True
        chips = mesh.devices.size
        specs, meta = input_specs(arch, shape_name, mesh, variant=variant)
        fn, donate = build_step(
            cfg, shape, meta["k"], variant,
            shard_clients_mesh=mesh if shard_clients
            and shape.kind == "train" and variant == "zo" else None,
            audit=audit)
        with shd.hints(mesh, bf16_reduce):
            lowered = jax.jit(fn, donate_argnums=donate).lower(
                **{k2: v for k2, v in specs.items()})
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        flops, bytes_a = rl.compiled_cost(compiled)
        coll, coll_by_op = rl.collective_bytes(compiled.as_text())
        out.update({
            "status": "ok",
            "chips": int(chips),
            "n_clients": meta["k"],
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes_per_device": int(ma.argument_size_in_bytes),
                "output_bytes_per_device": int(ma.output_size_in_bytes),
                "temp_bytes_per_device": int(ma.temp_size_in_bytes),
                "alias_bytes_per_device": int(ma.alias_size_in_bytes),
                "peak_bytes_per_device": int(ma.argument_size_in_bytes
                                             + ma.output_size_in_bytes
                                             + ma.temp_size_in_bytes
                                             - ma.alias_size_in_bytes),
            },
            "raw_cost_analysis": {"flops_per_device_scan_once": flops,
                                  "bytes_per_device_scan_once": bytes_a},
            "full_program_collectives": {"bytes_per_device_scan_once": coll,
                                         "by_op": coll_by_op},
        })

        if cost:
            # the run-time introspection view (repro.obs.hlo) of the same
            # compiled cell — census with per-op counts/group sizes on
            # top of the raw numbers above, printed compile-only
            from repro.obs import hlo as _hlo
            stats = _hlo.analyze_compiled(compiled)
            out["cost_stats"] = stats.to_dict()
            print(_hlo.describe(stats, indent="    "), flush=True)

        if with_roofline and not multi_pod:
            probes = rl.build_probes(cfg, shape, mesh, DTYPE)
            costs = [rl.run_probe(p, mesh, bf16_reduce) for p in probes]
            report = rl.aggregate(arch, shape, mesh_name, int(chips), costs,
                                  cfg)
            out["roofline"] = report.to_dict()
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        out["status"] = "failed"
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-4000:]
    out["wall_s"] = round(time.time() - t0, 2)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default all)")
    ap.add_argument("--shape", default=None,
                    help="one shape name (default all four)")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--variant", default="zo",
                    choices=["zo", "fo", "fo_sgd"])
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--bf16-reduce", action="store_true",
                    help="bf16 TP psums (§Perf beyond-paper optimization)")
    ap.add_argument("--shard-clients", action="store_true",
                    help="compile the shard_map'd ZO step (clients manual "
                         "over pod/data, model under GSPMD auto) — proves "
                         "the cross-device psum aggregate lowers on the "
                         "production mesh (train cells only)")
    ap.add_argument("--audit", action="store_true",
                    help="compile the eavesdropper-capture step variant "
                         "(repro.privacy observation capture as obs_* "
                         "metrics) — proves the privacy subsystem's "
                         "capture path lowers at production scale "
                         "(train cells only)")
    ap.add_argument("--cost", action="store_true",
                    help="print the repro.obs.hlo introspection of each "
                         "compiled cell (flops / memory / collective "
                         "census) and record it as cost_stats — "
                         "compile-only, nothing executes")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES_BY_NAME)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {r["cell"] for r in results if r.get("status") == "ok"
            or r.get("status") == "skipped"}

    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                mesh_name = "pod2x16x16" if multi else "pod16x16"
                cell_id = make_cell_id(arch, shape_name, mesh_name,
                                       args.variant,
                                       bf16_reduce=args.bf16_reduce,
                                       shard_clients=args.shard_clients,
                                       audit=args.audit)
                if cell_id in done:
                    print(f"[skip-done] {cell_id}", flush=True)
                    continue
                print(f"[cell] {cell_id} ...", flush=True)
                r = run_cell(arch, shape_name, multi, args.variant,
                             with_roofline=not args.no_roofline,
                             bf16_reduce=args.bf16_reduce,
                             shard_clients=args.shard_clients,
                             audit=args.audit, cost=args.cost)
                print(f"  -> {r['status']} ({r.get('wall_s', 0)}s)"
                      + (f" err={r.get('error', '')[:200]}"
                         if r["status"] == "failed" else ""), flush=True)
                results = [x for x in results if x["cell"] != cell_id]
                results.append(r)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    fail = sum(1 for r in results if r["status"] == "failed")
    print(f"\ndone: {ok} ok, {sk} skipped, {fail} failed -> {args.out}")


if __name__ == "__main__":
    main()
