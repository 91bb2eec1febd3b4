"""Table II reproduction: memory overhead + per-iteration upload, OPT-125M.

Three sources, cross-checked:
  * analytic memory accounting (the paper's own FP16 method): params /
    grads / optimizer states / ZO's inference-level footprint;
  * the TRANSPORT registry: the communication column is computed from each
    mechanism's `Transport.payload_bits` / `bits_per_round` (uplink payload
    x clients), never hard-coded — including the conventional digital
    quantized baseline the paper compares against;
  * the COMPILER: XLA memory_analysis() of the compiled ZO step vs the FO
    SGD/Adam steps (run in a subprocess so device-count flags stay local).

    PYTHONPATH=src python -m benchmarks.table2_memory_comm [--compiled]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from repro.configs.base import PairZeroConfig, TransportConfig, ZOConfig
from repro.core import transport as tp
from repro.models import registry

FP16 = 2  # bytes, as in the paper's Table II

# Table II rows -> (transport mechanism, analytic memory multiplier vs the
# fp16 model). Memory: ZO is inference-level (params + ~5% activations);
# digital transmits quantized ZO updates so its footprint matches ZO's;
# FO SGD adds grads+acts, FO Adam adds the two moments on top.
ROWS = (
    ("Sign-pAirZero", "sign", 1.05),
    ("pAirZero", "analog", 1.05),
    ("Digital-ZO (8-bit)", "digital", 1.05),
    ("FO SGD", "fo", 2.5),
    ("FO Adam", "fo", 4.0),
)


def _fmt_bits(bits: int) -> str:
    if bits < 8 * 1024:
        return f"{bits} bits"
    if bits < 8e6:
        return f"{bits / 8e3:.2f} KB"
    return f"{bits / 8e6:.2f} MB"


def analytic_table(arch: str = "opt-125m", n_clients: int = 5) -> dict:
    cfg = registry.get_arch(arch)
    d = registry.count_params(cfg)
    model_mb = d * FP16 / 1e6
    pz = PairZeroConfig(n_clients=n_clients, zo=ZOConfig(n_perturb=1),
                        transport=TransportConfig())
    rows = {"model_size_mb": round(model_mb, 2), "params": d,
            "n_clients": n_clients}
    for label, mechanism, mem_mult in ROWS:
        t = tp.get(mechanism).from_config(TransportConfig(mechanism), pz)
        rows[label] = {
            "memory_mb": round(model_mb * mem_mult, 1),
            "upload_per_iter": _fmt_bits(t.payload_bits(pz, d)),
            "bits_per_round": t.bits_per_round(pz, d),
        }
    return rows


_COMPILED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from jax.sharding import AxisType
import repro.launch.dryrun as dr
from repro.configs import base

def small_mesh(*, multi_pod=False):
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

dr.make_production_mesh = small_mesh
dr.SHAPES_BY_NAME["train_4k"] = base.ShapeConfig("train_4k", 256, 8, "train")

out = {}
for variant in ("zo", "fo_sgd", "fo"):
    r = dr.run_cell("opt-125m", "train_4k", False, variant,
                    with_roofline=False)
    key = {"zo": "pAirZero(ZO)", "fo_sgd": "FO SGD", "fo": "FO Adam"}[variant]
    if r["status"] == "ok":
        m = r["memory"]
        out[key] = {
            "peak_bytes_per_device": m["peak_bytes_per_device"],
            "peak_mb_total_8dev": round(
                m["peak_bytes_per_device"] * 8 / 1e6, 1)}
    else:
        out[key] = {"error": r.get("error", "?")[:300]}
print("TABLE2" + json.dumps(out))
"""


def compiled_table() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"    # a fake-device rehearsal, never the chip
    res = subprocess.run([sys.executable, "-c", _COMPILED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    for line in res.stdout.splitlines():
        if line.startswith("TABLE2"):
            return json.loads(line[len("TABLE2"):])
    raise RuntimeError(res.stderr[-2000:])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compiled", action="store_true",
                    help="also measure via XLA memory_analysis (slow)")
    args = ap.parse_args()

    table = {"analytic": analytic_table()}
    a = table["analytic"]
    print(f"OPT-125M: {a['params'] / 1e6:.1f}M params, model "
          f"{a['model_size_mb']:.1f} MB (fp16), K={a['n_clients']} clients")
    for label, _, _ in ROWS:
        r = a[label]
        print(f"  {label:19s} memory ≈ {r['memory_mb']:8.1f} MB   "
              f"upload/iter = {r['upload_per_iter']:>10s}   "
              f"total/round = {_fmt_bits(r['bits_per_round'])}")

    if args.compiled:
        table["compiled"] = compiled_table()
        print("\ncompiled (XLA memory_analysis, bf16, 8-device mesh):")
        for k, v in table["compiled"].items():
            print(f"  {k:14s} {v}")

    os.makedirs("results", exist_ok=True)
    with open("results/table2_memory_comm.json", "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
