"""Dry-run machinery smoke test (subprocess: needs its own device count).

The full 40-cell × 2-mesh sweep is the deliverable run separately
(results/dryrun.json); here we prove the machinery end-to-end on a small
fake-device mesh so the test suite stays fast and self-contained.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

import repro.launch.dryrun as dr
import repro.launch.mesh as mesh_mod

# shrink the production mesh to the 8 fake devices: (data=2, model=4)
def small_mesh(*, multi_pod=False):
    auto = jax.sharding.AxisType.Auto
    if multi_pod:
        return jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(auto,) * 3)
    return jax.make_mesh((2, 4), ("data", "model"), axis_types=(auto,) * 2)

dr.make_production_mesh = small_mesh

# shrink the shape cells and configs
from repro.configs import base
small_shapes = {
    "train_4k": base.ShapeConfig("train_4k", 64, 8, "train"),
    "prefill_32k": base.ShapeConfig("prefill_32k", 128, 4, "prefill"),
    "decode_32k": base.ShapeConfig("decode_32k", 128, 8, "decode"),
}
dr.SHAPES_BY_NAME.update(small_shapes)

from repro.models import registry
orig_get = registry.get_arch
registry.get_arch = lambda a: orig_get(a).reduced(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab_size=512, head_dim=16)

results = []
for shape in ("train_4k", "prefill_32k", "decode_32k"):
    for multi in (False, True):
        r = dr.run_cell("yi-6b", shape, multi, "zo",
                        with_roofline=(shape == "train_4k" and not multi))
        results.append({"cell": r["cell"], "status": r["status"],
                        "err": r.get("error", ""),
                        "has_roofline": "roofline" in r})
print("RESULTS" + json.dumps(results))
"""


@pytest.mark.slow
def test_dryrun_machinery_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS")][0]
    results = json.loads(line[len("RESULTS"):])
    assert len(results) == 6
    for r in results:
        assert r["status"] == "ok", r
    assert any(r["has_roofline"] for r in results)
