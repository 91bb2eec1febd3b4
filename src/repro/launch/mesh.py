"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across two pods; the
"pod" axis extends the client/data-parallel dimension (pAirZero clients ≡
(pod, data) groups; only scalar psums ever cross the pod boundary, which is
exactly what makes the paper's scheme attractive at multi-pod scale: DCI/ICI
bandwidth between pods is never on the critical path of a ZO round).

`make_production_mesh` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
ordinary training/serving builds the mesh from the real device set.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh for CPU tests/examples (same axis names)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_client_mesh(spec: str = "auto", n_clients: int = 0):
    """Client mesh for the shard_map'd train step, from a CLI spec string.

    Spellings:
      "auto"  — all local devices on a ('data',) axis; when `n_clients` is
                given, uses the largest divisor of n_clients that fits the
                device count (pAirZero clients split evenly or not at all).
      "8"     — ('data',) axis of exactly 8 devices.
      "2x8"   — ('pod', 'data') = (2, 8): 16 devices, pod-major client ids
                (matching how PartitionSpec(('pod','data')) tiles the
                client dim).

    The mesh carries only client axes — `runtime.sharding` treats a missing
    'model' axis as TP of 1, so the same param/batch rules apply unchanged.
    """
    import numpy as np

    devices = jax.devices()
    if spec == "auto":
        n = len(devices)
        if n_clients:
            while n > 1 and n_clients % n != 0:
                n -= 1
        shape, axes = (n,), ("data",)
    elif "x" in spec:
        pod, data = (int(v) for v in spec.split("x"))
        shape, axes = (pod, data), ("pod", "data")
    else:
        shape, axes = (int(spec),), ("data",)
    want = int(np.prod(shape))
    if want > len(devices):
        raise ValueError(f"mesh spec {spec!r} wants {want} devices but only "
                         f"{len(devices)} are visible (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={want} "
                         "for a CPU mesh)")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices[:want]).reshape(shape), axes)


def client_submesh(mesh):
    """The (pod, data) client-axes view of a production mesh: one device
    column along 'model'.

    Used by `dryrun --shard-clients`: jax 0.4.x's partial-auto shard_map
    (manual clients + auto TP) trips an XLA manual-subgroup check on
    large TP-sharded models, and the failure is a process abort rather
    than a catchable error. Compiling the shard_map'd step on the client
    submesh proves the cross-device psum + client fan-out lower at
    production client counts; TP stays a GSPMD-auto concern of the
    standard cells until the upstream partitioner handles the mix.
    """
    import numpy as np

    from jax.sharding import Mesh
    if "model" not in mesh.axis_names:
        return mesh
    idx = tuple(0 if a == "model" else slice(None)
                for a in mesh.axis_names)
    names = tuple(a for a in mesh.axis_names if a != "model")
    return Mesh(np.asarray(mesh.devices)[idx], names)


def n_clients(mesh) -> int:
    """pAirZero clients ≡ product of the (pod, data) axes."""
    k = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            k *= mesh.shape[a]
    return k
