"""Crash-safe checkpointing for federated ZO training.

ZO state is tiny by construction: (params, step, base seed, spent DP budget,
optional FO optimizer state). Saves are atomic (write to a temp dir, fsync,
rename) with a CRC-32 manifest so a torn write is detected at restore instead
of silently resuming from garbage. Privacy accounting is part of the state —
a crash can never reset the spent (ε, δ) budget.

Layout:
  <dir>/step_<N>/arrays.npz      one entry per pytree leaf ("path" keys)
  <dir>/step_<N>/manifest.json   {step, extra, crc32s, leaf paths/treedef}
"""
from __future__ import annotations

import json
import os
import shutil
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.obs import spans as ob

PyTree = Any


def _leaf_paths(tree: PyTree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return names, leaves, treedef


def save(directory: str, step: int, params: PyTree,
         extra: Optional[Dict] = None, keep: int = 3) -> str:
    """Atomically persist (params, step, extra). Returns the final path."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)

    names, leaves, _ = _leaf_paths(params)
    arrays = {n: np.asarray(leaf)
              for n, leaf in zip(names, leaves, strict=True)}
    npz_path = os.path.join(tmp, "arrays.npz")
    np.savez(npz_path, **arrays)

    crcs = {n: zlib.crc32(a.tobytes()) for n, a in arrays.items()}
    manifest = {
        "step": int(step),
        "extra": extra or {},
        "crc32": crcs,
        "dtypes": {n: str(a.dtype) for n, a in arrays.items()},
        "shapes": {n: list(a.shape) for n, a in arrays.items()},
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())

    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    _retain(directory, keep)
    return final


def _retain(directory: str, keep: int) -> None:
    ckpts = sorted(d for d in os.listdir(directory)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    for stale in ckpts[:-keep]:
        shutil.rmtree(os.path.join(directory, stale))


@jax.jit
def _device_snapshot(params: PyTree) -> PyTree:
    """Bit-exact on-device copy of the pytree into fresh (non-donated)
    buffers. The training carry is buffer-donated through the next chunk,
    so snapshotting the *carry itself* would either block the next dispatch
    (sync device_get) or race the donation; copying first decouples the
    checkpoint's device→host transfer from the training stream entirely.
    `optimization_barrier` (not a bare identity) defeats jit's input→output
    forwarding fast path, which would hand back the original buffers."""
    return jax.lax.optimization_barrier(params)


class AsyncCheckpointer:
    """Non-blocking checkpointing: double-buffered snapshot, write off-thread.

    Default (`double_buffer=True`) boundary cost on the training thread is
    one async dispatch: the params are copied on-device into fresh buffers,
    the device→host transfer is started with `copy_to_host_async`, and the
    worker thread materializes the host copy (blocking only itself until
    the transfer lands) before serializing + CRC + fsync. The donated carry
    is never touched after dispatch, so the next chunk launches without
    waiting for the snapshot — the historical synchronous `device_get`
    serialized compute-finish + D2H onto the training thread.

    `double_buffer=False` keeps that historical synchronous snapshot (the
    measurement baseline). `stall_s` accumulates the training-thread time
    spent inside `save()` either way, so the boundary stall attributable to
    the snapshot is directly comparable across modes. `wait()` joins the
    in-flight write (writes never interleave).

    Degradation contract: `save` is atomic at the filesystem level AND
    best-effort at the run level. A failing write is retried
    `write_retries` times with backoff (`retry` spans via
    repro.runtime.inject); if it still fails the failure is swallowed,
    counted in `write_failures`, and the run keeps its last good
    checkpoint instead of aborting (resume picks it up via
    `latest_valid`). A failing snapshot dispatch likewise skips the
    boundary (`snapshot_failures`) rather than killing training.
    `injector` (repro.runtime.FaultInjector) arms the `ckpt_snapshot` /
    `ckpt_write` sites; its `torn_write` mode truncates the just-written
    `arrays.npz` to simulate bitrot that `restore` must reject and
    `latest_valid` must skip.
    """

    def __init__(self, directory: str, keep: int = 3,
                 double_buffer: bool = True,
                 tracer: ob.Tracer = ob.NULL_TRACER,
                 injector: Optional[Any] = None,
                 write_retries: int = 3):
        self.directory = directory
        self.keep = keep
        self.double_buffer = double_buffer
        self.stall_s = 0.0
        self.write_failures = 0
        self.snapshot_failures = 0
        self.retries: Dict[str, int] = {}
        self.write_retries = write_retries
        self._thread = None
        self._tracer = tracer
        self._injector = injector

    def _save_retrying(self, step: int, host_params: PyTree,
                       extra: Optional[Dict]) -> None:
        """save() with bounded retry + keep-last-good on final failure."""
        from repro.runtime import inject as inj

        def attempt():
            torn = None
            if self._injector is not None:
                torn = self._injector.fire("ckpt_write")
            path = save(self.directory, step, host_params, extra=extra,
                        keep=self.keep)
            if torn == "torn_write":
                tear_checkpoint(path)

        try:
            inj.with_retries(attempt, site="ckpt_write",
                             attempts=self.write_retries,
                             tracer=self._tracer, retries=self.retries)
        except Exception as exc:  # noqa: BLE001 - keep-last-good
            self.write_failures += 1
            self._tracer.instant("ckpt_write_failed", step=step,
                                 error=type(exc).__name__)

    def _write(self, step: int, snap: PyTree, extra: Optional[Dict]) -> None:
        with self._tracer.span("ckpt_write", step=step):
            host_params = jax.tree_util.tree_map(lambda a: np.asarray(a),
                                                 snap)
            self._save_retrying(step, host_params, extra)

    def _write_host(self, step: int, host_params: PyTree,
                    extra: Optional[Dict]) -> None:
        with self._tracer.span("ckpt_write", step=step):
            self._save_retrying(step, host_params, extra)

    def save(self, step: int, params: PyTree,
             extra: Optional[Dict] = None) -> None:
        import threading
        import time

        with self._tracer.annotation("ckpt_snapshot", step=step):
            t0 = time.perf_counter()
            self.wait()
            try:
                if self._injector is not None:
                    self._injector.fire("ckpt_snapshot")
                if self.double_buffer and any(
                        isinstance(leaf, jax.Array)
                        for leaf in jax.tree_util.tree_leaves(params)):
                    snap = _device_snapshot(params)
                    for leaf in jax.tree_util.tree_leaves(snap):
                        leaf.copy_to_host_async()
                    self._thread = threading.Thread(
                        target=self._write, args=(step, snap, extra),
                        daemon=True)
                else:
                    host_params = jax.tree_util.tree_map(   # sync D2H
                        lambda a: np.asarray(a), params)       # baseline
                    self._thread = threading.Thread(
                        target=self._write_host,
                        args=(step, host_params, extra), daemon=True)
                self._thread.start()
            except Exception as exc:  # noqa: BLE001 - skip the boundary
                self.snapshot_failures += 1
                self._tracer.instant("ckpt_skipped", step=step,
                                     error=type(exc).__name__)
            t1 = time.perf_counter()
        self.stall_s += t1 - t0
        # span == the exact stall_s increment (same endpoints): the
        # training-thread cost of dispatching this snapshot
        self._tracer.add_span("ckpt_snapshot", t0, t1, step=step)

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None


def latest(directory: str) -> Optional[str]:
    """Path of the newest step_* checkpoint (no integrity check)."""
    if not os.path.isdir(directory):
        return None
    ckpts = sorted(d for d in os.listdir(directory)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    return os.path.join(directory, ckpts[-1]) if ckpts else None


def valid_checkpoint(path: str) -> bool:
    """Whether `path` holds a complete, CRC-consistent checkpoint.

    Tolerant by design: any missing/undecodable manifest, unreadable or
    truncated npz, missing leaf or CRC mismatch makes the checkpoint
    invalid rather than raising — `latest_valid` uses this to fall back
    past torn writes.
    """
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        with np.load(os.path.join(path, "arrays.npz")) as data:
            for n, crc in manifest["crc32"].items():
                if n not in data.files:
                    return False
                if zlib.crc32(data[n].tobytes()) != int(crc):
                    return False
        return True
    except Exception:  # noqa: BLE001 - any damage means "not valid"
        return False


def latest_valid(directory: str) -> Optional[str]:
    """Path of the newest checkpoint that passes full CRC validation.

    Walks step_* newest-first, skipping torn/corrupt ones (a SIGKILL mid
    `os.rename`, simulated bitrot, a half-written npz) — the crash-
    consistent resume entry point: the atomic save protocol plus this
    fallback guarantee a resumable state whenever ANY save completed.
    """
    if not os.path.isdir(directory):
        return None
    ckpts = sorted((d for d in os.listdir(directory)
                    if d.startswith("step_") and not d.endswith(".tmp")),
                   reverse=True)
    for name in ckpts:
        path = os.path.join(directory, name)
        if valid_checkpoint(path):
            return path
    return None


def tear_checkpoint(path: str) -> None:
    """Truncate a checkpoint's arrays.npz in half (simulated torn write).

    The result keeps its manifest, so naive `latest` still returns it —
    `valid_checkpoint` must reject it and `latest_valid` must fall back
    to the previous intact checkpoint. Used by the chaos harness and the
    `torn_write` injection mode.
    """
    npz = os.path.join(path, "arrays.npz")
    size = os.path.getsize(npz)
    with open(npz, "r+b") as f:
        f.truncate(max(size // 2, 1))
        f.flush()
        os.fsync(f.fileno())


def restore(path: str, params_like: PyTree
            ) -> Tuple[PyTree, int, Dict]:
    """Load a checkpoint into the structure of `params_like` (verifying
    integrity). Returns (params, step, extra)."""
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))

    names, leaves, treedef = _leaf_paths(params_like)
    restored = []
    for n, like in zip(names, leaves, strict=True):
        arr = data[n]
        crc = zlib.crc32(arr.tobytes())
        if crc != manifest["crc32"][n]:
            raise IOError(f"checkpoint corruption detected in leaf {n!r} "
                          f"(crc {crc} != {manifest['crc32'][n]})")
        if list(arr.shape) != list(like.shape):
            raise ValueError(f"leaf {n!r} shape {arr.shape} != expected "
                             f"{like.shape}")
        restored.append(jax.numpy.asarray(arr).astype(like.dtype))
    params = jax.tree_util.tree_unflatten(treedef, restored)
    return params, int(manifest["step"]), manifest["extra"]
