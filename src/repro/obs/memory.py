"""Device-memory watermark sampling (the trilemma's memory axis, per run).

The paper's memory claim is inference-level footprint; the benchmarks
measure it offline (`benchmarks/kernel_memory.py`). This module measures
it *per run*: periodic samples of live device bytes at chunk boundaries,
folded into a `peak_bytes` watermark surfaced on `RunResult` and in every
trilemma-ledger row.

Two sources, best first:

  * `device.memory_stats()` — the fullest device's `peak_bytes_in_use`
    (buffers) plus `peak_bytes_reserved` (the region held for programs'
    temporaries), when the backend reports them (TPU/GPU; CPU returns
    None) — the reading of the benchmark's `peak_hbm_gb`;
  * sum of `a.nbytes` over `jax.live_arrays()` — live-buffer bytes at the
    sample instant (always available; an instantaneous view, so the
    boundary cadence is what makes it a useful watermark).

Sampling is host-side and read-only — it never touches the traced program
(structural-neutrality pin: telemetry-off runs are bit-identical).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax

from repro.obs import spans


def live_buffer_bytes(arrays=None) -> int:
    """Total bytes of live jax arrays on this process's devices.

    Donated carry buffers can still appear in `jax.live_arrays()` at a
    chunk-boundary sample even though their storage is gone (the Python
    handle outlives the donation), so anything whose `.is_deleted()` is
    true is skipped — counting it would double-book the carry against its
    replacement. `arrays` defaults to the live-array walk; tests pass an
    explicit list to pin the skip.
    """
    total = 0
    if arrays is None:
        arrays = jax.live_arrays()
    for a in arrays:
        try:
            if a.is_deleted():
                continue
            total += int(a.nbytes)
        except Exception:  # deleted/donated buffers race the walk
            continue
    return total


def device_peak_bytes() -> Optional[int]:
    """What the runtime has held at its peak on the fullest device:
    buffers (`peak_bytes_in_use`) plus the region it reserves for
    programs' temporaries (`peak_bytes_reserved`, which the buffer count
    leaves out on TPU), or None when the backend (e.g. CPU) reports no
    memory stats."""
    peak = None
    for dev in jax.devices():
        stats = dev.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            held = int(stats["peak_bytes_in_use"]) \
                + int(stats.get("peak_bytes_reserved", 0))
            peak = held if peak is None else max(peak, held)
    return peak


class MemoryWatermark:
    """Periodic device-memory sampler with a running peak.

    `sample_every` is a round period gating `due(t)`; the driver samples
    at chunk boundaries that cross it (cadence 0: sampling never realigns
    chunk boundaries, so it can never change compiled chunk shapes).
    """

    def __init__(self, sample_every: int = 32):
        self.sample_every = max(1, int(sample_every))
        self.peak_bytes = 0
        self.samples: List[Tuple[int, int]] = []   # (round, bytes)
        self._last_t: Optional[int] = None

    def due(self, t: int) -> bool:
        """Whether round t crosses the sampling period since last sample."""
        return self._last_t is None or t - self._last_t >= self.sample_every

    def sample(self, t: int,
               tracer: spans.Tracer = spans.NULL_TRACER) -> int:
        """Take one sample at round t; returns the bytes observed and
        advances the `peak_bytes` watermark (also emitted as a trace
        counter event for the timeline view)."""
        peak = device_peak_bytes()
        b = peak if peak is not None else live_buffer_bytes()
        self.peak_bytes = max(self.peak_bytes, b)
        self.samples.append((int(t), int(b)))
        self._last_t = int(t)
        tracer.counter("device_bytes", b, round=int(t))
        return b
