"""Observability: spans, watermarks, ledger, device profile, HLO, health.

Host-side pillars, all structurally neutral (telemetry off runs the
bit-exact historical program — pinned in tests/test_obs.py):

  1. **Span timeline** (`repro.obs.spans`) — a `Tracer` of nested
     wall-clock spans instrumented into the driver (`fedsim.Experiment`),
     `ChunkPrefetcher` kick/stall, chunk prep, `BatchStager`,
     `AsyncCheckpointer` snapshot/write, schedule solves, dispatch and
     metric flushes; exported as Chrome trace-event JSON
     (`train.py --trace-out trace.json`, loadable in Perfetto), and
     mirrored as profiler annotations into any running `jax.profiler`
     capture. Per-chunk stall spans use the SAME perf_counter endpoints
     as the legacy `prep_stall_s`/`ckpt_stall_s` scalars, which are kept
     as derived sums.
  2. **Compilation & memory watermarks** (`repro.obs.retrace`,
     `repro.obs.memory`) — build/retrace counters inside the memoized
     step/executor factories (surfaced as `RunResult.compile_stats`; a
     warm rerun must show zero) and periodic device-memory sampling at
     chunk boundaries (`RunResult.peak_bytes`).
  3. **Trilemma ledger** (`repro.obs.ledger`) — a `MetricsSink` round
     hook streaming one JSONL record per round: loss, uplink bits
     (the driver's own `transport.uplink_bits_total` accounting),
     cumulative (ε, δ) spend, peak memory, wall time
     (`train.py --metrics-out metrics.jsonl`).

And the device-visible half:

  4. **Profiler merge** (`repro.obs.profile`) — opt-in `jax.profiler`
     capture whose device-op events are aligned onto the tracer's
     perf_counter epoch via a TraceAnnotation anchor and merged into the
     same Chrome trace (`train.py --profile-out`).
  5. **HLO introspection** (`repro.obs.hlo`) — compiler-reported FLOPs,
     bytes, peak memory and a structured collective census read off the
     memoized executors' compiled programs (AOT, never executed);
     surfaced as `RunResult.cost_stats` (the Telemetry `cost` flag),
     the `bench_engine/v3` per-engine breakdown, and `dryrun --cost`.
  6. **Run health** (`repro.obs.health`) — a duck-typed `HealthMonitor`
     round hook (NaN/divergence/plateau detectors) with a
     warn/checkpoint-then-abort policy; aborts land on `RunResult` so
     `--audit` consumes the realized (shorter) privacy spend.

`Telemetry` bundles the per-run pieces; `Telemetry.off()` (the default
everywhere) carries the shared no-op tracer and no sampler, so the
instrumented call sites cost one no-op method call when disabled.
tools/check_trace.py validates the artifact schemas in CI.
"""
from __future__ import annotations

from typing import Optional

from repro.obs import health, hlo, ledger, memory, profile, retrace, spans
from repro.obs.health import HealthAbort, HealthMonitor
from repro.obs.hlo import CostStats
from repro.obs.ledger import MetricsSink, final_row, read_ledger
from repro.obs.memory import MemoryWatermark
from repro.obs.profile import ProfilerSession
from repro.obs.spans import NULL_TRACER, NullTracer, Tracer

__all__ = [
    "Telemetry", "Tracer", "NullTracer", "NULL_TRACER", "MemoryWatermark",
    "MetricsSink", "read_ledger", "final_row",
    "HealthMonitor", "HealthAbort", "ProfilerSession", "CostStats",
    "health", "hlo", "ledger", "memory", "profile", "retrace", "spans",
]


class Telemetry:
    """Per-run observability bundle: tracer + memory sampler + cost flag.

    Pass one to `fedsim.Experiment(telemetry=...)` / `fedsim.run(...)`.
    The default (`Telemetry.off()`) is inert: the shared `NULL_TRACER`,
    no memory sampling, no cost analysis — the historical program, bit
    for bit. `cost=True` asks the driver to read the compiled executor's
    cost/memory/collective analysis into `RunResult.cost_stats` after
    the run (AOT introspection under `retrace.suspended()`: compile-only,
    numerically passive, invisible to the compile-watermark pins).
    """

    def __init__(self, tracer: Optional[Tracer] = None,
                 memory: Optional[MemoryWatermark] = None,
                 cost: bool = False):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if self.tracer.enabled and self.tracer.annotate is None:
            # a recording tracer mirrors its spans into any running
            # jax.profiler capture (the null tracer opens nothing)
            self.tracer.annotate = profile.trace_annotation
        self.memory = memory
        self.cost = bool(cost)

    @property
    def enabled(self) -> bool:
        """Whether any pillar is live (tracer, sampler, or cost stats)."""
        return self.tracer.enabled or self.memory is not None or self.cost

    @classmethod
    def on(cls, memory_sample_every: int = 32,
           cost: bool = False) -> "Telemetry":
        """Full telemetry: recording tracer + memory watermark sampler
        (+ optionally the post-run compiled-cost analysis)."""
        return cls(tracer=Tracer(),
                   memory=MemoryWatermark(memory_sample_every), cost=cost)

    @classmethod
    def off(cls) -> "Telemetry":
        """Inert telemetry (the default): no recording, no sampling."""
        return cls()
