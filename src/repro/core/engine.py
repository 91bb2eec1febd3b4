"""Round executors: per-round dispatch (loop) and scan-over-rounds (scan).

The per-round loop dispatches one jitted step per round from Python: every
round pays a kernel-launch round trip and a blocking metric sync. But a
pAirZero trajectory is a *pure function* of (params, seeds, schedule): the
per-round control — c(t), σ(t), the broadcast seed, the channel-noise key,
the survival mask — is all known the moment the base station solves the
power schedule. So we precompute the whole control trace as stacked device
arrays once per chunk; `ScanExecutor` compiles `lax.scan` over the existing
ZO step (one dispatch per `chunk_rounds` rounds, parameters donated through
the whole chunk, metrics returned stacked) while `LoopExecutor` walks the
same trace one jitted call at a time. Both consume identical inputs, so the
driver in fedsim is engine-agnostic.

The host stays in charge of everything a real server does *between* chunks:
DP accounting (the run's Transport prices each round; the hard privacy stop
truncates the chunk at the first round that would overspend), eval,
checkpointing, and fault-trace generation (the FaultModel RNG is stateful,
so masks are drawn host-side in round order — identical for both engines).

Invariant: for the ZO variants (analog/sign), `engine="scan"` and
`engine="loop"` produce bit-identical loss trajectories at fixed seed
(tests/test_engine.py enforces this). The scan body is the *same* step
function the loop jits; only the dispatch granularity changes. The FO
baseline agrees to fp tolerance only (XLA fuses value_and_grad differently
under scan).
"""
from __future__ import annotations

import functools
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import transport as tp
from repro.core import zo
from repro.core.dp import PrivacyAccountant
from repro.obs import retrace
from repro.obs import spans as ob
from repro.runtime.fault import combined_mask

PyTree = Any


# ---------------------------------------------------------------------------
# Control-trace precomputation (host → device, once per chunk)
# ---------------------------------------------------------------------------

@dataclass
class ControlTrace:
    """Stacked per-round control for rounds [t0, t0+R) plus the host-side
    accounting view of the same schedule slice.

    `ctl` mirrors `pairzero.make_control` exactly, with a leading round axis:
      seed [R] u32, c [R] f32, sigma [R,K] f32, n0 [R] f32, mask [R,K] f32,
      g [R,K] f32 (per-client cos θ CSI factors from the channel trace),
      noise_bits [R,2] u32.

    `host_masks` is the host-side numpy view of ctl["mask"] — the driver's
    uplink-bit accounting reads it instead of syncing the device copy back.
    `host_stale` is the matching [R, K] view of ctl["dsync_stale"] when a
    desync model is active (None otherwise) — the ledger's k_sync column
    derives from it the same way.
    """
    t0: int
    ctl: Dict[str, jnp.ndarray]
    acct_cost: np.ndarray     # [R] per-round DP cost (Transport.round_dp_costs)
    charged: bool             # whether these rounds cost privacy at all
    host_masks: Optional[np.ndarray] = None   # [R, K] survival view
    host_stale: Optional[np.ndarray] = None   # [R, K] desync stale view

    def __len__(self) -> int:
        return int(self.ctl["seed"].shape[0])

    def rows(self, n: int) -> Dict[str, jnp.ndarray]:
        """First n rounds of the stacked control block (for a truncated
        chunk after a privacy stop)."""
        if n == len(self):
            return self.ctl
        return {k: v[:n] for k, v in self.ctl.items()}


@jax.jit
def _noise_bits_trace(key_base: jax.Array, ts: jnp.ndarray) -> jnp.ndarray:
    """[R, 2] key_data(fold_in(key_base, t)) for each round t."""
    return jax.vmap(
        lambda t: jax.random.key_data(jax.random.fold_in(key_base, t)))(ts)


def build_trace(schedule, pz, t0: int, t1: int, *,
                transport=None, fault=None, elastic=None,
                channel=None, ctl_sharding=None,
                behavior=None, defense=None,
                desync=None, tracer: ob.Tracer = ob.NULL_TRACER
                ) -> ControlTrace:
    """Precompute the control trace for rounds [t0, t1).

    Mask generation consumes the (stateful) FaultModel RNG in round order, so
    calling build_trace over consecutive chunks replays the identical fault
    trace the per-round loop would draw. DP accounting (per-round cost,
    whether the rounds are charged at all) is delegated to the Transport.

    `channel` is the horizon's realized ChannelTrace (repro.channel); its
    per-round views ride device-resident inside the scanned chunk: cos θ
    CSI factors as ctl["g"], deep-fade participation folded into
    ctl["mask"] alongside the fault/elastic survival masks. None (or a
    perfect-CSI, no-outage trace) reproduces the historical control block
    bit for bit.

    The whole control block is staged host-side and shipped in ONE
    `jax.device_put` of the dict — with `ctl_sharding` (a pytree of
    NamedShardings from `runtime.sharding.control_sharding`) the block
    lands replicated across the client mesh at transfer time.

    `behavior` (repro.byzantine.ClientBehavior) adds its [K] malicious-
    cohort indicator as a per-round ctl["byz"] row — the mask rides the
    same device-resident path as survival/outage, so the attacked step is
    one traced program across engines. `defense` (repro.byzantine.Defense)
    takes over the DP pricing (a transmit clip tightens the Lemma-1
    sensitivity; delegation keeps the accounting Transport-owned). None
    for either reproduces the historical trace bit for bit.

    `desync` (repro.runtime.DesyncModel) adds the synchronization-failure
    rows: the lagged broadcast seed ctl["dsync_seed"] plus the per-client
    ctl["dsync_stale"] / ctl["dsync_a"] / ctl["dsync_frame"] rows. The
    per-round draws are seeded by (desync seed, round), so the trace is
    invariant to chunk boundaries and resume points. None keeps the rows
    absent — the historical block, bit for bit.

    `tracer` times the two places the build leaves the host: the wait for
    the eager round-seed and noise-key ops (`prep_device_wait`; they queue
    behind whatever the device is running) and the control block's
    transfer (`h2d_put`).
    """
    if transport is None:
        transport = tp.resolve(pz)
    k = pz.n_clients
    rounds = int(t1 - t0)
    ts = np.arange(t0, t1, dtype=np.int64)

    # vectorized zo.round_seed: fmix32 is elementwise over the round index
    seeds = zo.round_seed(pz.seed, jnp.asarray(ts, jnp.uint32))

    key_base = jax.random.key(pz.seed ^ 0x5EED)
    noise_bits = _noise_bits_trace(key_base, jnp.asarray(ts, jnp.int32))

    if fault is None and elastic is None:
        masks = np.ones((rounds, k), dtype=np.float32)
    else:
        masks = np.stack([combined_mask(int(t), fault, elastic, n_clients=k)
                          for t in ts])

    if channel is None:
        g = np.ones((rounds, k), dtype=np.float32)
    else:
        g = np.asarray(np.cos(channel.phase[t0:t1]), dtype=np.float32)
        participation = np.asarray(channel.participation[t0:t1], np.float32)
        survival = masks                 # fault/elastic view, pre-outage
        masks = masks * participation
        # outage x faults can zero a whole round even though each mask
        # alone never does; re-admit the strongest FAULT-SURVIVING client
        # that round (combined_mask's never-empty convention, pilot-
        # informed — a crashed client must never be resurrected)
        empty = np.flatnonzero(masks.sum(axis=1) == 0)
        if empty.size:
            h_rows = np.asarray(channel.h[t0:t1])[empty] * survival[empty]
            masks[empty, np.argmax(h_rows, axis=1)] = 1.0

    c_slice = np.asarray(schedule.c[t0:t1], dtype=np.float64)
    sigma_slice = np.asarray(schedule.sigma[t0:t1], dtype=np.float64)
    masks = np.asarray(masks, dtype=np.float32)
    with tracer.span("prep_device_wait", t0=t0, t1=t1):
        jax.block_until_ready((seeds, noise_bits))
    host_ctl = {
        "seed": np.asarray(seeds, dtype=np.uint32),
        "c": np.asarray(c_slice, dtype=np.float32),
        "sigma": np.asarray(sigma_slice, dtype=np.float32),
        "n0": np.full((rounds,), schedule.n0, dtype=np.float32),
        "mask": masks,
        "g": np.asarray(g, dtype=np.float32),
        "noise_bits": np.asarray(noise_bits, dtype=np.uint32),
    }
    if behavior is not None:
        host_ctl["byz"] = np.broadcast_to(
            behavior.client_mask(k)[None, :], (rounds, k)).copy()
    host_stale = None
    if desync is not None:
        from repro.runtime import desync as ds
        dsync_rows, host_stale = ds.control_rows(desync, pz.seed, t0, t1, k)
        host_ctl.update(dsync_rows)
    # one transfer for the whole block (sharded placement, when requested,
    # happens here rather than as a post-hoc reshard)
    with tracer.span("h2d_put", t0=t0, t1=t1):
        ctl = jax.device_put(host_ctl, ctl_sharding)

    if defense is not None:
        charged = bool(defense.charges_privacy(transport, schedule, pz))
        acct_cost = defense.round_dp_costs(transport, schedule, t0, t1, pz) \
            if charged else np.zeros(rounds)
    else:
        charged = bool(transport.charges_privacy(schedule, pz))
        acct_cost = transport.round_dp_costs(schedule, t0, t1, pz) \
            if charged else np.zeros(rounds)
    return ControlTrace(t0=t0, ctl=ctl, acct_cost=acct_cost, charged=charged,
                        host_masks=masks, host_stale=host_stale)


def affordable_rounds(accountant: PrivacyAccountant, trace: ControlTrace,
                      slack: float = 1e-6) -> int:
    """How many leading rounds of `trace` the DP budget affords.

    Pure lookahead — charges nothing. One `np.cumsum` over the cost vector,
    seeded with the current ledger so the accumulation is the same float64
    left fold the historical per-round `would_violate` loop performed
    (cumsum is strictly sequential): a mid-chunk trip lands on the
    bit-identical round under either engine and either implementation
    (tests/test_engine.py pins this against the reference loop).
    """
    if not trace.charged:
        return len(trace)
    costs = np.asarray(trace.acct_cost, dtype=np.float64)
    # cum[r] = ledger after charging rounds < r, starting from `spent`
    cum = np.cumsum(np.concatenate(([accountant.spent], costs)))
    over = np.flatnonzero(cum[1:] > accountant.budget * (1.0 + slack))
    return int(over[0]) if over.size else len(trace)


def charge_rounds(accountant: PrivacyAccountant, trace: ControlTrace,
                  n: int) -> None:
    """Charge the accountant for the first n rounds of the trace (what the
    loop does before each step, batched into one `spend_batch` call with
    the identical sequential-accumulation semantics)."""
    if not trace.charged or n <= 0:
        return
    accountant.spend_batch(np.asarray(trace.acct_cost[:n], dtype=np.float64))


# ---------------------------------------------------------------------------
# Batch staging (host → device, one transfer per chunk)
# ---------------------------------------------------------------------------

class BatchStager:
    """Preallocated, slot-rotated host staging for chunk batches.

    Per (slot, chunk shape) this keeps ONE host buffer per batch key; each
    chunk is stacked *into* the buffer in place and shipped with a single
    `jax.device_put` of the whole dict, carrying the target NamedShardings
    when a mesh is active — sharded placement happens at transfer time, not
    as a post-hoc reshard, and no per-key np.stack→jnp.asarray round trip
    ever materializes a second host copy.

    Slots exist because the prefetch thread prepares chunk i+1 while chunk
    i may still be in flight. NOTE the lifetime contract: on the CPU
    backend `device_put` may zero-copy ALIAS the host buffer, so staged
    arrays are valid only until their slot is rewritten (two `stage` calls
    later) — the driver guarantees safety by kicking chunk i+1's prep only
    after chunk i-1's execution has been synced (ChunkPrefetcher.kick);
    the belt-and-braces `block_until_ready` below additionally covers
    real-transfer backends where readiness lags the `device_put` call.
    """

    def __init__(self, pipeline, sharding_fn: Optional[Callable] = None,
                 slots: int = 2, tracer: ob.Tracer = ob.NULL_TRACER):
        self._pipeline = pipeline
        self._sharding_fn = sharding_fn
        self._slots: List[Dict] = [{"bufs": {}, "inflight": None}
                                   for _ in range(max(1, slots))]
        self._next = 0
        self._tracer = tracer

    def stage(self, t0: int, t1: int) -> Dict[str, jnp.ndarray]:
        """Stacked round batches [R, ...] for rounds [t0, t1), on device
        (labels dropped, exactly as the loop path feeds the step)."""
        with self._tracer.span("batch_stage", t0=t0, t1=t1):
            return self._stage(t0, t1)

    def _stage(self, t0: int, t1: int) -> Dict[str, jnp.ndarray]:
        slot = self._slots[self._next]
        self._next = (self._next + 1) % len(self._slots)
        if slot["inflight"] is not None:
            with self._tracer.span("prep_device_wait", t0=t0, t1=t1):
                jax.block_until_ready(slot["inflight"])  # buffer reusable
            slot["inflight"] = None
        with self._tracer.span("batch_gen", t0=t0, t1=t1):
            per_round = [self._pipeline.batch(int(t))
                         for t in range(t0, t1)]
        rounds = len(per_round)
        host: Dict[str, np.ndarray] = {}
        for k, first in per_round[0].items():
            if k == "labels":
                continue
            shape = (rounds,) + np.shape(first)
            buf = slot["bufs"].get(k)
            if buf is None or buf.shape != shape:
                buf = np.empty(shape, dtype=np.asarray(first).dtype)
                slot["bufs"][k] = buf
            for r, b in enumerate(per_round):
                buf[r] = b[k]
            host[k] = buf
        sharding = self._sharding_fn(host) if self._sharding_fn else None
        with self._tracer.span("h2d_put", t0=t0, t1=t1):
            out = jax.device_put(host, sharding)
        slot["inflight"] = out
        return out


def stack_batches(pipeline, t0: int, t1: int) -> Dict[str, jnp.ndarray]:
    """Stacked round batches [R, ...] for rounds [t0, t1) — one-shot
    convenience over `BatchStager` (no buffer reuse across calls)."""
    return BatchStager(pipeline, slots=1).stage(t0, t1)


# ---------------------------------------------------------------------------
# Chunk prefetch (host-side prep of chunk i+1 overlaps device compute of i)
# ---------------------------------------------------------------------------

class ChunkPrefetcher:
    """One-chunk-ahead host pipeline with an explicit safety handshake.

    `prepare(a, b)` does the host work for chunk [a, b) — control-trace
    build (which consumes the stateful FaultModel RNG, so chunks MUST be
    prepared in round order: one worker, submissions in sequence) plus
    batch staging. The driver calls `kick(i + 1)` only AFTER it has synced
    chunk i-1's metrics: chunk i-1's execution is then provably complete,
    so the stager slot that chunk shares with i+1 can be rewritten — this
    matters because `jax.device_put` may ZERO-COPY alias host buffers on
    the CPU backend, making "transfer complete" no guarantee that the
    execution stopped reading them.

    `get(i)` waits for the kicked prep (or runs it inline when nothing was
    kicked — chunk 0, or `overlap=False`); the wait time accumulates in
    `stall_s`, so the no-overlap control measures the full prep cost and
    the overlapped path only the residual.

    Telemetry: each prep runs inside a `chunk_prep` span (on the worker
    thread when kicked), every kick drops a `prefetch_kick` instant, and
    each `get` is a live `prep_stall` span whose recorded endpoints are
    the SAME perf_counter reads that feed `stall_s` — span sums equal the
    scalar exactly.

    Degradation: a kicked prep that died on the worker thread no longer
    aborts the run from `get()` — the failure is logged as a
    `prefetch_degraded` span and the prep is re-run inline ONCE (counted
    in `degraded`); only a second failure propagates. The inline re-run
    is deterministic because chunks are prepared in round order and an
    injected fault (`injector`, site "chunk_prep") fires at prep ENTRY —
    before the stateful FaultModel RNG is consumed.
    """

    def __init__(self, prepare: Callable[[int, int], Any],
                 bounds: Sequence[Tuple[int, int]], overlap: bool = True,
                 tracer: ob.Tracer = ob.NULL_TRACER,
                 injector: Optional[Any] = None):
        self._prepare = prepare
        self._bounds = list(bounds)
        self._overlap = overlap and len(self._bounds) > 0
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="chunk-prefetch") \
            if self._overlap else None
        self._fut: Optional[Future] = None
        self._fut_i = -1
        self._next = 0            # next chunk index the driver may get()
        self.stall_s = 0.0
        self.degraded = 0         # kicked preps recovered inline
        self._tracer = tracer
        self._injector = injector

    def _run_prepare(self, i: int, kicked: bool) -> Any:
        a, b = self._bounds[i]
        with self._tracer.span("chunk_prep", chunk=i, t0=a, t1=b,
                               kicked=kicked):
            if self._injector is not None:
                self._injector.fire("chunk_prep")
            return self._prepare(a, b)

    def kick(self, i: int) -> None:
        """Start chunk i's prep on the worker thread (no-op when overlap
        is off, i is out of range, or i was already kicked/consumed)."""
        if (self._overlap and self._fut is None and i == self._next
                and i < len(self._bounds)):
            self._fut_i = i
            self._tracer.instant("prefetch_kick", chunk=i)
            self._fut = self._pool.submit(self._run_prepare, i, True)

    def get(self, i: int) -> Any:
        """Prepared payload for chunk i (blocks; stall time recorded)."""
        assert i == self._next, "chunks must be consumed in order"
        self._next += 1
        with self._tracer.annotation("prep_stall", chunk=i):
            t0 = time.perf_counter()
            if self._fut is not None:
                assert self._fut_i == i
                try:
                    out = self._fut.result()
                except Exception as exc:  # noqa: BLE001 - degrade
                    self.degraded += 1
                    with self._tracer.span("prefetch_degraded", chunk=i,
                                           error=type(exc).__name__):
                        out = self._run_prepare(i, False)  # re-run, once
                self._fut = None
            else:
                out = self._run_prepare(i, False)
            t1 = time.perf_counter()
        self.stall_s += t1 - t0
        self._tracer.add_span("prep_stall", t0, t1, chunk=i)
        return out

    def close(self) -> None:
        if self._pool is not None:
            if self._fut is not None:              # drain an abandoned prep
                try:
                    self._fut.result()
                except Exception:
                    pass
                self._fut = None
            self._pool.shutdown(wait=True)
            self._pool = None


# ---------------------------------------------------------------------------
# Executors: per-round dispatch (loop) and chunked lax.scan (scan)
# ---------------------------------------------------------------------------

def _specs_sig(*trees) -> tuple:
    """Hashable shape/dtype signature of ShapeDtypeStruct (or array) trees —
    the memoization key for analysis-only AOT compiles."""
    sig = []
    for tree in trees:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        sig.append((str(treedef),
                    tuple((tuple(x.shape), str(x.dtype)) for x in leaves)))
    return tuple(sig)


class LoopExecutor:
    """Per-round dispatch over an already-jitted step — no chunk compile
    cost, and the bit-identity oracle for ScanExecutor.

    Consumes the same (trace rows, stacked batches) interface as the scan
    executor, so the driver in fedsim is engine-agnostic: loop and scan
    differ only in dispatch granularity, never in orchestration.
    """

    def __init__(self, step: Callable):
        self._step = step                   # jitted, carry donated
        self._aot: Dict[tuple, Any] = {}    # analysis-only compiles, by sig

    def aot_compiled(self, carry_spec: PyTree,
                     ctl_spec: Dict[str, Any],
                     batch_spec: Dict[str, Any]):
        """Compile (never run) the per-round step for these specs.

        Takes the same stacked trees `run()` consumes and slices one round
        off the stacks, so callers (repro.obs.hlo) stay engine-agnostic.
        The lowering re-enters the traced step body, so the retrace
        counters are suspended — introspection is not a driver recompile.
        Memoized per shape signature.
        """
        key = _specs_sig(carry_spec, ctl_spec, batch_spec)
        if key not in self._aot:
            def row(tree):
                return {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                        for k, v in tree.items()}
            with retrace.suspended():
                lowered = self._step.lower(carry_spec, row(batch_spec),
                                           row(ctl_spec))
            self._aot[key] = lowered.compile()
        return self._aot[key]

    def run(self, carry: PyTree, ctl_stack: Dict[str, jnp.ndarray],
            batch_stack: Dict[str, jnp.ndarray]
            ) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
        rounds = int(ctl_stack["seed"].shape[0])
        collected: Optional[Dict[str, list]] = None
        for r in range(rounds):
            ctl = {k: v[r] for k, v in ctl_stack.items()}
            batch = {k: v[r] for k, v in batch_stack.items()}
            carry, metrics = self._step(carry, batch, ctl)
            if collected is None:
                collected = {k: [] for k in metrics}
            for k, v in metrics.items():
                collected[k].append(v)    # device arrays — no per-round sync
        # stacked device-side; the driver's flush path converts to host in
        # ONE np.asarray per metric (for the 1-round spans the loop engine
        # runs on, that flush is immediate, so on_round stays live)
        metrics = {} if collected is None else \
            {k: jnp.stack(v) for k, v in collected.items()}
        return carry, metrics


@functools.lru_cache(maxsize=64)
def get_loop_executor(step: Callable) -> "LoopExecutor":
    """Executor cache keyed on the jitted step object (mirrors
    `get_executor`) so identical configs share one executor."""
    retrace.bump(retrace.LOOP_EXEC_BUILD)   # lru MISS: a fresh executor
    return LoopExecutor(step)


class ScanExecutor:
    """Compiles lax.scan over a per-round step; one program per chunk length.

    `step(carry, batch, ctl) -> (carry, metrics)` is the *same* function the
    per-round loop jits (ZO: carry = params; FO: carry = (params, opt_state)
    via an adapter in fedsim). The carry buffer is donated, so parameters
    live on device across the whole chunk — the MeZO in-place chain extended
    over rounds.

    unroll=None (default) fully unrolls each chunk: XLA then compiles the
    round body exactly as it compiles the standalone per-round jit, which is
    what makes engine="scan" *bitwise* identical to engine="loop" (a rolled
    while-loop body fuses with slightly different fp rounding on CPU).
    Compile time grows with chunk length; pass an int (e.g. unroll=1) for an
    O(1)-size rolled program that is numerically equivalent only up to fp
    rounding — the right trade once chunks are long and models are large.
    """

    def __init__(self, step: Callable, unroll: Optional[int] = None):
        @functools.partial(jax.jit, donate_argnums=(0,),
                           static_argnums=(3,))
        def chunk(carry, ctl_stack, batch_stack, _unroll):
            # trace-time side effect only: fires once per XLA compilation
            # of this chunk program, never on cached executions
            retrace.bump(retrace.CHUNK_TRACE)

            def body(c, xs):
                ctl, batch = xs
                return step(c, batch, ctl)
            return jax.lax.scan(body, carry, (ctl_stack, batch_stack),
                                unroll=_unroll)

        self._chunk = chunk
        self._unroll = unroll
        self._aot: Dict[tuple, Any] = {}    # analysis-only compiles, by sig

    def aot_compiled(self, carry_spec: PyTree,
                     ctl_spec: Dict[str, Any],
                     batch_spec: Dict[str, Any]):
        """Compile (never run) the chunk program for these specs — the
        exact program `run()` would dispatch for stacks of this shape,
        including the mesh shardings riding on the specs. Lowering
        re-enters the traced chunk body, so the retrace counters are
        suspended (introspection must not perturb the cold/warm count
        pins). Memoized per shape signature.
        """
        rounds = int(ctl_spec["seed"].shape[0])
        unroll = rounds if self._unroll is None else min(self._unroll, rounds)
        key = _specs_sig(carry_spec, ctl_spec, batch_spec)
        if key not in self._aot:
            with retrace.suspended():
                lowered = self._chunk.lower(carry_spec, ctl_spec,
                                            batch_spec, unroll)
            self._aot[key] = lowered.compile()
        return self._aot[key]

    def run(self, carry: PyTree, ctl_stack: Dict[str, jnp.ndarray],
            batch_stack: Dict[str, jnp.ndarray]
            ) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
        """Execute one chunk; returns (carry, metrics stacked over rounds)."""
        rounds = int(ctl_stack["seed"].shape[0])
        unroll = rounds if self._unroll is None else min(self._unroll, rounds)
        return self._chunk(carry, ctl_stack, batch_stack, unroll)


@functools.lru_cache(maxsize=64)
def get_executor(step: Callable, unroll: Optional[int] = None
                 ) -> "ScanExecutor":
    """Executor cache keyed on the step function object. Paired with the
    memoized `pairzero.make_zo_step`, identical configs share one compiled
    chunk program across fedsim.run invocations."""
    retrace.bump(retrace.SCAN_EXEC_BUILD)   # lru MISS: a fresh executor
    return ScanExecutor(step, unroll=unroll)


def chunk_boundaries(start: int, stop: int, chunk_rounds: int,
                     align: Tuple[int, ...] = ()) -> list:
    """Split [start, stop) into chunks of ≤ chunk_rounds, additionally
    cutting at every multiple of each period in `align` (eval/checkpoint
    cadences), so host-side side effects fire at exactly the rounds the
    per-round loop fires them."""
    periods = [p for p in align if p and p > 0]
    bounds = []
    t = start
    while t < stop:
        nxt = min(t + max(1, chunk_rounds), stop)
        for p in periods:
            # next multiple of p strictly after t
            m = ((t // p) + 1) * p
            if t < m < nxt:
                nxt = m
        bounds.append((t, nxt))
        t = nxt
    return bounds
