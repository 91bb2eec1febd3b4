"""Federated simulation driver: the paper's Algorithm 1 end to end.

`Experiment` is the single host-side orchestrator (what the edge server +
base station do):

  1. realize the wireless channel for the horizon via the channel registry
     (repro.channel: fading magnitudes, residual CSI phases, deep-fade
     participation — whatever stack pz.channel configures),
  2. ask the run's Transport (repro.core.transport) for its schedule —
     Theorem-3/4 power control for the OTA mechanisms, a trivial plan for
     the digital/FO baselines,
  3. run the rounds through one of two executors sharing ONE driver loop:
       engine="scan": the device-resident scan-over-rounds engine
         (core/engine.py) — `chunk_rounds` rounds per dispatch under one
         lax.scan with parameter-buffer donation;
       engine="loop" (default): per-round dispatch — no chunk compile
         cost, and the bit-identical equivalence oracle for scan,
  4. charge the DP accountant with the Transport's per-round costs (hard
     stop on overspend — privacy over utility), handle faults (survival
     masks), and fire the round hooks.

Eval, checkpointing and logging are uniform `RoundHook`s shared by both
engines: the driver aligns chunk boundaries to every hook cadence, so a
hook fires at exactly the same rounds regardless of dispatch granularity.

`run(...)` keeps the historical flat-kwarg surface (it builds the hooks and
delegates); its `variant=`/`scheme=` kwargs are a one-release deprecation
shim routed through the transport registry.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import byzantine as byz
from repro import channel
from repro import obs
from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import ModelConfig, PairZeroConfig
from repro.core import engine as eng
from repro.core import pairzero
from repro.core import transport as tp
from repro.core.dp import PrivacyAccountant, cumulative_spend
from repro.data.pipeline import FederatedPipeline
from repro.models import registry
from repro.optim import fo as fo_opt
from repro.runtime import desync as dsync
from repro.runtime import inject as inj
from repro.runtime import sharding as shd
from repro.runtime.fault import ElasticSchedule, FaultModel


@functools.lru_cache(maxsize=32)
def _fo_scan_step(raw_step: Callable) -> Callable:
    """Adapter: FO step's (params, opt_state) pair as a single carry.
    Memoized on the (memoized) raw step so the executor cache hits too."""
    def scan_step(carry, batch, ctl):
        p, o, metrics = raw_step(carry[0], carry[1], batch, ctl)
        return (p, o), metrics
    return scan_step


@dataclass
class RunResult:
    losses: List[float] = field(default_factory=list)
    accuracies: List[float] = field(default_factory=list)
    p_hats: List[float] = field(default_factory=list)
    privacy_spent: float = 0.0
    privacy_budget: float = 0.0
    steps: int = 0
    wall_time_s: float = 0.0
    resumed_from: int = 0
    privacy_exhausted_at: int = -1   # round at which the guard tripped
    uplink_bits: int = 0             # total uplink spend (Transport-accounted)
    params: Optional[Any] = None     # final model parameters
    # the base station's offline solve, exposed for post-hoc analysis
    # (privacy audits/attacks consume the realized schedule + transport)
    schedule: Optional[Any] = None
    transport: Optional[Any] = None
    # chunk-boundary stall accounting (seconds over the whole run):
    prep_stall_s: float = 0.0        # driver blocked on host-side chunk prep
    ckpt_stall_s: float = 0.0        # driver blocked on checkpoint snapshots
    # observability (repro.obs):
    peak_bytes: int = 0              # device-memory watermark (0: no sampler)
    # build/retrace counter deltas for this run (always recorded — a warm
    # rerun of an identical config must show all zeros)
    compile_stats: Dict[str, int] = field(default_factory=dict)
    # [steps] cumulative Eq.-16 ledger after each executed round (the
    # accountant's own float64 fold — dp.cumulative_spend); the audit CLI
    # and the MetricsSink trilemma ledger read these same numbers
    privacy_spent_per_round: Optional[np.ndarray] = None
    # robustness accounting (repro.runtime.inject): nonzero retry /
    # degradation counters by site ("dispatch", "ckpt_write",
    # "prefetch_degraded", "ckpt_write_failed", "ckpt_snapshot_failed").
    # Empty on a clean run — the ledger's final row asserts against it.
    retry_attempts: Dict[str, int] = field(default_factory=dict)
    # compiled-program introspection (repro.obs.hlo), populated when the
    # run's Telemetry has cost=True: XLA-reported flops / bytes / peak
    # memory / collective census for the executor program actually
    # dispatched ({"error": ...} if the analysis itself failed)
    cost_stats: Optional[Dict[str, Any]] = None
    # run-health outcome (repro.obs.health): the round and detector kind
    # of a policy="abort" stop; -1/"" on a run that finished naturally.
    # The accountant only ever charged executed rounds, so privacy_spent
    # remains the realized spend `--audit` should consume.
    health_abort_round: int = -1
    health_abort_reason: str = ""


# ---------------------------------------------------------------------------
# Round hooks — eval / checkpoint / logging, uniform across engines
# ---------------------------------------------------------------------------

class RoundHook:
    """Host-side side effect wired into the driver loop.

    `cadence` (rounds) aligns chunk boundaries so `on_boundary` fires at
    exactly the multiples it would under per-round dispatch. `on_round`
    receives every round's host metrics (one chunk late under the scan
    engine's software pipelining — never reordered).
    """
    cadence: int = 0

    def on_start(self, exp: "Experiment") -> None:
        """Before round execution; may restore state (params, accountant)."""

    def on_round(self, t: int, metrics: Dict[str, np.ndarray]) -> None:
        """Per executed round, with that round's host-side metrics."""

    def on_boundary(self, t_done: int, exp: "Experiment") -> None:
        """At every aligned chunk boundary (t_done rounds executed)."""

    def close(self, exp: "Experiment") -> None:
        """After the run (flush async work)."""


class EvalHook(RoundHook):
    """Greedy eval on the held-out batch every `cadence` rounds."""

    def __init__(self, every: int, eval_n: int = 64):
        self.cadence = every
        self.eval_n = eval_n
        self._fn = None

    def on_start(self, exp: "Experiment") -> None:
        model_cfg, impl, dtype = exp.model_cfg, exp.impl, exp.dtype
        mod = registry.get_module(model_cfg)

        def eval_fn(p, ebatch):
            toks = jnp.asarray(ebatch["tokens"])
            if model_cfg.family == "audio":
                frames = jnp.zeros((toks.shape[0],
                                    model_cfg.frontend.n_frontend_tokens,
                                    model_cfg.d_model), dtype)
                enc = mod.encode(p, model_cfg, frames, impl=impl)
                x = mod.decode_hidden(p, model_cfg, toks, enc, impl=impl)
            else:
                x = mod.forward(p, model_cfg, toks, impl=impl)
            from repro.models import layers as L
            head = p.get("lm_head", p.get("embed", p.get("dec_embed")))
            return L.unembed(head, x)

        self._fn = jax.jit(eval_fn)

    def on_boundary(self, t_done: int, exp: "Experiment") -> None:
        if self.cadence and t_done % self.cadence == 0:
            ebatch = exp.pipeline.eval_batch(self.eval_n)
            logits = np.asarray(self._fn(exp.params, ebatch))
            from repro.data import tasks as T
            exp.result.accuracies.append(T.accuracy(logits, ebatch))


class CheckpointHook(RoundHook):
    """Crash-safe restore-on-start + async save every `cadence` rounds.

    `double_buffer` selects the non-blocking snapshot path (on-device copy
    + `copy_to_host_async`, materialized on the writer thread) — the next
    chunk dispatches without waiting for the device→host transfer. False
    keeps the historical synchronous `device_get` (the stall baseline).
    """

    def __init__(self, directory: str, every: int = 0,
                 double_buffer: bool = True):
        self.directory = directory
        self.cadence = every
        self.double_buffer = double_buffer
        self._saver = None

    def on_start(self, exp: "Experiment") -> None:
        # newest *CRC-valid* checkpoint: a SIGKILL mid-write (or simulated
        # bitrot) leaves a torn step_N that plain `latest` would return —
        # crash-consistent resume falls back to the last intact save
        latest = ckpt.latest_valid(self.directory)
        if latest:
            exp.params, exp.start_round, extra = ckpt.restore(latest,
                                                              exp.params)
            exp.accountant = PrivacyAccountant.from_state_dict(
                extra["accountant"])
            exp.result.resumed_from = exp.start_round
        if self.cadence:
            self._saver = ckpt.AsyncCheckpointer(
                self.directory, double_buffer=self.double_buffer,
                tracer=exp.telemetry.tracer, injector=exp.injector)

    def on_boundary(self, t_done: int, exp: "Experiment") -> None:
        if self._saver is not None and t_done % self.cadence == 0:
            self._saver.save(
                t_done, exp.params,
                extra={"accountant": exp.accountant.state_dict(),
                       "round": t_done})

    def close(self, exp: "Experiment") -> None:
        if self._saver is not None:
            self._saver.wait()


class CallbackHook(RoundHook):
    """Per-round logging callback (the historical `on_round=` kwarg)."""

    def __init__(self, fn: Callable[[int, Dict], None]):
        self._fn = fn

    def on_round(self, t: int, metrics: Dict[str, np.ndarray]) -> None:
        self._fn(t, metrics)


# ---------------------------------------------------------------------------
# The orchestrator
# ---------------------------------------------------------------------------

class Experiment:
    """One federated run: model + pAirZero config + data + a Transport.

    The driver is deliberately boring: every interesting decision lives in
    core/{zo,transport,dp,power_control,pairzero,engine}. Both engines run
    the SAME loop here — chunk boundaries, control traces, DP lookahead and
    hooks are shared; only the executor (per-round jit vs chunked lax.scan)
    differs, which is what makes loop/scan bit-identity testable.
    """

    def __init__(self, model_cfg: ModelConfig, pz: PairZeroConfig,
                 pipeline: FederatedPipeline, rounds: int, *,
                 engine: str = "loop", chunk_rounds: int = 32,
                 transport: Optional[tp.Transport] = None,
                 channel_model: Optional[channel.ChannelModel] = None,
                 hooks: Sequence[RoundHook] = (),
                 fault: Optional[FaultModel] = None,
                 elastic: Optional[ElasticSchedule] = None,
                 impl: Optional[str] = None, dtype=jnp.float32,
                 params: Optional[Any] = None,
                 mesh: Optional[Mesh] = None, overlap: bool = True,
                 adversary: Optional[Any] = None,
                 behavior: Optional[Any] = None,
                 defense: Optional[Any] = None,
                 telemetry: Optional[obs.Telemetry] = None,
                 desync: Optional[dsync.DesyncModel] = None,
                 injector: Optional[inj.FaultInjector] = None):
        if engine not in ("scan", "loop"):
            raise ValueError(
                f"unknown engine: {engine!r} (want 'scan'|'loop')")
        self.model_cfg = model_cfg
        self.pz = pz
        self.pipeline = pipeline
        self.rounds = rounds
        self.engine = engine
        self.chunk_rounds = chunk_rounds
        self.transport = transport if transport is not None \
            else tp.resolve(pz)
        # explicit ChannelModel overrides the pz.channel config stack
        # (mirrors `transport=`) — how user-built/wrapped models run
        self.channel_model = channel_model if channel_model is not None \
            else channel.from_config(pz.channel)
        self.hooks = list(hooks)
        self.fault = fault
        self.elastic = elastic
        self.impl = impl
        self.dtype = dtype
        self.params = params
        self.mesh = mesh
        self.overlap = overlap
        # eavesdropper observation capture (repro.privacy.Adversary): the
        # step emits obs_* metrics; pair with an AttackHook to collect them
        self.adversary = adversary
        # active-adversary scenario (repro.byzantine): explicit instances
        # override the pz.byzantine config resolution (mirrors transport=)
        self.behavior = behavior if behavior is not None \
            else byz.resolve_behavior(pz)
        self.defense = defense if defense is not None \
            else byz.resolve_defense(pz)
        # imperfect synchronization (repro.runtime.desync): explicit model
        # overrides the pz.desync config resolution (mirrors transport=).
        # Unlike byzantine, desync IS meaningful for the FO baseline — the
        # Dirichlet frame-gain collapse is the fig_desync comparison.
        self.desync = desync if desync is not None else dsync.resolve(pz)
        if self.desync is not None and not self.desync.active:
            self.desync = None     # inert config == historical program
        # chaos testing (repro.runtime.inject): deterministic fault
        # injection at the named host sites; None arms nothing
        self.injector = injector
        if self.transport.kind == "fo" and (self.behavior is not None
                                            or self.defense is not None):
            raise ValueError(
                "Byzantine behaviors/defenses act on the scalar ZO payload "
                "vector; the FO baseline has no scalar uplink to attack or "
                "defend — run it without a ByzantineConfig")
        # realized channel + schedule, exposed after run() for post-hoc
        # attacks/audits (the adversary knows both — they are broadcast)
        self.channel_trace = None
        self.schedule = None
        if mesh is not None:
            cl = shd.client_axes(mesh)
            n_shards = shd.axis_size(mesh, cl)
            if not cl or n_shards <= 0:
                raise ValueError(f"mesh {mesh.axis_names} has no client "
                                 "axes (want 'pod' and/or 'data')")
            if pz.n_clients % n_shards != 0:
                raise ValueError(
                    f"n_clients={pz.n_clients} must divide evenly over the "
                    f"{n_shards} client shards of mesh {dict(mesh.shape)} — "
                    "pAirZero clients split evenly or not at all")
            if self.transport.kind == "fo":
                raise ValueError(
                    "the FO baseline has no shard_map variant (it uploads "
                    "d-dimensional gradients, not a scalar) — run it "
                    "without mesh=")
        # host-side observability (repro.obs): span timeline + memory
        # watermark. The default is the inert bundle (NULL_TRACER, no
        # sampler) — instrumentation sites are then no-op method calls and
        # the traced program is the bit-exact historical one.
        self.telemetry = telemetry if telemetry is not None \
            else obs.Telemetry.off()
        # populated by run()/hooks
        self.result = RunResult()
        self.accountant = PrivacyAccountant(pz.dp.epsilon, pz.dp.delta)
        self.start_round = 0
        # per-round observability state populated by run(): realized
        # K_eff(t) per executed round (the ledger's bit accounting), and
        # the accountant ledger position when the run started (restored
        # checkpoints begin with spent > 0 and an empty history)
        self.round_k_eff: List[float] = []
        # per executed round: surviving clients whose scalar rode the
        # CURRENT round seed (K_eff minus the stale stragglers) — the
        # ledger's k_sync column; == round_k_eff when desync is off
        self.round_k_sync: List[float] = []
        self.spent_at_start = 0.0
        self.hist_at_start = 0
        # bounded-retry counters by site, merged into result.retry_attempts
        self._retries: Dict[str, int] = {}

    # -- engine plumbing --------------------------------------------------
    def _build_step(self):
        """(step_fn, carry): the scan-body step and its initial carry."""
        if self.transport.kind == "fo":
            optimizer = fo_opt.make("adam", self.pz.zo.lr)
            raw = pairzero.make_fo_step(self.model_cfg, optimizer,
                                        impl=self.impl,
                                        adversary=self.adversary,
                                        desync=self.desync)
            return _fo_scan_step(raw), (self.params,
                                        optimizer.init(self.params))
        raw = pairzero.make_zo_step(self.model_cfg, self.pz, impl=self.impl,
                                    transport=self.transport, mesh=self.mesh,
                                    adversary=self.adversary,
                                    behavior=self.behavior,
                                    defense=self.defense,
                                    desync=self.desync)
        return raw, self.params

    def _executor(self, step_fn):
        if self.engine == "scan":
            return eng.get_executor(step_fn)
        return eng.get_loop_executor(pairzero.jit_zo_step(step_fn))

    # -- the run ----------------------------------------------------------
    def run(self) -> RunResult:
        t0 = time.time()
        pz, result = self.pz, self.result
        tr = self.telemetry.tracer
        mem = self.telemetry.memory
        compile_before = obs.retrace.snapshot()
        result.privacy_budget = self.accountant.budget

        # channel + transmit schedule (the base station's offline solve).
        # Realized/solved over the PLANNED horizon (pz.rounds), not this
        # invocation's `rounds`: Theorem 3/4 budget privacy across all T,
        # and a resumed run must replay the identical channel + schedule.
        horizon = max(pz.rounds, self.rounds)
        with tr.span("channel_realize", horizon=horizon):
            ctrace = self.channel_model.realize(pz.seed ^ 0xC4A7, horizon,
                                                pz.n_clients)
        # an active defense may fold its PHY constraint into the solve
        # (transmit clip => tightened Theorem-3/4 sensitivity)
        with tr.span("schedule_solve", transport=self.transport.name):
            schedule = self.transport.make_schedule(ctrace, pz) \
                if self.defense is None \
                else self.defense.make_schedule(self.transport, ctrace, pz)
        self.channel_trace, self.schedule = ctrace, schedule
        result.schedule, result.transport = schedule, self.transport

        if self.params is None:
            with tr.span("params_init"):
                self.params = registry.init_params(jax.random.key(pz.seed),
                                                   self.model_cfg,
                                                   self.dtype)
        for hook in self.hooks:
            hook.on_start(self)
        # the accountant may have been replaced by a restoring hook; the
        # ledger position NOW is what per-round spend curves fold from
        self.spent_at_start = self.accountant.spent
        self.hist_at_start = len(self.accountant.history)
        if mem is not None:
            mem.sample(self.start_round, tracer=tr)
        if self.mesh is not None:
            # FSDP placement over the client axes ('model' TP when present);
            # restored checkpoints land default-placed, so this reshards
            # fresh-init and resumed runs alike
            self.params = jax.device_put(
                self.params, shd.params_sharding(self.mesh, self.params))

        step_fn, carry = self._build_step()
        executor = self._executor(step_fn)
        align = tuple(hk.cadence for hk in self.hooks if hk.cadence)
        # The loop engine dispatches (and syncs) one round at a time — run
        # it on 1-round spans so metrics/on_round stay live and batches
        # transfer per round, exactly as per-round dispatch always did.
        # Span length never changes numerics (trace values are split-
        # invariant); only the scan engine benefits from longer spans.
        span = 1 if self.engine == "loop" else self.chunk_rounds
        bounds = eng.chunk_boundaries(self.start_round, self.rounds,
                                      span, align)

        # Host-side chunk prep — control trace (+ its single device_put,
        # replicated over the mesh) and batch staging into preallocated
        # buffers — runs one chunk ahead on the prefetch thread while the
        # device executes the current chunk. Prep order == round order, so
        # the stateful FaultModel RNG replays exactly the per-round draw.
        ctl_shard = NamedSharding(self.mesh, PartitionSpec()) \
            if self.mesh is not None else None
        stager = eng.BatchStager(
            self.pipeline,
            sharding_fn=(lambda like:
                         shd.chunk_batch_sharding(self.mesh, like))
            if self.mesh is not None else None,
            tracer=tr)

        def prepare(a: int, b: int):
            with tr.span("ctl_build", t0=a, t1=b):
                trace = eng.build_trace(schedule, pz, a, b,
                                        transport=self.transport,
                                        fault=self.fault,
                                        elastic=self.elastic,
                                        channel=ctrace,
                                        ctl_sharding=ctl_shard,
                                        behavior=self.behavior,
                                        defense=self.defense,
                                        desync=self.desync, tracer=tr)
            return trace, stager.stage(a, b)

        prefetch = eng.ChunkPrefetcher(prepare, bounds,
                                       overlap=self.overlap, tracer=tr,
                                       injector=self.injector)
        # dispatch retry is sound only for entry injection: the executor
        # donates the carry buffers, so a REAL mid-flight failure is not
        # replayable — without an armed injector, fail fast (attempts=1)
        dispatch_attempts = 3 if (self.injector is not None
                                  and self.injector.armed("dispatch")) else 1

        # Software-pipelined chunk loop: the metric sync for chunk i is
        # deferred until chunk i+1 has been *dispatched*, so both the
        # prefetch thread and the flush overlap the device executing the
        # current chunk.
        pending = None            # (first_round, n_rounds, metrics)
        client_rounds = 0.0       # Σ_t K_eff(t) over executed rounds
        # dispatch-arg specs for post-run cost analysis, captured on the
        # first chunk BEFORE the executor donates the carry buffers
        cost_specs = None
        # HealthMonitor(policy="abort") raises from on_round inside a
        # flush; caught at chunk granularity so executed == charged rounds
        health_abort: Optional[obs.HealthAbort] = None
        last_boundary = self.start_round   # newest completed hook boundary

        def flush() -> None:
            nonlocal pending
            if pending is None:
                return
            a0, n_rounds, metrics = pending
            pending = None
            with tr.span("metrics_flush", t0=a0, rounds=n_rounds):
                host = {k: np.asarray(v) for k, v in metrics.items()}
                result.losses.extend(float(x) for x in host["loss"])
                if "p_hat" in host:
                    result.p_hats.extend(float(x) for x in host["p_hat"])
                for hook in self.hooks:
                    for r in range(n_rounds):
                        hook.on_round(a0 + r,
                                      {k: v[r] for k, v in host.items()})

        try:
            for i, (a, b) in enumerate(bounds):
                with tr.step_span("chunk", i, chunk=i, t0=a, t1=b):
                    trace, batches = prefetch.get(i)
                    n_ok = eng.affordable_rounds(self.accountant, trace)
                    if n_ok == 0:
                        result.privacy_exhausted_at = a
                        break
                    eng.charge_rounds(self.accountant, trace, n_ok)
                    # uplink accounting: only clients that actually
                    # transmit (survival mask 1) are billed their payload
                    # this round; the per-round K_eff view feeds the
                    # trilemma ledger (obs.MetricsSink)
                    k_rows = trace.host_masks[:n_ok].sum(axis=1)
                    client_rounds += float(k_rows.sum())
                    self.round_k_eff.extend(float(x) for x in k_rows)
                    # synchronized survivors: exclude the stale stragglers
                    # whose scalar rode a lagged round seed this round
                    if trace.host_stale is not None:
                        sync_rows = (trace.host_masks[:n_ok]
                                     * (1.0 - trace.host_stale[:n_ok])
                                     ).sum(axis=1)
                    else:
                        sync_rows = k_rows
                    self.round_k_sync.extend(float(x) for x in sync_rows)
                    if n_ok < b - a:  # guard trips mid-chunk: truncate
                        batches = {k: v[:n_ok] for k, v in batches.items()}
                    if self.telemetry.cost and cost_specs is None:
                        cost_specs = (obs.hlo.specs_of(carry),
                                      obs.hlo.specs_of(trace.rows(n_ok)),
                                      obs.hlo.specs_of(batches))
                    with tr.span("dispatch", chunk=i, rounds=n_ok):
                        carry, metrics = inj.with_retries(
                            lambda: executor.run(carry, trace.rows(n_ok),
                                                 batches),
                            site="dispatch", attempts=dispatch_attempts,
                            injector=self.injector, tracer=tr,
                            retries=self._retries)
                    flush()       # sync chunk i-1 while chunk i runs
                    pending = (a, n_ok, metrics)
                    if self.engine == "loop":
                        # per-round dispatch already synced each round —
                        # deliver metrics/on_round immediately (live
                        # logging), nothing to pipeline against.
                        flush()
                    # chunk i-1 is now synced ⇒ its stager slot (shared
                    # with chunk i+1) is reusable: start the next prep
                    prefetch.kick(i + 1)
                    self.params = carry[0] if self.transport.kind == "fo" \
                        else carry
                    t_done = a + n_ok
                    if n_ok < b - a:  # guard tripped mid-chunk: hard stop
                        flush()
                        result.privacy_exhausted_at = t_done
                        break
                    if mem is not None and mem.due(t_done):
                        mem.sample(t_done, tracer=tr)
                    with tr.span("hooks_boundary", t=t_done):
                        for hook in self.hooks:
                            hook.on_boundary(t_done, self)
                    last_boundary = t_done
        except obs.HealthAbort as e:
            health_abort = e
            pending = None       # rounds past the abort stay unreported
        finally:
            prefetch.close()
        # final watermark BEFORE the last flush: MetricsSink rows and
        # result.peak_bytes then report the same peak
        if mem is not None:
            mem.sample(self.start_round + len(self.round_k_eff), tracer=tr)
        if health_abort is None:
            try:
                flush()
            except obs.HealthAbort as e:
                health_abort = e
                pending = None

        if health_abort is not None:
            result.health_abort_round = int(health_abort.round)
            result.health_abort_reason = str(health_abort.reason)
            # checkpoint-then-abort: persist the newest consistent state
            # (params + accountant at the last completed boundary) so the
            # run can be resumed/inspected; best effort — the abort report
            # must survive a failing writer
            for hk in self.hooks:
                if isinstance(hk, CheckpointHook) and hk._saver is not None:
                    try:
                        hk._saver.save(
                            last_boundary, self.params,
                            extra={"accountant":
                                   self.accountant.state_dict(),
                                   "round": last_boundary})
                    except Exception:
                        pass

        for hook in self.hooks:
            hook.close(self)
        if health_abort is not None:
            # every charged round executed; rounds after the abort within
            # the final chunk were bought and ran, so they count as steps
            result.steps = len(self.round_k_eff)
        else:
            result.steps = max(0,
                               result.privacy_exhausted_at - self.start_round
                               if result.privacy_exhausted_at >= 0
                               else self.rounds - self.start_round)
        result.privacy_spent = self.accountant.spent
        # the per-round ε ledger: the accountant's own charges for this
        # run's executed rounds, folded with the identical float64 cumsum
        # (uncharged transports: a flat curve at the starting ledger)
        costs = np.asarray(
            self.accountant.history[self.hist_at_start:], dtype=np.float64)
        if costs.size != result.steps:
            costs = np.zeros(result.steps, dtype=np.float64)
        result.privacy_spent_per_round = cumulative_spend(
            costs, initial=self.spent_at_start)
        # payload per transmitting client x Σ_t K_eff(t): dropped/silenced
        # clients send nothing, so they cost nothing; an active defense
        # scales the payload (re-transmission factors) and bills its own
        # side-channel bits per executed round. uplink_bits_total is the
        # ONE accounting expression — the MetricsSink ledger calls it too.
        result.uplink_bits = tp.uplink_bits_total(
            self.transport, self.defense, pz, self.model_cfg.param_count(),
            client_rounds, result.steps)
        result.prep_stall_s = prefetch.stall_s
        result.ckpt_stall_s = sum(
            hk._saver.stall_s for hk in self.hooks
            if isinstance(hk, CheckpointHook) and hk._saver is not None)
        # robustness ledger: only nonzero counters, so a clean run reports
        # an empty dict (asserted bit-for-bit by the trace checker)
        attempts = dict(self._retries)
        if prefetch.degraded:
            attempts["prefetch_degraded"] = prefetch.degraded
        for hk in self.hooks:
            if isinstance(hk, CheckpointHook) and hk._saver is not None:
                for site, n in hk._saver.retries.items():
                    attempts[site] = attempts.get(site, 0) + n
                if hk._saver.write_failures:
                    attempts["ckpt_write_failed"] = (
                        attempts.get("ckpt_write_failed", 0)
                        + hk._saver.write_failures)
                if hk._saver.snapshot_failures:
                    attempts["ckpt_snapshot_failed"] = (
                        attempts.get("ckpt_snapshot_failed", 0)
                        + hk._saver.snapshot_failures)
        result.retry_attempts = {k: v for k, v in attempts.items() if v}
        result.peak_bytes = mem.peak_bytes if mem is not None else 0
        result.compile_stats = obs.retrace.since(compile_before)
        result.wall_time_s = time.time() - t0
        result.params = self.params
        if cost_specs is not None:
            # AOT introspection of the dispatched program (repro.obs.hlo):
            # compile-only, after the run clock stopped, counters
            # suspended — timing, numerics and compile-watermark pins are
            # untouched. Analysis failure must not fail a finished run.
            try:
                result.cost_stats = obs.hlo.analyze_executor(
                    executor, *cost_specs).to_dict()
            except Exception as exc:  # noqa: BLE001 - record, don't raise
                result.cost_stats = {"error": f"{type(exc).__name__}: {exc}"}
        return result


# ---------------------------------------------------------------------------
# Flat-kwarg compatibility surface
# ---------------------------------------------------------------------------

def run(model_cfg: ModelConfig, pz: PairZeroConfig,
        pipeline: FederatedPipeline, rounds: int, *,
        engine: str = "loop", chunk_rounds: int = 32,
        eval_every: int = 0, eval_n: int = 64,
        checkpoint_dir: Optional[str] = None, checkpoint_every: int = 0,
        fault: Optional[FaultModel] = None,
        elastic: Optional[ElasticSchedule] = None,
        impl: Optional[str] = None, dtype=jnp.float32,
        params: Optional[Any] = None,
        on_round: Optional[Callable[[int, Dict], None]] = None,
        transport: Optional[tp.Transport] = None,
        channel_model: Optional[channel.ChannelModel] = None,
        mesh: Optional[Mesh] = None, overlap: bool = True,
        adversary: Optional[Any] = None,
        behavior: Optional[Any] = None,
        defense: Optional[Any] = None,
        hooks: Sequence[RoundHook] = (),
        telemetry: Optional[obs.Telemetry] = None,
        desync: Optional[dsync.DesyncModel] = None,
        injector: Optional[inj.FaultInjector] = None,
        variant: Optional[str] = None,
        scheme: Optional[str] = None) -> RunResult:
    """Run T rounds of pAirZero (or a baseline transport) on one host.

    Thin wrapper over `Experiment`: builds the eval/checkpoint/logging
    hooks from the historical kwargs and delegates. `mesh=` runs the
    shard_map'd step with clients mapped over the mesh's (pod, data) axes
    (see `pairzero.make_zo_step`); `overlap=False` disables the prefetch
    thread (the no-overlap stall control). `adversary=` (a
    `repro.privacy.Adversary`) switches on eavesdropper observation
    capture — pair it with a `repro.privacy.AttackHook` in `hooks=` to
    collect the observations. `behavior=`/`defense=` (repro.byzantine)
    override the pz.byzantine config resolution with explicit instances —
    the active-adversary scenario axis. `telemetry=` (a
    `repro.obs.Telemetry`) switches on the host-side span timeline and
    device-memory watermark; pair it with a `repro.obs.MetricsSink` in
    `hooks=` for the per-round trilemma ledger — all host-side, so the
    trajectory is bitwise unchanged. `desync=` (a
    `repro.runtime.DesyncModel`) switches on imperfect-synchronization
    modeling — stale stragglers riding lagged round seeds plus fractional
    timing misalignment entering the OTA superposition; `injector=` (a
    `repro.runtime.FaultInjector`) arms deterministic fault injection at
    the named host sites for chaos testing. Both default to None, tracing
    the bit-exact historical program. `variant=`/`scheme=` are the
    DEPRECATED string spellings, routed through the transport registry for
    one more release — pass `transport=` or put a TransportConfig in
    `pz.transport` instead.
    """
    if variant is not None or scheme is not None:
        tp.deprecated_strings(variant or pz.variant,
                              scheme or pz.power.scheme, "fedsim.run")
        pz = dataclasses.replace(
            pz, variant=variant or pz.variant,
            power=dataclasses.replace(pz.power,
                                      scheme=scheme or pz.power.scheme),
            transport=None)
    all_hooks: List[RoundHook] = list(hooks)
    if eval_every:
        all_hooks.append(EvalHook(eval_every, eval_n))
    if checkpoint_dir:
        all_hooks.append(CheckpointHook(checkpoint_dir, checkpoint_every))
    if on_round is not None:
        all_hooks.append(CallbackHook(on_round))
    return Experiment(model_cfg, pz, pipeline, rounds, engine=engine,
                      chunk_rounds=chunk_rounds, transport=transport,
                      channel_model=channel_model, hooks=all_hooks,
                      fault=fault, elastic=elastic, impl=impl, dtype=dtype,
                      params=params, mesh=mesh, overlap=overlap,
                      adversary=adversary, behavior=behavior,
                      defense=defense, telemetry=telemetry,
                      desync=desync, injector=injector).run()
