"""Training launcher: federated pAirZero fine-tuning from the CLI.

    PYTHONPATH=src python -m repro.launch.train \
        --arch opt-125m --task sst2 --transport analog --scheme solution \
        --rounds 800 --clients 5 --lr 5e-7 --checkpoint-dir ckpt/

The uplink mechanism is any registered Transport (analog | sign | perfect |
digital | fo — see repro.core.transport); `--variant` remains as a
deprecated alias for one release. The wireless channel is any registered
ChannelModel (see repro.channel), optionally wrapped:

    --channel rician --rician-k 4 --csi-phase-err 0.1 --outage-db -10 \
        --cell-radius 150

Mobility is specified physically for --channel ar1 via --doppler-hz (and
--round-s): the lag-1 correlation is derived by Jakes' J0(2*pi*f_D*tau).
`--audit` switches on the privacy subsystem (repro.privacy): eavesdropper
observation capture, the seed-replay reconstruction attack, and — on DP
transports — the empirical Clopper-Pearson eps_hat audit checked against
the analytic accountant (non-zero exit on violation: a CI-able gate).

`--mesh auto|8|2x8` shards the clients over a device mesh: each shard runs
its clients' forwards and the OTA scalar aggregate becomes a real
cross-device psum (bit-identical to the single-device run). On CPU, set
XLA_FLAGS=--xla_force_host_platform_device_count=8 before launch to get a
multi-device mesh.

On a real multi-host TPU fleet this process runs once per host after
jax.distributed.initialize() (see launch/scripts/); on CPU it runs the same
code on a 1-device mesh. Architecture choice is --arch <id> over the full
assigned-architecture registry.
"""
from __future__ import annotations

import argparse
import json
from typing import Any, Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro import byzantine as byz
from repro.configs.base import (ByzantineConfig, ChannelConfig, DesyncConfig,
                                DPConfig, ModelConfig, PairZeroConfig,
                                PowerControlConfig, TransportConfig,
                                ZOConfig)
from repro.core import fedsim, transport
from repro.data.pipeline import FederatedPipeline
from repro.data.tasks import TaskSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.runtime.fault import ElasticSchedule, FaultModel
from repro.runtime.inject import FaultInjector


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="opt-125m",
                    help=f"one of {registry.list_archs()}")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced same-family config (CPU-scale)")
    ap.add_argument("--task", default="sst2",
                    choices=["sst2", "squad", "lm"])
    ap.add_argument("--transport", default=None,
                    help="uplink mechanism from the transport registry "
                         f"{transport.available()}; default: --variant")
    ap.add_argument("--variant", default="analog",
                    choices=["analog", "sign", "fo"],
                    help="DEPRECATED alias for --transport")
    ap.add_argument("--scheme", default="solution",
                    choices=["solution", "static", "reversed", "perfect"],
                    help="power-control schedule for the OTA transports")
    ap.add_argument("--quant-bits", type=int, default=8,
                    help="bits/coordinate for --transport digital")
    ap.add_argument("--channel", default=None,
                    help="base fading model from the channel registry "
                         "(rayleigh | rician | static | ar1 | user-"
                         "registered); default rayleigh. The geometry/"
                         "imperfect-CSI/outage wrappers compose on top via "
                         "--cell-radius/--csi-phase-err/--outage-db")
    ap.add_argument("--rician-k", type=float, default=3.0,
                    help="K-factor for --channel rician")
    ap.add_argument("--ar1-rho", type=float, default=0.9,
                    help="lag-1 temporal correlation for --channel ar1")
    ap.add_argument("--doppler-hz", type=float, default=None,
                    help="maximum Doppler shift f_D (Hz) for --channel "
                         "ar1: rho is derived physically via Jakes' "
                         "J0(2*pi*f_D*tau) instead of --ar1-rho")
    ap.add_argument("--round-s", type=float, default=1e-3,
                    help="round duration tau (s) entering the Jakes "
                         "mapping of --doppler-hz")
    ap.add_argument("--csi-phase-err", type=float, default=0.0,
                    help="residual CSI phase-error std (radians); >0 wraps "
                         "the channel in ImperfectCSI")
    ap.add_argument("--outage-db", type=float, default=None,
                    help="deep-fade outage threshold (dB); set to wrap the "
                         "channel in OutageModel (straggling clients)")
    ap.add_argument("--cell-radius", type=float, default=0.0,
                    help="cell radius (m); >0 wraps the channel in "
                         "PathLossGeometry (per-client mean powers)")
    ap.add_argument("--shadow-std-db", type=float, default=0.0,
                    help="correlated log-normal shadowing std (dB) on the "
                         "PathLossGeometry gains; requires --cell-radius")
    ap.add_argument("--shadow-corr", type=float, default=0.5,
                    help="inter-client shadowing correlation rho in [0,1] "
                         "for --shadow-std-db")
    ap.add_argument("--rounds", type=int, default=800)
    ap.add_argument("--engine", default="loop", choices=["loop", "scan"],
                    help="round executor: per-round dispatch (loop) or the "
                         "device-resident chunked scan engine (scan)")
    ap.add_argument("--chunk-rounds", type=int, default=32,
                    help="rounds per device dispatch for --engine scan")
    ap.add_argument("--mesh", default=None,
                    help="shard clients over a device mesh: 'auto' (all "
                         "local devices on a data axis), '8' (data=8), or "
                         "'2x8' (pod=2, data=8). Clients must divide "
                         "evenly over the client shards; the OTA scalar "
                         "aggregate becomes a real cross-device psum")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the chunk-prefetch thread (host prep of "
                         "chunk i+1 normally overlaps device compute of "
                         "chunk i) — the stall-measurement control")
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--batch", type=int, default=8,
                    help="per-client batch size")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--mu", type=float, default=1e-3)
    ap.add_argument("--gamma", type=float, default=5.0)
    ap.add_argument("--n-perturb", type=int, default=4)
    ap.add_argument("--epsilon", type=float, default=5.0)
    ap.add_argument("--delta", type=float, default=0.01)
    ap.add_argument("--power", type=float, default=100.0)
    ap.add_argument("--n0", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=100)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--dropout-p", type=float, default=0.0,
                    help="per-round client dropout probability")
    ap.add_argument("--straggler-p", type=float, default=0.0)
    ap.add_argument("--elastic", default=None,
                    help="membership events: 'round:K,round:K' e.g. "
                         "'200:3,400:5'")
    ap.add_argument("--byzantine", default="none",
                    help="active-adversary client behavior from the "
                         f"byzantine registry {byz.available_behaviors()}; "
                         "'none' (default) runs the honest cohort — "
                         "bit-identical to a build without the subsystem")
    ap.add_argument("--byzantine-frac", type=float, default=0.25,
                    help="fraction of clients running --byzantine "
                         "(cohort size = round(frac * clients); 0 disables "
                         "the attack)")
    ap.add_argument("--byzantine-scale", type=float, default=3.0,
                    help="behavior parameter: lambda for scaled_poison, "
                         "the noise std for gaussian_noise")
    ap.add_argument("--defense", default="none",
                    help="server/PHY-side countermeasure from the byzantine "
                         f"registry {byz.available_defenses()}; 'none' "
                         "(default) keeps the mechanism's plain decode")
    ap.add_argument("--defense-groups", type=int, default=4,
                    help="orthogonal decode sub-slots for --defense "
                         "robust_decode/reweight (robustness grows with "
                         "groups at a linear resource-block cost)")
    ap.add_argument("--defense-clip-factor", type=float, default=0.5,
                    help="transmit-clip bound for --defense clip: "
                         "gamma_d = factor * gamma, folded into the "
                         "power-control solve")
    ap.add_argument("--desync-frac", type=float, default=0.0,
                    help="per-round probability a client is a stale "
                         "straggler whose scalar rode a lagged round seed "
                         "(repro.runtime.desync); 0 disables desync "
                         "modeling — bit-identical to a build without it")
    ap.add_argument("--desync-max-lag", type=int, default=4,
                    help="max staleness (rounds) for --desync-frac "
                         "stragglers; the realized lag is drawn per round")
    ap.add_argument("--desync-phase-std", type=float, default=0.0,
                    help="fractional-timing phase-error std (radians): "
                         "every client's OTA contribution is attenuated "
                         "by cos(theta) of its realized misalignment")
    ap.add_argument("--desync-frame-symbols", type=int, default=1,
                    help="symbols per OTA frame for the conventional "
                         "d-dimensional baseline's Dirichlet frame gain "
                         "(only affects --transport fo under desync)")
    ap.add_argument("--inject", action="append", default=[],
                    metavar="SITE:MODE[:SEL]",
                    help="arm a deterministic host fault (repeatable): "
                         "site in {chunk_prep, dispatch, ckpt_snapshot, "
                         "ckpt_write}, mode in {exception, delay, "
                         "torn_write}, selector '@2,5' (exact invocation "
                         "indices) or a probability like '0.1' (default: "
                         "every invocation). The run recovers via bounded "
                         "retries / graceful degradation and reports the "
                         "counters under summary.retry_attempts")
    ap.add_argument("--inject-seed", type=int, default=0,
                    help="seed for probabilistic --inject selectors "
                         "(fires are a pure function of seed, site, "
                         "invocation index)")
    ap.add_argument("--audit", action="store_true",
                    help="eavesdropper capture + empirical privacy audit "
                         "(repro.privacy): records what an over-the-air "
                         "listener sees every round, runs the seed-replay "
                         "reconstruction attack on it, and — for DP "
                         "transports — checks the Clopper-Pearson eps_hat "
                         "lower bound against the analytic accountant "
                         "(exit 1 if the audit ever exceeds it)")
    ap.add_argument("--audit-trials", type=int, default=1500,
                    help="paired canary traces for the eps_hat audit")
    ap.add_argument("--trace-out", default=None,
                    help="write the host-side span timeline here as Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing): chunk prep/prefetch/stall, "
                         "dispatch, metric flush, checkpoint snapshot "
                         "spans, plus the run's compile/stall counters "
                         "under otherData (see docs/observability.md)")
    ap.add_argument("--metrics-out", default=None,
                    help="stream the per-round trilemma ledger here as "
                         "JSONL (schema trilemma_ledger/v2): one record "
                         "per round with loss, uplink bits, cumulative "
                         "(eps, delta) spend, and the peak device-memory "
                         "watermark — machine-readable evidence for all "
                         "three trilemma axes")
    ap.add_argument("--obs-sample-every", type=int, default=32,
                    help="device-memory sampling period (rounds) for the "
                         "--trace-out/--metrics-out watermark; samples "
                         "are taken at chunk boundaries, so cadence never "
                         "changes chunk shapes")
    ap.add_argument("--profile-out", default=None,
                    help="capture the run under jax.profiler and write a "
                         "MERGED Chrome trace here: XLA device-op events "
                         "aligned onto the host span timeline via a "
                         "perf_counter anchor, so dispatch/prep_stall "
                         "spans and executable launches render on one "
                         "Perfetto timeline (see docs/observability.md)")
    ap.add_argument("--health-policy", default="off",
                    choices=["off", "warn", "abort"],
                    help="run-health monitor (repro.obs.health): NaN/Inf, "
                         "loss-divergence and plateau detectors over the "
                         "per-round metrics. 'warn' records events in the "
                         "summary; 'abort' checkpoints the last boundary, "
                         "stops the run and exits with status 3 — the "
                         "accountant keeps only the realized spend, which "
                         "--audit then consumes")
    ap.add_argument("--health-divergence", type=float, default=10.0,
                    help="divergence factor: abort/warn when loss exceeds "
                         "this multiple of the running best (<=0 disables "
                         "the detector)")
    ap.add_argument("--health-plateau", type=int, default=0,
                    help="plateau window (rounds with no new best loss) "
                         "before the plateau detector fires; 0 disables")
    ap.add_argument("--out", default=None, help="write result JSON here")
    return ap


def make_configs(args: argparse.Namespace
                 ) -> Tuple[ModelConfig, PairZeroConfig, FederatedPipeline]:
    """(model config, pAirZero config, data pipeline) for parsed CLI args
    — what `main` trains, for callers that rebuild the same run."""
    cfg = registry.get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    mechanism = args.transport or args.variant
    byzcfg = None
    if args.byzantine != "none" or args.defense != "none":
        byzcfg = ByzantineConfig(
            behavior=args.byzantine, fraction=args.byzantine_frac,
            scale=args.byzantine_scale, defense=args.defense,
            groups=args.defense_groups,
            clip_factor=args.defense_clip_factor, seed=args.seed)
    desynccfg = None
    if args.desync_frac or args.desync_phase_std:
        desynccfg = DesyncConfig(
            fraction=args.desync_frac, max_lag=args.desync_max_lag,
            phase_std=args.desync_phase_std,
            frame_symbols=args.desync_frame_symbols, seed=args.seed)
    pz = PairZeroConfig(
        variant=args.variant, n_clients=args.clients, rounds=args.rounds,
        zo=ZOConfig(mu=args.mu, lr=args.lr, clip_gamma=args.gamma,
                    n_perturb=args.n_perturb),
        channel=ChannelConfig(n0=args.n0, power=args.power,
                              d=cfg.param_count(),
                              model=args.channel, rician_k=args.rician_k,
                              ar1_rho=args.ar1_rho,
                              doppler_hz=args.doppler_hz,
                              round_duration_s=args.round_s,
                              phase_err_std=args.csi_phase_err,
                              outage_db=args.outage_db,
                              cell_radius=args.cell_radius,
                              shadow_std_db=args.shadow_std_db,
                              shadow_corr=args.shadow_corr),
        dp=DPConfig(epsilon=args.epsilon, delta=args.delta),
        power=PowerControlConfig(scheme=args.scheme),
        transport=TransportConfig(mechanism=mechanism, scheme=args.scheme,
                                  quant_bits=args.quant_bits),
        byzantine=byzcfg,
        desync=desynccfg,
        seed=args.seed)

    pipe = FederatedPipeline(
        task=args.task,
        spec=TaskSpec(args.task, cfg.vocab_size, args.seq_len),
        n_clients=args.clients, per_client_batch=args.batch, seed=args.seed,
        frontend_tokens=cfg.frontend.n_frontend_tokens,
        d_model=cfg.d_model)
    return cfg, pz, pipe


def main(argv: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Run the CLI; returns the printed summary plus per-round `losses`
    and `p_hats`. `argv` defaults to `sys.argv[1:]`."""
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, pz, pipe = make_configs(args)
    mechanism = pz.transport.mechanism
    byzcfg, desynccfg = pz.byzantine, pz.desync

    fault = None
    if args.dropout_p or args.straggler_p:
        fault = FaultModel(args.clients, dropout_p=args.dropout_p,
                           straggler_p=args.straggler_p, seed=args.seed)
    elastic = None
    if args.elastic:
        events = tuple(tuple(int(v) for v in e.split(":"))
                       for e in args.elastic.split(","))
        elastic = ElasticSchedule(args.clients, events=events)

    def log(t, metrics):
        if t % 50 == 0:
            print(f"round {t:5d} loss {metrics['loss']:.4f}", flush=True)

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_client_mesh
        mesh = make_client_mesh(args.mesh, n_clients=args.clients)
        print(f"client mesh: {dict(mesh.shape)} over "
              f"{mesh.devices.size} devices", flush=True)

    adversary, attack_hook, extra_hooks = None, None, []
    if args.audit:
        from repro import privacy as pv
        adversary = pv.Adversary()
        # the OTA/digital observations are scalars per round; FO's is a
        # full [d] gradient — cap the host-side stream (the attacks
        # consume the early rounds; the eps_hat audit needs no capture)
        cap = 8 if mechanism == "fo" else None
        attack_hook = pv.AttackHook(max_rounds=cap)
        extra_hooks = [attack_hook]

    # observability (repro.obs): span timeline + memory watermark +
    # trilemma ledger + device profile — host-side only (the profiler
    # observes, never reschedules), trajectory bitwise unchanged
    telemetry, profiler, health = None, None, None
    if args.trace_out or args.metrics_out or args.profile_out:
        from repro import obs
        telemetry = obs.Telemetry.on(
            memory_sample_every=args.obs_sample_every,
            cost=bool(args.trace_out or args.profile_out))
        if args.metrics_out:
            extra_hooks = extra_hooks + [obs.MetricsSink(args.metrics_out)]
    if args.health_policy != "off":
        from repro import obs
        health = obs.HealthMonitor(
            args.health_policy,
            divergence_factor=args.health_divergence,
            plateau_rounds=args.health_plateau)
        extra_hooks = extra_hooks + [health]
    if args.profile_out:
        from repro import obs
        profiler = obs.ProfilerSession()
        profiler.start()

    injector = None
    if args.inject:
        from repro.obs.spans import NULL_TRACER
        injector = FaultInjector.from_specs(
            args.inject, seed=args.inject_seed,
            tracer=telemetry.tracer if telemetry is not None
            else NULL_TRACER)

    res = fedsim.run(cfg, pz, pipe, rounds=args.rounds,
                     engine=args.engine, chunk_rounds=args.chunk_rounds,
                     eval_every=args.eval_every,
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=args.checkpoint_every,
                     fault=fault, elastic=elastic, dtype=jnp.float32,
                     mesh=mesh, overlap=not args.no_overlap,
                     adversary=adversary, hooks=extra_hooks,
                     telemetry=telemetry, injector=injector, on_round=log)

    if profiler is not None:
        profiler.stop()

    if args.trace_out or args.profile_out:
        metadata = {
            "engine": args.engine,
            "overlap": not args.no_overlap,
            "prep_stall_s": res.prep_stall_s,
            "ckpt_stall_s": res.ckpt_stall_s,
            "peak_bytes": res.peak_bytes,
            "compile_stats": res.compile_stats,
        }
        if res.cost_stats is not None:
            metadata["cost_stats"] = res.cost_stats
        if args.trace_out:
            telemetry.tracer.export_chrome(args.trace_out,
                                           metadata=metadata)
            print(f"trace timeline -> {args.trace_out}", flush=True)
        if args.profile_out:
            device_events, profile_meta = profiler.device_events(
                telemetry.tracer.epoch)
            telemetry.tracer.export_chrome(
                args.profile_out,
                metadata={**metadata, "profile": profile_meta},
                extra_events=device_events)
            print(f"merged device+host timeline -> {args.profile_out} "
                  f"({profile_meta['events']} device events)", flush=True)

    audit_summary = None
    if args.audit:
        audit_summary = run_audit(pz, res, attack_hook, args)

    summary = {
        "arch": cfg.name, "transport": mechanism, "scheme": args.scheme,
        "channel": args.channel or "rayleigh",
        "engine": args.engine,
        "byzantine": ({"behavior": args.byzantine,
                       "fraction": args.byzantine_frac,
                       "defense": args.defense}
                      if byzcfg is not None else None),
        "desync": ({"fraction": args.desync_frac,
                    "max_lag": args.desync_max_lag,
                    "phase_std": args.desync_phase_std}
                   if desynccfg is not None else None),
        "retry_attempts": res.retry_attempts,
        "injected": injector.fired if injector is not None else {},
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "rounds": res.steps,
        "uplink_bits": res.uplink_bits,
        "final_loss": res.losses[-1] if res.losses else None,
        "accuracies": res.accuracies,
        "privacy_spent": res.privacy_spent,
        "privacy_budget": res.privacy_budget,
        "wall_time_s": round(res.wall_time_s, 1),
        "prep_stall_s": round(res.prep_stall_s, 3),
        "ckpt_stall_s": round(res.ckpt_stall_s, 3),
        "peak_bytes": res.peak_bytes,
        "compile_stats": res.compile_stats,
        "resumed_from": res.resumed_from,
    }
    if res.cost_stats is not None:
        summary["cost_stats"] = res.cost_stats
    if health is not None:
        summary["health"] = {
            "policy": args.health_policy,
            "events": health.events,
            "abort_round": res.health_abort_round,
            "abort_reason": res.health_abort_reason,
        }
    if audit_summary is not None:
        summary["audit"] = audit_summary
    print(json.dumps(summary, indent=2))
    result = {**summary, "losses": res.losses, "p_hats": res.p_hats}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f)
    if audit_summary is not None and not audit_summary.get("dominated", True):
        raise SystemExit("AUDIT FAILURE: empirical eps_hat "
                         f"{audit_summary['eps_hat']:.4f} exceeds the "
                         "analytic accountant's "
                         f"{audit_summary['eps_analytic']:.4f}")
    if res.health_abort_round >= 0:
        # distinct exit status so CI can tell "health abort, audit clean"
        # (3) from an audit violation (1)
        print(f"HEALTH ABORT: {res.health_abort_reason} at round "
              f"{res.health_abort_round} — accountant charged only the "
              f"{res.steps} executed rounds", flush=True)
        raise SystemExit(3)
    return result


def run_audit(pz, res, attack_hook, args) -> dict:
    """Post-run privacy audit: seed-replay reconstruction on the captured
    observations + the paired-trace eps_hat bound vs the analytic ledger.
    Consumes the realized schedule/transport the run exposes on its
    RunResult — the adversary knows both (they are broadcast). An active
    defense adjusts the audited config (a transmit clip shrinks the
    canary's worst-case payload to gamma_d) so the audit measures the
    mechanism actually on the air."""
    from repro import privacy as pv
    defense = byz.resolve_defense(pz)
    if defense is not None:
        pz = defense.audited_pz(pz)
    out: dict = {}
    obs = attack_hook.observations()
    payloads = attack_hook.payloads()
    if payloads is not None and ("obs_y" in obs or "obs_q" in obs):
        # score against what was actually radiated (±1 ballots for sign)
        payloads = np.asarray(res.transport.transmitted(payloads))
        replay = pv.get("seed_replay")().run(
            obs, payloads, res.schedule.c, attack_hook.k_eff())
        out["seed_replay"] = {
            "victim_rmse": replay["victim_rmse"],
            "mean_rmse": replay["mean_rmse"],
            "per_client_exposed": replay["per_client_exposed"],
        }
    if res.transport.canary_payload(pz) is not None:
        # analytic side fed from the run's OWN accountant ledger (the
        # per-round spend curve on RunResult) instead of re-deriving the
        # spend from the schedule — one accounting, audit and ledger agree
        audit = pv.audit_transport(
            res.transport, res.schedule, pz,
            rounds=max(res.steps, 1), trials=args.audit_trials,
            spent=res.privacy_spent)
        out.update(audit.to_dict())
        verdict = "OK (eps_hat <= analytic)" if audit.dominated \
            else "VIOLATED"
        print(f"privacy audit: eps_hat={audit.eps_hat:.4f} <= "
              f"analytic eps={audit.eps_analytic:.4f}? {verdict}",
              flush=True)
    else:
        out["auditable"] = False
        print(f"privacy audit: transport {res.transport.name!r} provides "
              "no DP guarantee (payloads individually exposed; see "
              "seed_replay metrics)", flush=True)
    return out


if __name__ == "__main__":
    main()
