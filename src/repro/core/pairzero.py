"""pAirZero step factory: the paper's algorithm as composable jitted steps.

One round of Algorithm 1, as a single jitted function over the client mesh:

  1. every client evaluates its clipped gradient projection p_k from the
     shared round seed (two forwards, MeZO-chained — inference-level memory);
  2. the round's Transport (repro.core.transport) recovers p̂ from the [K]
     payload vector — for the OTA mechanisms that is superposition +
     channel inversion, ONE scalar psum over the client axes (the paper's
     O(1) communication claim, visible in HLO); for the digital baseline it
     is per-slot decode + average;
  3. every replica applies w ← w − η p̂ z from the same seed (replicas stay
     bit-identical by construction — no parameter broadcast ever happens).

Round-varying control (c(t), σ(t), round seed, survival mask, noise key) is
passed as *data*, so the step compiles exactly once per shape. The uplink
mechanism is part of the (hashable) config, so the memoized factories and
jit caches key on it.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.byzantine import behaviors as byz_behaviors
from repro.configs.base import ModelConfig, PairZeroConfig
from repro.core import transport as tp
from repro.core import zo
from repro.models import registry
from repro.obs import retrace
from repro.runtime import desync as ds

PyTree = Any


def make_loss_fn(model_cfg: ModelConfig, impl: Optional[str] = None
                 ) -> Callable[[PyTree, Dict], jnp.ndarray]:
    """Per-client loss vector [K] for this architecture."""
    mod = registry.get_module(model_cfg)

    def loss_fn(params: PyTree, batch: Dict) -> jnp.ndarray:
        return mod.loss_per_client(params, model_cfg, batch, impl=impl)

    return loss_fn


def control_spec(n_clients: int,
                 transport: Optional[tp.Transport] = None,
                 behavior: Optional[Any] = None,
                 desync: Optional[Any] = None
                 ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract shapes of the per-round control block (dry-run input spec).

    The spec is owned by the Transport; the default is the standard block
    shared by every built-in mechanism. An active `behavior`
    (repro.byzantine) extends it with the [K] cohort indicator row; an
    active `desync` (repro.runtime.desync) with the lagged round seed and
    the [K] stale/alignment/frame rows."""
    t = transport if transport is not None else tp.Transport()
    spec = t.control_spec(n_clients)
    if behavior is not None:
        spec = dict(spec)
        spec["byz"] = jax.ShapeDtypeStruct((n_clients,), jnp.float32)
    if desync is not None:
        spec = dict(spec)
        spec["dsync_seed"] = jax.ShapeDtypeStruct((), jnp.uint32)
        for row in ("dsync_stale", "dsync_a", "dsync_frame"):
            spec[row] = jax.ShapeDtypeStruct((n_clients,), jnp.float32)
    return spec


def make_control(t: int, schedule, base_seed: int, n_clients: int,
                 mask=None, g=None, byz=None, dsync=None) -> Dict:
    """Host-side: build round-t control block from a PowerSchedule.

    `g` is the round's [K] per-client effective-gain (cos θ) vector from
    the channel trace; None means perfect CSI (all ones — bitwise neutral
    in the step). `byz` is the [K] malicious-cohort indicator
    (repro.byzantine); None keeps the historical block — the key is only
    present when a behavior is active, mirroring `engine.build_trace`.
    `dsync` is the round's desync row dict (dsync_seed / dsync_stale /
    dsync_a / dsync_frame, from `repro.runtime.desync.control_rows`);
    None likewise keeps the rows absent."""
    key = jax.random.fold_in(jax.random.key(base_seed ^ 0x5EED), t)
    ctl = {
        "seed": zo.round_seed(base_seed, t),
        "c": jnp.float32(schedule.c[t]),
        "sigma": jnp.asarray(schedule.sigma[t], jnp.float32),
        "n0": jnp.float32(schedule.n0),
        "mask": jnp.ones((n_clients,), jnp.float32) if mask is None
        else jnp.asarray(mask, jnp.float32),
        "g": jnp.ones((n_clients,), jnp.float32) if g is None
        else jnp.asarray(g, jnp.float32),
        "noise_bits": jax.random.key_data(key),
    }
    if byz is not None:
        ctl["byz"] = jnp.asarray(byz, jnp.float32)
    if dsync is not None:
        ctl["dsync_seed"] = jnp.asarray(dsync["dsync_seed"], jnp.uint32)
        for row in ("dsync_stale", "dsync_a", "dsync_frame"):
            ctl[row] = jnp.asarray(dsync[row], jnp.float32)
    return ctl


@functools.lru_cache(maxsize=128)
def make_zo_step(model_cfg: ModelConfig, pz: PairZeroConfig,
                 impl: Optional[str] = None,
                 scheme: Optional[str] = None,
                 transport: Optional[tp.Transport] = None,
                 mesh: Optional[Mesh] = None,
                 adversary: Optional[Any] = None,
                 behavior: Optional[Any] = None,
                 defense: Optional[Any] = None,
                 desync: Optional[Any] = None) -> Callable:
    """Build the jitted ZO train step for any scalar-payload Transport
    (analog / sign / perfect / digital / user-registered).

    Returns step(params, batch, ctl) → (new_params, metrics).

    Memoized on the (frozen, hashable) configs and the (frozen, hashable)
    Transport: repeated runs with identical configs get the *same* function
    object back, so jit/scan caches hit instead of retracing — fedsim and
    the scan engine stay compile-once across invocations (benchmarks,
    tests, resumed runs). `scheme` is the deprecated string override kept
    for one release; prefer `transport` or `pz.transport`.

    `mesh` (hashable, part of the memo key) selects the shard_map'd
    variant: the per-client dual forward runs on the mesh's (pod, data)
    client axes — each shard holds its clients' batch slice and evaluates
    only their losses — and the Transport's scalar decode consumes ONE
    `jax.lax.psum` over those axes (`Transport.aggregate_mesh`), the
    cross-device all-reduce the paper's O(1) uplink maps onto. Params and
    control enter replicated w.r.t. the client axes (a 'model' axis, if
    present, stays under GSPMD auto for TP/FSDP); the trajectory is
    bit-identical to the single-device step (tests/test_mesh_engine.py).

    `adversary` (a frozen `repro.privacy.Adversary`, hashable — part of the
    memo key) switches on eavesdropper observation capture: the round's
    Transport recomputes what an over-the-air listener sees (same per-round
    key as the decode ⇒ bit-identical noise draws) and the observation
    rides the metrics stream as `obs_*` entries — device-resident through a
    scanned chunk, stacked identically by both executors. Capture is
    passive: the training trajectory is bitwise unchanged, and
    `adversary=None` traces the exact historical program.

    `behavior` (a frozen `repro.byzantine.ClientBehavior`) rewrites the
    [K] payload vector AFTER projection and BEFORE the Transport aggregate
    — the malicious payload superposes through the real decode path on
    every engine, gated per client by the device-resident ctl["byz"]
    cohort row. `defense` (a frozen `repro.byzantine.Defense`) applies the
    PHY transmit constraint to every client and, when it overrides the
    decode, replaces the aggregate call (sub-slot group decodes). Both are
    part of the memo key; None traces the historical program unchanged —
    Byzantine neutrality is structural, like the adversary's.

    `desync` (a frozen `repro.runtime.DesyncModel`) models clients that
    missed the round-t seed broadcast: each stale client's scalar is the
    projection of an EXTRA fresh-mode dual forward evaluated against the
    lagged broadcast seed ctl["dsync_seed"] (z_{t−d}), selected per client
    by the device-resident ctl["dsync_stale"] row before the behavior /
    defense / Transport chain; the per-client timing attenuation
    ctl["dsync_a"] enters `ota.superpose` inside the Transports. Part of
    the memo key; None traces the bit-exact synchronized program.
    """
    retrace.bump(retrace.ZO_STEP_BUILD)     # lru MISS: a fresh step build
    loss_fn = make_loss_fn(model_cfg, impl=impl)
    transport = transport if transport is not None \
        else tp.resolve(pz, scheme=scheme)
    mu = pz.zo.mu
    lr = pz.zo.lr
    gamma = pz.zo.clip_gamma
    n_perturb = pz.zo.n_perturb
    if pz.fused_perturbation:
        # fused dual forward: z regenerated inside the layer kernels
        # (zo.tag_perturbed) — wired for the transformer families only
        if model_cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"fused_perturbation supports the dense/moe families; "
                f"{model_cfg.name!r} is family {model_cfg.family!r} "
                "(its layer stack has consumers without a fused path)")
        mode = "fused"
    else:
        mode = "chained" if pz.zo.dual_mode in ("chained", "sequential") \
            else "fresh"

    def round_body(params: PyTree, batch: Dict, ctl: Dict,
                   client_ids: Optional[jnp.ndarray] = None,
                   client_axes: Tuple[str, ...] = ()
                   ) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
        """One pAirZero round. With `client_axes` set this runs as a
        shard_map body: the dual forward sees only the local client shard
        (`client_ids` is its slice of the global client-id iota — data, not
        `axis_index`, so the same body lowers on partial-auto meshes);
        (L+, L−) are reassembled across shards for the loss/projection
        metrics while the Transport performs its own client-axis psum."""
        metrics = {}
        p_hat_sum = jnp.float32(0.0)
        loss_acc = jnp.float32(0.0)
        k_total = ctl["mask"].shape[-1]
        for j in range(n_perturb):
            seed = zo.perturb_seed(ctl["seed"], j)
            if desync is not None:
                # stale clients evaluated against the LAGGED broadcast
                # seed: a non-destructive fresh-mode dual forward BEFORE
                # the main (possibly chained, in-place) walk below
                s_seed = zo.perturb_seed(ctl["dsync_seed"], j)
                lp_s, lm_s, _ = zo.dual_forward(
                    lambda p: loss_fn(p, batch), params, s_seed, mu,
                    mode="fresh", impl=impl)
            lp, lm, params_at = zo.dual_forward(
                lambda p: loss_fn(p, batch), params, seed, mu, mode=mode,
                impl=impl)
            # projection, the Transport's decode and its DP noise, and
            # below the round's loss and p̂ metrics: `ota_aggregate`
            with jax.named_scope("ota_aggregate"):
                noise_key = jax.random.wrap_key_data(ctl["noise_bits"])
                round_key = jax.random.fold_in(noise_key, j)
                if client_axes:
                    offset = client_ids[0]        # shard's first global client
                    p_local = zo.projection(lp, lm, mu, gamma)    # [K/n]
                    if desync is not None:
                        p_local = ds.stale_payload(
                            p_local, zo.projection(lp_s, lm_s, mu, gamma),
                            ctl, offset)
                    if behavior is not None:
                        p_local = byz_behaviors.apply_behavior(
                            behavior, p_local, ctl, round_key, offset)
                    if defense is not None:
                        p_local = defense.transmit(p_local, ctl)
                        p_hat = defense.aggregate_mesh(
                            transport, p_local, ctl, round_key, client_axes,
                            offset)
                    else:
                        p_hat = transport.aggregate_mesh(
                            p_local, ctl, round_key, client_axes, offset)
                    if desync is not None:
                        lp, lm, lp_s, lm_s = tp.client_all_gather(
                            jnp.stack([lp, lm, lp_s, lm_s]), client_axes,
                            offset, k_total)
                    else:
                        lp, lm = tp.client_all_gather(
                            jnp.stack([lp, lm]), client_axes, offset, k_total)
                    p_k = zo.projection(lp, lm, mu, gamma)        # [K], full
                    # the full radiated payload for metrics/observations:
                    # re-applying attack + PHY clip on the gathered vector is
                    # bit-identical to the concatenation of the shard-local
                    # payloads (elementwise ops; shared draws sliced per shard)
                    if desync is not None:
                        p_k = ds.stale_payload(
                            p_k, zo.projection(lp_s, lm_s, mu, gamma), ctl)
                    if behavior is not None:
                        p_k = byz_behaviors.apply_behavior(
                            behavior, p_k, ctl, round_key)
                    if defense is not None:
                        p_k = defense.transmit(p_k, ctl)
                else:
                    p_k = zo.projection(lp, lm, mu, gamma)        # [K]
                    if desync is not None:
                        p_k = ds.stale_payload(
                            p_k, zo.projection(lp_s, lm_s, mu, gamma), ctl)
                    if behavior is not None:
                        p_k = byz_behaviors.apply_behavior(
                            behavior, p_k, ctl, round_key)
                    if defense is not None:
                        p_k = defense.transmit(p_k, ctl)
                        p_hat = defense.aggregate(transport, p_k, ctl,
                                                  round_key)
                    else:
                        p_hat = transport.aggregate(p_k, ctl, round_key)
            # restore + update fused into one axpy (chained mode)
            params = zo.apply_update(params_at, seed, p_hat,
                                     lr / n_perturb, mu, mode=mode,
                                     impl=impl)
            with jax.named_scope("ota_aggregate"):
                p_hat_sum += p_hat.astype(jnp.float32)
                loss_acc += jnp.mean(0.5 * (lp + lm)).astype(jnp.float32)
            if j == 0:
                metrics["p_clients"] = p_k
                if adversary is not None:
                    # what the eavesdropper records this round (first
                    # perturbation direction): same payload vector and same
                    # round key as the decode, so the captured observation
                    # is bit-identical to the signal the server inverted
                    metrics.update(
                        adversary.observe(transport, p_k, ctl, round_key))
        with jax.named_scope("ota_aggregate"):
            metrics["loss"] = loss_acc / n_perturb
            metrics["p_hat"] = p_hat_sum / n_perturb
            metrics["k_eff"] = jnp.sum(ctl["mask"])
        return params, metrics

    if mesh is None:
        return round_body

    from repro.runtime import sharding as shd
    axes = shd.client_axes(mesh)
    if not axes:
        raise ValueError(f"mesh {mesh.axis_names} has no client axes "
                         "(want 'pod' and/or 'data')")
    body = functools.partial(round_body, client_axes=axes)

    def sharded_step(params: PyTree, batch: Dict, ctl: Dict
                     ) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
        repl = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)
        bspecs = jax.tree_util.tree_map(
            lambda l: P(axes, *([None] * (l.ndim - 1))), batch)
        metric_specs = {"p_clients": P(), "loss": P(), "p_hat": P(),
                        "k_eff": P()}
        if adversary is not None:
            # observations are computed from the gathered [K] payload and
            # the replicated control block — replicated w.r.t. the client
            # axes like every other scalar metric
            metric_specs.update({k: P() for k in adversary.observation_spec(
                transport, pz.n_clients)})
        out_specs = (repl(params), metric_specs)
        k_total = ctl["mask"].shape[-1]
        ids = jnp.arange(k_total, dtype=jnp.int32)

        def manual_body(pr, ba, ct, ci):
            # model-side sharding hints must not mention the now-manual
            # client axes (with_sharding_constraint would reject them)
            with shd.manual_axes(axes):
                return body(pr, ba, ct, client_ids=ci)

        new_params, metrics = jax.shard_map(
            manual_body, mesh=mesh,
            in_specs=(repl(params), bspecs, repl(ctl), P(axes)),
            out_specs=out_specs, axis_names=set(axes), check_vma=False)(
                params, batch, ctl, ids)
        # pin the carry back to the FSDP layout so a surrounding lax.scan
        # keeps one stable placement instead of round-tripping per round
        new_params = jax.lax.with_sharding_constraint(
            new_params, shd.params_sharding(mesh, new_params))
        return new_params, metrics

    return sharded_step


@functools.lru_cache(maxsize=128)
def make_fo_step(model_cfg: ModelConfig, optimizer,
                 impl: Optional[str] = None,
                 adversary: Optional[Any] = None,
                 desync: Optional[Any] = None) -> Callable:
    """First-order FedSGD baseline: full backprop + cross-client grad
    averaging (the d-dimensional all-reduce the paper eliminates).

    Memoized like `make_zo_step` — optimizers are frozen dataclasses, so
    equal configs return the same function object and jit caches hit.

    `adversary` captures what the FO uplink leaks: the victim client's raw
    d-dimensional gradient (flattened, f32) as the `obs_grad0` metric — the
    classic gradient-inversion surface repro.privacy's DLG attack consumes.
    Capture is honest about FO's cost: one EXTRA per-client backward per
    round, and a [d] f32 observation riding every round's metrics (a scan
    chunk carries chunk_rounds of them) — at production model sizes run
    audited FO on short horizons/small chunks and cap the host-side stream
    with `AttackHook(max_rounds=...)`.

    `desync` (a frozen `repro.runtime.DesyncModel`) models what frame
    desynchronization does to this CONVENTIONAL d-dimensional analog OTA
    uplink: a client's phase error θ accumulates across the
    frame_symbols-slot frame, so the coordinate riding symbol k combines
    with gain cos(kθ) — averaged over clients the late-frame coordinates
    random-phase out while the server still inverts by the full
    surviving count (`desync.conventional_frame`; stale clients
    contribute nothing — their frame carries an old round), and the
    energy the misaligned clients lose re-enters as inter-symbol
    interference noise on the decoded gradient
    (`desync.conventional_ici`, keyed off the round's noise_bits). The
    degraded decode drives the GRADIENT only; the reported `loss` metric
    stays the true masked mean, so desynced and clean runs are directly
    comparable. None traces the bit-exact synchronized program.
    """
    retrace.bump(retrace.FO_STEP_BUILD)     # lru MISS: a fresh step build
    loss_fn = make_loss_fn(model_cfg, impl=impl)

    def step(params: PyTree, opt_state: PyTree, batch: Dict, ctl: Dict
             ) -> Tuple[PyTree, PyTree, Dict[str, jnp.ndarray]]:
        def mean_loss(p):
            per_client = loss_fn(p, batch)                    # [K]
            mask = ctl["mask"]
            return jnp.sum(per_client * mask) / jnp.maximum(
                jnp.sum(mask), 1.0)

        loss, grads = jax.value_and_grad(mean_loss)(params)
        if desync is not None:
            # the server decodes a per-coordinate attenuated frame (phase
            # error accumulating over the frame's symbol slots) plus the
            # lost energy as interference; the reported `loss` metric
            # stays the true masked mean so desynced and clean runs are
            # directly comparable
            noise_key = jax.random.wrap_key_data(ctl["noise_bits"])
            framed = ds.conventional_frame(grads, ctl,
                                           desync.frame_symbols)
            grads = ds.conventional_ici(framed, ctl, noise_key,
                                        ref=grads)
        metrics = {"loss": loss}
        if adversary is not None:
            from jax.flatten_util import ravel_pytree
            from repro.privacy.adversary import OBS_PREFIX
            g0 = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
            metrics[OBS_PREFIX + "grad0"] = ravel_pytree(
                jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), g0))[0]
        params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, metrics

    return step


@functools.lru_cache(maxsize=128)
def jit_zo_step(step: Callable, donate: bool = True):
    """jit with parameter-buffer donation (the MeZO in-place chain).

    Memoized so the same step object maps to the same jitted wrapper (and
    therefore the same XLA executable cache) across fedsim.run calls.

    The wrapper's only addition over a bare `jax.jit(step)` is a Python
    side effect at TRACE time (`retrace.STEP_TRACE`): it calls `step`
    unchanged, so the jaxpr — and therefore the loop engine's trajectory —
    is bit-identical to the historical direct jit.
    """
    @functools.wraps(step)
    def traced(*args):
        retrace.bump(retrace.STEP_TRACE)
        return step(*args)

    return jax.jit(traced, donate_argnums=(0,) if donate else ())
