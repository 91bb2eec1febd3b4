"""Client-mesh lane: shard_map scan engine ≡ single-device engine, bitwise.

These tests need a multi-device host. CI runs them in a dedicated lane:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_mesh_engine.py -q

On a single-device host every test skips (the flag must be set before the
first jax import, so it cannot be applied from inside the suite).

The contract under test: with `mesh=`, the per-client dual forward runs
shard_map'd over the mesh's (pod, data) client axes and the Transport's
scalar decode consumes a genuine cross-device `jax.lax.psum` (asserted
against the compiled HLO) — while the loss/p_hat/privacy trajectory stays
*bitwise* identical to the single-device engines at fixed seed.
"""
import numpy as np
import pytest

import jax

from repro.channel import RayleighFading
from repro.core import fedsim, pairzero
from repro.core import transport as tp
from repro.launch.mesh import make_client_mesh
from repro.models import registry
from repro.runtime import sharding as shd

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="client-mesh lane needs >= 8 devices (set XLA_FLAGS="
           "--xla_force_host_platform_device_count=8 before jax imports)")


@pytest.fixture(scope="module")
def mesh8():
    return make_client_mesh("8")


def _runs(cfg, pz, make_pipeline, mesh, *, rounds=6, chunk=4, **kw):
    pipe = lambda: make_pipeline(vocab=cfg.vocab_size, n_clients=8, batch=2,
                                 seq=16)
    ref = fedsim.run(cfg, pz, pipe(), rounds=rounds, engine="scan",
                     chunk_rounds=chunk, **kw)
    res = fedsim.run(cfg, pz, pipe(), rounds=rounds, engine="scan",
                     chunk_rounds=chunk, mesh=mesh, **kw)
    return ref, res


# ---------------------------------------------------------------------------
# Bitwise identity: mesh scan == single-device scan (== loop)
# ---------------------------------------------------------------------------

def test_mesh_scan_bitwise_analog_opt125m(opt125m_reduced, make_pz,
                                          make_pipeline, mesh8):
    """The acceptance-criterion test, on the paper's own architecture:
    8 clients shard_map'd over an 8-device ('data',) mesh, uneven chunks."""
    pz = make_pz(scheme="solution", n_perturb=1, rounds=8, n_clients=8)
    pipe = lambda: make_pipeline(vocab=opt125m_reduced.vocab_size,
                                 n_clients=8, batch=2, seq=16)
    res_loop = fedsim.run(opt125m_reduced, pz, pipe(), rounds=8,
                          engine="loop")
    res_scan = fedsim.run(opt125m_reduced, pz, pipe(), rounds=8,
                          engine="scan", chunk_rounds=3)
    res_mesh = fedsim.run(opt125m_reduced, pz, pipe(), rounds=8,
                          engine="scan", chunk_rounds=3, mesh=mesh8)
    assert res_mesh.losses == res_scan.losses == res_loop.losses
    assert res_mesh.p_hats == res_scan.p_hats
    assert res_mesh.privacy_spent == res_scan.privacy_spent
    assert len(res_mesh.losses) == 8


def test_mesh_scan_bitwise_sign(tiny_model, make_pz, make_pipeline, mesh8):
    pz = make_pz(variant="sign", scheme="solution", lr=2e-2, rounds=6,
                 n_clients=8)
    ref, res = _runs(tiny_model, pz, make_pipeline, mesh8)
    assert res.losses == ref.losses
    assert res.p_hats == ref.p_hats


def test_mesh_scan_bitwise_digital(tiny_model, make_pz, make_pipeline,
                                   mesh8):
    """The quantizer draws from the replicated round key, so the digital
    baseline is bit-identical under the mesh too."""
    pz = make_pz(scheme="perfect", rounds=6, n_clients=8)
    transport = tp.DigitalTDMA(quant_bits=8, clip=float(pz.zo.clip_gamma))
    ref, res = _runs(tiny_model, pz, make_pipeline, mesh8,
                     transport=transport)
    assert res.losses == ref.losses


def test_mesh_multiple_clients_per_shard(tiny_model, make_pz,
                                         make_pipeline):
    """K=8 over 4 shards (2 clients per device) — the gather reassembles
    multi-client slices, not just scalars."""
    mesh4 = make_client_mesh("4")
    pz = make_pz(scheme="solution", rounds=6, n_clients=8)
    ref, res = _runs(tiny_model, pz, make_pipeline, mesh4)
    assert res.losses == ref.losses


def test_mesh_pod_data_axes(tiny_model, make_pz, make_pipeline):
    """(pod=2, data=4): client ids linearize pod-major, matching the
    PartitionSpec(('pod','data')) batch tiling."""
    mesh2x4 = make_client_mesh("2x4")
    pz = make_pz(scheme="solution", rounds=6, n_clients=8)
    ref, res = _runs(tiny_model, pz, make_pipeline, mesh2x4)
    assert res.losses == ref.losses
    assert res.p_hats == ref.p_hats


def test_mesh_loop_engine_bitwise(tiny_model, make_pz, make_pipeline,
                                  mesh8):
    """The shard_map'd step under per-round dispatch (engine='loop') —
    executors only change dispatch granularity, never numerics."""
    pz = make_pz(scheme="solution", rounds=5, n_clients=8)
    pipe = lambda: make_pipeline(vocab=tiny_model.vocab_size, n_clients=8,
                                 batch=2, seq=16)
    ref = fedsim.run(tiny_model, pz, pipe(), rounds=5, engine="loop")
    res = fedsim.run(tiny_model, pz, pipe(), rounds=5, engine="loop",
                     mesh=mesh8)
    assert res.losses == ref.losses


def test_mesh_with_model_axis_runs(tiny_model, make_pz, make_pipeline):
    """(data=4, model=2): the 'model' axis stays under GSPMD auto inside
    the shard_map (TP). TP re-tiles contractions, so this is fp-tolerance
    equivalence, not bitwise — the lane proves the partial-auto path
    compiles and trains."""
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    pz = make_pz(scheme="solution", rounds=4, n_clients=8)
    ref, res = _runs(tiny_model, pz, make_pipeline, mesh, rounds=4, chunk=2)
    np.testing.assert_allclose(res.losses, ref.losses, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Byzantine lane: the behavior mask survives shard_map
# ---------------------------------------------------------------------------

def test_mesh_byzantine_attack_bitwise(tiny_model, make_pz, make_pipeline,
                                       mesh8):
    """An attacked+defended run on the mesh is bitwise the single-device
    run: the ctl['byz'] cohort row shards with the control block, each
    shard rewrites only its own client slice, and the grouped robust
    decode consumes the psum-gathered full payload."""
    import dataclasses

    from repro.configs.base import ByzantineConfig
    bz = ByzantineConfig(behavior="sign_flip", fraction=0.25,
                         defense="robust_decode", groups=4)
    pz = dataclasses.replace(
        make_pz(scheme="solution", rounds=6, n_clients=8), byzantine=bz)
    ref, res = _runs(tiny_model, pz, make_pipeline, mesh8)
    assert res.losses == ref.losses
    assert res.p_hats == ref.p_hats
    # and the attack is genuinely on in both runs
    clean = fedsim.run(tiny_model,
                       make_pz(scheme="solution", rounds=6, n_clients=8),
                       make_pipeline(vocab=tiny_model.vocab_size,
                                     n_clients=8, batch=2, seq=16),
                       rounds=6, engine="scan", chunk_rounds=4)
    assert res.losses != clean.losses


def test_mesh_byzantine_noise_behavior_bitwise(tiny_model, make_pz,
                                               make_pipeline, mesh8):
    """gaussian_noise draws the full [K] noise vector then slices at the
    shard offset — the draw-then-slice contract that keeps per-client
    randomness identical however clients are sharded."""
    import dataclasses

    from repro.configs.base import ByzantineConfig
    bz = ByzantineConfig(behavior="gaussian_noise", fraction=0.5, scale=2.0)
    pz = dataclasses.replace(
        make_pz(scheme="solution", rounds=5, n_clients=8), byzantine=bz)
    ref, res = _runs(tiny_model, pz, make_pipeline, mesh8, rounds=5)
    assert res.losses == ref.losses
    # multi-client shards slice interior offsets of the same noise vector
    mesh4 = make_client_mesh("4")
    ref4, res4 = _runs(tiny_model, pz, make_pipeline, mesh4, rounds=5)
    assert res4.losses == ref4.losses == res.losses


def test_mesh_desync_bitwise(tiny_model, make_pz, make_pipeline, mesh8):
    """Active desync on the mesh == single-device, bitwise: the full-[K]
    dsync_stale/dsync_a rows ship replicated with the control block and
    each shard slices its own client window (draw-then-slice, like the
    byzantine noise behavior), while the stale dual forward rides the
    same shard_map body."""
    import dataclasses

    from repro.configs.base import DesyncConfig
    dz = DesyncConfig(fraction=0.5, max_lag=2, phase_std=0.2, seed=0)
    pz = dataclasses.replace(
        make_pz(scheme="solution", rounds=6, n_clients=8), desync=dz)
    ref, res = _runs(tiny_model, pz, make_pipeline, mesh8)
    assert res.losses == ref.losses
    assert res.p_hats == ref.p_hats
    # multi-client shards slice interior offsets of the same stale rows
    mesh4 = make_client_mesh("4")
    ref4, res4 = _runs(tiny_model, pz, make_pipeline, mesh4)
    assert res4.losses == ref4.losses == res.losses
    # and the scenario is genuinely active in the meshed run
    clean = fedsim.run(tiny_model,
                       make_pz(scheme="solution", rounds=6, n_clients=8),
                       make_pipeline(vocab=tiny_model.vocab_size,
                                     n_clients=8, batch=2, seq=16),
                       rounds=6, engine="scan", chunk_rounds=4)
    assert res.p_hats != clean.p_hats


# ---------------------------------------------------------------------------
# Telemetry neutrality on the mesh lane
# ---------------------------------------------------------------------------

def test_mesh_telemetry_is_numerically_passive(tiny_model, make_pz,
                                               make_pipeline, mesh8,
                                               tmp_path):
    """Telemetry ON (tracer + sampler + trilemma ledger + HLO cost
    analysis + health monitor) under an 8-way client mesh vs the default
    OFF: losses, p_hats, and privacy spend stay bitwise identical, the
    ledger's final row equals the mesh run's own RunResult accounting
    exactly, and the introspection sees the mesh program's collectives."""
    from repro import obs
    pz = make_pz(scheme="solution", rounds=6, n_clients=8)
    pipe = lambda: make_pipeline(vocab=tiny_model.vocab_size, n_clients=8,
                                 batch=2, seq=16)
    ref = fedsim.run(tiny_model, pz, pipe(), rounds=6, engine="scan",
                     chunk_rounds=4, mesh=mesh8)
    ledger = str(tmp_path / "mesh_metrics.jsonl")
    res = fedsim.run(tiny_model, pz, pipe(), rounds=6, engine="scan",
                     chunk_rounds=4, mesh=mesh8,
                     telemetry=obs.Telemetry.on(memory_sample_every=2,
                                                cost=True),
                     hooks=[obs.MetricsSink(ledger),
                            obs.HealthMonitor(policy="warn")])
    assert res.losses == ref.losses
    assert res.p_hats == ref.p_hats
    assert res.privacy_spent == ref.privacy_spent
    final = obs.final_row(ledger)
    assert final["bits_cum"] == res.uplink_bits
    assert final["dp_spent_cum"] == res.privacy_spent
    assert final["peak_bytes"] == res.peak_bytes > 0
    assert res.health_abort_round == -1
    # the compiled-program view of the same run: real flops and the
    # client-axis all-reduce the OTA aggregate lowers to
    assert res.cost_stats["flops"] > 0
    assert res.cost_stats["collectives"]["all-reduce"]["count"] >= 1


# ---------------------------------------------------------------------------
# The collective is real: all-reduce in the compiled HLO
# ---------------------------------------------------------------------------

def test_mesh_hlo_contains_client_all_reduce(tiny_model, make_pz,
                                             make_pipeline, mesh8):
    """Structured collective census of the mesh step's compiled HLO
    (repro.obs.hlo): one all-reduce carrying the OTA scalar aggregate
    (Transport.aggregate_mesh's psum) and the loss gather, spanning the
    full 8-client axis; the single-device step compiles collective-
    free; and the census byte total agrees with roofline's independent
    HLO parser on the same text."""
    pz = make_pz(scheme="solution", rounds=4, n_clients=8)
    transport = tp.resolve(pz)
    pipe = make_pipeline(vocab=tiny_model.vocab_size, n_clients=8, batch=2,
                         seq=16)
    batch = {k: v for k, v in pipe.batch(0).items() if k != "labels"}
    params = registry.init_params(jax.random.key(0), tiny_model,
                                  jax.numpy.float32)
    h = RayleighFading().realize(pz.seed ^ 0xC4A7, 4, 8).h
    sched = transport.make_schedule(h, pz)
    ctl = pairzero.make_control(0, sched, pz.seed, 8)

    from repro.launch.roofline import collective_bytes
    from repro.obs.hlo import collective_census

    step = pairzero.make_zo_step(tiny_model, pz, transport=transport)
    single = jax.jit(step).lower(params, batch, ctl).compile().as_text()
    assert collective_census(single) == {}

    mstep = pairzero.make_zo_step(tiny_model, pz, transport=transport,
                                  mesh=mesh8)
    args = (jax.device_put(params, shd.params_sharding(mesh8, params)),
            jax.device_put(batch, shd.batch_sharding(mesh8, batch)),
            jax.device_put(ctl, shd.control_sharding(mesh8, ctl)))
    meshed = jax.jit(mstep).lower(*args).compile().as_text()
    census = collective_census(meshed)
    ar = census["all-reduce"]
    # XLA's combiner fuses the two psums (the OTA payload [8] and the
    # (L+, L-) gather [2, 8]) into ONE tuple all-reduce over all 8 clients
    assert ar["count"] == 1
    assert ar["group_sizes"] == [8]
    assert ar["bytes"] == 4 * (8 + 2 * 8)
    # two independent HLO parsers, one answer: the census byte totals
    # must match roofline's analytic collective model on the same text
    total, by_op = collective_bytes(meshed)
    assert sum(c["bytes"] for c in census.values()) == total
    assert {op: c["bytes"] for op, c in census.items()} == by_op


# ---------------------------------------------------------------------------
# Sharded checkpoint / resume
# ---------------------------------------------------------------------------

def test_mesh_checkpoint_resume_bitwise(tiny_model, make_pz, make_pipeline,
                                        mesh8, tmp_path):
    """Interrupt a mesh run at a chunk-boundary checkpoint, resume on the
    mesh — the tail matches the uninterrupted single-device loop bitwise
    (FSDP-sharded params gather into the npz and reshard on restore)."""
    pz = make_pz(scheme="solution", rounds=8, n_clients=8)
    pipe = lambda: make_pipeline(vocab=tiny_model.vocab_size, n_clients=8,
                                 batch=2, seq=16)
    res_ref = fedsim.run(tiny_model, pz, pipe(), rounds=8, engine="loop")

    ck = str(tmp_path / "ck")
    fedsim.run(tiny_model, pz, pipe(), rounds=4, engine="scan",
               chunk_rounds=4, mesh=mesh8, checkpoint_dir=ck,
               checkpoint_every=4)
    res = fedsim.run(tiny_model, pz, pipe(), rounds=8, engine="scan",
                     chunk_rounds=4, mesh=mesh8, checkpoint_dir=ck,
                     checkpoint_every=1000)
    assert res.resumed_from == 4
    assert res.losses == res_ref.losses[4:]
    assert res.privacy_spent == pytest.approx(res_ref.privacy_spent)


# ---------------------------------------------------------------------------
# Guard rails
# ---------------------------------------------------------------------------

def test_mesh_rejects_indivisible_clients(tiny_model, make_pz,
                                          make_pipeline, mesh8):
    pz = make_pz(rounds=4, n_clients=5)
    with pytest.raises(ValueError, match="divide evenly"):
        fedsim.run(tiny_model, pz, make_pipeline(n_clients=5), rounds=4,
                   engine="scan", mesh=mesh8)


def test_mesh_rejects_fo(tiny_model, make_pz, make_pipeline, mesh8):
    pz = make_pz(variant="fo", scheme="perfect", rounds=4, n_clients=8)
    with pytest.raises(ValueError, match="FO baseline"):
        fedsim.run(tiny_model, pz, make_pipeline(n_clients=8), rounds=4,
                   engine="scan", mesh=mesh8)


# ---------------------------------------------------------------------------
# Privacy capture on the mesh (repro.privacy)
# ---------------------------------------------------------------------------

def test_mesh_observation_capture_bitwise(tiny_model, make_pz,
                                          make_pipeline, mesh8):
    """Eavesdropper capture under shard_map: the observation is computed
    from the psum-gathered [K] payload and the replicated control block,
    so it must be bitwise what the single-device engines record — and
    capture must stay passive on the mesh too."""
    from repro import privacy as pv
    pz = make_pz(scheme="solution", n_perturb=1, rounds=6, n_clients=8)
    pipe = lambda: make_pipeline(vocab=tiny_model.vocab_size, n_clients=8,
                                 batch=2, seq=16)
    h_ref, h_mesh = pv.AttackHook(), pv.AttackHook()
    ref = fedsim.run(tiny_model, pz, pipe(), rounds=6, engine="scan",
                     chunk_rounds=4, adversary=pv.Adversary(),
                     hooks=[h_ref])
    res = fedsim.run(tiny_model, pz, pipe(), rounds=6, engine="scan",
                     chunk_rounds=4, mesh=mesh8, adversary=pv.Adversary(),
                     hooks=[h_mesh])
    assert res.losses == ref.losses                  # capture stays passive
    np.testing.assert_array_equal(h_mesh.observations()["obs_y"],
                                  h_ref.observations()["obs_y"])
    np.testing.assert_array_equal(h_mesh.payloads(), h_ref.payloads())
