"""Device-visible observability: HLO introspection, profiler merge,
health monitor, bench history.

The contracts under test, in order of importance:

  1. NEUTRALITY — `Telemetry(cost=True)` and an attached HealthMonitor
     (warn) are numerically passive on loop and scan, and the HLO
     analysis (which lowers the executor's program once) never bumps a
     retrace counter (`retrace.suspended`), so the CI's exact
     compile-count pins survive.
  2. INTROSPECTION — `obs.hlo` reads the compiled executable's own
     numbers: positive flops/peak on real programs, a collective census
     that parses both literal and iota replica_groups, and byte totals
     that agree with roofline's independent HLO parser.
  3. HEALTH — the three detectors (nonfinite/divergence/plateau) fire on
     rising edges, `abort` stops the run at chunk granularity with
     executed == charged rounds, and the abort lands on RunResult.
  4. CRASH CONSISTENCY — `read_ledger` tolerates exactly one torn
     trailing record (strict=False) and never a torn middle line.
  5. MERGED TIMELINE — a real `ProfilerSession` capture anchors onto the
     tracer epoch and the merged trace passes
     `check_trace.py --require-device-lane`.
  6. HISTORY — bench_history rows validate, and `check_bench --history`
     gates same-hardware regressions while ignoring other hosts.
"""
import json
import math
import subprocess
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import fedsim
from repro.obs import hlo as ohlo
from repro.obs import retrace
from repro.obs.health import HealthAbort, HealthMonitor
from repro.obs.ledger import MetricsSink, read_ledger

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

import bench_history  # noqa: E402


def _run(cfg, pz, make_pipeline, *, rounds, engine="scan", chunk=3, **kw):
    pipe = make_pipeline(vocab=cfg.vocab_size, n_clients=pz.n_clients,
                         batch=2, seq=16)
    return fedsim.run(cfg, pz, pipe, rounds=rounds, engine=engine,
                      chunk_rounds=chunk, **kw)


# ---------------------------------------------------------------------------
# 1. Collective census parsing (pure text)
# ---------------------------------------------------------------------------

def test_census_parses_literal_and_iota_groups():
    hlo = """
  %ar = f32[128,4]{1,0} all-reduce(f32[128,4]{1,0} %x), replica_groups={{0,1},{2,3}}, to_apply=%add
  %ag.1 = bf16[256]{0} all-gather(bf16[32]{0} %y), replica_groups=[2,4]<=[8], dimensions={0}
  %rs = f32[16]{0} reduce-scatter-start(f32[64]{0} %z), replica_groups={{0,1,2,3}}, to_apply=%add
"""
    census = ohlo.collective_census(hlo)
    ar = census["all-reduce"]
    assert ar["count"] == 1
    assert ar["bytes"] == 128 * 4 * 4
    assert ar["group_sizes"] == [2, 2]          # literal {{0,1},{2,3}}
    ag = census["all-gather"]
    assert ag["bytes"] == 256 * 2
    assert ag["group_sizes"] == [4, 4]          # iota [2,4]<=[8]: 2 groups of 4
    rs = census["reduce-scatter"]               # -start folds into the base op
    assert rs["count"] == 1
    assert rs["group_sizes"] == [4]


def test_census_ignores_non_collective_text():
    hlo = """
  %d = f32[64,64]{1,0} dot(f32[64,64]{1,0} %a, f32[64,64]{1,0} %b)
  ROOT %t = (f32[64,64]{1,0}) tuple(%d)
  // an all-reduce mentioned in a comment must not count
"""
    assert ohlo.collective_census(hlo) == {}


def test_custom_call_census_counts_targets():
    """Pallas kernels compile to `tpu_custom_call`s on a TPU; the census
    counts each custom-call target of a module's HLO text."""
    hlo = """
  %a = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), custom_call_target="tpu_custom_call", backend_config="x"
  %b = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %a), custom_call_target="tpu_custom_call"
  %c = s32[4]{0} custom-call(s32[4]{0} %i), custom_call_target="AssumeGatherIndicesInBound"
  %d = f32[8,128]{1,0} add(f32[8,128]{1,0} %a, f32[8,128]{1,0} %b)
"""
    assert ohlo.custom_call_census(hlo) == {
        "tpu_custom_call": 2, "AssumeGatherIndicesInBound": 1}
    assert ohlo.custom_call_census("") == {}


# ---------------------------------------------------------------------------
# 2. Compiled-executable introspection
# ---------------------------------------------------------------------------

def test_analyze_compiled_reports_real_numbers():
    @jax.jit
    def f(a, b):
        return jnp.tanh(a @ b).sum()

    spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    stats = ohlo.analyze_compiled(f.lower(spec, spec).compile())
    assert stats.flops > 0
    assert stats.peak_bytes > 0
    assert stats.argument_bytes >= 2 * 64 * 64 * 4
    assert stats.collectives == {}
    assert stats.custom_calls == {}     # XLA:CPU, no Pallas kernel
    d = stats.to_dict()
    assert d["flops"] == stats.flops
    assert "collective_bytes" in d
    assert d["custom_calls"] == {}
    text = ohlo.describe(stats)
    assert "flops" in text and "peak" in text


def test_cost_stats_ride_run_result(tiny_model, make_pz, make_pipeline):
    pz = make_pz(scheme="solution", rounds=4)
    res = _run(tiny_model, pz, make_pipeline, rounds=4, chunk=2,
               telemetry=obs.Telemetry(cost=True))
    cs = res.cost_stats
    assert cs is not None and "error" not in cs
    assert cs["flops"] > 0 and cs["peak_bytes"] > 0
    # single-device program: the census must be empty, not missing
    assert cs["collectives"] == {}


def test_cost_analysis_is_passive_and_retrace_silent(tiny_model, make_pz,
                                                     make_pipeline):
    """The analysis lowers the executor's program a second time; without
    `retrace.suspended` that lowering would re-enter the traced bodies
    and bump the counters the CI pins exactly."""
    pz = make_pz(scheme="solution", rounds=6)
    for engine in ("loop", "scan"):
        _run(tiny_model, pz, make_pipeline, rounds=6,
             engine=engine, chunk=3)            # pay the cold compile
        ref = _run(tiny_model, pz, make_pipeline, rounds=6,
                   engine=engine, chunk=3)
        res = _run(tiny_model, pz, make_pipeline, rounds=6,
                   engine=engine, chunk=3,
                   telemetry=obs.Telemetry(cost=True))
        assert res.losses == ref.losses, engine
        assert res.privacy_spent == ref.privacy_spent, engine
        # warm + warm: both all-zero — the analysis lowering must not
        # re-fire any build/trace counter
        assert all(v == 0 for v in ref.compile_stats.values()), engine
        assert all(v == 0 for v in res.compile_stats.values()), engine
        assert res.cost_stats is not None


def test_suspended_blocks_bump_and_restores():
    before = retrace.snapshot()
    with retrace.suspended():
        retrace.bump("zo_step_build")
        with retrace.suspended():        # reentrant
            retrace.bump("zo_step_build")
        retrace.bump("zo_step_build")
    assert all(v == 0 for v in retrace.since(before).values())
    retrace.bump("zo_step_build")
    assert retrace.since(before)["zo_step_build"] == 1


# ---------------------------------------------------------------------------
# 3. Health monitor
# ---------------------------------------------------------------------------

def test_health_detectors_fire_on_rising_edge():
    hm = HealthMonitor(policy="warn", divergence_factor=10.0,
                       plateau_rounds=2)
    hm.on_start(None)
    hm.on_round(0, {"loss": 1.0})
    hm.on_round(1, {"loss": 50.0})       # divergence: > 10x best
    hm.on_round(2, {"loss": 60.0})       # still firing: no new event
    assert [e["kind"] for e in hm.events] == ["divergence"]
    assert hm.events[0]["round"] == 1
    hm.on_round(3, {"loss": 0.5})        # recovery clears the edge
    hm.on_round(4, {"loss": 0.6})
    hm.on_round(5, {"loss": 0.7})        # 2 rounds without improvement
    kinds = [e["kind"] for e in hm.events]
    assert kinds == ["divergence", "plateau"]
    hm.on_round(6, {"loss": float("nan")})
    assert [e["kind"] for e in hm.events][-1] == "nonfinite"


def test_health_abort_raises_with_round_and_reason():
    hm = HealthMonitor(policy="abort")
    hm.on_start(None)
    hm.on_round(0, {"loss": 2.0})
    with pytest.raises(HealthAbort) as ei:
        hm.on_round(7, {"loss": float("inf")})
    assert ei.value.round == 7
    assert ei.value.reason == "nonfinite"
    with pytest.raises(ValueError):
        HealthMonitor(policy="explode")


def test_health_warn_is_numerically_passive(tiny_model, make_pz,
                                            make_pipeline):
    pz = make_pz(scheme="solution", rounds=6)
    for engine in ("loop", "scan"):
        ref = _run(tiny_model, pz, make_pipeline, rounds=6, engine=engine)
        hm = HealthMonitor(policy="warn")
        res = _run(tiny_model, pz, make_pipeline, rounds=6, engine=engine,
                   hooks=[hm])
        assert res.losses == ref.losses, engine
        assert res.privacy_spent == ref.privacy_spent, engine
        assert res.health_abort_round == -1


def test_health_abort_realized_spend(tiny_model, make_pz, make_pipeline):
    """Abort mid-run: executed rounds == charged rounds, so the spend on
    RunResult is the realized (shorter) ledger, not the planned one."""
    pz = make_pz(scheme="solution", rounds=12)
    full = _run(tiny_model, pz, make_pipeline, rounds=12, chunk=2)
    # fire deterministically at round 4 regardless of the loss curve
    hm = HealthMonitor(policy="abort")
    fired = {}

    def fire_at(t, metrics, _orig=hm.on_round):
        if t >= 4 and not fired:
            fired["t"] = t
            raise HealthAbort(t, "synthetic")
    hm.on_round = fire_at
    res = _run(tiny_model, pz, make_pipeline, rounds=12, chunk=2,
               hooks=[hm])
    assert res.health_abort_round == 4
    assert res.health_abort_reason == "synthetic"
    # round 4's metrics flush after the NEXT chunk is dispatched (the
    # driver pipelines), so charged == executed == 8 of 12 rounds
    assert res.steps < 12
    assert len(res.privacy_spent_per_round) == res.steps
    assert res.privacy_spent < full.privacy_spent
    # per-round is the cumulative fold; its last entry IS the spend
    assert res.privacy_spent == float(res.privacy_spent_per_round[-1])


# ---------------------------------------------------------------------------
# 4. Torn ledger + deleted-buffer watermark
# ---------------------------------------------------------------------------

def _write_ledger(path, n_rows, torn_at=None):
    sink_header = {"schema": MetricsSink.SCHEMA, "arch": "tiny"}
    lines = [json.dumps(sink_header)]
    for i in range(n_rows):
        lines.append(json.dumps({"round": i, "loss": 1.0}))
    if torn_at is not None:
        lines[torn_at] = lines[torn_at][: len(lines[torn_at]) // 2]
    path.write_text("\n".join(lines) + "\n")


def test_read_ledger_tolerates_torn_tail_only(tmp_path):
    p = tmp_path / "m.jsonl"
    _write_ledger(p, 4, torn_at=4)          # last row torn
    with pytest.raises(json.JSONDecodeError):
        read_ledger(str(p))                 # strict default
    led = read_ledger(str(p), strict=False)
    assert led["truncated"] is True
    assert len(led["rows"]) == 3
    _write_ledger(p, 4, torn_at=2)          # torn MIDDLE line: corruption
    with pytest.raises(json.JSONDecodeError):
        read_ledger(str(p), strict=False)
    _write_ledger(p, 4)
    led = read_ledger(str(p), strict=False)
    assert led["truncated"] is False and len(led["rows"]) == 4


def test_live_buffer_bytes_skips_deleted(tiny_model):
    """Donated carry buffers linger in jax.live_arrays() as deleted
    husks; counting them double-charges the watermark (the v3 fix)."""
    from repro.obs.memory import live_buffer_bytes
    a = jnp.ones((128,), jnp.float32)
    b = jnp.ones((256,), jnp.float32)
    total = live_buffer_bytes([a, b])
    f = jax.jit(lambda x: x * 2.0, donate_argnums=(0,))
    c = f(b)                                # b's buffer is now deleted
    jax.block_until_ready(c)
    assert b.is_deleted()
    assert live_buffer_bytes([a, b]) == a.nbytes
    assert total == a.nbytes + 256 * 4


# ---------------------------------------------------------------------------
# 5. Profiler-merged timeline (real capture, CPU)
# ---------------------------------------------------------------------------

def test_profiler_merge_passes_device_lane_gate(tmp_path):
    tracer = obs.Tracer()
    prof = obs.ProfilerSession(logdir=str(tmp_path / "prof"))
    prof.start()
    with tracer.span("chunk", chunk=0):
        with tracer.span("dispatch"):
            x = jnp.ones((256, 256), jnp.float32)
            jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
        with tracer.span("chunk_prep", chunk=1, kicked=False):
            pass
        with tracer.span("prep_stall"):
            pass
        with tracer.span("metrics_flush"):
            pass
    prof.stop()
    events, meta = prof.device_events(tracer.epoch)
    assert meta["events"] > 0
    assert meta["anchor"] is True           # exact clock join, no fallback
    assert all(e.get("pid") != 0 for e in events)
    assert not any(str(e.get("name", "")).startswith("$") for e in events)

    trace = tmp_path / "merged.json"
    tracer.export_chrome(str(trace), metadata={"profile": meta},
                         extra_events=events)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_trace.py"),
         str(trace), "--require-device-lane"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_device_lane_gate_rejects_host_only_trace(tmp_path):
    tracer = obs.Tracer()
    for name in ("chunk", "dispatch", "chunk_prep", "prep_stall",
                 "metrics_flush"):
        with tracer.span(name):
            pass
    trace = tmp_path / "host_only.json"
    tracer.export_chrome(str(trace))
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_trace.py"),
         str(trace), "--require-device-lane"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    assert "no device-lane" in proc.stdout


def test_check_trace_reports_torn_ledger_without_failing(
        tiny_model, make_pz, make_pipeline, tmp_path):
    pz = make_pz(scheme="solution", rounds=4)
    trace, ledger = tmp_path / "t.json", tmp_path / "m.jsonl"
    tel = obs.Telemetry.on()
    _run(tiny_model, pz, make_pipeline, rounds=4, chunk=2, telemetry=tel,
         hooks=[obs.MetricsSink(str(ledger))])
    tel.tracer.export_chrome(str(trace))
    raw = ledger.read_bytes()
    ledger.write_bytes(raw[:-20])           # SIGKILL mid-append
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_trace.py"),
         str(trace), "--ledger", str(ledger)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "torn trailing record" in proc.stdout


# ---------------------------------------------------------------------------
# 6. Bench history: schema + regression gate
# ---------------------------------------------------------------------------

def test_bench_history_row_roundtrip(tmp_path):
    p = tmp_path / "hist.jsonl"
    row = bench_history.append_row(
        str(p), "engine", {"scan_rounds_per_s": 10.0})
    assert row["schema"] == "bench_history/v1"
    assert row["host"]["devices"] >= 1
    rows = bench_history.read_history(str(p))
    assert len(rows) == 1 and rows[0]["kind"] == "engine"
    with pytest.raises(ValueError):
        bench_history.make_row("engine", {"wrong_metric": 1.0})
    with pytest.raises(ValueError):
        bench_history.make_row("nope", {"scan_rounds_per_s": 1.0})


def _hist_row(kind, val, host=None):
    row = bench_history.make_row(
        kind, {bench_history.GATE_METRIC[kind]: val})
    if host:
        row["host"] = host
    return row


def _write_hist(path, rows):
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))


def _check_history(path, *extra):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_bench.py"),
         str(path), "--history", *extra],
        capture_output=True, text=True, cwd=REPO)


def test_check_bench_history_gates_same_host_regression(tmp_path):
    p = tmp_path / "hist.jsonl"
    _write_hist(p, [_hist_row("engine", 10.0), _hist_row("engine", 9.0)])
    proc = _check_history(p)                # 10% drop: within 30% allowance
    assert proc.returncode == 0, proc.stdout
    _write_hist(p, [_hist_row("engine", 10.0), _hist_row("engine", 5.0)])
    proc = _check_history(p)                # 50% drop: regression
    assert proc.returncode == 1
    assert "regressed" in proc.stdout
    # the same drop on DIFFERENT hardware never gates
    other = {"platform": "tpu", "devices": 8, "machine": "other"}
    _write_hist(p, [_hist_row("engine", 10.0),
                    _hist_row("engine", 5.0, host=other)])
    proc = _check_history(p)
    assert proc.returncode == 0, proc.stdout
    # and a tighter allowance flips the verdict
    _write_hist(p, [_hist_row("engine", 10.0), _hist_row("engine", 9.0)])
    proc = _check_history(p, "--max-regression", "0.05")
    assert proc.returncode == 1


def test_check_bench_history_rejects_bad_rows(tmp_path):
    p = tmp_path / "hist.jsonl"
    bad = _hist_row("engine", 10.0)
    del bad["git_sha"]
    _write_hist(p, [bad])
    assert _check_history(p).returncode == 1
    bad = _hist_row("kernels", 10.0)
    bad["metrics"] = {"fused_duals_per_s": 0.0}
    _write_hist(p, [bad])
    assert _check_history(p).returncode == 1
