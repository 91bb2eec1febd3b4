"""The readers of the program's own spans and scopes (bench/lanes.py,
the metrics of trace_layers.NEW_METRICS) on hand-made contexts, on the
recorded chip trace, and in a CPU rehearsal of bench/trace_layers.py."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import lanes  # noqa: E402
from test_rehearsal import ROOT, tiny_root  # noqa: E402,F401

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_axpy_flash.xplane.pb"
DRIVER = Path(__file__).resolve().parent / "cpu_layers_driver.py"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ev(name, a, b):
    return lanes.Event(name, a, b)


def synthetic_ctx():
    driver = lanes.Lane("python", [
        ev("chunk", 0, 100), ev("dispatch", 0, 5),
        ev("PjitFunction(chunk)", 1, 4), ev("prep_stall", 60, 100)])
    worker = lanes.Lane("python", [
        ev("chunk_prep", 10, 90), ev("ctl_build", 10, 40),
        ev("prep_device_wait", 20, 35), ev("batch_stage", 40, 90),
        ev("prep_device_wait", 50, 70), ev("batch_gen", 70, 80),
        ev("h2d_put", 80, 90)])
    runtime = lanes.Lane("pjrt-tpu-tasks", [ev("XlaLinearize", 0, 100)])
    chip = devtrace.DeviceOps(0, [
        ("%seeded_axpy_pallas.1 = f32[8] custom-call()", 0, 20),
        ("%fusion.2 = f32[8] fusion()", 20, 30),
        ("%fusion.3 = f32[8] fusion()", 30, 45),
        ("%copy.4 = f32[8] copy()", 45, 50)])
    return {"window_ns": (0, 100), "window_s": 100e-9, "devices": [chip],
            "busy_by_chip": [50e-9],
            "host_lanes": [driver, worker, runtime],
            "op_scopes": {"seeded_axpy_pallas.1": "theta_plus",
                          "fusion.2": "theta_update",
                          "fusion.3": "lm_head_ce", "copy.4": None}}


@pytest.mark.parametrize("name,want", [
    ("host.prep_device_wait_share", 35.0),        # (15 + 20) / 100
    # idle 50..100; the driver works 50..60, the worker 70..90
    ("device.idle_host_work_share", 30.0),
    ("theta_phase.busy_share", 60.0),             # (20 + 10) / 50
    ("lm_head.busy_share", 30.0)])                # 15 / 50
def test_reader_on_a_synthetic_context(name, want):
    mod = reader(name)
    assert (mod.UNIT, mod.MOVES) == ("%", "rounds_per_s")
    assert mod.read(synthetic_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "host.prep_device_wait_share", "device.idle_host_work_share",
    "theta_phase.busy_share", "lm_head.busy_share"])
def test_reader_without_its_keys_reads_nothing(name):
    ctx = synthetic_ctx()
    del ctx["host_lanes"], ctx["op_scopes"]
    assert reader(name).read(ctx) is None


def test_innermost_names_each_piece_after_the_inner_span():
    worker = synthetic_ctx()["host_lanes"][1]
    assert lanes.innermost(worker, (0, 100)) == [
        ("ctl_build", 10, 20), ("prep_device_wait", 20, 35),
        ("ctl_build", 35, 40), ("batch_stage", 40, 50),
        ("prep_device_wait", 50, 70), ("batch_gen", 70, 80),
        ("h2d_put", 80, 90)]
    assert lanes.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10),
                                                               (20, 25)]
    assert lanes.instruction("%fusion.12 = f32[2] fusion()") == "fusion.12"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_host_lanes_on_the_recorded_chip_trace():
    found = lanes.host_lanes(str(RECORDED))
    assert any(ln.holds("bench_window_open") for ln in found)
    for ln in found:
        assert all(e.end >= e.start for e in ln.events)
    # the recording has no program spans: no driver or worker lane
    assert lanes.driver_lane(found) is None
    assert lanes.worker_lane(found) is None
    runs = lanes.module_runs(str(RECORDED), match=lambda n: True)
    assert runs[0] and all(a < b for a, b in runs[0])


def test_trace_layers_rehearsal_reports_the_new_readers(tiny_root):  # noqa: F811
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, str(DRIVER), str(tiny_root), "none", "--",
         "--workload", "tiny.one", "--seed", "2147483700", "--seconds",
         "0.5", "--trace", "1"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    layers = result["breakdown"]["layers"]
    for name in ("host.prep_device_wait_share",
                 "device.idle_host_work_share"):
        assert 0.0 <= layers["metrics"][name] <= 100.0
    # a CPU trace has no device plane: no chunk program to scope or pair
    assert layers["coverage"] is None and layers["clock"] is None
    assert layers["idle_by_worker_span"]["idle_s"] >= 0.0


def test_trace_layers_tracer_only_run_reports_end_to_end(tiny_root):  # noqa: F811
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, str(DRIVER), str(tiny_root), "none", "--",
         "--workload", "tiny.one", "--seed", "7", "--seconds", "0.5",
         "--trace", "0", "--tracer", "1"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["rounds_per_s"]["value"] > 0


@pytest.mark.parametrize("argv,tracer_only,rest", [
    (["--trace", "1", "--seed", "3"], False, ["--trace", "1", "--seed", "3"]),
    (["--trace", "0", "--tracer", "1"], True, ["--trace", "0"])])
def test_trace_layers_hands_run_py_its_own_arguments(monkeypatch, argv,
                                                     tracer_only, rest):
    import trace_layers
    seen = {}
    monkeypatch.setattr(trace_layers, "install",
                        lambda t: seen.setdefault("tracer_only", t))
    monkeypatch.setattr(trace_layers.run, "main",
                        lambda a: seen.setdefault("argv", a) and 0)
    trace_layers.main(argv)
    assert seen == {"tracer_only": tracer_only, "argv": rest}


def test_clock_check_pairs_dispatches_with_chunk_runs():
    import trace_layers
    ctx = synthetic_ctx()
    ctx["host_lanes"][0].events.append(
        ev("PJRT_LoadedExecutable_Execute", 2, 3))
    ok = trace_layers.clock_check(ctx, {0: [(4, 50)]})
    assert ok["ok"] and ok["chips"][0]["min_lead_ms"] == pytest.approx(4e-6)
    assert ok["chips"][0]["launch_lead_ms"] == [pytest.approx(2e-6)] * 2
    early = trace_layers.clock_check(ctx, {0: [(-1, 50)]})
    assert early["ok"] is False
