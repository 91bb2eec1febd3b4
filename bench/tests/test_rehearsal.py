"""CPU rehearsal of bench/run.py at a tiny size: a sound run is correct,
and a run with its timed path broken underneath is not."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DRIVER = Path(__file__).resolve().parent / "cpu_driver.py"

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "d_ff": 128, "vocab_size": 512, "head_dim": 16}
# limits for the tiny CPU cell: sound runs read ~1e-7 (loss), ~3e-3
# (dtheta, the chained walk) and 0 (decode) on the CPU; the faults read
# 5e-3 (loss), 0.03 (dtheta) and 0.18 (decode, the exchange) and up
TINY_LIMITS = {"loss": 1e-5, "dtheta": 1e-2, "decode": 1e-3}


def write_tiny(root, family="dense_decoder"):
    """The tiny cells `tiny.one` and `tiny.mesh` (2 chips) under `root`: a
    BENCHMARK.json, and a configuration of `family`, a traffic file and
    limits under root/bench."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.models import registry
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    cfg = json.loads((BENCH / "configs" / "opt-125m.json").read_text())
    cfg["as_run"].update(hidden_size=64, ffn_dim=128, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, vocab_size=512)
    cfg["model"] = TINY_MODEL
    cfg["params"] = dataclasses.replace(registry.get_arch("opt-125m"),
                                        **TINY_MODEL).param_count()
    cfg["family"] = family
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    traffic = json.loads((BENCH / "traffic" / "sst2-k5.json").read_text())
    traffic.update(clients=4, batch=2, seq_len=16, chunk_rounds=2,
                   horizon_rounds=64)
    (root / "bench/traffic/tiny.json").write_text(json.dumps(traffic))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(spec["configs"][0], name="tiny",
                            file="bench/configs/tiny.json")]
    spec["workloads"] = [
        {"name": "tiny.one", "config": "tiny", "traffic": "tiny",
         "chips": 1, "why": "tiny"},
        {"name": "tiny.mesh", "config": "tiny", "traffic": "tiny",
         "chips": 2, "why": "tiny"}]
    for metric in spec["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = ["tiny.mesh"]
    # the client mesh's reader, kept for a mesh cell (PERF.md, Open
    # questions), is exercised on the tiny mesh
    spec["per_layer"].append({
        "name": "collective.exposed_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "client mesh",
        "moves": "rounds_per_s", "workloads": ["tiny.mesh"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for name in ("tiny.one", "tiny.mesh"):
        (root / f"bench/limits/{name}.json").write_text(
            json.dumps(TINY_LIMITS))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny(tmp_path_factory.mktemp("tiny"))


def run_cell(root, workload, fault="none", trace=0, seed=2147483700,
             driver=DRIVER):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, str(driver), str(root), fault, "--",
         "--workload", workload, "--seed", str(seed), "--seconds", "0.5",
         "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_sound_run_is_correct_and_reports_end_to_end(tiny_root):
    result, err = run_cell(tiny_root, "tiny.one")
    assert result["correct"] is True, err[-2000:]
    assert set(result["metrics"]) == {"rounds_per_s", "peak_hbm_gb",
                                      "setup_s"}
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(TINY_LIMITS)
    assert result["window_compiles"] == 0
    assert "check dtheta" in err.strip().splitlines()[-1] or \
        "check" in err.strip().splitlines()[-1]


def test_traced_run_reports_per_layer_metrics(tiny_root):
    result, _ = run_cell(tiny_root, "tiny.mesh", trace=1)
    assert result["correct"] is True
    names = set(result["metrics"])
    assert {"host.prep_stall_share", "device.idle_share", "mfu",
            "collective.exposed_share"} <= names
    for entry in result["metrics"].values():
        if entry["unit"] == "%":
            assert 0.0 <= entry["value"] <= 100.0
    assert result["device"]["window_s"] > 0
    assert len(result["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("workload,fault", [
    ("tiny.one", "unchanged"), ("tiny.one", "half_batch"),
    ("tiny.one", "token"), ("tiny.mesh", "exchange")])
def test_broken_timed_path_is_not_correct(tiny_root, workload, fault):
    result, err = run_cell(tiny_root, workload, fault=fault)
    assert result["correct"] is False, err[-2000:]


def test_no_chip_exits_without_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "opt-125m.sst2-k5", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=300,
        cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_only_benchmark_files_exits_without_result(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "opt-125m.sst2-k5",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
