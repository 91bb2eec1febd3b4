"""CLI launchers + serve loop integration tests."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import serve_loop
from repro.models import registry

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_serve_loop_matches_teacher_forcing():
    """Greedy decode through serve_loop is self-consistent: feeding the
    generated tokens back through forward reproduces the same argmax."""
    cfg = registry.get_arch("yi-6b").reduced()
    params = registry.init_params(jax.random.key(0), cfg, jnp.float32)
    rng = np.random.default_rng(0)
    prompts = rng.integers(8, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    out = serve_loop(cfg, params, prompts, gen_steps=6)
    assert out.shape == (2, 18)
    mod = registry.get_module(cfg)
    x = mod.forward(params, cfg, jnp.asarray(out))
    logits = mod.logits_from_hidden(params, x)
    # position t's argmax must equal the token generated at t+1
    for t in range(11, 16):
        pred = np.asarray(jnp.argmax(logits[:, t], axis=-1))
        np.testing.assert_array_equal(pred, out[:, t + 1])


def test_serve_loop_ssm():
    cfg = registry.get_arch("mamba2-370m").reduced()
    params = registry.init_params(jax.random.key(0), cfg, jnp.float32)
    rng = np.random.default_rng(1)
    prompts = rng.integers(8, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    out = serve_loop(cfg, params, prompts, gen_steps=4)
    assert out.shape == (2, 12)


@pytest.mark.slow
def test_train_cli_smoke(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "opt-125m",
         "--reduced", "--rounds", "30", "--clients", "3", "--batch", "4",
         "--seq-len", "16", "--scheme", "perfect", "--n-perturb", "1",
         "--eval-every", "0", "--out", str(tmp_path / "run.json")],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "final_loss" in res.stdout


@pytest.mark.slow
def test_serve_cli_smoke():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "recurrentgemma-2b", "--reduced", "--batch", "2", "--prompt-len",
         "16", "--gen", "4"],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "tok/s" in res.stdout


def test_train_main_takes_argv_and_returns_losses(monkeypatch, tmp_path):
    """`train.main(argv)` runs in-process and returns the summary with the
    per-round losses and p̂ (what chip_smoke.py reads)."""
    from repro.launch import train
    # a set cache variable makes the compile-cache helper a no-op here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = train.main(["--arch", "opt-125m", "--reduced", "--rounds", "2",
                      "--clients", "2", "--batch", "2", "--seq-len", "8",
                      "--n-perturb", "1", "--eval-every", "0",
                      "--engine", "scan", "--chunk-rounds", "2"])
    assert out["rounds"] == 2
    assert len(out["losses"]) == len(out["p_hats"]) == 2
    assert out["final_loss"] == out["losses"][-1]
    assert all(np.isfinite(out["losses"]))
