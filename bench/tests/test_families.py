"""Model families as files of bench/families/: the dense decoder's layout is
the program's parameter tree, a family is added by one new file, and a
configuration's `model` changes reach nested config dataclasses."""
import dataclasses
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import cell  # noqa: E402
import weights  # noqa: E402
from test_rehearsal import ROOT, run_cell, write_tiny  # noqa: E402


def test_families_are_the_files_of_the_directory():
    assert "dense_decoder" in cell.FAMILIES
    assert set(cell.FAMILIES) == {
        p.stem for p in (BENCH / "families").glob("*.py")}


@pytest.mark.parametrize("workload", ["opt-125m.sst2-k5",
                                      "yi-6b.l8.squad512"])
def test_dense_decoder_layout_is_the_program_tree(workload):
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from repro.models import registry
    c = cell.load(workload)
    cfg, _ = cell.program_configs(c, seed=1)
    m = weights.dims(c.config)
    assert m["family"] == "dense_decoder"
    want = jax.tree_util.tree_flatten_with_path(weights.shapes(m))[0]
    got = jax.tree_util.tree_flatten_with_path(
        registry.abstract_params(cfg, jnp.float32))[0]
    assert [(p, (s.shape, s.dtype)) for p, s in want] == \
        [(p, (s.shape, s.dtype)) for p, s in got]


def test_nested_model_override_builds_a_new_moe_config():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs.base import MoEConfig
    from repro.models import registry
    arch = registry.get_arch("deepseek-v2-236b")
    tiny = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
            "d_ff": 128, "vocab_size": 512, "head_dim": 16}
    want = dataclasses.replace(
        arch, **tiny, moe=dataclasses.replace(arch.moe, n_experts=8,
                                              d_expert=64))
    traffic = json.loads((BENCH / "traffic" / "sst2-k5.json").read_text())
    c = cell.Cell(name="tiny.moe", chips=1, why="tiny", traffic=traffic,
                  config={"name": "tiny-moe", "arch": "deepseek-v2-236b",
                          "model": dict(tiny, moe={"n_experts": 8,
                                                   "d_expert": 64}),
                          "params": want.param_count()},
                  per_layer=[], end_to_end=[], limits=None)
    cfg, pz = cell.program_configs(c, seed=1)
    assert isinstance(cfg.moe, MoEConfig) and cfg.moe is not arch.moe
    assert cfg == want
    assert cfg.moe.n_experts_per_tok == arch.moe.n_experts_per_tok
    assert arch.moe.n_experts == 160             # the registry's is kept
    assert pz.channel.d == want.param_count()
    assert cell.override(arch, {"n_layers": 3}) == \
        dataclasses.replace(arch, n_layers=3)


def test_a_family_is_one_new_file(tmp_path):
    """A checkout whose bench/ differs from this one by one file, a copy of
    dense_decoder.py under another name, runs the tiny cell of that family
    correct; without the file the cell is refused."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    write_tiny(tmp_path, family="copied_decoder")
    with pytest.raises(SystemExit, match="copied_decoder"):
        cell.load("tiny.one", root=tmp_path)     # this checkout lacks it
    shutil.copy(BENCH / "families" / "dense_decoder.py",
                tmp_path / "bench" / "families" / "copied_decoder.py")
    result, err = run_cell(tmp_path, "tiny.one",
                           driver=tmp_path / "bench/tests/cpu_driver.py")
    assert result["correct"] is True, err[-2000:]
    assert set(result["checks"]) == {"loss", "dtheta", "decode"}
