"""One benchmark cell: its entry in BENCHMARK.json, its configuration file
and its traffic file, found by name; and the model families.

A cell names a configuration (`bench/configs/<config>.json`, the file that
BENCHMARK.json's `configs` entry gives) and a traffic mix
(`bench/traffic/<traffic>.json`). The configuration names its model
family, one file `bench/families/<family>.py` that gives the sizes, the
weights' layout, the reference's loss and the FLOP counts of that family
(the contract is in bench/families/dense_decoder.py's docstring). Adding a
cell, a configuration or a family adds files and entries; nothing here
changes. A new task is shared code: its sampler in traffic_gen.SAMPLERS and
its scored positions in counts.SCORED.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the program keeps its seeds in int32 (`zo.round_seed` converts through
# jnp.asarray); a larger --seed is folded into this range for the program
PROGRAM_SEED_MOD = 2 ** 31 - 1
FAMILY_DIR = BENCH / "families"
# model families with a weight layout, a plain reference and FLOP counts:
# the files of bench/families/
FAMILIES = tuple(sorted(p.stem for p in FAMILY_DIR.glob("*.py")))


@dataclass(frozen=True)
class Cell:
    """Everything one run of one workload needs, read from files."""
    name: str
    chips: int
    why: str
    config: Dict[str, Any]          # bench/configs/<config>.json
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    per_layer: List[Dict[str, Any]]   # BENCHMARK.json entries this cell reports
    end_to_end: List[Dict[str, Any]]
    limits: Optional[Dict[str, float]]  # bench/limits/<cell>.json, if any


def _read(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load(workload: str, root: Optional[Path] = None) -> Cell:
    """The cell named `workload` in `root`/BENCHMARK.json (the checkout
    holding this file by default)."""
    root = Path(root) if root is not None else ROOT
    spec = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _read(root / cfg_entry["file"])
    if config.get("family") not in FAMILIES:
        raise SystemExit(f"{w['config']}: family {config.get('family')!r} "
                         f"has no layout, reference or counts here; the "
                         f"benchmark knows {FAMILIES}")
    traffic = _read(root / BENCH.name / "traffic" / f"{w['traffic']}.json")

    def reports(metric: Dict[str, Any]) -> bool:
        return workload in metric.get("workloads", [workload])

    limits_path = root / BENCH.name / "limits" / f"{workload}.json"
    return Cell(name=workload, chips=int(w["chips"]), why=w["why"],
                config=config, traffic=traffic,
                per_layer=[m for m in spec["per_layer"] if reports(m)],
                end_to_end=[m for m in spec["end_to_end"] if reports(m)],
                limits=_read(limits_path) if limits_path.exists() else None)


@functools.lru_cache(maxsize=None)
def family(name: str):
    """bench/families/<name>.py, loaded by file name."""
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name}", FAMILY_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_seed(seed: int) -> int:
    """The seed handed to the program: --seed folded into int32 range."""
    return int(seed) % PROGRAM_SEED_MOD


def train_argv(cell: Cell, seed: int) -> List[str]:
    """The CLI arguments `repro.launch.train` would take for this cell."""
    t = cell.traffic
    argv = ["--arch", cell.config["arch"], "--task", t["task"],
            "--transport", t["transport"], "--scheme", t["scheme"],
            "--clients", str(t["clients"]), "--batch", str(t["batch"]),
            "--seq-len", str(t["seq_len"]),
            "--n-perturb", str(t["n_perturb"]), "--mu", str(t["mu"]),
            "--lr", str(t["lr"]), "--gamma", str(t["gamma"]),
            "--rounds", str(t["horizon_rounds"]), "--engine", "scan",
            "--chunk-rounds", str(t["chunk_rounds"]), "--eval-every", "0",
            "--seed", str(program_seed(seed))]
    if cell.chips > 1:
        argv += ["--mesh", str(cell.chips)]
    return argv


def program_configs(cell: Cell, seed: int):
    """(ModelConfig, PairZeroConfig) built the way `train.make_configs`
    builds them, with the configuration file's changes applied to the
    registered architecture and `pz.channel.d` counted on the result."""
    from repro.launch import train as train_cli
    args = train_cli.build_parser().parse_args(train_argv(cell, seed))
    cfg, pz, _ = train_cli.make_configs(args)
    cfg = override(cfg, cell.config.get("model", {}))
    n_params = cfg.param_count()
    if n_params != cell.config["params"]:
        raise SystemExit(f"{cell.config['name']}: the program counts "
                         f"{n_params} parameters, the file says "
                         f"{cell.config['params']}")
    pz = dataclasses.replace(
        pz, channel=dataclasses.replace(pz.channel, d=n_params))
    return cfg, pz


def override(obj, changes: Dict[str, Any]):
    """`dataclasses.replace(obj, **changes)`, where a dict given for a field
    that is itself a dataclass replaces that dataclass field by field:
    {"moe": {"n_experts": 8}} builds a new MoEConfig."""
    return dataclasses.replace(obj, **{
        k: override(getattr(obj, k), v)
        if isinstance(v, dict) and dataclasses.is_dataclass(getattr(obj, k))
        else v for k, v in changes.items()})


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)
