"""One benchmark cell: its entry in BENCHMARK.json, its configuration file
and its traffic file, found by name.

A cell names a configuration (`bench/configs/<config>.json`, the file that
BENCHMARK.json's `configs` entry gives) and a traffic mix
(`bench/traffic/<traffic>.json`). Adding a cell of a known model family
and task adds files and entries; nothing here changes. The weights'
layout (weights.py), the reference (reference.py) and the FLOP counts
(counts.py) know one family, the dense decoder, and the traffic generator
the tasks of traffic_gen.SAMPLERS: another family or task is code, and
comes with a benchmark PR.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the program keeps its seeds in int32 (`zo.round_seed` converts through
# jnp.asarray); a larger --seed is folded into this range for the program
PROGRAM_SEED_MOD = 2 ** 31 - 1
# model families with a weight layout, a plain reference and FLOP counts
FAMILIES = ("dense_decoder",)


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
    cfg = dataclasses.replace(cfg, **cell.config.get("model", {}))
    n_params = cfg.param_count()
    if n_params != cell.config["params"]:
        raise SystemExit(f"{cell.config['name']}: the program counts "
                         f"{n_params} parameters, the file says "
                         f"{cell.config['params']}")
    pz = dataclasses.replace(
        pz, channel=dataclasses.replace(pz.channel, d=n_params))
    return cfg, pz


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)
