"""The comparison that decides `correct`: the program's first chunk against
the plain reference, from the same weights and traffic.

Numbers, each a worst case over what it ranges over:

  loss    per-round loss (mean over clients of (L+ + L−)/2), relative gap
  dtheta  θ after the first chunk: per leaf, the norm of the gap
          ‖θ_C − θ_C^ref‖ (the program's θ_C copied to the host at the
          chunk boundary) over the larger of the reference's change
          ‖θ_C^ref − θ_0‖ of that leaf and of the median leaf. A wrong
          sign, z stream or offset of the update reads about 1 or more
  decode  the transport's decode: per round, the program's p̂ against the
          reference's decode of the program's own payload p_k (its
          schedule c(t) and receiver noise), over the chunk's largest
          decoded |p̂|. Compared stage-wise on the program's p_k because at
          μ 1e-3 the chip's p_k differ from the reference's by rounding
          (PERF.md, Open question 1); read only where n_perturb is 1
  proj    round 0's clipped projections p_k, largest gap over the clip
          bound γ (recorded; compared only where bench/limits names it)

A number is compared where bench/limits/<workload>.json gives its limit;
the run is correct when every compared number lies at or under it.
"""
from __future__ import annotations

import statistics
import sys
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import cell as cell_mod
import reference
import weights

NUMBERS = ("loss", "dtheta", "decode", "proj")


class LeafNorms:
    """The per-leaf norms the comparison takes, one leaf on the device at
    a time: ‖θ − θ_0‖ with θ_0 regenerated from the seed inside the jitted
    norm, and ‖a − b‖ between two copies of θ (either on the host)."""

    def __init__(self, m: Dict[str, Any]):
        n = len(jax.tree_util.tree_leaves(weights.shapes(m)))

        def delta(i):
            return jax.jit(lambda x, base: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - weights.leaf(base, m, i)))))
        self._delta = [delta(i) for i in range(n)]
        self._gap = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))))

    def delta(self, leaves: List[Any], seed: int) -> List[float]:
        base = weights.base_key(seed)
        return [float(f(x, base)) for f, x in zip(self._delta, leaves)]

    def gaps(self, a: List[Any], b: List[Any]) -> List[float]:
        return [float(self._gap(x, y)) for x, y in zip(a, b)]


def host_copy(tree) -> List[np.ndarray]:
    """θ's leaves in flatten order, as host arrays."""
    return [np.asarray(x) for x in
            jax.device_get(jax.tree_util.tree_leaves(tree))]


def readings(prog: Dict[str, Any], ref: Dict[str, Any], gaps: List[float],
             decoded: Optional[List[float]],
             gamma: float) -> Dict[str, float]:
    """The numbers for one pair of runs. `prog`/`ref` carry per-round
    `loss`, `p_hat` and `p_clients`; `ref` carries `dnorm`, its per-leaf
    ‖θ_C^ref − θ_0‖; `gaps` is the per-leaf ‖θ_C − θ_C^ref‖ and `decoded`
    the reference's decode of the program's p_k per round (or None)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                   ref["loss"]))
    med = statistics.median(ref["dnorm"])
    dtheta = max(g / max(r, med, 1e-30) for g, r in zip(gaps, ref["dnorm"]))
    proj = max(abs(a - b) for a, b in zip(prog["p_clients"][0],
                                          ref["p_clients"][0])) / gamma
    out = {"loss": loss, "dtheta": dtheta, "proj": proj}
    if decoded is not None:
        scale = max(max(abs(d) for d in decoded), 1e-30)
        out["decode"] = max(abs(p - d) for p, d in
                            zip(prog["p_hat"], decoded)) / scale
    return out


def channel_of(pz) -> Dict[str, float]:
    """The deployment settings the reference's schedule needs, as the
    program's configuration holds them."""
    return {"power": pz.channel.power, "n0": pz.channel.n0,
            "epsilon": pz.dp.epsilon, "delta": pz.dp.delta,
            "contraction": pz.power.contraction_a}


def first_batches(pipe, chunk: int) -> List[Dict[str, np.ndarray]]:
    return [{k: v for k, v in pipe.batch(t).items() if k != "labels"}
            for t in range(chunk)]


def reference_run(ref: "reference.Reference", norms: LeafNorms,
                  seed: int, program_seed: int, batches, device,
                  heard: int = 0) -> Dict[str, Any]:
    """One reference run from the weights of `seed`: its per-round
    numbers, its final `leaves` (on `device`) and their per-leaf change
    `dnorm`."""
    one = jax.sharding.SingleDeviceSharding(device)
    params = weights.make(seed, ref.m, jax.tree_util.tree_map(
        lambda _: one, weights.shapes(ref.m)))
    out = ref.run(params, batches, program_seed, heard=heard)
    out["dnorm"] = norms.delta(out["leaves"], seed)
    return out


def decoded_of(ref: "reference.Reference", run: Dict[str, Any],
               program_seed: int) -> Optional[List[float]]:
    """The reference's decode of a run's own payloads, where one p_k per
    round stands for the round (n_perturb 1)."""
    if ref.n_perturb != 1:
        return None
    return ref.decode_rounds(run["p_clients"], program_seed)


def check(cell, m: Dict[str, Any], seed: int, prog: Dict[str, Any], pipe,
          *, device, pz, norms: LeafNorms) -> Dict[str, Any]:
    """Run the reference and compare; returns {name: {value, limit}} for
    the compared numbers, and `_correct`. `prog` is the program's first
    chunk: per-round `loss`, `p_hat`, `p_clients` and θ_C's host
    `leaves`."""
    t = cell.traffic
    chunk = int(t["chunk_rounds"])
    pseed = cell_mod.program_seed(seed)
    ref = reference.Reference(m, t, channel_of(pz))
    r = reference_run(ref, norms, seed, pseed, first_batches(pipe, chunk),
                      device)
    gaps = norms.gaps(prog["leaves"], r.pop("leaves"))
    values = readings(prog, r, gaps, decoded_of(ref, prog, pseed),
                      float(t["gamma"]))
    limits = cell.limits or {}
    checks: Dict[str, Any] = {}
    for name in NUMBERS:
        if name in limits:
            checks[name] = {"value": values.get(name, float("nan")),
                            "limit": limits[name]}
    finite = all(np.isfinite(c["value"]) for c in checks.values())
    correct = bool(limits) and finite and all(
        c["value"] <= c["limit"] for c in checks.values())
    for name in NUMBERS:
        if name not in limits and name in values:
            print(f"[bench] reading {name}: {values[name]!r} (not compared)",
                  file=sys.stderr, flush=True)
    checks["_correct"] = correct
    return checks
