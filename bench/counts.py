"""Operations and bytes of one federated round, counted from shapes.

Model FLOPs are forward-only (a ZO round has no backward): 2·n_perturb
rollouts per client, each over K·B sequences of S tokens, counting the
projection and MLP matmuls at every position, causal attention at the
model's own head dim, and the LM head only where the task scores a
position. The z draw, padding and any recompute are not counted.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"
# positions each task scores per sequence (None: every position)
SCORED = {"sst2": 1, "squad": 1, "lm": None}
F32 = 4


def peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's peaks; a device missing from the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def _shape(m: Dict[str, Any], traffic: Dict[str, Any]):
    seqs = int(traffic["clients"]) * int(traffic["batch"])
    s = int(traffic["seq_len"])
    rollouts = 2 * int(traffic["n_perturb"])
    return seqs, s, rollouts


def attention_flops_per_seq(m: Dict[str, Any], s: int) -> float:
    """Causal QKᵀ and PV over the S(S+1)/2 visible pairs, all layers."""
    return m["layers"] * m["heads"] * 2.0 * 2.0 * m["head_dim"] \
        * s * (s + 1) / 2.0


def round_flops(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    seqs, s, rollouts = _shape(m, traffic)
    d, hd = m["d"], m["head_dim"]
    q, kv = m["heads"] * hd, m["kv_heads"] * hd
    per_token = 2.0 * m["layers"] * (d * q + 2 * d * kv + q * d
                                     + 3 * d * m["d_ff"])
    scored = SCORED[traffic["task"]]
    scored = s if scored is None else scored
    head = 2.0 * d * m["vocab"] * scored
    per_seq = per_token * s + attention_flops_per_seq(m, s) + head
    return rollouts * seqs * per_seq


def axpy_bytes(n_params: int, traffic: Dict[str, Any]) -> float:
    """θ-sized passes of the chained dual forward: +μz, −2μz and the
    fused restore+update, each reading and writing every float32 once."""
    return 3.0 * int(traffic["n_perturb"]) * 2 * F32 * n_params


def attention_flops(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    seqs, s, rollouts = _shape(m, traffic)
    return rollouts * seqs * attention_flops_per_seq(m, s)


def attention_bytes(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """q, k, v read and o written once, float32, at the model's head dim."""
    seqs, s, rollouts = _shape(m, traffic)
    per_layer = (2 * m["heads"] + 2 * m["kv_heads"]) * s * m["head_dim"] \
        * F32
    return rollouts * seqs * m["layers"] * per_layer
