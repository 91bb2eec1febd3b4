"""Operations and bytes of one federated round, counted from shapes.

Model FLOPs are forward-only (a ZO round has no backward): 2·n_perturb
rollouts per client, each over K·B sequences of S tokens, counting the
projection and MLP matmuls at every position, causal attention at the
model's own head dim, and the LM head only where the task scores a
position. The z draw, padding and any recompute are not counted.

The model's FLOPs and its attention's FLOPs and bytes are counted by its
family (bench/families/<family>.py, named by m["family"]); what every
family shares stays here: the round's shape, the positions each task
scores, the θ update's bytes and the chip's peaks. A new task is a new
entry of SCORED.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

import cell

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


def round_shape(traffic: Dict[str, Any]):
    """(sequences, tokens per sequence, rollouts) of one round: K·B
    sequences of S tokens, two rollouts per perturbation direction."""
    seqs = int(traffic["clients"]) * int(traffic["batch"])
    s = int(traffic["seq_len"])
    rollouts = 2 * int(traffic["n_perturb"])
    return seqs, s, rollouts


def round_flops(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """Model FLOPs of one round, as the model's family counts them."""
    return cell.family(m["family"]).round_flops(m, traffic)


def axpy_bytes(n_params: int, traffic: Dict[str, Any]) -> float:
    """θ-sized passes of the chained dual forward: +μz, −2μz and the
    fused restore+update, each reading and writing every float32 once."""
    return 3.0 * int(traffic["n_perturb"]) * 2 * F32 * n_params


def attention_flops(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """The attention kernel's FLOPs in one round, by the family's count."""
    return cell.family(m["family"]).attention_flops(m, traffic)


def attention_bytes(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """The attention kernel's q/k/v/o bytes in one round, by the family's
    count."""
    return cell.family(m["family"]).attention_bytes(m, traffic)
