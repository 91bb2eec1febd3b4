"""The FLOP and byte counts against hand-worked shapes, and the peaks."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counts  # noqa: E402
import weights  # noqa: E402

# one layer, d 4, 2 query heads and 1 KV head of dim 2, d_ff 8, vocab 10
TINY = {"layers": 1, "d": 4, "heads": 2, "kv_heads": 1, "head_dim": 2,
        "d_ff": 8, "vocab": 10}
ONE_SEQ = {"clients": 1, "batch": 1, "seq_len": 3, "n_perturb": 1}


@pytest.mark.parametrize("task,flops", [
    # per token 2·(4·4 + 2·4·2 + 4·4 + 3·4·8) = 288, ×3 tokens = 864;
    # attention 2 heads · 4 · hd 2 · (3·4/2 pairs) = 96; head 2·4·10 per
    # scored position; two rollouts
    ("lm", 2 * (864 + 96 + 240)),
    ("sst2", 2 * (864 + 96 + 80)),
    ("squad", 2 * (864 + 96 + 80)),
])
def test_round_flops_by_hand(task, flops):
    assert counts.round_flops(TINY, dict(ONE_SEQ, task=task)) == flops


def test_round_flops_scale_with_clients_rows_and_directions():
    base = counts.round_flops(TINY, dict(ONE_SEQ, task="lm"))
    more = dict(ONE_SEQ, task="lm", clients=3, batch=2, n_perturb=4)
    assert counts.round_flops(TINY, more) == base * 3 * 2 * 4


def test_attention_counts_by_hand():
    t = dict(ONE_SEQ, task="lm")
    assert counts.attention_flops(TINY, t) == 2 * 96
    # q, k, v read and o written: (2·2 + 2·1) heads · 3 · 2 · 4 bytes
    assert counts.attention_bytes(TINY, t) == 2 * 6 * 3 * 2 * 4


def test_axpy_bytes_three_passes_per_direction():
    assert counts.axpy_bytes(100, {"n_perturb": 2}) == 3 * 2 * 8 * 100


def test_opt_125m_paper_job():
    import json
    bench = Path(__file__).resolve().parents[1]
    m = weights.dims(json.loads((bench / "configs/opt-125m.json")
                                .read_text()))
    t = json.loads((bench / "traffic/sst2-k5.json").read_text())
    # 40 sequences × 2 rollouts × (64 · 226,492,416 + 76,677,120 +
    # 77,217,792) FLOPs
    assert counts.round_flops(m, t) == 1_171_952_762_880
    assert counts.axpy_bytes(190_483_200, t) == 4_571_596_800


def test_peaks_table():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
