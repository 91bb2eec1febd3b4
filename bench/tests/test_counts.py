"""The FLOP and byte counts against hand-worked shapes, and the peaks."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counts  # noqa: E402
import weights  # noqa: E402

# one dense decoder layer, d 4, 2 query heads and 1 KV head of dim 2,
# d_ff 8, vocab 10
TINY = {"family": "dense_decoder", "layers": 1, "d": 4, "heads": 2,
        "kv_heads": 1, "head_dim": 2, "d_ff": 8, "vocab": 10}
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


@pytest.mark.parametrize("config,traffic,flops,attn_flops,attn_bytes", [
    ("opt-125m", "sst2-k5", 1_171_952_762_880, 6_134_169_600, 754_974_720),
    ("yi-6b.l8", "squad512", 22_961_230_708_736, 275_414_777_856,
     2_415_919_104),
    ("opt-125m", "long2k", 5_594_765_328_384, 618_777_280_512,
     2_415_919_104),
    ("opt-125m", "mesh4-k20", 4_687_811_051_520, 24_536_678_400,
     3_019_898_880),
])
def test_cell_counts_pinned(config, traffic, flops, attn_flops, attn_bytes):
    """The counts that mfu and flash_attention_roofline read in each cell,
    as the dense decoder's counts gave them before it was its own file."""
    import json
    bench = Path(__file__).resolve().parents[1]
    m = weights.dims(json.loads((bench / f"configs/{config}.json")
                                .read_text()))
    t = json.loads((bench / f"traffic/{traffic}.json").read_text())
    assert counts.round_flops(m, t) == flops
    assert counts.attention_flops(m, t) == attn_flops
    assert counts.attention_bytes(m, t) == attn_bytes


def test_peaks_table():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
