"""Per-kernel sweeps: Pallas (interpret mode) vs pure-jnp oracles in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref, seeded_axpy

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# seeded_axpy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128,), (300, 70), (8, 16, 33),
                                   (1, 1), (5000,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seeded_axpy_matches_ref(shape, dtype):
    w = jax.random.normal(jax.random.key(0), shape, jnp.float32).astype(dtype)
    o_ref = ref.seeded_axpy_ref(w, 42, 0.25)
    o_pl = ops.seeded_axpy(w, 42, 0.25, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pl, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("shape", [(256,), (64, 50)])
def test_seeded_axpy_z_stream_bitwise(shape):
    """The z-stream itself is bitwise identical: kernel == XLA == ref."""
    zeros = jnp.zeros(shape, jnp.float32)
    z_ref = ref.seeded_axpy_ref(zeros, 7, 1.0)
    z_pl = ops.seeded_axpy(zeros, 7, 1.0, impl="pallas_interpret")
    z_xla = ops.seeded_axpy(zeros, 7, 1.0, impl="xla")
    assert np.array_equal(np.asarray(z_ref), np.asarray(z_pl))
    assert np.array_equal(np.asarray(z_ref), np.asarray(z_xla))


# (shape, block, grid): edges of the 2-D grid over the leaf's own layout
AXPY_EDGES = [((1000, 768), 16 * 768, (63, 1)),    # ragged last row block
              ((16, 1000), 2048, (2, 4)),          # column blocks, ragged
              ((3, 40, 256), 16 * 256, (8, 1))]    # stacked 3-D leaf


@pytest.mark.parametrize("shape,block,grid", AXPY_EDGES,
                         ids=["ragged_rows", "column_blocks", "stacked"])
def test_seeded_axpy_pallas_edges_match_ref(shape, block, grid):
    """Each edge case really is one (its grid), and the kernel equals the
    ref there bit for bit: the z stream itself and the axpy on random w."""
    rows, cols = seeded_axpy._matrix_view(shape)
    br, bc = seeded_axpy._block_shape(rows, cols, block)
    assert (-(-rows // br), -(-cols // bc)) == grid
    zeros = jnp.zeros(shape, jnp.float32)
    z_pl = seeded_axpy.seeded_axpy_pallas(zeros, jnp.uint32(7), 1.0,
                                          block=block, interpret=True)
    assert np.array_equal(np.asarray(z_pl),
                          np.asarray(ref.seeded_axpy_ref(zeros, 7, 1.0)))
    assert np.array_equal(np.asarray(z_pl),
                          np.asarray(ops.seeded_axpy(zeros, 7, 1.0,
                                                     impl="xla")))
    w = jax.random.normal(jax.random.key(0), shape, jnp.float32)
    o_pl = seeded_axpy.seeded_axpy_pallas(w, jnp.uint32(42), 0.25,
                                          block=block, interpret=True)
    assert np.array_equal(np.asarray(o_pl),
                          np.asarray(ref.seeded_axpy_ref(w, 42, 0.25)))


def test_seeded_axpy_pallas_donated_walk_is_bitwise():
    """The chained +μ, −2μ, +μ walk updates θ in place under donation and
    gives the bits of the walk that keeps its input."""
    mu = 1e-3

    def walk(w):
        for scale in (mu, -2 * mu, mu):
            w = seeded_axpy.seeded_axpy_pallas(w, jnp.uint32(9), scale,
                                               block=16 * 384,
                                               interpret=True)
        return w

    w = np.asarray(jax.random.normal(jax.random.key(1), (2, 40, 384)))
    donated = jax.jit(walk, donate_argnums=0)(jnp.asarray(w))
    kept = jax.jit(walk)(jnp.asarray(w))
    assert np.array_equal(np.asarray(donated), np.asarray(kept))
    np.testing.assert_allclose(np.asarray(donated), w, atol=1e-5)


def test_seeded_axpy_deterministic_and_seed_sensitive():
    w = jnp.zeros((1000,), jnp.float32)
    a = ops.seeded_axpy(w, 3, 1.0, impl="xla")
    b = ops.seeded_axpy(w, 3, 1.0, impl="xla")
    c = ops.seeded_axpy(w, 4, 1.0, impl="xla")
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_seeded_axpy_gaussian_moments():
    z = np.asarray(ops.seeded_axpy(jnp.zeros(200_000), 11, 1.0, impl="xla"))
    assert abs(z.mean()) < 0.01
    assert abs(z.std() - 1.0) < 0.01
    assert abs((z ** 3).mean()) < 0.05         # skewness
    assert abs((z ** 4).mean() - 3.0) < 0.15   # kurtosis


def test_mezo_chain_restores_weights():
    """w → +μz → −2μz → +μz returns w (the MeZO memory trick)."""
    w = jax.random.normal(jax.random.key(1), (400, 30))
    mu = 1e-3
    p1 = ops.seeded_axpy(w, 9, mu, impl="xla")
    p2 = ops.seeded_axpy(p1, 9, -2 * mu, impl="xla")
    p3 = ops.seeded_axpy(p2, 9, mu, impl="xla")
    np.testing.assert_allclose(np.asarray(p3), np.asarray(w), atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (b, hq, hkv, sq, skv, d, causal, window)
    (2, 4, 4, 64, 64, 32, True, None),       # MHA causal
    (2, 8, 2, 64, 64, 32, True, None),       # GQA
    (1, 4, 1, 128, 128, 64, True, None),     # MQA
    (2, 4, 4, 64, 64, 32, False, None),      # bidirectional (encoder)
    (2, 4, 2, 64, 64, 32, True, 16),         # local window
    (1, 4, 2, 1, 64, 32, True, None),        # decode: q = last position
    (2, 4, 4, 48, 96, 32, True, None),       # chunked prefill (sq < skv)
    (2, 4, 4, 64, 64, 64, True, None),       # d = 64, no head-dim pad
    (1, 2, 1, 1024, 1024, 64, True, None),   # multi-tile causal walk
    (2, 8, 2, 64, 64, 64, True, None),       # q group folded per kv head
    (8, 4, 2, 32, 32, 64, True, None),       # several kv rows in one step
    (1, 2, 2, 700, 700, 64, True, None),     # q and kv tails off the tile
    (1, 2, 2, 1100, 1100, 32, True, 300),    # window spanning tiles
    (1, 2, 2, 600, 1300, 64, True, None),    # multi-tile sq < skv
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_vs_ref(case):
    b, hq, hkv, sq, skv, d, causal, window = case
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), jnp.float32)
    o_ref = ref.attention_ref(q, k, v, causal=causal, window=window)
    o_pl = ops.attention(q, k, v, causal=causal, window=window,
                         impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", ATTN_CASES)
def test_xla_chunked_attention_vs_ref(case):
    b, hq, hkv, sq, skv, d, causal, window = case
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), jnp.float32)
    o_ref = ref.attention_ref(q, k, v, causal=causal, window=window)
    o_x = ops.attention(q, k, v, causal=causal, window=window,
                        impl="xla_chunked")
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 32)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 64, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 64, 32)).astype(jnp.bfloat16)
    o_ref = ref.attention_ref(q, k, v, causal=True)
    o_pl = ops.attention(q, k, v, causal=True, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pl, np.float32),
                               np.asarray(o_ref, np.float32), atol=3e-2)


# (b, hq, hkv, sq, skv, d, causal, window): shapes whose walks are checked
PLAN_CASES = [
    (4, 12, 12, 2048, 2048, 64, True, None),    # long2k
    (1, 2, 2, 1100, 1100, 32, True, 300),       # window across tiles
    (1, 2, 2, 600, 1300, 64, True, None),       # sq < skv
    (1, 2, 2, 700, 700, 64, True, None),        # q and kv tails
    (1, 4, 2, 1, 64, 32, True, None),           # decode
    (2, 4, 4, 1000, 1000, 64, False, None),     # bidirectional
]

# the benchmark cells' attention calls: (b, hq, hkv, s, d)
CELL_SHAPES = {"sst2-k5": (40, 12, 12, 64, 64),
               "long2k": (4, 12, 12, 2048, 64),
               "yi-squad512": (8, 32, 4, 512, 128)}


def _cell_plan(name):
    b, hq, hkv, s, d = CELL_SHAPES[name]
    return fa.attention_tile_plan(b, hq, hkv, s, s, d)


@pytest.mark.parametrize("case", PLAN_CASES)
def test_attention_tile_plan_walks_live_pairs_only(case):
    """Every walked pair has a visible (q, k) element and every visible
    element lies in a walked pair; a pair is flagged masked exactly when
    part of its tile is invisible or past the kv end; each q tile's walk
    is contiguous, flagged first and last at its ends."""
    b, hq, hkv, sq, skv, d, causal, window = case
    plan = fa.attention_tile_plan(b, hq, hkv, sq, skv, d, d, causal, window)
    tq, tk = plan.blk_q, plan.blk_k
    q_pos = np.arange(sq)[:, None] + skv - sq
    k_pos = np.arange(skv)[None, :]
    vis = np.ones((sq, skv), bool)
    if causal:
        vis &= k_pos <= q_pos
    if window is not None:
        vis &= k_pos > q_pos - window
    walked = {(i, j): f for i, j, f in plan.pairs}
    assert len(walked) == len(plan.pairs)
    n_q, n_k = -(-sq // tq), -(-skv // tk)
    for i in range(n_q):
        for j in range(n_k):
            tile = vis[i * tq:(i + 1) * tq, j * tk:(j + 1) * tk]
            if (i, j) not in walked:
                assert not tile.any(), (i, j)
                continue
            masked = not tile.all() or (j + 1) * tk > skv
            assert bool(walked[(i, j)] & fa.MASKED) == masked, (i, j)
    qis = [i for i, _, _ in plan.pairs]
    assert qis == sorted(qis) and set(qis) == set(range(n_q))
    for i in range(n_q):
        flags = [f for qi, _, f in plan.pairs if qi == i]
        assert [bool(f & fa.FIRST) for f in flags] == \
            [True] + [False] * (len(flags) - 1)
        assert [bool(f & fa.LAST) for f in flags] == \
            [False] * (len(flags) - 1) + [True]
    blocks = (b * hkv // plan.rows) * (hq // hkv // plan.heads)
    assert plan.steps == blocks * len(plan.pairs)
    assert plan.dead == blocks * (n_q * n_k - len(plan.pairs))


def test_attention_tile_plan_long_causal_counts():
    """Causal S = 2048 at 512 tiles: 10 of the 16 pairs are live."""
    plan = _cell_plan("long2k")
    assert (plan.blk_q, plan.blk_k) == (512, 512)
    assert len(plan.pairs) == 10
    assert plan.steps == 48 * 10 and plan.dead == 48 * 6


def _kv_fetches(plan, b, hq, hkv):
    """DMAs of k/v blocks: the walk fetches one when the block index
    (kv row, kv tile) changes from the step before."""
    idx = [(r, ki) for r in range(b * hkv // plan.rows)
           for _ in range(hq // hkv // plan.heads) for _, ki, _ in plan.pairs]
    return 1 + sum(x != y for x, y in zip(idx, idx[1:]))


@pytest.mark.parametrize("shape", [(2, 8, 2, 64, 64), (8, 32, 4, 512, 128),
                                   (8, 4, 2, 32, 64)],
                         ids=["gqa", "yi", "folded_rows"])
def test_attention_tile_plan_folds_gqa_group(shape):
    """The q heads of one kv head share a step, so k/v are fetched once per
    kv head, not once per q head."""
    b, hq, hkv, s, d = shape
    plan = fa.attention_tile_plan(b, hq, hkv, s, s, d)
    assert plan.heads == hq // hkv
    assert plan.blk_k == s and _kv_fetches(plan, b, hq, hkv) == \
        b * hkv // plan.rows


@pytest.mark.parametrize("name", sorted(CELL_SHAPES))
def test_attention_tile_plan_fits_vmem(name):
    plan = _cell_plan(name)
    assert plan.vmem_bytes <= fa.VMEM_BUDGET
    assert plan.vmem_bytes + fa.VMEM_SLACK <= plan.vmem_limit


# name: (blk_q, blk_k, rows, heads, steps per call, dead pairs per call);
# the fixed 128×128 walk had 480 / 12,288 (4,608 dead) / 4,096 (1,536)
CELL_PLANS = {"sst2-k5": (64, 64, 32, 1, 15, 0),
              "long2k": (512, 512, 1, 1, 480, 288),
              "yi-squad512": (256, 512, 1, 8, 64, 0)}


@pytest.mark.parametrize("name", sorted(CELL_PLANS))
def test_attention_tile_plan_cells_pinned(name):
    p = _cell_plan(name)
    assert (p.blk_q, p.blk_k, p.rows, p.heads, p.steps, p.dead) == \
        CELL_PLANS[name]


def test_attention_tile_plan_decode_no_more_steps():
    """One q row: q takes 8 rows, kv its own 64 (no pad to 128), and the
    group shares a step: 2 steps where the fixed walk took 4."""
    p = fa.attention_tile_plan(1, 4, 2, 1, 64, 32)
    assert (p.blk_q, p.blk_k, p.heads, p.steps) == (8, 64, 2, 2)


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 32, 16), (3, 64, 48), (2, 128, 256)])
def test_linear_recurrence(shape):
    b, s, d = shape
    ks = jax.random.split(jax.random.key(3), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], shape))
    x = jax.random.normal(ks[1], shape)
    h0 = jax.random.normal(ks[2], (b, d))
    hs_ref, hl_ref = ref.linear_recurrence_ref(a, x, h0)
    for impl in ("xla", "pallas_interpret"):
        hs, hl = ops.linear_recurrence(a, x, h0, impl=impl)
        np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_ref),
                                   atol=2e-5, rtol=2e-4, err_msg=impl)
        np.testing.assert_allclose(np.asarray(hl), np.asarray(hl_ref),
                                   atol=2e-5, rtol=2e-4, err_msg=impl)


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [(1, 32, 2, 8, 16, 16),
                                  (2, 64, 4, 16, 32, 16),
                                  (1, 128, 2, 64, 128, 32)])
def test_ssd(dims):
    B, S, H, P, N, chunk = dims
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, N)) * 0.3
    c = jax.random.normal(ks[4], (B, S, N)) * 0.3
    s0 = jnp.zeros((B, H, P, N))
    y_ref, st_ref = ref.ssd_ref(x, dt, a, b, c, s0)
    for impl in ("xla", "pallas_interpret"):
        y, st = ops.ssd(x, dt, a, b, c, s0, chunk=chunk, impl=impl)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-3, rtol=1e-3, err_msg=impl)
        np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                                   atol=1e-3, rtol=1e-3, err_msg=impl)


def test_ssd_decode_step_matches_scan():
    """Sequential decode steps reproduce the chunked scan outputs."""
    B, S, H, P, N = 1, 16, 2, 8, 16
    ks = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, N)) * 0.3
    c = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y_ref, st_ref = ref.ssd_ref(x, dt, a, b, c)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        y_t, state = ops.ssd_decode_step(state, x[:, t], dt[:, t], a,
                                         b[:, t], c[:, t])
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(st_ref),
                               atol=1e-4, rtol=1e-4)
