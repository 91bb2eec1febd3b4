"""Flash attention (fused online-softmax) Pallas TPU kernel.

Supports causal masking, local windows (RecurrentGemma), GQA and q rows
that are the last Sq of Skv positions (decode, chunked prefill).

The tiling comes from one pure function of the shapes,
`attention_tile_plan`, which both the kernel and the tests read:

* q and kv tile lengths: a sequence that fits in one tile is one tile (q
  rounded up to 8 rows, kv at its own length, never padded to 128);
  longer ones take the largest of 512/256/128 whose step fits the VMEM
  budget.
* the rows of one grid step: q is viewed as [B·Hkv, group, Sq, D], so a
  step takes the q heads of one kv head together and k/v are fetched once
  per group, not once per q head. When both sequences are single tiles,
  a step also folds several (batch, kv head) rows.
* the live (q-tile, kv-tile) pairs, walked in order through a
  scalar-prefetched table: the kv grid axis runs over live pairs only, so
  a causally dead (or out-of-window) tile is neither a grid step nor a
  DMA. Init and finalize follow the first and last pair of each q tile.

Each pair carries a flag: only tiles that straddle the diagonal, the
window edge or the kv tail build the iota/compare/where mask. The head
dim is the array's own (64 is the full dimension, which Mosaic accepts),
so the wrapper neither pads q/k/v nor slices the output.

Both dots take float32 operands and accumulate in float32; the f32
accumulator, running max m and normalizer l live in VMEM scratch that
persists across a q tile's kv steps.

ZO perturbation fusion: attention itself has no weights, so the fused dual
forward (PairZeroConfig.fused_perturbation) perturbs the QKV/O *projections*
feeding this kernel via kernels/perturbed_matmul.py — the scores/output math
here runs unchanged on already-perturbed activations, and no perturbed weight
tensor is ever materialized for the attention block either.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

TILES = (512, 256, 128)        # multi-tile lengths, largest first
VMEM_BUDGET = 24 << 20         # estimated bytes one grid step may hold
VMEM_FLOOR = 16 << 20          # the compiler's default scoped VMEM (v5e)
VMEM_SLACK = 8 << 20           # scoped VMEM asked for above the estimate
SCORE_ROWS = 2048              # score rows (rows · heads · blk_q) per step
MIN_STEPS = 8                  # folded steps per call, to keep DMA pipelined

FIRST, LAST, MASKED = 1, 2, 4  # pair flags


class TilePlan(NamedTuple):
    """How `flash_attention_pallas` walks one call's shapes.

    A grid step takes `rows` (batch, kv head) rows, `heads` q heads of each
    and one live (q tile, kv tile) pair. `pairs` holds (qi, ki, flags) in
    walk order; `steps` and `dead` count grid steps per call and the dead
    pairs left out of them. `vmem_limit` is the scoped VMEM the kernel asks
    for: its estimate plus slack, never below the compiler's default. No
    more: scoped VMEM the kernel holds is VMEM that XLA cannot give the
    program's other buffers (a fixed 64 MiB raised long2k's chunk
    temporaries by 27.6 MB on a described v5e)."""
    blk_q: int
    blk_k: int
    rows: int
    heads: int
    pairs: Tuple[Tuple[int, int, int], ...]
    steps: int
    dead: int
    vmem_bytes: int
    vmem_limit: int


def _up(x: int, m: int) -> int:
    return -(-x // m) * m


def _vmem_bytes(rows, heads, blk_q, blk_k, dq, dv) -> int:
    """f32 bytes of a step: double-buffered q/o and k/v blocks, the acc/m/l
    scratch and three score-sized temporaries (s, p and one more)."""
    lanes = lambda n: _up(n, 128)
    rq = rows * heads * _up(blk_q, 8)
    blocks = 2 * (rq * (lanes(dq) + lanes(dv))
                  + rows * _up(blk_k, 8) * (lanes(dq) + lanes(dv)))
    scratch = rq * (lanes(dv) + 2 * 128)
    return 4 * (blocks + scratch + 3 * rq * lanes(blk_k))


def make_plan(b: int, hq: int, hkv: int, sq: int, skv: int, dq: int,
              dv: int, causal: bool, window: Optional[int], *,
              blk_q: int, blk_k: int, rows: int = 1,
              heads: Optional[int] = None) -> TilePlan:
    """The plan for given tiles and fold: the live pairs and their flags."""
    group = hq // hkv
    heads = group if heads is None else heads
    assert group % heads == 0 and (b * hkv) % rows == 0
    off = skv - sq                       # q rows are the last sq positions
    n_q, n_k = -(-sq // blk_q), -(-skv // blk_k)
    pairs = []
    for i in range(n_q):
        q_first = i * blk_q + off
        q_last = min((i + 1) * blk_q, sq) - 1 + off
        live = []
        for j in range(n_k):
            k_first = j * blk_k
            k_last = min((j + 1) * blk_k, skv) - 1
            if causal and k_first > q_last:
                continue
            if window is not None and k_last <= q_first - window:
                continue
            masked = ((j + 1) * blk_k > skv
                      or (causal and k_last > q_first)
                      or (window is not None
                          and k_first <= q_last - window))
            live.append((i, j, MASKED if masked else 0))
        assert live, f"q tile {i} sees no key"
        live[0] = live[0][:2] + (live[0][2] | FIRST,)
        live[-1] = live[-1][:2] + (live[-1][2] | LAST,)
        pairs += live
    blocks = (b * hkv // rows) * (group // heads)
    vmem = _vmem_bytes(rows, heads, blk_q, blk_k, dq, dv)
    return TilePlan(blk_q, blk_k, rows, heads, tuple(pairs),
                    blocks * len(pairs), blocks * (n_q * n_k - len(pairs)),
                    vmem, max(VMEM_FLOOR, _up(vmem + VMEM_SLACK, 1 << 20)))


def _divisors_desc(n: int):
    return [r for r in range(n, 0, -1) if n % r == 0]


def attention_tile_plan(b: int, hq: int, hkv: int, sq: int, skv: int,
                        dq: int, dv: Optional[int] = None,
                        causal: bool = True,
                        window: Optional[int] = None) -> TilePlan:
    """Choose tiles and fold from the shapes alone.

    Tiles: a sequence within the largest tile is one tile; otherwise the
    largest of `TILES`. The whole q group of a kv head shares a step (and
    its k/v fetch) unless the score tile would pass `SCORE_ROWS` rows or
    the VMEM budget, which shrink the q tile first, then split the group.
    When both sequences are single tiles, a step folds the most (batch,
    kv head) rows that keep `MIN_STEPS` steps and fit both limits."""
    dv = dq if dv is None else dv
    group = hq // hkv
    q_tiles = [_up(sq, 8)] if sq <= TILES[0] else []
    q_tiles += [t for t in TILES if t < sq]
    k_tiles = [skv] if skv <= TILES[0] else []
    k_tiles += [t for t in TILES if t < skv]

    def fits(rows, heads, tq, tk):
        return (rows * heads * tq <= SCORE_ROWS and _vmem_bytes(
            rows, heads, tq, tk, dq, dv) <= VMEM_BUDGET)

    tq = tk = None
    for heads in _divisors_desc(group):
        pick = [(q, k) for q in q_tiles for k in k_tiles
                if fits(1, heads, q, k)]
        if pick:
            tq, tk = max(pick, key=lambda t: (t[0] * t[1], t[1]))
            break
    if tq is None:                       # nothing fits: smallest step
        heads, tq, tk = 1, q_tiles[-1], k_tiles[-1]
    rows = 1
    if heads == group and tq >= sq and tk >= skv:
        for r in _divisors_desc(b * hkv):
            if b * hkv // r >= min(MIN_STEPS, b * hkv) and fits(
                    r, heads, tq, tk):
                rows = r
                break
    return make_plan(b, hq, hkv, sq, skv, dq, dv, causal, window,
                     blk_q=tq, blk_k=tk, rows=rows, heads=heads)


def _flash_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                  window: Optional[int], blk_q: int, blk_k: int, seq_k: int,
                  sq_offset: int, kv_tail: bool):
    p = pl.program_id(2)
    qi, ki, flags = qi_ref[p], ki_ref[p], flag_ref[p]
    rows, heads = q_ref.shape[0], q_ref.shape[1]

    @pl.when((flags & FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(masked: bool):
        q = q_ref[...].astype(jnp.float32) * scale
        q = q.reshape(rows, heads * blk_q, q.shape[-1])
        k = k_ref[...].astype(jnp.float32)                 # [r, blk_k, d]
        v = v_ref[...].astype(jnp.float32)                 # [r, blk_k, dv]
        s = jnp.einsum("rqd,rkd->rqk", q, k,
                       preferred_element_type=jnp.float32)
        if masked:
            q_pos = qi * blk_q + sq_offset + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            k_pos = ki * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            mask = k_pos < seq_k
            if causal:
                mask &= k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s.reshape(rows, heads, blk_q, blk_k),
                          NEG_INF).reshape(rows, heads * blk_q, blk_k)
            if kv_tail:                # rows past seq_k hold no data
                v_pos = ki * blk_k + jax.lax.broadcasted_iota(
                    jnp.int32, v.shape, 1)
                v = jnp.where(v_pos < seq_k, v, 0.0)

        m_prev = m_ref[...]                                # [r, rq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        pr = jnp.exp(s - m_new)                            # [r, rq, blk_k]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(pr, axis=2, keepdims=True)
        pv = jnp.einsum("rqk,rkd->rqd", pr, v,
                        preferred_element_type=jnp.float32)
        acc_ref[...] = alpha * acc_ref[...] + pv
        m_ref[...] = m_new

    masked = (flags & MASKED) != 0
    pl.when(masked)(lambda: update(True))
    pl.when(jnp.logical_not(masked))(lambda: update(False))

    @pl.when((flags & LAST) != 0)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def flash_attention_planned(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            plan: TilePlan, *, causal: bool = True,
                            window: Optional[int] = None,
                            scale: Optional[float] = None,
                            interpret: bool = False) -> jnp.ndarray:
    """Run the kernel on a given plan (see `flash_attention_pallas`)."""
    b, hq, sq, dq = q.shape
    _, hkv, skv, dv = v.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (dq ** 0.5)
    blk_q, blk_k, rows, heads = plan.blk_q, plan.blk_k, plan.rows, plan.heads

    q_pad = _up(sq, 8) - sq if blk_q >= sq else 0     # decode: q to 8 rows
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    sq_p = sq + q_pad
    qr = q.reshape(b * hkv, group, sq_p, dq)
    kr = k.reshape(b * hkv, skv, dq)
    vr = v.reshape(b * hkv, skv, dv)
    table = np.asarray(plan.pairs, np.int32).T
    qi_t, ki_t, fl_t = (jnp.asarray(t) for t in table)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, seq_k=skv, sq_offset=skv - sq,
        kv_tail=skv % blk_k != 0)
    q_map = lambda r, h, p, qt, kt, ft: (r, h, qt[p], 0)
    kv_map = lambda r, h, p, qt, kt, ft: (r, kt[p], 0)
    rq = heads * blk_q

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * hkv // rows, group // heads, len(plan.pairs)),
            in_specs=[pl.BlockSpec((rows, heads, blk_q, dq), q_map),
                      pl.BlockSpec((rows, blk_k, dq), kv_map),
                      pl.BlockSpec((rows, blk_k, dv), kv_map)],
            out_specs=pl.BlockSpec((rows, heads, blk_q, dv), q_map),
            scratch_shapes=[pltpu.VMEM((rows, rq, dv), jnp.float32),
                            pltpu.VMEM((rows, rq, 1), jnp.float32),
                            pltpu.VMEM((rows, rq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, sq_p, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit),
        interpret=interpret,
    )(qi_t, ki_t, fl_t, qr, kr, vr)
    out = out.reshape(b, hq, sq_p, dv)
    return out[:, :, :sq] if q_pad else out


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "scale", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, Hq, Sq, D]; k: [B, Hkv, Skv, D]; v: [B, Hkv, Skv, Dv].
    Returns [B, Hq, Sq, Dv].

    Sq may be smaller than Skv (q rows are the final Sq positions — decode /
    chunked prefill). Any head dim: blocks take the array's own."""
    b, hq, sq, dq = q.shape
    _, hkv, skv, dv = v.shape
    assert hq % hkv == 0
    plan = attention_tile_plan(b, hq, hkv, sq, skv, dq, dv, causal, window)
    return flash_attention_planned(q, k, v, plan, causal=causal,
                                   window=window, scale=scale,
                                   interpret=interpret)
