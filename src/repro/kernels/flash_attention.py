"""Flash attention (fused online-softmax) Pallas TPU kernel.

Supports causal masking, local windows (RecurrentGemma), and GQA via the
BlockSpec index_map (kv blocks are fetched for head h using h // group — no
jnp.repeat materialization).

Grid layout: (batch·heads, num_q_blocks, num_kv_blocks) with the kv dimension
innermost and sequential ("arbitrary" semantics): the f32 accumulator, running
max m and normalizer l live in VMEM scratch that persists across kv steps.
Causal/window block-level skipping uses pl.when — skipped blocks cost zero
MXU work (the dominant saving for long-sequence causal training).

Block shapes are multiples of (8, 128) so the MXU sees aligned tiles; head_dim
is padded by the wrapper in ops.py if needed.

ZO perturbation fusion: attention itself has no weights, so the fused dual
forward (PairZeroConfig.fused_perturbation) perturbs the QKV/O *projections*
feeding this kernel via kernels/perturbed_matmul.py — the scores/output math
here runs unchanged on already-perturbed activations, and no perturbed weight
tensor is ever materialized for the attention block either.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  blk_q: int, blk_k: int, seq_k: int, sq_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions: q rows are the last Sq positions of the kv range
    q_start = qi * blk_q + sq_offset
    k_start = ki * blk_k

    # block-level relevance test (static per (qi, ki) only via traced compare)
    q_last = q_start + blk_q - 1
    k_first = k_start
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_first <= q_last
    if window is not None:
        # highest q position must still see the *end* of this kv block
        relevant &= (k_start + blk_k - 1) > (q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [blk_q, d]
        k = k_ref[0].astype(jnp.float32)                  # [blk_k, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (blk_q, blk_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (blk_q, blk_k), 1)
        mask = jnp.ones((blk_q, blk_k), dtype=bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        mask &= k_pos < seq_k                              # tail padding
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                # [blk_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # [blk_q, blk_k]
        alpha = jnp.exp(m_prev - m_new)                    # [blk_q, 1]
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                   # [blk_k, d]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = alpha * acc_ref[...] + pv
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "blk_q", "blk_k",
                     "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           blk_q: int = 128, blk_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D]. Returns [B, Hq, Sq, D].

    Sq may be smaller than Skv (q rows are the final Sq positions — decode /
    chunked prefill). D must be 128-aligned (ops.py pads otherwise).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    blk_q = min(blk_q, max(sq, 8))
    blk_k = min(blk_k, max(skv, 128))
    q_pad = (-sq) % blk_q
    k_pad = (-skv) % blk_k
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    sq_p, skv_p = sq + q_pad, skv + k_pad

    qr = q.reshape(b * hq, sq_p, d)
    kr = k.reshape(b * hkv, skv_p, d)
    vr = v.reshape(b * hkv, skv_p, d)
    n_q, n_kv = sq_p // blk_q, skv_p // blk_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, seq_k=skv, sq_offset=skv - sq)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, blk_k, d),
                         lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, blk_k, d),
                         lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, d), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out[:, :sq].reshape(b, hq, sq, d)
