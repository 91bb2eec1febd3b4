"""Mamba-2 SSD (state-space duality) chunked Pallas TPU kernel (ngroups = 1).

The SSD insight (arXiv:2405.21060): within a chunk of length Q the recurrence
is a masked attention-like matmul (MXU work); across chunks only the [P, N]
state is carried. This maps perfectly onto a Pallas grid with a sequential
chunk dimension:

  per (batch, head, chunk) step, all in VMEM/f32:
    g        = cumsum(a·dt)                       chunk-local log-decay
    L        = exp(g_i − g_j) · (i ≥ j)           [Q, Q] causal decay mask
    y_intra  = ((C Bᵀ) ⊙ L) (x·dt)                [Q, P] quadratic-in-chunk
    y_inter  = exp(g) ⊙ (C S_prev)                contribution of carried state
    S_new    = exp(g_last − g) scaled Bᵀ(x·dt) + exp(g_last)·S_prev

Chunk = 128 keeps every matmul MXU-shaped ([128,128]×[128,P]) and the whole
working set (few hundred KB) in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref,
                y_ref, slast_ref, state_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)        # [Q, 1]
    a = a_ref[0]                              # [1, 1] (per-head decay rate)
    bmat = b_ref[0].astype(jnp.float32)       # [Q, N]
    cmat = c_ref[0].astype(jnp.float32)       # [Q, N]

    adt = a[0, 0] * dt[:, 0]                  # [Q]  (a < 0)
    g = jnp.cumsum(adt)                       # [Q]  inclusive log-decay
    xdt = x * dt                              # [Q, P]

    # --- intra-chunk (quadratic within chunk) ---
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    gi = g[:, None]
    gj = g[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # decay from j to i (i ≥ j): exp(g_i − g_j); iota mask gives causality
    l_mask = jnp.where(ii >= jj, jnp.exp(gi - gj), 0.0)
    y_intra = jax.lax.dot_general(cb * l_mask, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # --- inter-chunk (carried state) ---
    s_prev = state_ref[...]                   # [N, P]
    cs = jax.lax.dot_general(cmat, s_prev, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, P]
    y_inter = jnp.exp(g)[:, None] * cs

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # --- state update ---
    g_last = g[chunk - 1]
    w = jnp.exp(g_last - g)[:, None] * bmat   # [Q, N] decay-to-chunk-end
    s_new = jnp.exp(g_last) * s_prev + jax.lax.dot_general(
        w, xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # [N, P]
    state_ref[...] = s_new

    @pl.when(ci == pl.num_programs(2) - 1)
    def _fin():
        slast_ref[0, 0] = s_new.astype(slast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray, c: jnp.ndarray, state0: jnp.ndarray,
                    chunk: int = 128, interpret: bool = False):
    """x: [B,S,H,P], dt: [B,S,H], a: [H], b/c: [B,S,N], state0: [B,H,N,P].

    Returns (y [B,S,H,P], state_last [B,H,N,P]). S must divide by chunk.
    NOTE: state layout here is [N, P] (transposed vs ref.py's [P, N]) to keep
    the MXU contractions layout-friendly; ops.py adapts.
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, "pad S to a multiple of chunk"
    n_c = S // chunk

    # layout: fold head into batch-like grid dims; broadcast b/c across heads
    xt = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtt = dt.transpose(0, 2, 1).reshape(B * H, S, 1)
    at = jnp.repeat(a.reshape(1, H), B, axis=0).reshape(B * H, 1, 1)
    s0 = state0.reshape(B * H, 1, N, P)

    y, s_last = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B, H, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bi, hi, ci, H=H:
                         (bi * H + hi, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci, H=H:
                         (bi * H + hi, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, hi, ci, H=H:
                         (bi * H + hi, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bi, hi, ci, H=H:
                         (bi * H + hi, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bi, hi, ci, H=H:
                         (bi * H + hi, ci, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bi, hi, ci, H=H:
                         (bi * H + hi, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, 1, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, at, b, c, s0)
    return (y.reshape(B, H, S, P).transpose(0, 2, 1, 3),
            s_last.reshape(B, H, N, P))
