"""RG-LRU / first-order linear recurrence Pallas TPU kernel.

Computes h_t = a_t ⊙ h_{t-1} + x_t over the sequence with the gate a already
materialized (the RG-LRU gating algebra — exp(−c·softplus(Λ)·σ(r_t)) and the
√(1−a²) input scaling — is cheap elementwise work done by the caller; the
recurrence is the part XLA serializes badly on TPU).

Strategy: grid (B, D/blk_d, S/chunk) with the sequence dimension innermost and
sequential. The carried state h lives in a VMEM scratch row persisting across
chunk steps. Within a chunk the recurrence is an in-VMEM fori_loop over time —
serial in S but each step is a (1, blk_d) VPU op over the channel block, and
the HBM traffic is one read of (a, x) and one write of h per element: the
kernel is bandwidth-bound at exactly its roofline minimum (3 streams).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rglru_kernel(a_ref, x_ref, h0_ref, o_ref, hlast_ref, state_ref, *,
                  chunk: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        state_ref[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):
        a_t = a_ref[0, t].astype(jnp.float32)
        x_t = x_ref[0, t].astype(jnp.float32)
        h = a_t * h + x_t
        o_ref[0, t] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, state_ref[...])
    state_ref[...] = h

    @pl.when(si == pl.num_programs(2) - 1)
    def _fin():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "blk_d", "interpret"))
def rglru_scan_pallas(a: jnp.ndarray, x: jnp.ndarray, h0: jnp.ndarray,
                      chunk: int = 256, blk_d: int = 512,
                      interpret: bool = False):
    """a, x: [B, S, D]; h0: [B, D] → (h [B,S,D], h_last [B,D]).

    S must be divisible by `chunk` (callers pad); D by 128 (lane width).
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, "pad S to a multiple of chunk"
    blk_d = min(blk_d, d)
    n_d = (d + blk_d - 1) // blk_d
    n_s = s // chunk

    out, h_last = pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=(b, n_d, n_s),
        in_specs=[
            pl.BlockSpec((1, chunk, blk_d), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, chunk, blk_d), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, blk_d), lambda bi, di, si: (bi, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, blk_d), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, blk_d), lambda bi, di, si: (bi, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d), x.dtype),
            jax.ShapeDtypeStruct((b, d), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((blk_d,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x, h0)
    return out, h_last
