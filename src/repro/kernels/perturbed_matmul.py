"""Fused perturbed matmul kernel: out = x @ (w + eps · z(seed)).

The second half of the paper's memory trick (the first is
`seeded_axpy.py`). A naive ZO dual forward materializes the perturbed
parameter tree θ±εz in HBM before each rollout; here the perturbation is
generated *inside the kernel, per weight tile, in VMEM* from the same
counter-hash stream (`gaussian_from_counter`) — the perturbed weights never
exist as a tensor anywhere in the memory hierarchy. HBM sees exactly one
read of `w` per tile, the same traffic as an unperturbed matmul.

Counter layout: element (k, n) of `w` draws counter

    idx = off + k · N + n                 (row-major over the ORIGINAL w)

where `off` is the leaf's base offset into its per-leaf stream (0 for a
whole leaf; `layer · K · N` for a layer sliced out of a scan-stacked
[L, K, N] leaf — see `kernels.ops.PerturbedParam`). This makes the fused
draw bitwise identical to `ref.draw_z_ref` / `seeded_axpy` on the same
leaf: the stream is a pure function of (seed, flat element index),
invariant to tiling, grid shape, and scan slicing.

Grid layout: (m, n, k) with the contraction dim innermost and sequential
("arbitrary" semantics); the f32 accumulator lives in VMEM scratch across
k steps. Padding is carried by zero-filled x columns (0 · (w + εz) = 0
exactly), so no masking is needed in-kernel and the identity-probe
property holds bitwise: x = I returns (w + εz) rows unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.seeded_axpy import gaussian_from_counter

LANE = 128


def _pmm_kernel(seed_ref, off_ref, eps_ref, x_ref, w_ref, o_ref, acc_ref, *,
                bk: int, bn: int, n_orig: int):
    ki = pl.program_id(2)
    nj = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # in-VMEM z for this (k, n) tile of w: counters are flat row-major
    # indices over the ORIGINAL (unpadded) w, shifted by the leaf offset
    r_iota = jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1)
    k0 = (ki * bk).astype(jnp.uint32)
    n0 = (nj * bn).astype(jnp.uint32)
    idx = off_ref[0, 0] + (k0 + r_iota) * jnp.uint32(n_orig) + (n0 + c_iota)
    z = gaussian_from_counter(idx, seed_ref[0, 0])
    wz = w_ref[...].astype(jnp.float32) + eps_ref[0, 0] * z
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), wz,
                            preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "bn", "interpret"))
def perturbed_matmul_pallas(x: jnp.ndarray, w: jnp.ndarray,
                            seed: jnp.ndarray, off: jnp.ndarray, eps,
                            bm: int = 128, bk: int = 128, bn: int = 128,
                            interpret: bool = False) -> jnp.ndarray:
    """x [M, K] @ (w [K, N] + eps · z(seed, off)) with in-VMEM z generation.

    Args:
      x: [M, K] activations (any float dtype; accumulation is f32).
      w: [K, N] unperturbed weights.
      seed: uint32 scalar — the leaf's stream seed (`zo.leaf_seed`).
      off: uint32 scalar — base flat offset of `w` within its leaf stream
        (0 unless `w` is a slice of a scan-stacked leaf).
      eps: perturbation scale (traced or static scalar; ±μ in the dual
        forward).
      bm/bk/bn: tile sizes (clamped to the padded operand dims).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = x.dtype

    bm = min(bm, max(8, -(-m // 8) * 8))
    bk = min(bk, max(LANE, -(-k // LANE) * LANE))
    bn = min(bn, max(LANE, -(-n // LANE) * LANE))
    mp = -(-m // bm) * bm
    kp = -(-k // bk) * bk
    npad = -(-n // bn) * bn
    # zero-filled x columns kill the padded-K contributions exactly; padded
    # N columns are sliced off below (their z counters are junk by design).
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, npad) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, npad - n)))

    out = pl.pallas_call(
        functools.partial(_pmm_kernel, bk=bk, bn=bn, n_orig=n),
        grid=(mp // bm, npad // bn, kp // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, npad), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(seed).astype(jnp.uint32).reshape(1, 1),
      jnp.asarray(off).astype(jnp.uint32).reshape(1, 1),
      jnp.asarray(eps, jnp.float32).reshape(1, 1), x, w)
    return out[:m, :n]
