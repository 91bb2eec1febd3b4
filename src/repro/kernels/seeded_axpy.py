"""Fused seeded-perturbation kernel: out = w + scale · z(seed).

This is the paper's memory trick made TPU-native. A naive ZO perturbation
materializes z in HBM (read w, read z, write w': 3d bytes of HBM traffic per
axpy, plus d floats of live memory). Here z is generated *inside VMEM per
tile* from a counter-based hash RNG (murmur3 fmix32 finalizer + Box–Muller),
so HBM sees exactly one read and one write of w — z never exists as a tensor.

Why a counter-based hash instead of the TPU hardware PRNG
(`pltpu.prng_random_bits`): the stream becomes a *pure function of
(seed, element index)* — identical in the Mosaic kernel, the interpret-mode
kernel, the XLA fallback and the pure-jnp oracle (ref.py). That gives
  * bitwise kernel-vs-ref tests (not just statistical ones),
  * backend-independent training trajectories (CPU test == TPU run),
  * exact MeZO chain algebra: w → w+μz → w−μz → restore+update reuses the
    very same z at every step from nothing but the int32 seed.

Counters are element indices, so the stream is also invariant to tiling and
sharding — a resharded or differently-blocked call perturbs identically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANE = 128
DEFAULT_BLOCK = 2048 * LANE  # elements per grid step (1 MiB of f32 in VMEM)

_GOLDEN = 0x9E3779B9
_M1 = 0x7FEB352D
_M2 = 0x846CA68B
_TWO_PI = 6.283185307179586


def fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer — full-avalanche 32-bit bijection (uint32 in/out)."""
    x ^= x >> jnp.uint32(16)
    x *= jnp.uint32(_M1)
    x ^= x >> jnp.uint32(15)
    x *= jnp.uint32(_M2)
    x ^= x >> jnp.uint32(16)
    return x


def _bits_to_unit(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 → float32 uniform in [2^-24, 1): top 24 bits as mantissa.

    The shifted value is below 2^24, so going through int32 is exact on
    every backend; Mosaic has no direct uint32 → float32 cast.
    """
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    f = top.astype(jnp.float32) * jnp.float32(2 ** -24)
    return jnp.maximum(f, jnp.float32(2 ** -24))


def gaussian_from_counter(idx: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Standard normal z[idx] as a pure function of (seed, element index).

    idx: uint32 element indices (any shape); seed: uint32 scalar.
    Two decorrelated streams (counters 2i, 2i+1) feed Box–Muller.
    """
    base = idx * jnp.uint32(2) + seed * jnp.uint32(_GOLDEN)
    u1 = _bits_to_unit(fmix32(base))
    u2 = _bits_to_unit(fmix32(base + jnp.uint32(1)))
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(jnp.float32(_TWO_PI) * u2)


def _axpy_kernel(seed_ref, scale_ref, w_ref, o_ref, *, rows_per_block: int):
    tile = pl.program_id(0)
    rows, lanes = w_ref.shape
    row0 = tile * rows_per_block
    r_iota = jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 0)
    l_iota = jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 1)
    idx = (jnp.uint32(row0) + r_iota) * jnp.uint32(lanes) + l_iota
    z = gaussian_from_counter(idx, seed_ref[0])
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (w + scale_ref[0] * z).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def seeded_axpy_pallas(w: jnp.ndarray, seed: jnp.ndarray, scale,
                       block: int = DEFAULT_BLOCK,
                       interpret: bool = False) -> jnp.ndarray:
    """out = w + scale * z(seed), flattened-and-tiled over a 1D grid.

    Args:
      w: any-shape array (flattened internally; padded to the lane width).
      seed: uint32/int32 scalar (fold leaf/round indices in *before* calling).
      scale: traced or static scalar.
    """
    orig_shape, orig_dtype = w.shape, w.dtype
    n = w.size
    padded = max(((n + block - 1) // block) * block, 8 * LANE)
    flat = jnp.ravel(w)
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    grid = max(padded // block, 1)
    rows_per_block = padded // grid // LANE
    mat = flat.reshape(grid * rows_per_block, LANE)

    out = pl.pallas_call(
        functools.partial(_axpy_kernel, rows_per_block=rows_per_block),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rows_per_block, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_block, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(mat.shape, orig_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray([seed]).astype(jnp.uint32),
      jnp.asarray([scale], jnp.float32), mat)
    return out.reshape(-1)[:n].reshape(orig_shape)
