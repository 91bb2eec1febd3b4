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

The kernel works on each leaf where it lies: the leaf is viewed as
[prod(shape[:-1]), shape[-1]], a bitcast on the TPU's (8, 128) tiling, and
a 2-D grid of blocks of about 1 MiB walks that view and writes each block
back over the input (the output aliases w). No flat, padded or relaid-out
copy of θ is made; a ragged last block is masked, not padded.

Counters are flat row-major element indices, so the stream is also
invariant to tiling and sharding — a resharded or differently-blocked call
perturbs identically.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANE = 128
SUBLANE = 8
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


def _axpy_kernel(seed_ref, scale_ref, w_ref, o_ref, *, cols: int):
    rows, lanes = w_ref.shape
    row0 = (pl.program_id(0) * rows).astype(jnp.uint32)
    col0 = (pl.program_id(1) * lanes).astype(jnp.uint32)
    r_iota = jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 1)
    idx = (row0 + r_iota) * jnp.uint32(cols) + (col0 + c_iota)
    z = gaussian_from_counter(idx, seed_ref[0])
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (w + scale_ref[0] * z).astype(o_ref.dtype)


def _matrix_view(shape) -> tuple:
    """[R, C] of a leaf in its own layout: the leading dims merged into the
    second-minor one, which is a bitcast on the TPU's (8, 128) tiling.
    A 1-D leaf of whole lanes takes [n / 128, 128]; any other 0-d or 1-D
    leaf is one row."""
    if len(shape) >= 2:
        return math.prod(shape[:-1]), shape[-1]
    n = math.prod(shape)
    return (n // LANE, LANE) if n % LANE == 0 else (1, n)


def _block_shape(rows: int, cols: int, block: int) -> tuple:
    """(br, bc) of about `block` elements: whole rows while 8 of them fit,
    else 8 rows of whole lanes. br is a multiple of 8 or all rows; bc all
    columns or a multiple of 128."""
    min_rows = min(rows, SUBLANE)
    if min_rows * cols <= block:
        br = max(SUBLANE, block // cols // SUBLANE * SUBLANE)
        return min(br, rows), cols
    return min_rows, max(LANE, block // min_rows // LANE * LANE)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def seeded_axpy_pallas(w: jnp.ndarray, seed: jnp.ndarray, scale,
                       block: int = DEFAULT_BLOCK,
                       interpret: bool = False) -> jnp.ndarray:
    """out = w + scale * z(seed), in place on the leaf's own layout.

    The leaf is viewed as [R, C] (`_matrix_view`, a bitcast) and tiled by
    a 2-D grid of blocks of about `block` elements (`_block_shape`); a
    ragged last block in either dimension is masked by Pallas, never
    padded. The counter of an element is its flat row-major index, so z
    is the stream of `ref.draw_z_ref`. The output aliases `w`: each block
    is read and then written at the same index, so a donated `w` is
    updated with no copy.

    Args:
      w: any-shape array.
      seed: uint32/int32 scalar (fold leaf/round indices in *before* calling).
      scale: traced or static scalar.
      block: elements per grid step.
    """
    rows, cols = _matrix_view(w.shape)
    br, bc = _block_shape(rows, cols, block)
    mat = w.reshape(rows, cols)
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_axpy_kernel, cols=cols),
        grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec,
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(mat.shape, w.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray([seed]).astype(jnp.uint32),
      jnp.asarray([scale], jnp.float32), mat)
    return out.reshape(w.shape)
