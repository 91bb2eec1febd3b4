"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for kernel sweeps in tests/test_kernels.py and the
semantic reference for the XLA fallbacks in ops.py. They are deliberately
naive (materialize everything, O(S²) attention, sequential scans) — clarity
over speed.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# seeded_axpy: out = w + scale * z,  z = counter-hash N(0,1) stream from seed
# ---------------------------------------------------------------------------

# Trailing dims that are a multiple of one SIMD packet (16 f32 covers both
# AVX2 and AVX-512) vectorize log/cos without scalar tails, so the native-
# shape evaluation is bitwise identical to the kernel's, which evaluates
# blocks of the leaf's own [prod(shape[:-1]), shape[-1]] view.
_SIMD_PACKET = 16


def draw_z_ref(shape, seed) -> jnp.ndarray:
    """The canonical z-stream: fmix32 counter hash + Box–Muller, identical to
    the Pallas kernel's in-VMEM generation (bitwise).

    Counters are always flat element indices, so the stream's VALUES are a
    pure function of (seed, index) — but the last ulp of log/cos depends on
    how XLA:CPU vectorizes the evaluating loop. Two regimes:

    * SIMD-exact trailing dim (every real model leaf): counters come from
      per-dim broadcasted_iota and the chain stays purely elementwise in
      the consumer's own shape — it fuses into the consuming axpy (z never
      materializes), shards with the consumer under GSPMD, and compiles
      identically inside lax.scan and standalone jit (the engine bitwise
      invariant). No scalar libm tails, so it is bitwise equal to the
      kernel's blockwise evaluation.
    * awkward trailing dim (e.g. [64, 50]): native evaluation has shape-
      dependent scalar libm tails — the historical 1-2 ulp pallas-interpret
      drift. Evaluate on a canonical flat [rows, LANE] layout behind
      an optimization barrier so fusion cannot drag the transcendentals
      back into the consumer's iteration space (the barrier materializes z
      for these shapes — the price of bitwise stability off the lane grid).
    """
    from repro.kernels.seeded_axpy import LANE, gaussian_from_counter
    seed = jnp.asarray(seed).astype(jnp.uint32)
    if shape and shape[-1] % _SIMD_PACKET == 0:
        idx = jnp.zeros(shape, jnp.uint32)
        for k in range(len(shape)):
            stride_k = np_prod(shape[k + 1:]) & 0xFFFFFFFF
            idx = idx + jax.lax.broadcasted_iota(
                jnp.uint32, shape, k) * jnp.uint32(stride_k)
        return gaussian_from_counter(idx, seed)
    n = np_prod(shape) if shape else 1
    rows = (n + LANE - 1) // LANE
    idx = (jax.lax.broadcasted_iota(jnp.uint32, (rows, LANE), 0)
           * jnp.uint32(LANE)
           + jax.lax.broadcasted_iota(jnp.uint32, (rows, LANE), 1))
    z = jax.lax.optimization_barrier(gaussian_from_counter(idx, seed))
    return z.reshape(-1)[:n].reshape(shape)


def np_prod(dims) -> int:
    n = 1
    for d in dims:
        n *= int(d)
    return n


def seeded_axpy_ref(w: jnp.ndarray, seed, scale) -> jnp.ndarray:
    """Reference semantics of the fused perturb: deterministic standard-normal
    z from the counter-hash stream, scaled and added in f32."""
    z = draw_z_ref(w.shape, seed)
    return (w.astype(jnp.float32) + jnp.asarray(scale, jnp.float32) * z
            ).astype(w.dtype)


# ---------------------------------------------------------------------------
# attention: causal / local-window / GQA, full-softmax oracle
# ---------------------------------------------------------------------------

def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, window: Optional[int] = None,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """q: [B, Hq, Sq, D], k/v: [B, Hkv, Skv, D] (Hq % Hkv == 0).

    window=w restricts key j to q position i with i − w < j ≤ i (local attn).
    Assumes q positions are the LAST Sq positions of the Skv range (so decode
    with a prefix cache works: Sq=1, Skv=cache_len).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kf = jnp.repeat(kf, group, axis=1)
    vf = jnp.repeat(vf, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    q_pos = jnp.arange(sq) + (skv - sq)
    k_pos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# RG-LRU first-order linear recurrence: h_t = a_t * h_{t-1} + x_t
# ---------------------------------------------------------------------------

def linear_recurrence_ref(a: jnp.ndarray, x: jnp.ndarray,
                          h0: Optional[jnp.ndarray] = None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """a, x: [B, S, D]; h0: [B, D]. Returns (hs [B,S,D], h_last [B,D])."""
    b, s, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), dtype=jnp.float32)

    def step(h, inp):
        a_t, x_t = inp
        h = a_t * h + x_t
        return h, h

    a32 = a.astype(jnp.float32).swapaxes(0, 1)   # [S, B, D]
    x32 = x.astype(jnp.float32).swapaxes(0, 1)
    h_last, hs = jax.lax.scan(step, h0.astype(jnp.float32), (a32, x32))
    return hs.swapaxes(0, 1).astype(x.dtype), h_last.astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 SSD: y_t = C_tᵀ S_t x-state;  S_t = exp(a_t) S_{t-1} + B_t x_tᵀ dt_t
# ---------------------------------------------------------------------------

def ssd_ref(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
            c: jnp.ndarray, state0: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential state-space-duality oracle (ngroups = 1).

    x:  [B, S, H, P]   inputs per head
    dt: [B, S, H]      positive step sizes (already softplus'd)
    a:  [H]            negative decay rates (A = -exp(A_log) convention)
    b:  [B, S, N]      input projections (shared across heads, G=1)
    c:  [B, S, N]      output projections
    state0: [B, H, P, N]
    Returns (y [B,S,H,P], state_last [B,H,P,N]).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    if state0 is None:
        state0 = jnp.zeros((B, H, P, N), dtype=jnp.float32)

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cf = c.astype(jnp.float32)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp            # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(af[None, :] * dt_t)  # [B,H]
        # state: [B,H,P,N]
        upd = jnp.einsum("bhp,bn,bh->bhpn", x_t, b_t, dt_t)
        state = decay[:, :, None, None] * state + upd
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t)
        return state, y_t

    xs = (xf.swapaxes(0, 1), dtf.swapaxes(0, 1), bf.swapaxes(0, 1),
          cf.swapaxes(0, 1))
    state_last, ys = jax.lax.scan(step, state0, xs)
    return ys.swapaxes(0, 1).astype(x.dtype), state_last
