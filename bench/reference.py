"""Plain reference of a federated ZO round.

Written from the published description of the method and of the system's
documented seeded streams; it imports nothing of the program and takes no
array the program made. One round, for every perturbation direction j:

    z        = counter-hash N(0, 1) stream per leaf (fmix32 + Box-Muller)
    L±_k     = loss_k(θ ± μ z)           per client k, fresh from θ
    p_k      = clip((L+_k − L−_k) / 2μ, ±γ)
    p̂        = (c Σ_k (p_k + σ_k n_k) + √N0 ζ) / (K c)   analog OTA decode
    θ       ← θ − (η / n_perturb) p̂ z

with c(t) the Theorem-3 power-control schedule solved over the horizon
from a Rayleigh channel draw, σ = 0, and the receiver noise ζ drawn from
the round's key. The round, its streams and the shared pieces of a model
(`perturbed`, `mm`, `rmsnorm`, `rope`) are here; the model's loss is its
family's (bench/families/<family>.py, `make_loss`).

`dtype`/`precision` select the arithmetic: float32 at HIGHEST is the
reference; bfloat16 is the control that a correct limit has to reject.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import cell

PyTree = Any
HIGHEST = jax.lax.Precision.HIGHEST

# --- seeded streams (the documented counter-hash generator) -----------------

_GOLDEN = 0x9E3779B9
_M1, _M2 = 0x7FEB352D, 0x846CA68B
_ROUND_MULT = 0x85EBCA6B
_TWO_PI = 6.283185307179586
_NOISE_TAG, _CHANNEL_TAG = 0x5EED, 0xC4A7


def fmix32(x):
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(_M2)
    return x ^ (x >> jnp.uint32(16))


def _unit(bits):
    f = (bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(2 ** -24)
    return jnp.maximum(f, jnp.float32(2 ** -24))


def gaussian(idx, seed):
    """z at flat element indices `idx` of the stream `seed` (uint32)."""
    base = idx * jnp.uint32(2) + seed * jnp.uint32(_GOLDEN)
    u1 = _unit(fmix32(base))
    u2 = _unit(fmix32(base + jnp.uint32(1)))
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(jnp.float32(_TWO_PI) * u2)


def z_block(shape, seed, offset=0):
    """z for a row-major block of `shape` starting at flat index offset."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for k in reversed(range(len(shape))):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, k) \
            * jnp.uint32(stride & 0xFFFFFFFF)
        stride *= shape[k]
    return gaussian(idx + jnp.asarray(offset, jnp.uint32),
                    jnp.asarray(seed, jnp.uint32))


def round_seed(base, t):
    return fmix32(jnp.asarray(base, jnp.uint32) ^ (jnp.asarray(t, jnp.uint32)
                                      * jnp.uint32(_ROUND_MULT)))


def perturb_seed(rs, j: int):
    return fmix32(rs + jnp.uint32((_GOLDEN * (j + 1)) & 0xFFFFFFFF))


def leaf_seeds(seed, n_leaves: int):
    return fmix32(seed * jnp.uint32(_GOLDEN)
                  + jnp.arange(n_leaves, dtype=jnp.uint32))


# --- the power-control schedule (Theorem 3) and the DP radius ---------------

def _log_c(x: float) -> float:
    if x <= 0:
        return -math.inf
    return 0.5 * math.log(math.pi) + math.log(x) + x * x


def dp_radius(epsilon: float, delta: float) -> float:
    """R_dp(ε, δ) = (√(ε + C⁻¹(1/δ)²) − C⁻¹(1/δ))², C(x) = √π x e^{x²}."""
    log_y = math.log(1.0 / delta)
    lo, hi = 0.0, 1.0
    while _log_c(hi) < log_y:
        hi *= 2.0
    for _ in range(400):
        mid = 0.5 * (lo + hi)
        if _log_c(mid) < log_y:
            lo = mid
        else:
            hi = mid
        if hi - lo < 1e-14 * max(1.0, hi):
            break
    cinv = 0.5 * (lo + hi)
    return (math.sqrt(epsilon + cinv * cinv) - cinv) ** 2


def analog_schedule(seed: int, horizon: int, k: int, *, power: float,
                    n0: float, gamma: float, contraction: float,
                    epsilon: float, delta: float) -> np.ndarray:
    """c(t) over the horizon: Rayleigh |h| ~ CN(0, 1), full-power cap
    c_cap = min_k √P h_k / γ, and the A^{-t/4} profile scaled by the
    bisected ζ so that Σ_t 2 γ² c(t)² / N0 meets the DP radius."""
    rng = np.random.default_rng(seed ^ _CHANNEL_TAG)
    re = rng.normal(size=(horizon, k)) / np.sqrt(2.0)
    im = rng.normal(size=(horizon, k)) / np.sqrt(2.0)
    h = np.sqrt(re * re + im * im)
    gam = np.full(horizon, float(gamma))
    budget = dp_radius(epsilon, delta)
    c_cap = np.min(math.sqrt(power) * h / gam[:, None], axis=1)
    if float(np.sum(2.0 * gam ** 2 * c_cap ** 2 / n0)) <= budget:
        return c_cap
    t_idx = np.arange(1, horizon + 1, dtype=np.float64)

    def c_of(zeta: float) -> np.ndarray:
        adaptive = (contraction ** (-t_idx / 4.0)) * math.sqrt(n0) \
            / ((2.0 * zeta) ** 0.25 * np.sqrt(gam))
        return np.minimum(adaptive, c_cap)

    def spent(zeta: float) -> float:
        return float(np.sum(2.0 * gam ** 2 * c_of(zeta) ** 2 / n0))

    lo, hi = 0.0, 1.0
    while spent(hi) > budget:
        hi *= 4.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if spent(mid) > budget:
            lo = mid
        else:
            hi = mid
        if hi - lo <= 1e-12 * max(hi, 1.0):
            break
    return c_of(hi)


# --- pieces every family's model shares --------------------------------------

def mm(x, w, spec, dtype, precision):
    out = jnp.einsum(spec, x.astype(dtype), w.astype(dtype),
                     precision=precision,
                     preferred_element_type=jnp.float32)
    return out


def rmsnorm(x, g, eps):
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf * r * g.astype(jnp.float32)


def rope(x, theta):
    """x: [B, H, S, D]; rotate the two halves of D by position."""
    s, d = x.shape[-2], x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def perturbed(w, seed, eps, dtype, offset=0):
    """w + eps z in `dtype`, z the stream `seed` from flat index `offset`:
    a layer of a scan-stacked leaf starts at layer × its size."""
    z = z_block(w.shape, seed, offset)
    return (w.astype(jnp.float32) + eps * z).astype(dtype)


def make_loss(m: Dict[str, Any], leaf_paths: Sequence[str],
              dtype=jnp.float32, precision=HIGHEST):
    """loss(θ_leaves, seeds, eps, tokens, targets, mask) → [K] at θ + eps z,
    the model family's (bench/families/<family>.py).

    θ_leaves are in flatten order; seeds are the per-leaf stream seeds."""
    return cell.family(m["family"]).make_loss(m, leaf_paths, dtype,
                                              precision)


def path_names(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(p, "key", p)) for p in path)
            for path, _ in flat]


class Reference:
    """The reference for one configuration and traffic mix, in one
    arithmetic; its jitted pieces compile once for every seed."""

    def __init__(self, m: Dict[str, Any], traffic: Dict[str, Any],
                 channel: Dict[str, float], *, dtype=jnp.float32,
                 precision=HIGHEST):
        from weights import shapes
        self.m, self.traffic, self.channel = m, traffic, channel
        self.dtype = dtype
        self.k = int(traffic["clients"])
        self.n_perturb = int(traffic["n_perturb"])
        self.names = path_names(shapes(m))
        n = len(self.names)
        k = self.k
        n0 = float(channel["n0"])
        self.loss = jax.jit(make_loss(m, self.names, dtype, precision))

        def seeds_of(base, t, j):
            return leaf_seeds(perturb_seed(round_seed(base, t), j), n)

        self.seeds_of = jax.jit(seeds_of, static_argnums=2)

        def decode(p, c, key_t, j, heard):
            rk = jax.random.fold_in(key_t, j)
            nk, zk = jax.random.split(rk)
            n_k = jnp.zeros((k,), jnp.float32) * jax.random.normal(
                nk, (k,), jnp.float32)
            zeta = jnp.sqrt(jnp.float32(n0)) * jax.random.normal(
                zk, (), jnp.float32)
            w = (jnp.arange(k) < heard).astype(jnp.float32)
            y = c * jnp.sum(w * (p + n_k)) + zeta
            return y / (jnp.float32(k) * c)

        self.decode = jax.jit(decode, static_argnums=3)
        self.update = jax.jit(
            lambda ls, sd, scale: [
                (x.astype(jnp.float32) + scale * z_block(x.shape, sd[i])
                 ).astype(x.dtype) for i, x in enumerate(ls)],
            donate_argnums=(0,))

    def run(self, params: PyTree, batches: Sequence[Dict[str, np.ndarray]],
            program_seed: int, heard: int = 0) -> Dict[str, Any]:
        """Follow the first len(batches) rounds from `params` (consumed).

        `heard` < K leaves all but the first `heard` clients out of the
        superposition (the fault of a client-axis exchange left out).
        Returns per-round `loss` (mean over clients and ± of L), `p_hat`,
        `p_clients` (the first direction's [K] payload), and the final
        `leaves` in flatten order, in the reference's dtype."""
        t_cfg = self.traffic
        mu, lr, gamma = float(t_cfg["mu"]), float(t_cfg["lr"]), \
            float(t_cfg["gamma"])
        leaves = [x.astype(self.dtype)
                  for x in jax.tree_util.tree_leaves(params)]
        del params
        c_sched, key_of = self._channel(program_seed)
        heard = heard or self.k
        base = jnp.uint32(program_seed)
        out: Dict[str, Any] = {"loss": [], "p_hat": [], "p_clients": []}
        for t, batch in enumerate(batches):
            key_t = key_of(t)
            loss_acc, p_hat_acc = 0.0, 0.0
            for j in range(self.n_perturb):
                seeds = self.seeds_of(base, jnp.uint32(t), j)
                lp = self.loss(leaves, seeds, jnp.float32(mu),
                               batch["tokens"], batch["targets"],
                               batch["mask"])
                lm = self.loss(leaves, seeds, jnp.float32(-mu),
                               batch["tokens"], batch["targets"],
                               batch["mask"])
                p = jnp.clip((lp - lm) / (2.0 * mu), -gamma, gamma)
                p_hat = self.decode(p, jnp.float32(c_sched[t]), key_t, j,
                                    jnp.int32(heard))
                if j == 0:
                    out["p_clients"].append([float(x) for x in p])
                leaves = self.update(leaves, seeds,
                                     jnp.float32(-(lr / self.n_perturb))
                                     * p_hat)
                loss_acc += float(jnp.mean(0.5 * (lp + lm)))
                p_hat_acc += float(p_hat)
            out["loss"].append(loss_acc / self.n_perturb)
            out["p_hat"].append(p_hat_acc / self.n_perturb)
        out["leaves"] = leaves
        return out

    def _channel(self, program_seed: int):
        """(c(t) over the horizon, t → the round's noise key)."""
        t_cfg, ch = self.traffic, self.channel
        c_sched = analog_schedule(
            program_seed, int(t_cfg["horizon_rounds"]), self.k,
            power=ch["power"], n0=ch["n0"], gamma=float(t_cfg["gamma"]),
            contraction=ch["contraction"], epsilon=ch["epsilon"],
            delta=ch["delta"])
        key0 = jax.random.key(program_seed ^ _NOISE_TAG)
        return c_sched, lambda t: jax.random.fold_in(key0, jnp.int32(t))

    def decode_rounds(self, payloads: Sequence[Sequence[float]],
                      program_seed: int) -> List[float]:
        """p̂ of rounds 0, 1, ... decoded from the given [K] payloads (the
        first direction's) with every client heard."""
        c_sched, key_of = self._channel(program_seed)
        return [float(self.decode(jnp.asarray(p, jnp.float32),
                                  jnp.float32(c_sched[t]), key_of(t), 0,
                                  jnp.int32(self.k)))
                for t, p in enumerate(payloads)]
