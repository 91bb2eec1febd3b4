"""Zeroth-order (SPSA / MeZO-style) seeded gradient estimation (paper Sec. IV-A).

The central trick: the perturbation z ~ N(0, I_d) is *never stored and never
transmitted* — it is regenerated on demand from a shared round seed. A client
needs only

    p_k = ( F_k(w + μz) − F_k(w − μz) ) / (2μ)                    (Eq. 7)

and the server/global update is w ← w − η p̂ z (Algorithm 1, line 14).

Seeds are plain int32 scalars (what a base station actually broadcasts); each
parameter leaf gets an independent stream via a hash of (round_seed, leaf_idx).
The z-stream itself is the counter-hash generator shared bitwise by the
Pallas kernel, its interpret mode, and the XLA fallback (kernels/seeded_axpy).

Memory discipline (the paper's "inference-level memory" claim, made real):
`chained` mode walks the MeZO sequence  w → w+μz → w−μz → w−μz+(μ−ηp̂)z  with
every step an in-place-style axpy (buffer-donated under jit), so the peak
footprint is ONE copy of the parameters plus one layer's activations. The
final restore and the update share a single fused axpy.

`fresh` mode recomputes each perturbed copy directly from w (no chained
floating-point drift, 2× memory) — tests use it as the oracle for `chained`.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.seeded_axpy import fmix32

PyTree = Any


def leaf_seed(seed, leaf_idx: int) -> jnp.ndarray:
    """Independent per-leaf stream seed: fmix32(seed · φ + leaf_idx)."""
    s = jnp.asarray(seed).astype(jnp.uint32)
    return fmix32(s * jnp.uint32(0x9E3779B9) + jnp.uint32(leaf_idx))


def round_seed(base_seed: int, t) -> jnp.ndarray:
    """The seed the server broadcasts for round t (pure function — clients
    and a restarted server re-derive the identical stream)."""
    return fmix32(jnp.asarray(base_seed).astype(jnp.uint32)
                  ^ (jnp.asarray(t).astype(jnp.uint32)
                     * jnp.uint32(0x85EBCA6B)))


def perturb_seed(round_seed_t, j: int) -> jnp.ndarray:
    """Seed of perturbation direction j within a round (the stream the
    round body perturbs with). Derived from the broadcast round seed, so it
    is just as public — an eavesdropper replays z(perturb_seed) exactly,
    which is the premise of the seed-replay attack (repro.privacy)."""
    return fmix32(jnp.asarray(round_seed_t).astype(jnp.uint32)
                  + jnp.uint32((0x9E3779B9 * (j + 1)) & 0xFFFFFFFF))


# ---------------------------------------------------------------------------
# Seeded perturbation
# ---------------------------------------------------------------------------

def perturb(params: PyTree, seed, scale, impl=None) -> PyTree:
    """params + scale · z(seed), with z regenerated leaf-by-leaf.

    `scale` may be a traced scalar (e.g. −η·p̂) — the same code path serves
    perturbation, restoration and the model update.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = [kops.seeded_axpy(leaf, leaf_seed(seed, i), scale, impl=impl)
           for i, leaf in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def tag_perturbed(params: PyTree, seed, scale) -> PyTree:
    """Tag every leaf as lazily perturbed: leaf → PerturbedParam(leaf, …).

    The fused counterpart of `perturb`: instead of materializing
    params + scale · z, each leaf carries (seed, offset, scale) metadata and
    the consumers in models/layers.py regenerate z inside their own
    matmul/gather (kernels.ops.perturbed_matmul / perturbed_gather) or
    resolve a layer-sized transient. Leaf enumeration and per-leaf streams
    are identical to `perturb`, so the loss seen through a tagged tree
    equals the loss at `perturb(params, seed, scale)` up to matmul
    reassociation (bitwise for the z values themselves).

    Children are broadcast to each leaf's leading dim so scan-stacked
    leaves slice into valid per-layer tags under `lax.scan` (the slice's
    `off` continues the whole-leaf counter stream).
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = []
    for i, leaf in enumerate(leaves):
        ls = leaf_seed(seed, i)
        if leaf.ndim == 0:
            # 0-d leaf: nothing to fuse into — materialize directly
            out.append(kops.seeded_axpy(leaf.reshape(1), ls, scale,
                                        impl="xla").reshape(()))
            continue
        lead = leaf.shape[0]
        stride = 1
        for d in leaf.shape[1:]:
            stride *= d
        out.append(kops.PerturbedParam(
            leaf,
            jnp.broadcast_to(ls, (lead,)),
            jnp.arange(lead, dtype=jnp.uint32)
            * jnp.uint32(stride & 0xFFFFFFFF),
            jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (lead,))))
    return jax.tree_util.tree_unflatten(treedef, out)


def draw_z(params: PyTree, seed) -> PyTree:
    """Materialize z(seed) with the same per-leaf streams as `perturb`.

    Only used by tests and analysis tooling (e.g. the Fig. 4–6 sign-reversing
    study) — the training path never materializes z.
    """
    from repro.kernels.ref import draw_z_ref
    leaves, treedef = jax.tree_util.tree_flatten(params)
    zs = [draw_z_ref(leaf.shape, leaf_seed(seed, i)).astype(leaf.dtype)
          for i, leaf in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, zs)


# ---------------------------------------------------------------------------
# Dual forward: loss at w ± μz
# ---------------------------------------------------------------------------

def dual_forward(loss_fn: Callable[[PyTree], jnp.ndarray], params: PyTree,
                 seed, mu: float, mode: str = "chained",
                 impl: Optional[str] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, PyTree]:
    """Evaluate (loss(w+μz), loss(w−μz)) and return params positioned for update.

    Returns (loss_plus, loss_minus, params_at) where `params_at` is w−μz in
    chained mode (caller fuses restore+update via one axpy of (μ − η·p̂)·z)
    or w itself in fresh mode (caller applies −η·p̂·z). `impl` selects the
    seeded_axpy kernel of the chained/fresh perturbations (None: auto).

    The θ passes run under the named scopes `theta_plus` / `theta_minus`
    (`apply_update`'s under `theta_update`), so every op of a pass — the
    kernel and any copy XLA puts around it — carries its phase in the
    compiled program's metadata (`repro.obs.hlo.op_scopes`).
    """
    if mode == "chained":
        with jax.named_scope("theta_plus"):
            p_plus = perturb(params, seed, mu, impl)   # w + μz (donates w)
        loss_plus = loss_fn(p_plus)
        with jax.named_scope("theta_minus"):
            # data-depend the second axpy on loss_plus so XLA cannot
            # reorder the buffer chain (the scalar add is free).
            anchor = (jnp.sum(loss_plus) * 0.0).astype(jnp.float32)
            p_minus = perturb(p_plus, seed, -2.0 * mu + anchor,
                              impl)                    # w − μz
        loss_minus = loss_fn(p_minus)
        return loss_plus, loss_minus, p_minus
    if mode == "fresh":
        with jax.named_scope("theta_plus"):
            p_plus = perturb(params, seed, mu, impl)
        loss_plus = loss_fn(p_plus)
        with jax.named_scope("theta_minus"):
            p_minus = perturb(params, seed, -mu, impl)
        loss_minus = loss_fn(p_minus)
        return loss_plus, loss_minus, params
    if mode == "fused":
        # Perturbed weights never materialize tree-wide: consumers
        # regenerate z from the tags (see tag_perturbed) inside their own
        # matmul/gather, resolving at most one layer-sized transient.
        # Both rollouts run under ONE vmap over eps = (+μ, −μ): z depends
        # only on (seed, off) — never eps — so each leaf's z is generated
        # once per round and shared by the two rollouts. The one vmapped
        # tag serves both, under `theta_plus`.
        def one_rollout(eps):
            with jax.named_scope("theta_plus"):
                tagged = tag_perturbed(params, seed, eps)
            return loss_fn(tagged)

        lpm = jax.vmap(one_rollout)(jnp.asarray([mu, -mu], jnp.float32))
        return lpm[0], lpm[1], params
    raise ValueError(f"unknown dual mode: {mode}")


def projection(loss_plus: jnp.ndarray, loss_minus: jnp.ndarray, mu: float,
               clip_gamma: float) -> jnp.ndarray:
    """Gradient projection p = (L+ − L−)/(2μ), clipped to ±γ (Assumption 3)."""
    p = (loss_plus - loss_minus) / (2.0 * mu)
    return jnp.clip(p, -clip_gamma, clip_gamma)


def apply_update(params_at: PyTree, seed, p_hat: jnp.ndarray,
                 lr, mu: float, mode: str = "chained",
                 impl: Optional[str] = None) -> PyTree:
    """Global model update w ← w − η p̂ z (Algorithm 1 line 14).

    chained: params_at = w−μz ⇒ one fused axpy of (μ − η p̂)·z restores and
    updates simultaneously. fresh: params_at = w ⇒ axpy of (−η p̂)·z.
    Runs under the named scope `theta_update`.
    """
    if mode not in ("chained", "fresh", "fused"):
        raise ValueError(f"unknown dual mode: {mode}")
    with jax.named_scope("theta_update"):
        scale = mu - lr * p_hat if mode == "chained" else -lr * p_hat
        return perturb(params_at, seed, scale, impl)


# ---------------------------------------------------------------------------
# Reference SPSA estimator (tests / analysis)
# ---------------------------------------------------------------------------

def spsa_gradient(loss_fn: Callable[[PyTree], jnp.ndarray], params: PyTree,
                  seed, mu: float) -> PyTree:
    """g = p · z — the full estimated gradient (Eq. 6). Materializes z; for
    tests and the e₀ study only."""
    lp, lm, _ = dual_forward(loss_fn, params, seed, mu, mode="fresh")
    p = (lp - lm) / (2.0 * mu)
    z = draw_z(params, seed)
    return jax.tree_util.tree_map(lambda zl: p.astype(zl.dtype) * zl, z)


def directional_derivative(loss_fn: Callable[[PyTree], jnp.ndarray],
                           params: PyTree, seed) -> jnp.ndarray:
    """Exact zᵀ∇F(w) via jvp — oracle for SPSA projection tests and the
    Fig. 4–6 sign-reversing study."""
    z = draw_z(params, seed)
    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t)
    _, jvp_val = jax.jvp(lambda p: loss_fn(p), (f32(params),), (f32(z),))
    return jvp_val
