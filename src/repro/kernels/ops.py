"""Public kernel API: jit'd wrappers that dispatch Pallas ↔ XLA fallbacks.

Dispatch policy (`impl=None` ⇒ auto):
  * TPU backend        → Pallas (Mosaic) kernels.
  * CPU / tests        → XLA fallbacks (semantically identical to ref.py, but
    memory-efficient: chunked attention never materializes the S² matrix).
  * `pallas_interpret` → Pallas kernel body interpreted on CPU (kernel tests).

The XLA fallbacks are not an afterthought — they are what the multi-pod
dry-run compiles (this container has no TPU), so they are written to lower to
the same asymptotic memory/flops shape as the kernels (chunked online softmax,
scan-based recurrences) to keep the roofline honest.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.perturbed_matmul import perturbed_matmul_pallas
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.seeded_axpy import gaussian_from_counter, seeded_axpy_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

NEG_INF = -1e30


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# PerturbedParam — lazy w + eps · z(seed)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class PerturbedParam:
    """A parameter leaf tagged as "perturbed by eps · z(seed) at offset off".

    The fused dual forward (`zo.tag_perturbed`) replaces every leaf of the
    parameter tree with one of these; consumers in `models/layers.py` then
    either fuse the perturbation into their matmul/gather (z generated
    in-kernel, never stored) or `resolve()` a layer-sized transient. Either
    way no θ-sized perturbed tree ever exists.

    Children (all jax arrays, so the tag survives jit/scan/shard_map):
      w    — the unperturbed leaf, [lead, ...rest];
      seed — per-leaf stream seed (`zo.leaf_seed`), broadcast to [lead];
      off  — base flat offset of each leading-dim slice into the leaf's
             counter stream: off[l] = l · prod(rest), [lead];
      eps  — perturbation scale (±μ), broadcast to [lead].

    Every child carries the leaf's leading dim, so `lax.scan` over a
    scan-stacked tree ([L, ...] leaves) slices a PerturbedParam into valid
    per-layer PerturbedParams (w [...rest], scalar seed/off/eps) whose
    counters continue the whole-leaf stream: z values are bitwise identical
    to perturbing the full leaf with `kernels.seeded_axpy`.
    """

    def __init__(self, w, seed, off, eps):
        self.w = w
        self.seed = seed
        self.off = off
        self.eps = eps

    @property
    def shape(self):
        """Shape of the underlying (unperturbed) leaf."""
        return self.w.shape

    @property
    def dtype(self):
        """Dtype of the underlying (unperturbed) leaf."""
        return self.w.dtype

    @property
    def ndim(self):
        """Rank of the underlying (unperturbed) leaf."""
        return self.w.ndim

    def tree_flatten(self):
        return (self.w, self.seed, self.off, self.eps), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    def __repr__(self):
        return (f"PerturbedParam(w={self.w.shape}/{self.w.dtype}, "
                f"seed={self.seed.shape}, off={self.off.shape})")


def _scalar(v):
    """First element of a possibly-broadcast child (post-scan they are 0-d)."""
    v = jnp.asarray(v)
    return v.reshape(-1)[0] if v.ndim else v


def _flat_iota(shape) -> jnp.ndarray:
    """uint32 row-major flat index of every element of `shape` (mod 2³²)."""
    if not shape:
        return jnp.uint32(0)
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for k in range(len(shape) - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, k) \
            * jnp.uint32(stride & 0xFFFFFFFF)
        stride *= shape[k]
    return idx


def perturbed_z(pp: "PerturbedParam") -> jnp.ndarray:
    """Materialize z for a tagged leaf (f32) — same bits as the unfused
    stream `kernels.ref.draw_z_ref(leaf.shape, leaf_seed)` restricted to
    this slice. Used by the XLA fallback and `resolve`; the Pallas path
    generates the same values tile-by-tile in VMEM instead."""
    seed = _scalar(pp.seed)
    off = jnp.asarray(pp.off)
    w = pp.w
    if off.ndim == 0:
        idx = off + _flat_iota(w.shape)
    else:
        lead = off.shape[0]
        rest = w.shape[1:]
        idx = off.reshape((lead,) + (1,) * len(rest)) + _flat_iota(rest)[None]
    return gaussian_from_counter(idx, seed)


def resolve(pp) -> jnp.ndarray:
    """Materialize w + eps · z for one tagged leaf (a layer-sized transient,
    NOT a θ-sized one). Identity on plain arrays, so consumers can call it
    unconditionally on params that may or may not be tagged."""
    if not isinstance(pp, PerturbedParam):
        return pp
    wf = pp.w.astype(jnp.float32)
    return (wf + _scalar(pp.eps) * perturbed_z(pp)).astype(pp.w.dtype)


def perturbed_matmul(x: jnp.ndarray, pp: "PerturbedParam",
                     impl: Optional[str] = None) -> jnp.ndarray:
    """out = x @ (w + eps · z(seed)) for a 2-D tagged leaf; x: [..., K].

    The shared fused entry point of the ZO dual forward: both rollouts
    (eps = +μ and −μ) route every projection through here. Pallas impls
    generate z per weight tile in VMEM (kernels/perturbed_matmul.py); the
    XLA fallback materializes one layer-sized z transient and lets XLA fuse
    generation into the matmul's operand — in neither case does a perturbed
    copy of the full parameter tree exist.
    """
    impl = impl or _default_impl()
    w = pp.w
    assert w.ndim == 2, f"perturbed_matmul wants a 2-D leaf, got {w.shape}"
    if impl == "xla":
        # resolve a layer-sized w+εz transient and run ONE matmul. Under the
        # dual forward's vmap over eps = ±μ (zo.dual_forward mode="fused")
        # only eps is batched — z depends on (seed, off) alone, so XLA
        # materializes each layer's z once and shares it across the two
        # rollouts instead of drawing it per rollout.
        return jnp.einsum("...d,df->...f", x, resolve(pp),
                          preferred_element_type=jnp.float32).astype(x.dtype)
    if impl in ("pallas", "pallas_interpret"):
        batch = x.shape[:-1]
        m = 1
        for b in batch:
            m *= b
        out = perturbed_matmul_pallas(
            x.reshape(m, x.shape[-1]), w, _scalar(pp.seed), _scalar(pp.off),
            _scalar(pp.eps), interpret=(impl == "pallas_interpret"))
        return out.reshape(batch + (w.shape[1],))
    raise ValueError(f"unknown impl: {impl}")


def perturbed_unembed(x: jnp.ndarray, pp: "PerturbedParam") -> jnp.ndarray:
    """Fused lm-head contraction: [.., D] @ (w + εz)[V, D]ᵀ → f32 logits.

    Resolves a table-sized w+εz transient for the contraction; like
    `perturbed_matmul`, z depends only on (seed, off), so the dual
    forward's eps-vmap draws the [V, D] z once for both rollouts. The
    transient is freed after this op — no perturbed copy of the tree
    persists."""
    return jnp.einsum("...d,vd->...v", x, resolve(pp),
                      preferred_element_type=jnp.float32)


def perturbed_gather(pp: "PerturbedParam", tokens: jnp.ndarray
                     ) -> jnp.ndarray:
    """Embedding-table gather of (w + eps · z) rows: z is drawn ONLY for the
    gathered rows (row v, column j uses counter off[v] + j — the same bits
    the row has in the full-table stream), so the fused path never touches
    the [V, D] table beyond the rows the batch actually reads."""
    w, off = pp.w, jnp.asarray(pp.off)
    seed, eps = _scalar(pp.seed), _scalar(pp.eps)
    rows = jnp.take(w, tokens, axis=0).astype(jnp.float32)
    if off.ndim == 0:   # tagged leaf was already sliced — single-row table
        off_t = jnp.broadcast_to(off, tokens.shape)
    else:
        off_t = jnp.take(off, tokens, axis=0)
    d = w.shape[-1]
    idx = off_t[..., None] + jax.lax.broadcasted_iota(
        jnp.uint32, off_t.shape + (d,), off_t.ndim)
    z = gaussian_from_counter(idx, seed)
    return (rows + eps * z).astype(w.dtype)


# ---------------------------------------------------------------------------
# seeded_axpy
# ---------------------------------------------------------------------------

def seeded_axpy(w: jnp.ndarray, seed, scale,
                impl: Optional[str] = None) -> jnp.ndarray:
    """out = w + scale · z(seed); z is the counter-hash N(0,1) stream.

    `seed` is a uint32/int32 scalar (traced or static). All impls produce the
    SAME bits for the same (seed, shape) — backend-portable trajectories.
    """
    impl = impl or _default_impl()
    if impl == "xla":
        # identical stream, generated by fused XLA elementwise ops; z never
        # persists in HBM after fusion.
        return kref.seeded_axpy_ref(w, seed, scale)
    if impl in ("pallas", "pallas_interpret"):
        return seeded_axpy_pallas(w, jnp.asarray(seed).astype(jnp.uint32),
                                  scale,
                                  interpret=(impl == "pallas_interpret"))
    raise ValueError(f"unknown impl: {impl}")


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _xla_chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           causal: bool, window: Optional[int],
                           scale: float, blk_k: int = 512) -> jnp.ndarray:
    """Online-softmax attention scanning kv chunks — flash semantics in XLA.

    Never materializes [Sq, Skv]; peak transient is [B, H, Sq, blk_k].
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    blk_k = min(blk_k, skv)
    pad = (-skv) % blk_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_k = (skv + pad) // blk_k
    qf = q.astype(jnp.float32) * scale
    # fold GQA group into q's head dim: [B, Hkv, group, Sq, D]
    qg = qf.reshape(b, hkv, group, sq, d)
    kc = k.reshape(b, hkv, n_k, blk_k, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, n_k, blk_k, d).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(sq) + (skv - sq)

    def body(carry, inp):
        m, l, acc = carry
        ci, k_blk, v_blk = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_blk.astype(jnp.float32))
        k_pos = ci * blk_k + jnp.arange(blk_k)
        mask = k_pos[None, :] < skv
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_blk.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, group, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, group, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(n_k), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def _attn_layout_hints(q, k, v):
    """Pin the attention layout so GSPMD never splits the head_dim
    contraction (which would all-reduce the full S×S score matrix — the
    dominant pathology for head counts not divisible by the model axis).

    Heads divisible by the model axis → shard heads (classic TP attention).
    Otherwise → shard the QUERY sequence dim (sequence-parallel attention):
    scores come out [B, H, Sq/model, Skv] with k/v gathered — linear bytes
    instead of quadratic."""
    from repro.runtime.sharding import _HINT_MESH, hint
    mesh = _HINT_MESH.get()
    if mesh is None:
        return q, k, v
    model = mesh.shape.get("model", 1)
    hq, hkv, sq = q.shape[1], k.shape[1], q.shape[2]
    if hq % model == 0 and hkv % model == 0:
        q = hint(q, "client", "model", None, None)
        k = hint(k, "client", "model", None, None)
        v = hint(v, "client", "model", None, None)
    elif hq % model == 0:
        # pin q's head layout; let GSPMD place k/v freely (forcing them
        # replicated measurably regressed divisible-kv archs)
        q = hint(q, "client", "model", None, None)
    elif sq % model == 0 and sq > 1:
        # sequence-parallel branch: k/v MUST be pinned replicated-over-model
        # or GSPMD reshards them against the S-sharded q every layer
        q = hint(q, "client", None, "model", None)
        k = hint(k, "client", None, None, None)
        v = hint(v, "client", None, None, None)
    return q, k, v


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None,
              impl: Optional[str] = None) -> jnp.ndarray:
    """Fused attention. q: [B,Hq,Sq,D]; k,v: [B,Hkv,Skv,D] → [B,Hq,Sq,D]."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    impl = impl or _default_impl()
    q, k, v = _attn_layout_hints(q, k, v)
    if impl == "xla":
        if q.shape[2] * k.shape[2] <= 256 * 256:
            return kref.attention_ref(q, k, v, causal=causal, window=window,
                                      scale=scale)
        return _xla_chunked_attention(q, k, v, causal, window, scale)
    if impl == "xla_chunked":
        return _xla_chunked_attention(q, k, v, causal, window, scale)
    if impl == "xla_full":
        # materialized-softmax path: identical FLOPs to chunked/fused, no
        # inner scan — used by roofline probes (lower/compile only)
        return kref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale)
    if impl in ("pallas", "pallas_interpret"):
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      scale=scale,
                                      interpret=(impl == "pallas_interpret"))
    raise ValueError(f"unknown impl: {impl}")


# ---------------------------------------------------------------------------
# linear recurrence (RG-LRU)
# ---------------------------------------------------------------------------

def linear_recurrence(a: jnp.ndarray, x: jnp.ndarray,
                      h0: Optional[jnp.ndarray] = None,
                      impl: Optional[str] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h_t = a_t ⊙ h_{t-1} + x_t.  a, x: [B,S,D]; h0: [B,D]."""
    impl = impl or _default_impl()
    b, s, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), dtype=x.dtype)
    if impl == "xla":
        # log-depth associative scan — the production XLA path
        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, b1 * a2 + b2
        a32 = a.astype(jnp.float32)
        x32 = x.astype(jnp.float32)
        # fold h0 into the first input
        x32 = x32.at[:, 0].add(a32[:, 0] * h0.astype(jnp.float32))
        _, hs = jax.lax.associative_scan(combine, (a32, x32), axis=1)
        return hs.astype(x.dtype), hs[:, -1].astype(x.dtype)
    if impl in ("pallas", "pallas_interpret"):
        chunk = 256 if s % 256 == 0 else s
        return rglru_scan_pallas(a, x, h0, chunk=chunk,
                                 interpret=(impl == "pallas_interpret"))
    raise ValueError(f"unknown impl: {impl}")


# ---------------------------------------------------------------------------
# SSD (Mamba-2)
# ---------------------------------------------------------------------------

def ssd(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
        c: jnp.ndarray, state0: Optional[jnp.ndarray] = None,
        chunk: int = 128, impl: Optional[str] = None
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD. Shapes as kref.ssd_ref; state [B,H,P,N]."""
    impl = impl or _default_impl()
    B, S, H, P = x.shape
    N = b.shape[-1]
    if state0 is None:
        state0 = jnp.zeros((B, H, P, N), dtype=jnp.float32)
    if impl == "xla":
        return _xla_chunked_ssd(x, dt, a, b, c, state0, chunk)
    if impl in ("pallas", "pallas_interpret"):
        y, s_last = ssd_scan_pallas(
            x, dt, a, b, c, state0.swapaxes(-1, -2), chunk=chunk,
            interpret=(impl == "pallas_interpret"))
        return y, s_last.swapaxes(-1, -2)
    raise ValueError(f"unknown impl: {impl}")


def _xla_chunked_ssd(x, dt, a, b, c, state0, chunk):
    """Chunked SSD in pure XLA: scan over chunks, dense matmuls within."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, "pad S to a multiple of chunk"
    n_c = S // chunk
    xf = x.astype(jnp.float32).reshape(B, n_c, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(B, n_c, chunk, H)
    bf = b.astype(jnp.float32).reshape(B, n_c, chunk, N)
    cf = c.astype(jnp.float32).reshape(B, n_c, chunk, N)
    af = a.astype(jnp.float32)

    ii = jnp.arange(chunk)
    causal = ii[:, None] >= ii[None, :]

    def body(state, inp):
        x_c, dt_c, b_c, c_c = inp           # [B,Q,H,P],[B,Q,H],[B,Q,N]×2
        g = jnp.cumsum(af[None, None] * dt_c, axis=1)        # [B,Q,H]
        xdt = x_c * dt_c[..., None]
        cb = jnp.einsum("bqn,bkn->bqk", c_c, b_c)            # [B,Q,Q]
        decay = jnp.exp(g[:, :, None] - g[:, None])          # [B,Q,Q,H]
        l_mask = jnp.where(causal[None, :, :, None], decay, 0.0)
        y_intra = jnp.einsum("bqk,bqkh,bkhp->bqhp", cb, l_mask, xdt)
        y_inter = jnp.exp(g)[..., None] * jnp.einsum(
            "bhpn,bqn->bqhp", state, c_c)
        g_last = g[:, -1]                                     # [B,H]
        w = jnp.exp(g_last[:, None] - g)[..., None] * b_c[:, :, None]
        state = jnp.exp(g_last)[..., None, None] * state + jnp.einsum(
            "bqhn,bqhp->bhpn", w, xdt)
        return state, y_intra + y_inter

    xs = (xf.swapaxes(0, 1), dtf.swapaxes(0, 1), bf.swapaxes(0, 1),
          cf.swapaxes(0, 1))
    state_last, ys = jax.lax.scan(body, state0.astype(jnp.float32), xs)
    y = ys.swapaxes(0, 1).reshape(B, S, H, P).astype(x.dtype)
    return y, state_last


def ssd_decode_step(state: jnp.ndarray, x_t: jnp.ndarray, dt_t: jnp.ndarray,
                    a: jnp.ndarray, b_t: jnp.ndarray, c_t: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-token SSD update (decode). state: [B,H,P,N]; x_t: [B,H,P];
    dt_t: [B,H]; b_t/c_t: [B,N] → (y_t [B,H,P], state')."""
    decay = jnp.exp(a[None] * dt_t)                       # [B,H]
    upd = jnp.einsum("bhp,bn,bh->bhpn", x_t.astype(jnp.float32),
                     b_t.astype(jnp.float32), dt_t)
    state = decay[..., None, None] * state + upd
    y = jnp.einsum("bhpn,bn->bhp", state, c_t.astype(jnp.float32))
    return y.astype(x_t.dtype), state
