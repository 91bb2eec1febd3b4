"""Roofline derivation from compiled dry-run artifacts.

Why probes: XLA's cost_analysis counts a `while` (lax.scan) body ONCE, not
×trip-count — so the full scanned program under-reports FLOPs by ~n_layers.
Instead we compile small per-block PROBE programs with the *same shardings
and activation shapes* as one trip of each scan, read their compiled
cost_analysis + collective bytes, and scale by the statically-known trip
counts. The full program remains the compile/memory deliverable; probes are
the FLOPs/bytes/collectives ledger — and a fast feedback loop for §Perf.

Probes deliberately use the materialized-attention path (`impl="xla_full"`):
its FLOPs equal the chunked/fused path (same matmuls, different order), and
it contains no inner scan to undercount.

Roofline terms (per assignment; TPU v5e constants):
    compute    = FLOPs_total  / (chips × 197e12)
    memory     = bytes_total  / (chips × 819e9)
    collective = coll_bytes   / (chips × 50e9)
FLOPs_total/bytes_total are global (per-device probe numbers × chips);
collective bytes are summed over every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operand in the per-device
program, × chips (a link-bytes proxy; per-op breakdown is recorded so the
dominant collective is attributable).
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ModelConfig, ShapeConfig, TPU_V5E,
                                HardwareSpec)
from repro.models import registry
from repro.runtime import sharding as shd

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> Tuple[float, Dict[str, float]]:
    """Sum output-shape bytes of every collective op in a per-device HLO."""
    total = 0.0
    by_op: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        nbytes = 0.0
        for sm in _SHAPE_RE.finditer(shape_str):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        total += nbytes
        by_op[op] = by_op.get(op, 0.0) + nbytes
    return total, by_op


def compiled_cost(compiled) -> Tuple[float, float]:
    ca = compiled.cost_analysis() or {}
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


# ---------------------------------------------------------------------------
# Probes
# ---------------------------------------------------------------------------

@dataclass
class Probe:
    name: str
    mult: float                      # occurrences per step
    fn: Callable                     # jittable
    args: tuple                      # ShapeDtypeStructs w/ shardings
    donate: tuple = ()


@dataclass
class ProbeCost:
    name: str
    mult: float
    flops: float                     # per device, one occurrence
    bytes_accessed: float
    coll_bytes: float
    coll_by_op: Dict[str, float]


def run_probe(probe: Probe, mesh=None,
              bf16_reduce: bool = False) -> ProbeCost:
    # input shardings ride on the ShapeDtypeStructs; the hints context lets
    # model-side `shd.hint(...)` constraints resolve during tracing
    from contextlib import nullcontext
    ctx = shd.hints(mesh, bf16_reduce) if mesh is not None else nullcontext()
    with ctx:
        lowered = jax.jit(probe.fn, donate_argnums=probe.donate).lower(
            *probe.args)
    compiled = lowered.compile()
    flops, bytes_a = compiled_cost(compiled)
    coll, by_op = collective_bytes(compiled.as_text())
    return ProbeCost(probe.name, probe.mult, flops, bytes_a, coll, by_op)


def _abstract(tree, mesh, sharding_tree):
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                             sharding=s),
        tree, sharding_tree)


def _strip_layer_dim(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), tree)


def _block_params_spec(mesh, blocks_like, serve: bool = False):
    """Shardings for one layer's params (leading L stripped)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(blocks_like)
    out = []
    for path, leaf in flat:
        stripped = jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
        out.append(NamedSharding(mesh, shd.param_spec(mesh, path, stripped,
                                                      serve)))
    return jax.tree_util.tree_unflatten(treedef, out)


def _act_sds(mesh, shape, dtype=jnp.bfloat16):
    cl = shd.client_axes(mesh)
    lead = cl if shape[0] % shd.axis_size(mesh, cl) == 0 else None
    spec = P(lead, *([None] * (len(shape) - 1)))
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _tok_sds(mesh, shape):
    cl = shd.client_axes(mesh)
    lead = cl if shape[0] % shd.axis_size(mesh, cl) == 0 else None
    spec = P(lead, *([None] * (len(shape) - 1)))
    return jax.ShapeDtypeStruct(shape, jnp.int32,
                                sharding=NamedSharding(mesh, spec))


def _layer_cache_abstract(mesh, cache_like):
    """Per-layer cache SDS (leading L stripped) with decode shardings."""
    def one(a):
        shape = a.shape[1:]
        ndim = len(shape)
        out = [None] * ndim
        cl = shd.client_axes(mesh)
        if shape[0] % shd.axis_size(mesh, cl) == 0:
            out[0] = cl
        if ndim >= 2:
            rest = list(range(1, ndim))
            big = max(rest, key=lambda i: shape[i])
            if shape[big] % shd.axis_size(mesh, "model") == 0:
                out[big] = "model"
        return jax.ShapeDtypeStruct(shape, a.dtype,
                                    sharding=NamedSharding(mesh, P(*out)))
    return jax.tree_util.tree_map(one, cache_like)


# -------------------------- family probe builders --------------------------

def build_probes(cfg: ModelConfig, shape: ShapeConfig, mesh,
                 dtype=jnp.bfloat16, n_perturb: int = 1,
                 fused_perturbation: bool = False) -> List[Probe]:
    """Per-block probe programs for one (arch, shape) cell.

    `fused_perturbation` mirrors PairZeroConfig.fused_perturbation: the
    fused dual forward regenerates z inside the layer kernels, so the
    per-round θ-sized axpy count drops from 3 (MeZO chain: +μz, −2μz,
    restore+update) to 1 (the update) — see `_axpy_probe`."""
    kind = shape.kind
    del kind
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return _transformer_probes(cfg, shape, mesh, dtype, n_perturb,
                                   fused_perturbation)
    if fam == "ssm":
        return _ssm_probes(cfg, shape, mesh, dtype, n_perturb,
                           fused_perturbation)
    if fam == "hybrid":
        return _hybrid_probes(cfg, shape, mesh, dtype, n_perturb,
                              fused_perturbation)
    if fam == "audio":
        return _encdec_probes(cfg, shape, mesh, dtype, n_perturb,
                              fused_perturbation)
    raise ValueError(fam)


def _fwd_mult(kind: str, n_perturb: int) -> float:
    """Forward-pass multiplicity: ZO train = 2 forwards × n_perturb."""
    return 2.0 * n_perturb if kind == "train" else 1.0


def _transformer_probes(cfg, shape, mesh, dtype, n_perturb, fused=False):
    from repro.models import transformer as T
    from repro.models import layers as L

    b_tot = shape.global_batch
    s = shape.seq_len
    if cfg.frontend.kind == "vision" and shape.kind != "decode":
        s = s + cfg.frontend.n_frontend_tokens
    abs_params = registry.abstract_params(cfg, dtype)
    blk_like = _strip_layer_dim(abs_params["blocks"])
    blk_sds = _abstract(blk_like, mesh, _block_params_spec(
        mesh, abs_params["blocks"], serve=shape.kind == "decode"))
    # probe config: no inner scans (moe single dispatch group)
    pcfg = cfg
    if cfg.moe.enabled:
        pcfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, chunk=0))

    probes = []
    fm = _fwd_mult(shape.kind, n_perturb)

    if shape.kind in ("train", "prefill"):
        x_sds = _act_sds(mesh, (b_tot, s, cfg.d_model), dtype)
        positions = np.arange(s)

        def block_fn(bp, x):
            y, _ = T._block_apply(bp, x, jnp.asarray(positions), pcfg,
                                  cache=None, cache_pos=None,
                                  impl="xla_full")
            return y

        probes.append(Probe("block", fm * cfg.n_layers, block_fn,
                            (blk_sds, x_sds)))

        head_parts = {k: abs_params[k] for k in
                      ("embed", "final_norm") if k in abs_params}
        if "lm_head" in abs_params:
            head_parts["lm_head"] = abs_params["lm_head"]
        head_sds = _abstract(head_parts, mesh,
                             shd.params_sharding(mesh, head_parts))
        tok_sds = _tok_sds(mesh, (b_tot, shape.seq_len))

        def head_fn(hp, tokens, targets):
            x = L.embed(hp["embed"], tokens)
            xn = L.rmsnorm(hp["final_norm"], x, cfg.norm_eps)
            logits = L.unembed(hp.get("lm_head", hp["embed"]), xn)
            return jnp.mean(L.cross_entropy(
                logits, targets, jnp.ones_like(targets, jnp.float32)))

        probes.append(Probe("embed_head", fm, head_fn,
                            (head_sds, tok_sds, tok_sds)))
    else:  # decode
        x_sds = _act_sds(mesh, (b_tot, 1, cfg.d_model), dtype)
        cache_like = registry.serve_cache_shapes(cfg, b_tot, shape.seq_len,
                                                 dtype)
        layer_cache = _layer_cache_abstract(mesh, cache_like)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

        def block_fn(bp, lc, x, pos):
            y, nc = T._block_apply(bp, x, pos + jnp.arange(1), pcfg,
                                   cache=lc, cache_pos=pos, impl="xla_full")
            return y, nc

        probes.append(Probe("block_decode", float(cfg.n_layers), block_fn,
                            (blk_sds, layer_cache, x_sds, pos_sds),
                            donate=(1,)))

        head_parts = {k: abs_params[k] for k in
                      ("embed", "final_norm") if k in abs_params}
        if "lm_head" in abs_params:
            head_parts["lm_head"] = abs_params["lm_head"]
        head_sds = _abstract(head_parts, mesh,
                             shd.params_sharding(mesh, head_parts))
        tok_sds = _tok_sds(mesh, (b_tot, 1))

        def head_fn(hp, tokens):
            x = L.embed(hp["embed"], tokens)
            xn = L.rmsnorm(hp["final_norm"], x, cfg.norm_eps)
            return L.unembed(hp.get("lm_head", hp["embed"]), xn)

        probes.append(Probe("embed_head", 1.0, head_fn,
                            (head_sds, tok_sds)))
    if shape.kind == "train":
        probes.append(_axpy_probe(cfg, mesh, dtype, n_perturb, fused))
    return probes


def _axpy_probe(cfg, mesh, dtype, n_perturb, fused=False):
    """ZO perturb/update axpys: 3 per perturbation (MeZO chain), or 1 when
    the fused dual forward is on (z regenerated inside the layer kernels;
    only the model update touches θ).

    Probed on a representative stacked weight (bytes dominate; flops are
    the Box–Muller transcendentals)."""
    from repro.kernels import ops as kops
    n_params = registry.count_params(cfg)
    cl = shd.client_axes(mesh)
    row_q = shd.axis_size(mesh, cl)
    cols = 1024
    rows = -(-n_params // cols)
    rows = -(-rows // row_q) * row_q          # round up to divisibility
    rep = jax.ShapeDtypeStruct(
        (rows, cols), dtype,
        sharding=NamedSharding(mesh, P(cl, "model")))
    seed_sds = jax.ShapeDtypeStruct((), jnp.uint32)

    def fn(w, seed):
        return kops.seeded_axpy(w, seed, 1e-3, impl="xla")

    # one probe covers ~all params; 3 axpys per perturbation round in the
    # chained walk, 1 (the update) when perturbation is fused into kernels
    return Probe("zo_axpy", (1.0 if fused else 3.0) * n_perturb, fn,
                 (rep, seed_sds), donate=(0,))


def _ssm_probes(cfg, shape, mesh, dtype, n_perturb, fused=False):
    from repro.models import ssm as S
    from repro.models import layers as L

    b_tot = shape.global_batch
    s = shape.seq_len
    abs_params = registry.abstract_params(cfg, dtype)
    blk_like = _strip_layer_dim(abs_params["blocks"])
    blk_sds = _abstract(blk_like, mesh,
                        _block_params_spec(mesh, abs_params["blocks"]))
    probes = []
    fm = _fwd_mult(shape.kind, n_perturb)

    if shape.kind in ("train", "prefill"):
        chunk = min(cfg.ssm.chunk, s)
        n_chunks = s // chunk if s % chunk == 0 else 1
        if s % chunk != 0:
            chunk = s
        x_sds = _act_sds(mesh, (b_tot, chunk, cfg.d_model), dtype)

        def block_fn(bp, x):
            y, _ = S._block_apply(bp, x, cfg, state=None, impl="xla")
            return y

        probes.append(Probe("block", fm * cfg.n_layers * n_chunks,
                            block_fn, (blk_sds, x_sds)))
        probes.append(_lm_head_probe(cfg, shape, mesh, dtype, fm,
                                     abs_params))
    else:
        x_sds = _act_sds(mesh, (b_tot, 1, cfg.d_model), dtype)
        state_like = registry.serve_cache_shapes(cfg, b_tot, shape.seq_len,
                                                 dtype)
        layer_state = _layer_cache_abstract(mesh, state_like)

        def block_fn(bp, st, x):
            y, ns = S._block_apply(bp, x, cfg, state=st, impl="xla")
            return y, ns

        probes.append(Probe("block_decode", float(cfg.n_layers), block_fn,
                            (blk_sds, layer_state, x_sds), donate=(1,)))
        probes.append(_lm_head_probe(cfg, shape, mesh, dtype, 1.0,
                                     abs_params, decode=True))
    if shape.kind == "train":
        probes.append(_axpy_probe(cfg, mesh, dtype, n_perturb, fused))
    return probes


def _lm_head_probe(cfg, shape, mesh, dtype, mult, abs_params, decode=False,
                   embed_key="embed", norm_key="final_norm"):
    from repro.models import layers as L
    b_tot = shape.global_batch
    s = 1 if decode else shape.seq_len
    head_parts = {embed_key: abs_params[embed_key],
                  norm_key: abs_params[norm_key]}
    if "lm_head" in abs_params:
        head_parts["lm_head"] = abs_params["lm_head"]
    head_sds = _abstract(head_parts, mesh,
                         shd.params_sharding(mesh, head_parts))
    tok_sds = _tok_sds(mesh, (b_tot, s))

    def head_fn(hp, tokens, targets):
        x = L.embed(hp[embed_key], tokens)
        xn = L.rmsnorm(hp[norm_key], x, cfg.norm_eps)
        logits = L.unembed(hp.get("lm_head", hp[embed_key]), xn)
        return jnp.mean(L.cross_entropy(
            logits, targets, jnp.ones_like(targets, jnp.float32)))

    return Probe("embed_head", mult, head_fn, (head_sds, tok_sds, tok_sds))


def _hybrid_probes(cfg, shape, mesh, dtype, n_perturb, fused=False):
    from repro.models import hybrid as H

    b_tot = shape.global_batch
    s = shape.seq_len
    abs_params = registry.abstract_params(cfg, dtype)
    n_groups = abs_params["groups"]["a"]["norm"]["g"].shape[0]
    n_tail = len(abs_params["tail"])
    r_like = _strip_layer_dim(abs_params["groups"]["r1"])
    r_sds = _abstract(r_like, mesh,
                      _block_params_spec(mesh, abs_params["groups"]["r1"]))
    a_like = _strip_layer_dim(abs_params["groups"]["a"])
    a_sds = _abstract(a_like, mesh,
                      _block_params_spec(mesh, abs_params["groups"]["a"]))
    probes = []
    fm = _fwd_mult(shape.kind, n_perturb)

    if shape.kind in ("train", "prefill"):
        x_sds = _act_sds(mesh, (b_tot, s, cfg.d_model), dtype)
        positions = np.arange(s)

        def r_fn(bp, x):
            y, _ = H._rglru_block_apply(bp, x, cfg, impl="xla")
            return y

        def a_fn(bp, x):
            y, _ = H._attn_block_apply(bp, x, jnp.asarray(positions), cfg,
                                       impl="xla_full")
            return y

        probes.append(Probe("rglru_block", fm * (2 * n_groups + n_tail),
                            r_fn, (r_sds, x_sds)))
        probes.append(Probe("attn_block", fm * n_groups, a_fn,
                            (a_sds, x_sds)))
        probes.append(_lm_head_probe(cfg, shape, mesh, dtype, fm,
                                     abs_params))
    else:
        x_sds = _act_sds(mesh, (b_tot, 1, cfg.d_model), dtype)
        state_like = registry.serve_cache_shapes(cfg, b_tot, shape.seq_len,
                                                 dtype)
        r_state = _layer_cache_abstract(mesh, {
            "lru": state_like["r1"]["lru"], "conv": state_like["r1"]["conv"]})
        kv_state = _layer_cache_abstract(mesh, state_like["attn"])
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

        def r_fn(bp, st, x):
            y, ns = H._rglru_block_apply(bp, x, cfg, state=st, impl="xla")
            return y, ns

        def a_fn(bp, kv, x, pos):
            return H._attn_rolling(bp, x, pos + jnp.arange(1), cfg, kv, pos)

        probes.append(Probe("rglru_decode", float(2 * n_groups + n_tail),
                            r_fn, (r_sds, r_state, x_sds), donate=(1,)))
        probes.append(Probe("attn_decode", float(n_groups), a_fn,
                            (a_sds, kv_state, x_sds, pos_sds), donate=(1,)))
        probes.append(_lm_head_probe(cfg, shape, mesh, dtype, 1.0,
                                     abs_params, decode=True))
    if shape.kind == "train":
        probes.append(_axpy_probe(cfg, mesh, dtype, n_perturb, fused))
    return probes


def _encdec_probes(cfg, shape, mesh, dtype, n_perturb, fused=False):
    from repro.models import encdec as E
    from repro.models import layers as L

    b_tot = shape.global_batch
    s = shape.seq_len
    n_frames = cfg.frontend.n_frontend_tokens
    abs_params = registry.abstract_params(cfg, dtype)
    enc_like = _strip_layer_dim(abs_params["enc_blocks"])
    enc_sds = _abstract(enc_like, mesh,
                        _block_params_spec(mesh, abs_params["enc_blocks"]))
    dec_like = _strip_layer_dim(abs_params["dec_blocks"])
    dec_sds = _abstract(dec_like, mesh,
                        _block_params_spec(mesh, abs_params["dec_blocks"]))
    n_enc = cfg.n_encoder_layers or cfg.n_layers
    probes = []
    fm = _fwd_mult(shape.kind, n_perturb)

    frames_sds = _act_sds(mesh, (b_tot, n_frames, cfg.d_model), dtype)

    if shape.kind in ("train", "prefill"):
        x_sds = _act_sds(mesh, (b_tot, s, cfg.d_model), dtype)
        positions_e = np.arange(n_frames)
        positions_d = np.arange(s)

        def enc_fn(bp, x):
            hn = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
            a, _ = L.gqa_attend(bp["attn"], hn, jnp.asarray(positions_e),
                                cfg, causal=False, impl="xla_full")
            h = x + a
            return h + L.mlp(bp["mlp"],
                             L.rmsnorm(bp["ln2"], h, cfg.norm_eps))

        def dec_fn(bp, x, enc_out):
            y, _ = E._dec_block_apply(bp, x, enc_out,
                                      jnp.asarray(positions_d), cfg,
                                      impl="xla_full")
            return y

        probes.append(Probe("enc_block", fm * n_enc, enc_fn,
                            (enc_sds, frames_sds)))
        probes.append(Probe("dec_block", fm * cfg.n_layers, dec_fn,
                            (dec_sds, x_sds, frames_sds)))
        probes.append(_lm_head_probe(cfg, shape, mesh, dtype, fm,
                                     abs_params, embed_key="dec_embed",
                                     norm_key="dec_norm"))
    else:
        x_sds = _act_sds(mesh, (b_tot, 1, cfg.d_model), dtype)
        cache_like = registry.serve_cache_shapes(cfg, b_tot, shape.seq_len,
                                                 dtype)
        layer_cache = _layer_cache_abstract(mesh, cache_like)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim()

        def dec_fn(bp, lc, x, pos):
            b = x.shape[0]
            s_ = 1
            hn = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
            q = L.dense({"w": bp["self_attn"]["wq"]}, hn).reshape(
                b, s_, hq, hd)
            k = L.dense({"w": bp["self_attn"]["wk"]}, hn).reshape(
                b, s_, hkv, hd)
            v = L.dense({"w": bp["self_attn"]["wv"]}, hn).reshape(
                b, s_, hkv, hd)
            sk = jax.lax.dynamic_update_slice(
                lc["self_k"], k.astype(lc["self_k"].dtype),
                (0, pos, 0, 0))
            sv = jax.lax.dynamic_update_slice(
                lc["self_v"], v.astype(lc["self_v"].dtype),
                (0, pos, 0, 0))
            a = L.decode_attend(q, sk, sv, pos + jnp.arange(s_))
            h = x + L.dense({"w": bp["self_attn"]["wo"]},
                            a.reshape(b, s_, hq * hd))
            hx = L.rmsnorm(bp["ln_x"], h, cfg.norm_eps)
            qx = L.dense({"w": bp["cross_attn"]["wq"]}, hx).reshape(
                b, s_, hq, hd)
            ax = L.decode_attend(qx, lc["cross_k"], lc["cross_v"],
                                 jnp.full((s_,), n_frames - 1))
            h = h + L.dense({"w": bp["cross_attn"]["wo"]},
                            ax.reshape(b, s_, hq * hd))
            h = h + L.mlp(bp["mlp"], L.rmsnorm(bp["ln2"], h, cfg.norm_eps))
            return h, {"self_k": sk, "self_v": sv}

        probes.append(Probe("dec_block_decode", float(cfg.n_layers), dec_fn,
                            (dec_sds, layer_cache, x_sds, pos_sds),
                            donate=(1,)))
        probes.append(_lm_head_probe(cfg, shape, mesh, dtype, 1.0,
                                     abs_params, decode=True,
                                     embed_key="dec_embed",
                                     norm_key="dec_norm"))
    if shape.kind == "train":
        probes.append(_axpy_probe(cfg, mesh, dtype, n_perturb, fused))
    return probes


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------

@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_total: float               # global
    bytes_total: float
    coll_bytes_total: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float               # 6·N·D convention
    useful_ratio: float              # MODEL_FLOPS / HLO_FLOPs
    probe_costs: List[Dict]
    coll_by_op: Dict[str, float]

    def to_dict(self):
        return dataclasses.asdict(self)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N_active·D (train) / 2·N·D (prefill) / 2·N·B (decode, per step)."""
    n_act = registry.count_params(cfg, active_only=True)
    if shape.kind == "train":
        return 6.0 * n_act * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len
    return 2.0 * n_act * shape.global_batch


def aggregate(arch: str, shape: ShapeConfig, mesh_name: str, chips: int,
              costs: List[ProbeCost], cfg: ModelConfig,
              hw: HardwareSpec = TPU_V5E,
              extra_coll_bytes: float = 0.0) -> RooflineReport:
    flops_dev = sum(c.flops * c.mult for c in costs)
    bytes_dev = sum(c.bytes_accessed * c.mult for c in costs)
    coll_dev = sum(c.coll_bytes * c.mult for c in costs) + extra_coll_bytes
    by_op: Dict[str, float] = {}
    for c in costs:
        for op, v in c.coll_by_op.items():
            by_op[op] = by_op.get(op, 0.0) + v * c.mult * chips

    flops_total = flops_dev * chips
    bytes_total = bytes_dev * chips
    coll_total = coll_dev * chips
    compute_s = flops_total / (chips * hw.peak_flops)
    memory_s = bytes_total / (chips * hw.hbm_bw)
    collective_s = coll_total / (chips * hw.ici_bw)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        flops_total=flops_total, bytes_total=bytes_total,
        coll_bytes_total=coll_total, compute_s=compute_s,
        memory_s=memory_s, collective_s=collective_s, dominant=dominant,
        model_flops=mf,
        useful_ratio=mf / flops_total if flops_total else 0.0,
        probe_costs=[dataclasses.asdict(c) for c in costs],
        coll_by_op=by_op)
