"""The dense decoder: pre-norm RMSNorm blocks with rotary attention over
grouped KV heads, a SwiGLU feed-forward, an embedding, a final norm and an
untied LM head; the program's `dense` family (opt-125m, yi-6b).

A model family is one file of bench/families/, found by the `family` of a
configuration file. It provides:

  dims(config) -> m            the sizes read from the configuration file;
                               `m` holds "vocab", and the loader adds
                               m["family"]
  layout(m)                    {path: (shape, scale)}: the program's
                               parameter tree, in its flatten order; scale 0
                               marks a norm gain (ones), else the leaf is
                               scale times N(0, 1)
  make_loss(m, leaf_paths, dtype, precision)
                               loss(θ_leaves, seeds, eps, tokens, targets,
                               mask) → [K]: the plain per-client loss at
                               θ + eps z, each leaf perturbed through
                               reference.perturbed with its stream seed
  round_flops(m, traffic)      model FLOPs of one round (forward only)
  attention_flops(m, traffic)  the attention kernel's FLOPs in one round
  attention_bytes(m, traffic)  its q/k/v/o bytes in one round

The round around the loss (z stream, channel, decode, update), the weights'
stream and the θ-update bytes are shared (reference.py, weights.py,
counts.py). A new task, one not in traffic_gen.SAMPLERS or counts.SCORED,
is shared code.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import counts
from reference import HIGHEST, mm, perturbed, rmsnorm, rope


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the benchmark reads from a configuration file."""
    a = config["as_run"]
    d = a["hidden_size"]
    heads = a["num_attention_heads"]
    return {
        "layers": a["num_hidden_layers"], "d": d, "heads": heads,
        "kv_heads": a.get("num_key_value_heads", heads),
        "head_dim": a.get("head_dim", d // heads),
        "d_ff": a.get("intermediate_size", a.get("ffn_dim")),
        "vocab": a["vocab_size"], "rope_theta": float(a["rope_theta"]),
        "norm_eps": float(a["rms_norm_eps"]),
    }


def layout(m: Dict[str, Any]) -> Dict[str, Any]:
    """{path: (shape, scale)} of every leaf: scan-stacked blocks with a
    leading layer dim; norms at 1, embedding and head at N(0, 0.02^2),
    projections at N(0, 1/fan_in)."""
    L, d, hd = m["layers"], m["d"], m["head_dim"]
    q, kv, ff, v = m["heads"] * hd, m["kv_heads"] * hd, m["d_ff"], m["vocab"]
    lin = lambda d_in, d_out: ((L, d_in, d_out), 1.0 / math.sqrt(d_in))
    return {
        "blocks": {
            "attn": {"wq": lin(d, q), "wk": lin(d, kv), "wv": lin(d, kv),
                     "wo": lin(q, d)},
            "ln1": {"g": ((L, d), 0.0)}, "ln2": {"g": ((L, d), 0.0)},
            "mlp": {"wi": lin(d, ff), "wg": lin(d, ff), "wd": lin(ff, d)},
        },
        "embed": {"w": ((v, d), 0.02)},
        "final_norm": {"g": ((d,), 0.0)},
        "lm_head": {"w": ((v, d), 0.02)},
    }


def make_loss(m: Dict[str, Any], leaf_paths: Sequence[str],
              dtype=jnp.float32, precision=HIGHEST):
    """loss(θ_leaves, seeds, eps, tokens, targets, mask) → [K] at θ + eps z,
    masked mean cross-entropy per row averaged over each client's rows.

    θ_leaves are in flatten order; seeds are the per-leaf stream seeds.
    Perturbed weights are formed one layer at a time inside the layer
    scan, so the reference holds θ plus one layer's transient."""
    idx = {p: i for i, p in enumerate(leaf_paths)}
    hd, hq, hkv = m["head_dim"], m["heads"], m["kv_heads"]
    eps_norm, theta = m["norm_eps"], m["rope_theta"]
    act = dtype

    def loss(leaves, seeds, eps, tokens, targets, mask):
        k, b, s = tokens.shape
        tok = tokens.reshape(k * b, s)
        emb = perturbed(leaves[idx["embed/w"]], seeds[idx["embed/w"]], eps,
                        dtype)
        x = jnp.take(emb, tok, axis=0).astype(act)
        block_names = [p for p in leaf_paths if p.startswith("blocks/")]
        stacked = [leaves[idx[p]] for p in block_names]
        layer_ids = jnp.arange(stacked[0].shape[0], dtype=jnp.uint32)

        def layer(h, xs):
            li, ws = xs
            w = {}
            for name, wl in zip(block_names, ws):
                stride = int(np.prod(wl.shape))
                w[name] = perturbed(wl, seeds[idx[name]], eps, dtype,
                                    li * jnp.uint32(stride & 0xFFFFFFFF))
            a = rmsnorm(h, w["blocks/ln1/g"], eps_norm).astype(act)
            q = mm(a, w["blocks/attn/wq"], "bsd,df->bsf", dtype, precision)
            kk = mm(a, w["blocks/attn/wk"], "bsd,df->bsf", dtype, precision)
            v = mm(a, w["blocks/attn/wv"], "bsd,df->bsf", dtype, precision)
            q = q.astype(act).reshape(k * b, s, hq, hd).swapaxes(1, 2)
            kk = kk.astype(act).reshape(k * b, s, hkv, hd).swapaxes(1, 2)
            v = v.astype(act).reshape(k * b, s, hkv, hd).swapaxes(1, 2)
            q = rope(q.astype(jnp.float32), theta).astype(act)
            kk = rope(kk.astype(jnp.float32), theta).astype(act)
            group = hq // hkv
            kk = jnp.repeat(kk, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, kk, precision=precision,
                            preferred_element_type=jnp.float32) / math.sqrt(hd)
            causal = jnp.tril(jnp.ones((s, s), bool))
            sc = jnp.where(causal[None, None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", pr.astype(act), v,
                           precision=precision,
                           preferred_element_type=jnp.float32)
            o = o.astype(act).swapaxes(1, 2).reshape(k * b, s, hq * hd)
            h = h + mm(o, w["blocks/attn/wo"], "bsf,fd->bsd", dtype,
                       precision).astype(act)
            a = rmsnorm(h, w["blocks/ln2/g"], eps_norm).astype(act)
            gate = mm(a, w["blocks/mlp/wg"], "bsd,df->bsf", dtype, precision)
            up = mm(a, w["blocks/mlp/wi"], "bsd,df->bsf", dtype, precision)
            f = (jax.nn.silu(gate.astype(act).astype(jnp.float32))
                 * up.astype(act).astype(jnp.float32)).astype(act)
            h = h + mm(f, w["blocks/mlp/wd"], "bsf,fd->bsd", dtype,
                       precision).astype(act)
            return h, None

        x, _ = jax.lax.scan(layer, x, (layer_ids, stacked))
        fn = perturbed(leaves[idx["final_norm/g"]],
                       seeds[idx["final_norm/g"]], eps, dtype)
        x = rmsnorm(x, fn, eps_norm).astype(act)
        head = perturbed(leaves[idx["lm_head/w"]], seeds[idx["lm_head/w"]],
                         eps, dtype)
        logits = mm(x, head, "bsd,vd->bsv", dtype, precision)
        tgt_logit = jnp.take_along_axis(
            logits, targets.reshape(k * b, s)[..., None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - tgt_logit
        msk = mask.reshape(k * b, s)
        row = jnp.sum(nll * msk, -1) / jnp.maximum(jnp.sum(msk, -1), 1.0)
        return jnp.mean(row.reshape(k, b), axis=-1)

    return loss


# --- counts from shapes (see counts.py for what is and is not counted) -------

def attention_flops_per_seq(m: Dict[str, Any], s: int) -> float:
    """Causal QKᵀ and PV over the S(S+1)/2 visible pairs, all layers."""
    return m["layers"] * m["heads"] * 2.0 * 2.0 * m["head_dim"] \
        * s * (s + 1) / 2.0


def round_flops(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    seqs, s, rollouts = counts.round_shape(traffic)
    d, hd = m["d"], m["head_dim"]
    q, kv = m["heads"] * hd, m["kv_heads"] * hd
    per_token = 2.0 * m["layers"] * (d * q + 2 * d * kv + q * d
                                     + 3 * d * m["d_ff"])
    scored = counts.SCORED[traffic["task"]]
    scored = s if scored is None else scored
    head = 2.0 * d * m["vocab"] * scored
    per_seq = per_token * s + attention_flops_per_seq(m, s) + head
    return rollouts * seqs * per_seq


def attention_flops(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    seqs, s, rollouts = counts.round_shape(traffic)
    return rollouts * seqs * attention_flops_per_seq(m, s)


def attention_bytes(m: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """q, k, v read and o written once, float32, at the model's head dim."""
    seqs, s, rollouts = counts.round_shape(traffic)
    per_layer = (2 * m["heads"] + 2 * m["kv_heads"]) * s * m["head_dim"] \
        * counts.F32
    return rollouts * seqs * m["layers"] * per_layer
