"""Random weights for a dense decoder, made on the device from --seed.

The benchmark makes the weights (not the program), so that the reference
can be given the same weights without taking anything the program made.
Each leaf is the counter-hash Gaussian stream of bench/reference.py under
a seed of its own: a pure function of (seed, leaf, element index), cheap on
the chip and the same under any sharding.
The layout is the dense decoder's parameter tree: scan-stacked blocks with
a leading layer dim, an embedding, a final norm and an untied LM head.
Scales follow the usual decoder init: norms at 1, embedding and head at
N(0, 0.02^2), projections at N(0, 1/fan_in).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

import reference

# keeps the weight stream apart from every other use of the seed
_WEIGHTS_TAG = 0x3E1647


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
    """{path: (shape, scale)} of every leaf; scale 0 marks a norm gain."""
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


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def shapes(m: Dict[str, Any]) -> Any:
    """ShapeDtypeStruct tree (float32) of the layout."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s[0], jnp.float32), layout(m),
        is_leaf=_is_spec)


def leaf_init(base, index: int, shape: Tuple[int, ...],
              scale: float) -> jnp.ndarray:
    """One leaf: a norm gain of ones, or scale times the counter-hash
    N(0, 1) stream of leaf `index` under the weight key `base`."""
    if scale == 0.0:
        return jnp.ones(shape, jnp.float32)
    seed = reference.fmix32(base * jnp.uint32(reference._GOLDEN)
                            + jnp.uint32(index))
    return reference.z_block(shape, seed) * jnp.float32(scale)


def base_key(seed: int) -> jnp.ndarray:
    """The weight stream's uint32 key from --seed, hashed on the host so
    that any whole number works and the programs that take it compile once
    for every seed."""
    x = (int(seed) ^ _WEIGHTS_TAG) & 0xFFFFFFFFFFFFFFFF
    x = (x ^ (x >> 33)) * 0xFF51AFD7ED558CCD & 0xFFFFFFFFFFFFFFFF
    x = (x ^ (x >> 33)) * 0xC4CEB9FE1A85EC53 & 0xFFFFFFFFFFFFFFFF
    return jnp.uint32((x ^ (x >> 33)) & 0xFFFFFFFF)


def _leaves(m: Dict[str, Any]):
    flat, treedef = jax.tree_util.tree_flatten(layout(m), is_leaf=_is_spec)
    return flat, treedef


def make(seed: int, m: Dict[str, Any], shardings=None) -> Any:
    """All weights in one jitted call; `shardings` places them (a tree of
    shardings, e.g. the mesh's FSDP layout) as they are made."""
    flat, treedef = _leaves(m)

    def init(base):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf_init(base, i, shape, scale)
            for i, (shape, scale) in enumerate(flat)])

    return jax.jit(init, out_shardings=shardings)(base_key(seed))


def leaf(base, m: Dict[str, Any], index: int) -> jnp.ndarray:
    """Leaf `index` (flatten order) of the weights of key `base`; traceable
    inside another jitted computation."""
    shape, scale = _leaves(m)[0][index]
    return leaf_init(base, index, shape, scale)
