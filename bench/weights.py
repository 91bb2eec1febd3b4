"""Random weights, made on the device from --seed.

The benchmark makes the weights (not the program), so that the reference
can be given the same weights without taking anything the program made.
Each leaf is the counter-hash Gaussian stream of bench/reference.py under
a seed of its own: a pure function of (seed, leaf, element index), cheap on
the chip and the same under any sharding.
The sizes and the layout (the program's parameter tree, with each leaf's
init scale) are the model family's (bench/families/<family>.py); the
stream, the init of a leaf and the making of the whole tree are shared.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

import cell
import reference

# keeps the weight stream apart from every other use of the seed
_WEIGHTS_TAG = 0x3E1647


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the benchmark reads from a configuration file, as its
    family reads them, with the family's name under "family"."""
    name = config["family"]
    m = cell.family(name).dims(config)
    m["family"] = name
    return m


def layout(m: Dict[str, Any]) -> Dict[str, Any]:
    """{path: (shape, scale)} of every leaf, in the family's tree; scale 0
    marks a norm gain."""
    return cell.family(m["family"]).layout(m)


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
