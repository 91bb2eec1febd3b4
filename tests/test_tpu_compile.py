"""The main-path Pallas kernels compile for a TPU v5e at OPT-125M widths.

Nothing runs: each case lowers and compiles a kernel for one chip of a
described (not attached) `v5e:2x2` topology, which is what the TPU
compiler refuses or accepts, and checks that the compiled program holds
the Mosaic kernel (`tpu_custom_call`). Interpret mode cannot catch a cast
or a block shape Mosaic has no lowering for; these do.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the worker that runs
this file keeps it until it exits. Keep every such compile in this file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.perturbed_matmul import perturbed_matmul_pallas
from repro.kernels.seeded_axpy import seeded_axpy_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's programs can be written to the persistent
        # cache but never read back: keep the cache out of these compiles
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", [(12, 768, 3072), (50272, 768), (768,)],
                         ids=["stacked_ffn", "embedding", "bias"])
def test_seeded_axpy_compiles_for_v5e(one_chip, shape):
    text = _compiled_text(
        lambda w, seed, scale: seeded_axpy_pallas(w, seed, scale),
        _spec(one_chip, shape), _spec(one_chip, (), jnp.uint32),
        _spec(one_chip, ()))
    assert "tpu_custom_call" in text


def _leaf_opcodes(text: str, shape) -> set:
    """Opcodes of the entry computation's instructions that hold a value
    of the leaf's size."""
    n = math.prod(shape)
    entry = text[text.index("\nENTRY"):]
    found = set()
    for m in re.finditer(r"%\S+ = (.*?) ([a-z][a-z-]*)\(", entry):
        dims = re.findall(r"\[([\d,]*)\]", m.group(1))
        if any(math.prod(int(d) for d in ds.split(",")) == n
               for ds in dims if ds):
            found.add(m.group(2))
    return found


@pytest.mark.parametrize("shape", [(8, 4096, 11008), (64000, 4096),
                                   (12, 768, 768), (12, 768), (50272, 768)],
                         ids=["yi_stacked_ffn", "yi_embedding",
                              "opt_stacked_attn", "opt_gains",
                              "opt_embedding"])
def test_seeded_axpy_updates_in_place_for_v5e(one_chip, shape):
    """With θ donated, the kernel reads and writes the leaf where it lies:
    nothing but bitcasts around the custom call, no copy, pad, slice or
    relayout of the leaf."""
    text = jax.jit(lambda w, seed, scale: seeded_axpy_pallas(w, seed, scale),
                   donate_argnums=0).lower(
        _spec(one_chip, shape), _spec(one_chip, (), jnp.uint32),
        _spec(one_chip, ())).compile().as_text()
    assert "tpu_custom_call" in text
    assert _leaf_opcodes(text, shape) - {"bitcast"} == {"parameter",
                                                        "custom-call"}


def _pmm_specs(one_chip, m, k, n):
    return (_spec(one_chip, (m, k)), _spec(one_chip, (k, n)),
            _spec(one_chip, (), jnp.uint32), _spec(one_chip, (), jnp.uint32))


@pytest.mark.parametrize("mkn", [(2560, 768, 3072), (2560, 3072, 768)],
                         ids=["up_proj", "down_proj"])
def test_perturbed_matmul_compiles_for_v5e(one_chip, mkn):
    text = _compiled_text(
        lambda x, w, seed, off, eps: perturbed_matmul_pallas(
            x, w, seed, off, eps),
        *_pmm_specs(one_chip, *mkn), _spec(one_chip, ()))
    assert "tpu_custom_call" in text


def test_perturbed_matmul_vmapped_over_eps_compiles_for_v5e(one_chip):
    """The fused dual forward batches both rollouts (eps = ±μ) under one
    vmap; the batched SMEM scalars must still satisfy Mosaic's tiling."""
    m, k, n = 2560, 768, 3072

    def both_rollouts(x, w, seed, off, eps):
        return jax.vmap(lambda xe, e: perturbed_matmul_pallas(
            xe, w, seed, off, e))(x, eps)

    x, w, seed, off = _pmm_specs(one_chip, m, k, n)
    text = _compiled_text(both_rollouts, _spec(one_chip, (2, m, k)), w,
                          seed, off, _spec(one_chip, (2,)))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    qkv = _spec(one_chip, (8, 12, 64, 128))
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def _entry_opcodes(text: str) -> set:
    entry = text[text.index("\nENTRY"):]
    return set(re.findall(r"%\S+ = .*? ([a-z][a-z-]*)\(", entry))


@pytest.mark.parametrize("q_shape,kv_shape",
                         [((40, 12, 64, 64), (40, 12, 64, 64)),
                          ((4, 12, 2048, 64), (4, 12, 2048, 64)),
                          ((8, 32, 512, 128), (8, 4, 512, 128))],
                         ids=["opt_sst2_k5", "opt_long2k", "yi_squad512"])
def test_attention_cells_compile_without_pad_for_v5e(one_chip, q_shape,
                                                     kv_shape):
    """`ops.attention` at the benchmark cells' shapes is the kernel alone:
    the head dim of 64 enters unpadded, so no pad or slice surrounds the
    custom call (inputs and output pinned to the default layout, as the
    kernel takes them)."""
    row_major = lambda n: Format(Layout(tuple(range(n))), one_chip)
    spec = lambda s: jax.ShapeDtypeStruct(s, jnp.float32,
                                          sharding=row_major(len(s)))
    text = jax.jit(
        lambda q, k, v: ops.attention(q, k, v, causal=True, impl="pallas"),
        out_shardings=row_major(4)).lower(
        spec(q_shape), spec(kv_shape), spec(kv_shape)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "flash_attention_pallas" in text
    assert not _entry_opcodes(text) & {"pad", "slice", "slice-start",
                                       "copy", "copy-start", "fusion"}
