"""Sharding rules + roofline machinery unit tests (host-side, no devices)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.roofline import collective_bytes
from repro.models import registry
from repro.runtime import sharding as shd


@pytest.fixture
def mesh():
    # 1-device "production-shaped" mesh: axis names real, sizes 1 — lets the
    # spec logic run on CPU without fake-device flags
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _spec_for(mesh, tree, leaf_path):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if names[-2:] == leaf_path or names[-1:] == leaf_path:
            return shd.param_spec(mesh, path, leaf)
    raise KeyError(leaf_path)


def test_param_specs_follow_roles(mesh):
    cfg = registry.get_arch("yi-6b")
    params = registry.abstract_params(cfg, jnp.bfloat16)
    # col-parallel: [L, d_in, d_out] → (None, fsdp, tp)
    assert _spec_for(mesh, params, ["wq"]) == P(None, ("data",), "model")
    # row-parallel: wo → (None, tp, fsdp)
    assert _spec_for(mesh, params, ["wo"]) == P(None, "model", ("data",))
    # embeddings: (tp on vocab, fsdp on d)
    assert _spec_for(mesh, params, ["embed", "w"]) == P("model", ("data",))
    # norms replicated
    assert _spec_for(mesh, params, ["final_norm", "g"]) == P(None)


def test_moe_down_projection_is_col_parallel(mesh):
    """§Perf iteration 2: we_d must be (E, F→fsdp, D→tp) — a TP-sharded F
    contraction would psum the k·cf× larger pre-combine tensor."""
    cfg = registry.get_arch("deepseek-v2-236b")
    params = registry.abstract_params(cfg, jnp.bfloat16)
    # stacked [L, E, F, D]: last-two dims carry the roles
    assert _spec_for(mesh, params, ["we_d"]) == P(None, None, ("data",),
                                                  "model")
    assert _spec_for(mesh, params, ["we_i"]) == P(None, None, ("data",),
                                                  "model")


def test_divisibility_guard(mesh):
    # vocab 73448 not divisible by 1? always divisible by 1 — use a spec
    # helper directly with a fake axis size via _maybe logic
    assert shd._maybe(mesh, "model", 10) == "model"  # size 1 divides all


def test_batch_sharding_client_axis(mesh):
    batch = {"tokens": jax.ShapeDtypeStruct((4, 8, 16), jnp.int32)}
    s = shd.batch_sharding(mesh, batch)["tokens"]
    assert s.spec == P(("data",), None, None)


def test_cache_sharding_longest_dim(mesh):
    cache = {"k": jax.ShapeDtypeStruct((4, 8, 1024, 2, 64), jnp.bfloat16)}
    s = shd.cache_sharding(mesh, cache)["k"]
    # layer dim None, batch over clients, longest (seq=1024) over model
    assert s.spec == P(None, ("data",), "model", None, None)


def test_hint_noop_outside_context():
    x = jnp.ones((4, 4))
    assert shd.hint(x, "client", "model") is x


def test_hint_applies_in_context(mesh):
    x = jnp.ones((4, 4))
    with shd.hints(mesh):
        y = jax.jit(lambda a: shd.hint(a, "client", "model"))(x)
    assert (y == x).all()


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------

def test_collective_bytes_parser():
    hlo = """
  %ag = f32[16,1024]{1,0} all-gather(f32[1,1024]{1,0} %x), dimensions={0}
  %ar = bf16[8,8]{1,0} all-reduce(bf16[8,8]{1,0} %y), to_apply=%add
  %cp = f32[4]{0} collective-permute(f32[4]{0} %z), source_target_pairs={{0,1}}
  %dot = f32[128,128]{1,0} dot(f32[128,64]{1,0} %a, f32[64,128]{1,0} %b)
"""
    total, by_op = collective_bytes(hlo)
    assert by_op["all-gather"] == 16 * 1024 * 4
    assert by_op["all-reduce"] == 8 * 8 * 2
    assert by_op["collective-permute"] == 4 * 4
    assert total == sum(by_op.values())
    assert "dot" not in by_op


def test_collective_bytes_tuple_shapes():
    hlo = ("%f = (f32[2,3]{1,0}, f32[4]{0}) all-reduce(f32[2,3] %a, "
           "f32[4] %b), to_apply=%add")
    total, by_op = collective_bytes(hlo)
    assert total == (2 * 3 + 4) * 4


def test_model_flops_conventions():
    from repro.configs.base import SHAPES_BY_NAME
    from repro.launch.roofline import model_flops
    cfg = registry.get_arch("yi-6b")
    n = registry.count_params(cfg)
    s = SHAPES_BY_NAME["train_4k"]
    assert model_flops(cfg, s) == 6.0 * n * s.global_batch * s.seq_len
    d = SHAPES_BY_NAME["decode_32k"]
    assert model_flops(cfg, d) == 2.0 * n * d.global_batch
