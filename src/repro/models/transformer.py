"""Decoder-only transformer LM: dense / MoE / MLA variants.

Covers 7 of the 10 assigned architectures (moonshot, deepseek-v2,
deepseek-coder, granite, minicpm3, yi, internvl2-backbone) plus the paper's
OPT-125M. Layers are scan-stacked (leading L dim on every leaf): compile time
and HLO size stay O(1) in depth, and FSDP weight gathers stream layer-by-layer
under the scan.

Three entry points per the shape cells:
  * loss_per_client — train shapes (the ZO/FO objective)
  * prefill         — inference-prefill shapes (build cache, last logits)
  * decode_step     — decode shapes (one token against a full cache)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def _block_init(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p = {"ln1": L.rmsnorm_init(cfg.d_model, dtype),
         "ln2": L.rmsnorm_init(cfg.d_model, dtype)}
    if cfg.mla.enabled:
        p["attn"] = L.mla_init(ks[0], cfg, dtype)
    else:
        p["attn"] = L.gqa_init(ks[0], cfg, dtype)
    if cfg.moe.enabled:
        p["moe"] = L.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    # scan-stacked blocks: one init vmapped over layer keys
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    blocks = jax.vmap(lambda k: _block_init(k, cfg, dtype))(layer_keys)
    p = {
        "embed": L.embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype),
        "blocks": blocks,
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.embed_init(ks[2], cfg.vocab_size, cfg.d_model,
                                    dtype)
    return p


def _block_apply(bp: dict, x: jnp.ndarray, positions: jnp.ndarray,
                 cfg: ModelConfig, *, cache: Optional[dict],
                 cache_pos, impl: Optional[str]
                 ) -> Tuple[jnp.ndarray, Optional[dict]]:
    from repro.runtime.sharding import hint
    x = hint(x, "client", None, None)
    h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
    if cfg.mla.enabled:
        a, new_cache = L.mla_attend(bp["attn"], h, positions, cfg,
                                    kv_cache=cache, cache_pos=cache_pos,
                                    impl=impl)
    else:
        a, new_cache = L.gqa_attend(bp["attn"], h, positions, cfg,
                                    causal=True, kv_cache=cache,
                                    cache_pos=cache_pos, impl=impl)
    x = x + a
    h = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
    f = L.moe(bp["moe"], h, cfg) if cfg.moe.enabled else L.mlp(bp["mlp"], h)
    return x + f, new_cache


def forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray, *,
            prefix_embeds: Optional[jnp.ndarray] = None,
            inputs_embeds: Optional[jnp.ndarray] = None,
            impl: Optional[str] = None) -> jnp.ndarray:
    """tokens: [B, S] → hidden [B, S(+P), D]. prefix_embeds ([B, P, D])
    are prepended (VLM stub frontend). inputs_embeds ([B, S, D]) replaces
    the embedding lookup entirely (tokens may be None) — the continuous
    input surface gradient-inversion attacks (repro.privacy.attacks) and
    soft-token methods differentiate through. Runs under the named scope
    `forward_blocks`: the embedding, the layer scan and the final norm."""
    with jax.named_scope("forward_blocks"):
        x = L.embed(params["embed"], tokens) if inputs_embeds is None \
            else inputs_embeds
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        positions = jnp.arange(x.shape[1])

        def body(h, bp):
            h, _ = _block_apply(bp, h, positions, cfg, cache=None,
                                cache_pos=None, impl=impl)
            return h, None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def logits_from_hidden(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    head = params.get("lm_head", params["embed"])
    return L.unembed(head, x)


def token_nll(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
              targets: jnp.ndarray, mask: jnp.ndarray, *,
              prefix_embeds: Optional[jnp.ndarray] = None,
              inputs_embeds: Optional[jnp.ndarray] = None,
              impl: Optional[str] = None) -> jnp.ndarray:
    """Per-sequence-row mean NLL: [B, S] → [B]. (f32 CE over sharded vocab.)
    The LM head and the cross entropy run under the named scope
    `lm_head_ce`."""
    x = forward(params, cfg, tokens, prefix_embeds=prefix_embeds,
                inputs_embeds=inputs_embeds, impl=impl)
    if prefix_embeds is not None:
        x = x[:, prefix_embeds.shape[1]:]
    with jax.named_scope("lm_head_ce"):
        logits = logits_from_hidden(params, x)              # [B, S, V] f32
        return L.cross_entropy(logits, targets, mask)


def loss_per_client(params: dict, cfg: ModelConfig, batch: dict, *,
                    impl: Optional[str] = None) -> jnp.ndarray:
    """batch tokens/targets/mask: [K, b, S] → per-client losses [K]. The
    per-client mean joins the LM head's scope, `lm_head_ce`."""
    k, b, s = batch["tokens"].shape
    flat = lambda a: a.reshape((k * b,) + a.shape[2:])
    prefix = batch.get("prefix_embeds")
    nll = token_nll(params, cfg, flat(batch["tokens"]),
                    flat(batch["targets"]), flat(batch["mask"]),
                    prefix_embeds=flat(prefix) if prefix is not None else None,
                    impl=impl)
    with jax.named_scope("lm_head_ce"):
        return jnp.mean(nll.reshape(k, b), axis=-1)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32) -> dict:
    lcfg = cfg.n_layers
    if cfg.mla.enabled:
        return {
            "ckv": jnp.zeros((lcfg, batch, max_len, cfg.mla.kv_lora_rank),
                             dtype=dtype),
            "krope": jnp.zeros((lcfg, batch, max_len,
                                cfg.mla.qk_rope_head_dim), dtype=dtype),
        }
    hd = cfg.resolved_head_dim()
    return {
        "k": jnp.zeros((lcfg, batch, max_len, cfg.n_kv_heads, hd),
                       dtype=dtype),
        "v": jnp.zeros((lcfg, batch, max_len, cfg.n_kv_heads, hd),
                       dtype=dtype),
    }


def cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """ShapeDtypeStruct skeleton of init_cache (dry-run input specs)."""
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype))


def decode_step(params: dict, cfg: ModelConfig, cache: dict,
                tokens: jnp.ndarray, cache_pos, *,
                impl: Optional[str] = None) -> Tuple[jnp.ndarray, dict]:
    """One decode step. tokens: [B, S_new(=1)]; cache leaves: [L, B, ...].

    The cache rides in the scan CARRY (not xs→ys): while-loop carries alias
    in place under buffer donation, so the multi-GB cache updates without a
    second copy — scan-stacked xs/ys outputs cannot alias and would double
    the decode working set.
    """
    x = L.embed(params["embed"], tokens)
    positions = cache_pos + jnp.arange(tokens.shape[1])

    def body(carry, xs):
        h, full_cache = carry
        li, bp = xs
        layer_cache = jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_index_in_dim(c, li, 0, False),
            full_cache)
        h, new_cache = _block_apply(bp, h, positions, cfg, cache=layer_cache,
                                    cache_pos=cache_pos, impl=impl)
        full_cache = jax.tree_util.tree_map(
            lambda c, nc: jax.lax.dynamic_update_index_in_dim(
                c, nc.astype(c.dtype), li, 0),
            full_cache, new_cache)
        return (h, full_cache), None

    (x, new_cache), _ = jax.lax.scan(
        body, (x, cache),
        (jnp.arange(cfg.n_layers, dtype=jnp.int32), params["blocks"]))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_from_hidden(params, x), new_cache


def prefill(params: dict, cfg: ModelConfig, tokens: jnp.ndarray, *,
            prefix_embeds: Optional[jnp.ndarray] = None,
            impl: Optional[str] = None) -> Tuple[jnp.ndarray, dict]:
    """Full-sequence prefill: returns (last-position logits [B, V], cache).

    The cache is built by running each block in cache-write mode at pos 0
    with the full sequence (write-once, no dynamic slices on the hot path).
    """
    b, s = tokens.shape
    x = L.embed(params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    s_tot = x.shape[1]
    positions = jnp.arange(s_tot)
    cache = init_cache(cfg, b, s_tot, dtype=x.dtype)

    # cache filling recomputes this layer's k/v projection from the block
    # input (one extra projection per layer; no attention recompute).
    def body2(h, xs):
        bp, layer_cache = xs
        h_in = h
        h, _ = _block_apply(bp, h, positions, cfg, cache=None,
                            cache_pos=None, impl=impl)
        filled = _fill_cache(bp, L.rmsnorm(bp["ln1"], h_in, cfg.norm_eps),
                             layer_cache, positions, cfg)
        return h, filled

    x, new_cache = jax.lax.scan(body2, x, (params["blocks"], cache))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_from_hidden(params, x[:, -1:]), new_cache


def _fill_cache(bp: dict, h_norm: jnp.ndarray, layer_cache: dict,
                positions: jnp.ndarray, cfg: ModelConfig) -> dict:
    """Write this layer's k/v (or latent) projections into the cache."""
    b, s, _ = h_norm.shape
    if cfg.mla.enabled:
        m = cfg.mla
        kv = L.dense({"w": bp["attn"]["wkv_a"]}, h_norm)
        ckv = L.rmsnorm(bp["attn"]["kv_norm"], kv[..., :m.kv_lora_rank],
                        cfg.norm_eps)
        krope = L.rope(kv[..., m.kv_lora_rank:][:, None], positions,
                       cfg.rope_theta)[:, 0]
        from repro.runtime.sharding import hint
        return {
            "ckv": hint(layer_cache["ckv"].at[:, :s].set(
                ckv.astype(layer_cache["ckv"].dtype)),
                "client", "model", None),
            "krope": hint(layer_cache["krope"].at[:, :s].set(
                krope.astype(layer_cache["krope"].dtype)),
                "client", "model", None),
        }
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim()
    k = L.dense({"w": bp["attn"]["wk"]}, h_norm).reshape(b, s, hkv, hd)
    k = L.rope(k.swapaxes(1, 2), positions, cfg.rope_theta).swapaxes(1, 2)
    v = L.dense({"w": bp["attn"]["wv"]}, h_norm).reshape(b, s, hkv, hd)
    from repro.runtime.sharding import hint
    return {
        "k": hint(layer_cache["k"].at[:, :s].set(
            k.astype(layer_cache["k"].dtype)), "client", "model", None, None),
        "v": hint(layer_cache["v"].at[:, :s].set(
            v.astype(layer_cache["v"].dtype)), "client", "model", None, None),
    }
