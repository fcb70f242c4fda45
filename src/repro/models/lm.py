"""Unified decoder-only LM covering dense / vlm / moe / ssm families.

Layers are scan-stacked (params have a leading [L] axis) so HLO size is
O(1) in depth — essential for the 512-device dry-run compiles and the
production remat policy. Families share the same skeleton:

    x -> [ block_0 ... block_{L-1} ] -> final_norm -> lm_head

where block is (norm -> mixer -> residual -> norm -> ffn -> residual) and
the mixer/ffn pair depends on the family (attention+MLP, attention+MoE,
or Mamba2 which fuses mixer+ffn in one block).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers.attention import KVCache, attn_apply, attn_params
from repro.models.layers.mlp import mlp_apply, mlp_params
from repro.models.layers.moe import moe_apply, moe_params
from repro.models.layers.norm import apply_norm, norm_params
from repro.models.layers.ssm import (
    SSMState,
    mamba2_apply,
    mamba2_params,
    ssm_state_zeros,
)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def make_remat(cfg: ModelConfig):
    """Block-level jax.checkpoint wrapper honouring cfg.remat_policy."""
    if not cfg.remat:
        return lambda f: f
    if cfg.remat_policy == "dots_nb":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return lambda f: jax.checkpoint(f, policy=pol)
    return jax.checkpoint


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block_params(key, cfg: ModelConfig) -> dict:
    """Params for ONE block (caller vmaps over layers to stack)."""
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm":
        return {
            "ln1": norm_params(cfg.norm, cfg.d_model),
            "mixer": mamba2_params(ks[0], cfg, dt),
        }
    p = {
        "ln1": norm_params(cfg.norm, cfg.d_model),
        "attn": attn_params(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim_, bias=cfg.qkv_bias, dtype=dt),
        "ln2": norm_params(cfg.norm, cfg.d_model),
    }
    if cfg.family == "moe":
        p["moe"] = moe_params(ks[1], cfg.d_model, cfg.n_experts,
                              cfg.d_expert or cfg.d_ff, cfg.n_shared_experts, dt)
    else:
        p["mlp"] = mlp_params(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp, dt)
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    blocks = jax.vmap(lambda k: init_block_params(k, cfg))(
        jax.random.split(k_blocks, cfg.n_layers))
    params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_padded, cfg.d_model))
                  * (1.0 / math.sqrt(cfg.d_model))).astype(dt),
        "blocks": blocks,
        "final_norm": norm_params(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_padded))
            * (1.0 / math.sqrt(cfg.d_model))).astype(dt)
    return params


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------

def block_apply(cfg: ModelConfig, p: dict, x: jax.Array, *,
                cache: Any = None, positions=None):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        h, new_state = mamba2_apply(p["mixer"], apply_norm(cfg.norm, p["ln1"], x, cfg.rms_norm_eps),
                                    cfg, state=cache)
        return x + h, new_state, aux
    h, new_cache = attn_apply(
        p["attn"], apply_norm(cfg.norm, p["ln1"], x, cfg.rms_norm_eps),
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim_,
        rope_theta=cfg.rope_theta, window=cfg.sliding_window,
        kv_chunk=cfg.attn_kv_chunk, blocks_threshold=cfg.attn_blocks_threshold,
        use_pallas=cfg.use_pallas_attention, pallas_interpret=cfg.pallas_interpret,
        cache=cache, positions=positions)
    x = x + h
    h2 = apply_norm(cfg.norm, p["ln2"], x, cfg.rms_norm_eps)
    if cfg.family == "moe":
        h2, metrics = moe_apply(p["moe"], h2, top_k=cfg.top_k,
                                capacity_factor=cfg.capacity_factor,
                                ep_sharding=cfg.moe_ep_sharding)
        aux = metrics.aux_loss
    else:
        h2 = mlp_apply(p["mlp"], h2, cfg.mlp)
    return x + h2, new_cache, aux


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _stack_scan(cfg: ModelConfig, params: dict, x: jax.Array, caches,
                positions):
    """Scan blocks over the stacked [L, ...] params (+ optional caches)."""

    def body(carry, layer_in):
        h = carry
        if caches is None:
            lp = layer_in
            h, _, aux = block_apply(cfg, lp, h, positions=positions)
            return h, aux
        lp, lc = layer_in
        h, nc, aux = block_apply(cfg, lp, h, cache=lc, positions=positions)
        return h, (nc, aux)

    fn = make_remat(cfg)(body)
    xs = params["blocks"] if caches is None else (params["blocks"], caches)
    x, out = jax.lax.scan(fn, x, xs)
    if caches is None:
        return x, None, out.sum()
    new_caches, aux = out
    return x, new_caches, aux.sum()


def embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array,
                 prefix_embeds: jax.Array | None = None) -> jax.Array:
    x = params["embed"][tokens]
    if prefix_embeds is not None:  # vlm: image patches before text
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


def logits_from_hidden(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.rms_norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head, preferred_element_type=jnp.float32)


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
            prefix_embeds: jax.Array | None = None):
    """Training forward: tokens [B, S_text] -> logits [B, S, Vp], aux."""
    x = embed_tokens(cfg, params, tokens, prefix_embeds)
    positions = jnp.arange(x.shape[1])
    x, _, aux = _stack_scan(cfg, params, x, None, positions)
    return logits_from_hidden(cfg, params, x), aux


def init_cache(cfg: ModelConfig, batch: int, s_max: int):
    """Stacked [L, ...] decode cache."""
    dt = _dtype(cfg)
    if cfg.family == "ssm":
        st = ssm_state_zeros(cfg, batch, dt)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape), st)
    kv = KVCache.zeros(batch, s_max, cfg.n_kv_heads, cfg.head_dim_, dt)
    return KVCache(
        jnp.broadcast_to(kv.k[None], (cfg.n_layers,) + kv.k.shape),
        jnp.broadcast_to(kv.v[None], (cfg.n_layers,) + kv.v.shape),
        jnp.zeros((cfg.n_layers,), jnp.int32),
    )


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array, s_max: int, *,
            prefix_embeds: jax.Array | None = None):
    """Fill the cache from a prompt; returns (last_logits, cache)."""
    x = embed_tokens(cfg, params, tokens, prefix_embeds)
    caches = init_cache(cfg, x.shape[0], s_max)
    positions = jnp.arange(x.shape[1])
    x, new_caches, _ = _stack_scan(cfg, params, x, caches, positions)
    return logits_from_hidden(cfg, params, x[:, -1:]), new_caches


def prefill_chunked(cfg: ModelConfig, params: dict, tokens: jax.Array,
                    s_max: int, *, chunk: int = 4096,
                    prefix_embeds: jax.Array | None = None):
    """Blocks-mode prefill: run the prompt through the stack in sequence
    chunks, carrying the KV cache between chunks.

    Bounds every per-token intermediate (attention scores, MoE dispatch
    buffers) to O(B*chunk) instead of O(B*S) — the paper's Blocks
    partitioning applied to the prompt dimension. Semantically identical to
    :func:`prefill` (causal attention never looks ahead)."""
    x = embed_tokens(cfg, params, tokens, prefix_embeds)
    s = x.shape[1]
    caches = init_cache(cfg, x.shape[0], s_max)
    if s % chunk:
        raise ValueError(f"prompt length {s} not divisible by chunk {chunk}")
    last = None
    for c0 in range(0, s, chunk):
        xc = x[:, c0 : c0 + chunk]
        xc, caches, _ = _stack_scan(cfg, params, xc, caches, None)
        last = xc[:, -1:]
    return logits_from_hidden(cfg, params, last), caches


def decode_step(cfg: ModelConfig, params: dict, token: jax.Array, caches):
    """One decode step. token: [B, 1]; caches from prefill/init_cache."""
    x = embed_tokens(cfg, params, token)
    x, new_caches, _ = _stack_scan(cfg, params, x, caches, None)
    return logits_from_hidden(cfg, params, x), new_caches
