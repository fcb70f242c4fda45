"""Hybrid Mamba2 + shared-attention model (zamba2 backbone).

Zamba2's design: a deep stack of Mamba2 blocks, plus ONE shared transformer
block (attention + MLP over the concatenation [x, x_embed0], i.e. width
2*d_model) whose weights are reused at every application point, specialised
by per-application LoRA adapters (on the q projection and the MLP input
projection). The shared block runs before every group of
``hybrid_attn_every`` Mamba layers.

Scan layout: groups are a python loop (n_groups ~= 7 for zamba2-1.2b), the
mamba layers inside each group are a lax.scan over stacked params => HLO is
O(n_groups), not O(L).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers.attention import KVCache, attention, attn_params
from repro.models.layers.mlp import mlp_params
from repro.models.layers.norm import apply_norm, norm_params
from repro.models.layers.rope import apply_rope
from repro.models.layers.ssm import mamba2_apply, mamba2_params, ssm_state_zeros
from repro.models.lm import make_remat


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def n_groups(cfg: ModelConfig) -> int:
    return math.ceil(cfg.n_layers / cfg.hybrid_attn_every)


def group_sizes(cfg: ModelConfig) -> list[int]:
    full, rem = divmod(cfg.n_layers, cfg.hybrid_attn_every)
    return [cfg.hybrid_attn_every] * full + ([rem] if rem else [])


def _head_dim2(cfg: ModelConfig) -> int:
    return (2 * cfg.d_model) // cfg.n_heads


def init_params(key, cfg: ModelConfig) -> dict:
    dt = _dt(cfg)
    d2 = 2 * cfg.d_model
    km, ks1, ks2, ks3, kl, ke, kh = jax.random.split(key, 7)

    def one_mamba(k):
        return {"ln1": norm_params(cfg.norm, cfg.d_model),
                "mixer": mamba2_params(k, cfg, dt)}

    groups = []
    for gi, size in enumerate(group_sizes(cfg)):
        kg = jax.random.fold_in(km, gi)
        groups.append(jax.vmap(one_mamba)(jax.random.split(kg, size)))

    shared = {
        "ln1": norm_params(cfg.norm, d2),
        "attn": attn_params(ks1, d2, cfg.n_heads, cfg.n_kv_heads,
                            _head_dim2(cfg), bias=False, dtype=dt),
        "ln2": norm_params(cfg.norm, d2),
        "mlp": mlp_params(ks2, d2, cfg.d_ff, cfg.mlp, dt),
        "proj_out": (jax.random.normal(ks3, (d2, cfg.d_model))
                     * (1.0 / math.sqrt(d2))).astype(dt),
    }
    r = cfg.hybrid_lora_rank
    ng = n_groups(cfg)
    mlp_width = 2 * cfg.d_ff if cfg.mlp == "gated_silu" else cfg.d_ff
    loras = {
        "a_q": (jax.random.normal(kl, (ng, d2, r)) * (1.0 / math.sqrt(d2))
                ).astype(dt),
        "b_q": jnp.zeros((ng, r, cfg.n_heads * _head_dim2(cfg)), dt),
        "a_mlp": (jax.random.normal(jax.random.fold_in(kl, 1), (ng, d2, r))
                  * (1.0 / math.sqrt(d2))).astype(dt),
        "b_mlp": jnp.zeros((ng, r, mlp_width), dt),
    }
    return {
        "embed": (jax.random.normal(ke, (cfg.vocab_padded, cfg.d_model))
                  * (1.0 / math.sqrt(cfg.d_model))).astype(dt),
        "groups": groups,
        "shared": shared,
        "loras": loras,
        "final_norm": norm_params(cfg.norm, cfg.d_model),
        "lm_head": (jax.random.normal(kh, (cfg.d_model, cfg.vocab_padded))
                    * (1.0 / math.sqrt(cfg.d_model))).astype(dt),
    }


def _shared_block(cfg: ModelConfig, shared: dict, loras: dict, gi: int,
                  x: jax.Array, x0: jax.Array, *, cache: KVCache | None = None):
    """Shared attention+MLP over concat([x, x0]) with group-gi LoRA.

    Returns (new_x [B,S,D], new_cache)."""
    d2 = 2 * cfg.d_model
    hd = _head_dim2(cfg)
    b, s, _ = x.shape
    h = jnp.concatenate([x, x0], axis=-1)
    hn = apply_norm(cfg.norm, shared["ln1"], h, cfg.rms_norm_eps)

    p = shared["attn"]
    q = hn @ p["wq"] + (hn @ loras["a_q"][gi]) @ loras["b_q"][gi]  # LoRA on q
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = (hn @ p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = (hn @ p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    offset = cache.length if cache is not None else 0
    pos = jnp.arange(s) + offset
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    new_cache = None
    if cache is not None:
        ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype),
                                                 cache.length, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype),
                                                 cache.length, axis=1)
        new_cache = KVCache(ck, cv, cache.length + s)
        o = attention(q, ck, cv, causal=True, q_offset=offset,
                      kv_valid=cache.length + s, kv_chunk=cfg.attn_kv_chunk,
                      blocks_threshold=cfg.attn_blocks_threshold)
    else:
        o = attention(q, k, v, causal=True, kv_chunk=cfg.attn_kv_chunk,
                      blocks_threshold=cfg.attn_blocks_threshold)
    h = h + o.reshape(b, s, cfg.n_heads * hd) @ p["wo"]

    h2 = apply_norm(cfg.norm, shared["ln2"], h, cfg.rms_norm_eps)
    z = h2 @ shared["mlp"]["wi"] + (h2 @ loras["a_mlp"][gi]) @ loras["b_mlp"][gi]
    if cfg.mlp == "gated_silu":
        gate, up = jnp.split(z, 2, axis=-1)
        z = jax.nn.silu(gate) * up
    else:
        z = jax.nn.gelu(z)
    h = h + z @ shared["mlp"]["wo"]
    return h @ shared["proj_out"], new_cache


def _mamba_group_scan(cfg, gparams, x, states=None):
    """Scan the mamba layers of one group. states: stacked SSMState or None."""

    def body(h, inp):
        if states is None:
            lp = inp
            hn = apply_norm(cfg.norm, lp["ln1"], h, cfg.rms_norm_eps)
            out, _ = mamba2_apply(lp["mixer"], hn, cfg)
            return h + out, None
        lp, st = inp
        hn = apply_norm(cfg.norm, lp["ln1"], h, cfg.rms_norm_eps)
        out, new_st = mamba2_apply(lp["mixer"], hn, cfg, state=st)
        return h + out, new_st

    fn = make_remat(cfg)(body)
    xs = gparams if states is None else (gparams, states)
    return jax.lax.scan(fn, x, xs)


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array):
    """Training forward. Returns (logits [B,S,Vp], aux=0)."""
    x = params["embed"][tokens]
    x0 = x
    # remat the shared block: its [B,H,S,S] f32 scores otherwise sit in HBM
    # for the whole bwd (7 applications x ~2 GiB at train_4k)
    shared_fn = (jax.checkpoint(
        lambda sh, lo, gi, a, b: _shared_block(cfg, sh, lo, gi, a, b)[0],
        static_argnums=(2,)) if cfg.remat else
        lambda sh, lo, gi, a, b: _shared_block(cfg, sh, lo, gi, a, b)[0])
    for gi in range(n_groups(cfg)):
        h = shared_fn(params["shared"], params["loras"], gi, x, x0)
        x = x + h
        x, _ = _mamba_group_scan(cfg, params["groups"][gi], x)
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.rms_norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, jnp.zeros((), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, s_max: int):
    """Per-group: stacked SSM states + one shared-attn KV cache.

    For long-context decode the shared-attn cache is the only O(S) memory;
    SSM state is O(1) — this is why zamba2 runs the long_500k cell."""
    dt = _dt(cfg)
    st = ssm_state_zeros(cfg, batch, dt)
    hd = _head_dim2(cfg)
    caches = []
    for size in group_sizes(cfg):
        caches.append({
            "ssm": jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (size,) + a.shape), st),
            "kv": KVCache.zeros(batch, s_max, cfg.n_kv_heads, hd, dt),
        })
    return caches


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array, s_max: int):
    x = params["embed"][tokens]
    x0 = x
    caches = init_cache(cfg, x.shape[0], s_max)
    new_caches = []
    for gi in range(n_groups(cfg)):
        h, kv = _shared_block(cfg, params["shared"], params["loras"], gi, x, x0,
                              cache=caches[gi]["kv"])
        x = x + h
        x, ssm = _mamba_group_scan(cfg, params["groups"][gi], x,
                                   states=caches[gi]["ssm"])
        new_caches.append({"ssm": ssm, "kv": kv})
    x = apply_norm(cfg.norm, params["final_norm"], x[:, -1:], cfg.rms_norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, new_caches


def decode_step(cfg: ModelConfig, params: dict, token: jax.Array, caches):
    x = params["embed"][token]
    x0 = x
    new_caches = []
    for gi in range(n_groups(cfg)):
        h, kv = _shared_block(cfg, params["shared"], params["loras"], gi, x, x0,
                              cache=caches[gi]["kv"])
        x = x + h
        x, ssm = _mamba_group_scan(cfg, params["groups"][gi], x,
                                   states=caches[gi]["ssm"])
        new_caches.append({"ssm": ssm, "kv": kv})
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.rms_norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, new_caches
