"""Multi-head latent attention (MLA: DeepSeek-V2, arXiv:2405.04434, as the
DeepSeek-V3 block uses it) without a query low rank (``q_lora_rank`` null).

Per token, with h the normed input:

    q            = h W_q      -> H x (dn no-RoPE + dr RoPE)
    [c ; k_r]    = h W_kva    -> the rank-r latent c (RMS-normed) and one
                                 RoPE key k_r shared by every head
    [k_n ; v]    = c W_kvb    -> H x (dn + dv)
    score(t, u)  = (q_n(t).k_n(u) + q_r(t).k_r(u)) / sqrt(dn + dr)

The cache holds c and k_r only: r + dr numbers a token a layer. Attention
runs in the absorbed form: q_n goes into latent space through the k half
of W_kvb (q_lat = q_n W_uk^T), the scores are q_lat.c + q_r.k_r, and the
latent output comes back through the v half. These are the expanded
form's products in another order; k_n and v are never built.

RoPE pairs dimensions as the published modelling code does: the RoPE part
is read as interleaved pairs (2i, 2i+1), de-interleaved, then rotated by
halves (:func:`repro.models.layers.rope.apply_rope`). Stored RoPE keys are
in the de-interleaved order, which leaves every score unchanged.

Matmul inputs are in the weights' dtype and accumulate in float32; the
softmax is float32."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers.norm import rms_norm
from repro.models.layers.rope import apply_rope

NEG_INF = -2.0**30
F32 = jnp.float32


def mla_params(key, cfg, dtype) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def w(k, shape):
        return (jax.random.normal(k, shape) / math.sqrt(shape[0])).astype(dtype)

    return {"wq": w(k1, (d, h * (dn + dr))), "wkv_a": w(k2, (d, r + dr)),
            "kv_norm": {"scale": jnp.ones((r,), F32)},
            "wkv_b": w(k3, (r, h * (dn + dv))), "wo": w(k4, (h * dv, d))}


def _mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a.astype(b.dtype), b, preferred_element_type=F32)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [B, s, H, d] with interleaved pairs -> de-interleaved, rotated."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def project(p: dict, h: jax.Array, cfg, positions: jax.Array):
    """h [B, s, D] -> q_nope [B, s, H, dn], q_rope [B, s, H, dr] (f32), and
    the cache entries c [B, s, r], k_r [B, s, dr] (f32)."""
    b, s, _ = h.shape
    nh, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = _mm("bsd,dk->bsk", h, p["wq"]).reshape(b, s, nh, dn + dr)
    q_rope = _rope(q[..., dn:], positions, cfg.rope_theta)
    ckv = _mm("bsd,dk->bsk", h, p["wkv_a"])
    c = rms_norm(ckv[..., :r], p["kv_norm"]["scale"], cfg.rms_norm_eps)
    k_rope = _rope(ckv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    return q[..., :dn], q_rope, c, k_rope


def attend(p: dict, cfg, q_nope: jax.Array, q_rope: jax.Array,
           c: jax.Array, k_rope: jax.Array, ok: jax.Array) -> jax.Array:
    """Absorbed attention of queries [B, s, H, .] over cached latents c
    [B, t, r] and RoPE keys [B, t, dr]; ``ok`` [B, s, t] marks the
    positions each query sees. Returns the output projection [B, s, D]."""
    b, s, nh, dn = q_nope.shape
    dv, r = cfg.v_head_dim, cfg.kv_lora_rank
    w = p["wkv_b"].reshape(r, nh, dn + dv)
    q_lat = _mm("bshn,rhn->bshr", q_nope, w[..., :dn])
    scores = (_mm("bshr,btr->bhst", q_lat, c)
              + _mm("bshp,btp->bhst", q_rope, k_rope))
    scores = scores / math.sqrt(dn + cfg.qk_rope_head_dim)
    scores = jnp.where(ok[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = _mm("bhst,btr->bshr", probs, c)
    o = _mm("bshr,rhv->bshv", o_lat, w[..., dn:])
    return _mm("bsk,kd->bsd", o.reshape(b, s, nh * dv), p["wo"])
