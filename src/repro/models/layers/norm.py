"""Normalisation layers (pure functions over explicit params)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_params(kind: str, d: int):
    if kind == "rms":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def apply_norm(kind: str, params, x: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    """``eps`` is the RMS norm's (``ModelConfig.rms_norm_eps``); layer norm
    keeps its own 1e-5."""
    if kind == "rms":
        return rms_norm(x, params["scale"], eps)
    return layer_norm(x, params["scale"], params["bias"])
