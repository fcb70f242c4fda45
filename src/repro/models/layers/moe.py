"""Mixture-of-experts FFN with capacity-based dispatch (Switch-style).

Supports fine-grained MoE (deepseek: 64 routed top-6 + 2 shared experts,
narrow d_expert) and classic MoE (granite: 32 routed top-8).

Dispatch is capacity-based gather/scatter: tokens are routed to at most
``capacity`` slots per expert; experts run as one batched einsum over
stacked weights [E, D, F] (sharded over the 'model' axis = expert
parallelism). FLOPs are O(top_k * tokens * D * F) — the active-parameter
count — so the roofline 'useful FLOPs' ratio stays honest.

The expert all-to-all is the MoE incarnation of the paper's TX/RX balance
problem: dispatch (TX) and combine (RX) share the same ICI links, and the
blocks-mode chunking in repro.core.pipeline_collectives applies to both.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class MoEMetrics(NamedTuple):
    aux_loss: jax.Array  # load-balance loss (Switch)
    dropped_frac: jax.Array  # fraction of (token, slot) pairs over capacity


def _shard_experts(x: jax.Array, spec) -> jax.Array:
    """Constrain an expert-major intermediate to expert-parallel over the
    'model' axis. No-op when no mesh is active (CPU tests) or the expert
    count doesn't divide the axis."""
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*spec))
    except Exception:  # noqa: BLE001 — sharding hints must never break math
        return x


def moe_params(key, d_model: int, n_experts: int, d_expert: int,
               n_shared: int, dtype) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    sd_in = 1.0 / math.sqrt(d_model)
    sd_out = 1.0 / math.sqrt(2.0 * d_expert)
    p = {
        "router": (jax.random.normal(k1, (d_model, n_experts)) * sd_in)
        .astype(jnp.float32),
        "we_up": (jax.random.normal(k2, (n_experts, d_model, 2 * d_expert))
                  * sd_in).astype(dtype),
        "we_down": (jax.random.normal(k3, (n_experts, d_expert, d_model))
                    * sd_out).astype(dtype),
    }
    if n_shared:
        p["ws_up"] = (jax.random.normal(k4, (d_model, 2 * n_shared * d_expert))
                      * sd_in).astype(dtype)
        p["ws_down"] = (jax.random.normal(k5, (n_shared * d_expert, d_model))
                        * sd_out).astype(dtype)
    return p


def moe_apply(p: dict, x: jax.Array, *, top_k: int,
              capacity_factor: float = 1.25,
              ep_sharding: bool = True) -> tuple[jax.Array, MoEMetrics]:
    """x: [B, S, D] -> [B, S, D].

    Routing: softmax over experts, top-k, weights renormalised over the k.
    Tokens beyond an expert's capacity are dropped (their residual passes
    through) — standard capacity-based MoE semantics."""
    b, s, d = x.shape
    e = p["we_up"].shape[0]
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # [T, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = int(math.ceil(top_k * t / e * capacity_factor))
    capacity = max(capacity, 1)

    # position of each (token, slot) within its expert queue, k-major so the
    # primary expert of every token is seated before any secondary slots.
    # §Perf iteration B1: sort-based seat assignment — O(TK log TK) time and
    # O(TK) memory, replacing the one-hot cumsum whose [T*K, E] int32
    # materialisation dominated prefill_32k temp memory (105 GiB/device for
    # deepseek-moe: T=1M, K=6, E=64).
    flat_e = gate_idx.T.reshape(-1)  # [K*T], slot-major
    tk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)  # seats grouped by expert
    sorted_e = flat_e[order]
    arange = jnp.arange(tk, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, arange, 0))
    pos_sorted = arange - group_start
    pos = jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < capacity
    dropped = 1.0 - keep.mean()

    # dispatch into [E, C, D].
    # §Perf iteration B2/B3: per-k-slot dispatch + combine. The slot-major
    # [K*T, D] formulation materialised 48 GiB replicated f32 intermediates
    # and a 48 GiB all-reduce per layer (GSPMD gathering from the expert-
    # sharded buffer); per-k loops keep every tensor either token-major
    # [T, D] (data-sharded) or expert-major [E, C, D] (model-sharded).
    slot = jnp.where(keep, flat_e * capacity + pos, e * capacity)  # OOB -> drop
    slot_k = slot.reshape(top_k, t)  # [K, T]
    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    for k in range(top_k):
        buf = buf.at[slot_k[k]].set(xt, mode="drop")
    xe = buf[:-1].reshape(e, capacity, d)
    ep = ("model", None, None)
    if ep_sharding:
        xe = _shard_experts(xe, ep)

    # expert FFN (gated silu), batched over experts
    h = jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    if ep_sharding:
        h = _shard_experts(h, ep)
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    ye = jnp.einsum("ecf,efd->ecd", h, p["we_down"])  # [E, C, D]
    if ep_sharding:
        ye = _shard_experts(ye, ep)

    # combine: per-k gather (token-major, no scatter at all)
    yflat = ye.reshape(e * capacity, d)
    w = jnp.where(keep, gate_vals.T.reshape(-1), 0.0).astype(x.dtype)  # [K*T]
    w_k = w.reshape(top_k, t)
    out = jnp.zeros((t, d), x.dtype)
    for k in range(top_k):
        got = yflat[jnp.minimum(slot_k[k], e * capacity - 1)]  # [T, D]
        if ep_sharding:
            got = _shard_experts(got, ("data", None))  # token-major again
        out = out + got * w_k[k][:, None]

    # shared experts (always-on)
    if "ws_up" in p:
        hs = xt @ p["ws_up"]
        gs, us = jnp.split(hs, 2, axis=-1)
        out = out + (jax.nn.silu(gs) * us) @ p["ws_down"]

    # Switch aux loss: E * sum_e f_e * P_e
    f_e = jnp.zeros((e,), jnp.float32).at[flat_e].add(
        keep.astype(jnp.float32)) / jnp.maximum(keep.sum(), 1)
    p_e = probs.mean(0)
    aux = e * jnp.sum(f_e * p_e)
    return out.reshape(b, s, d), MoEMetrics(aux, dropped)


# ---------------------------------------------------------------------------
# Sigmoid-scored, bias-corrected routing (DeepSeek-V3 ``noaux_tc``, one
# group) and a dropless expert FFN applied one expert at a time
# ---------------------------------------------------------------------------

def sigmoid_route(router: jax.Array, bias: jax.Array, h: jax.Array, *,
                  top_k: int, scale: float) -> tuple[jax.Array, jax.Array]:
    """h [..., D] -> (ids [..., K] int32, weights [..., K] f32).

    Scores are ``sigmoid(h . router)`` in float32; the experts are the top
    ``top_k`` by score + ``bias`` (the correction bias only selects); each
    weight is its expert's unbiased score over the sum of the chosen
    scores, times ``scale`` (``norm_topk_prob`` and
    ``routed_scaling_factor``). With one group, group selection keeps
    every expert. Every token gets its ``top_k`` experts: nothing is
    dropped at a capacity."""
    logits = jnp.einsum("...d,de->...e", h.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * scale


def gated_silu(h: jax.Array, wi: jax.Array, wo: jax.Array) -> jax.Array:
    """``wo(silu(gate) * up)`` with ``wi`` the gate's columns then the
    up projection's; inputs in the weights' dtype, float32 out."""
    g = jnp.einsum("...d,df->...f", h.astype(wi.dtype), wi,
                   preferred_element_type=jnp.float32)
    gate, up = jnp.split(g, 2, axis=-1)
    a = (jax.nn.silu(gate) * up).astype(wo.dtype)
    return jnp.einsum("...f,fd->...d", a, wo,
                      preferred_element_type=jnp.float32)


def expert_add(x: jax.Array, h: jax.Array, ids: jax.Array, w: jax.Array,
               e: jax.Array, wi: jax.Array, wo: jax.Array) -> jax.Array:
    """x + (each token's routing weight of expert ``e``, 0 where it was not
    chosen) * expert ``e``'s FFN of h. Applying this for every expert any
    token chose gives the dropless routed output."""
    we = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
    return x + we[..., None] * gated_silu(h, wi, wo)
