"""Flash attention kernel: blocks-mode KV streaming with online softmax.

The TPU-native version of the model's jnp ``attention_blocks`` path: each
grid step DMAs one (block_q x block_kv) tile pair into VMEM, updates the
f32 accumulator/max/sum scratch, and Pallas double-buffers the revolving KV
tiles — the paper's double-buffered blocks DMA applied to the attention
score stream (NullHop's 'start computing after a couple of rows arrive').

Causal-aware grid: KV tiles strictly above the diagonal for every query in
the tile are skipped via pl.when (zero work, not just masked) — the
beyond-paper optimization measured in §Perf.

Grid: (batch*heads, q_tiles, kv_tiles), kv innermost ('arbitrary' so the
scratch carries across kv steps). GQA is handled by the kv index_map
(q-head -> kv-head, h // n_rep) so kv tiles are DMA'd once per group.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
_INV_LN2 = 1.4426950408889634


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  kv_steps: int, block_q: int, block_kv: int, scale: float,
                  causal: bool, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    kv_start = ki * block_kv

    def compute():
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bkv, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bkv]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                              block_kv), 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                               block_kv), 1)
        ok = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= qpos - kpos < window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp2((m_prev - m_new) * _INV_LN2)
        p = jnp.exp2((s - m_new[:, None]) * _INV_LN2)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip tiles entirely above the diagonal (no valid kv for any q)
        pl.when(kv_start <= q_start + block_q - 1)(compute)
    elif window > 0:
        pl.when((kv_start <= q_start + block_q - 1)
                & (q_start - (kv_start + block_kv - 1) < window))(compute)
    else:
        compute()

    @pl.when(ki == kv_steps - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "n_rep",
                     "interpret"))
def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         block_q: int = 512, block_kv: int = 512,
                         n_rep: int = 1, interpret: bool = False) -> jax.Array:
    """q: [BH, Sq, D]; k, v: [BHkv, Skv, D] with BH = BHkv * n_rep.

    Heads are flattened into the leading grid axis; the kv index_map maps
    query head -> kv head so GQA groups share kv tile DMAs."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, skv, bq, bkv)
    kv_steps = skv // bkv
    grid = (bh, sq // bq, kv_steps)
    kernel = functools.partial(
        _flash_kernel, kv_steps=kv_steps, block_q=bq, block_kv=bkv,
        scale=1.0 / math.sqrt(d), causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j, n_rep=n_rep: (h // n_rep, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j, n_rep=n_rep: (h // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
