"""Streamed matmul kernel — the paper's DMA policy matrix at the HBM->VMEM
boundary.

The paper's axes map onto the grid/BlockSpec structure:

- UNIQUE mode   : one grid step; whole operands DMA'd to VMEM, one dot.
  (Only legal when everything fits VMEM — the AXI 'single long burst'.)
- BLOCKS mode   : tiled (M/bm, N/bn, K/bk) grid; each step DMAs one
  (bm x bk) x (bk x bn) working set. Pallas' pipelining machinery
  double-buffers revolving grid windows automatically — arriving block
  k+1 overlaps the MXU dot on block k, exactly the paper's double-buffer
  overlap. Block sizes are the 'packet length' knob: too small pays
  per-DMA overhead every step (the paper's small-transfer regime), too
  large overflows VMEM (the paper's 8MB AXI limit analogue).

The K axis is innermost and 'arbitrary' (sequential) so the f32 accumulator
scratch lives across K steps; M/N are parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def matmul_blocks(x: jax.Array, w: jax.Array, *, block_m: int = 512,
                  block_n: int = 512, block_k: int = 512,
                  interpret: bool = False) -> jax.Array:
    """BLOCKS-mode matmul: [M, K] @ [K, N], tiled VMEM pipeline."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k})x({k},{n}) not divisible by blocks ({bm},{bn},{bk})")
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)


def _matmul_unique_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matmul_unique(x: jax.Array, w: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """UNIQUE-mode matmul: whole operands in one VMEM residency.

    VMEM budget check is the caller's job (ops.py enforces it) — this is
    the paper's 'send all the data at once' configuration."""
    m, k = x.shape
    _, n = w.shape
    return pl.pallas_call(
        _matmul_unique_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        in_specs=[pl.BlockSpec((m, k), lambda: (0, 0)),
                  pl.BlockSpec((k, n), lambda: (0, 0))],
        out_specs=pl.BlockSpec((m, n), lambda: (0, 0)),
        interpret=interpret,
    )(x, w)
