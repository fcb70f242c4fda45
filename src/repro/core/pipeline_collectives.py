"""Blocks-mode collectives: chunked, compute-overlapped rings.

The chip<->chip incarnation of the paper's BLOCKS + DOUBLE-buffer idea.
A monolithic ``all_gather`` ('Unique mode') serialises: all communication,
then all compute. Decomposing it into a ``ppermute`` ring of N-1 chunk steps
('Blocks mode') lets the matmul on chunk k overlap the transfer of chunk
k+1 — on TPU the async collective-permute engine runs concurrently with the
MXU, so the steady state is max(compute, comm) per chunk instead of
compute+comm. Same structure for reduce-scatter (the RX direction).

These run inside ``shard_map`` over the 'model' (and 'pod') axes. The paper's
TX/RX-balance concern (DDR can't read+write at once) maps to ICI: gather and
scatter chunks share links, so ``overlapped_matmul_ag``/``_rs`` interleave
them one chunk apart rather than back-to-back.

All functions have pure-jnp semantics equal to the unchunked collective —
property-tested in tests/test_collectives.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def ring_all_gather(x: jax.Array, axis_name: str, *, axis: int = 0) -> jax.Array:
    """All-gather via an N-1 step ppermute ring (blocks mode).

    Equivalent to ``lax.all_gather(x, axis_name, axis=axis, tiled=True)``."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(block, _):
        nxt = lax.ppermute(block, axis_name, perm)
        return nxt, nxt

    _, blocks = lax.scan(step, x, None, length=n - 1)
    # blocks[j] holds the shard of rank (idx - 1 - j) mod n; assemble in rank order.
    all_blocks = jnp.concatenate([x[None], blocks], axis=0)  # [n, *x.shape]
    src = (idx - jnp.arange(n)) % n  # all_blocks[j] came from rank src[j]
    order = jnp.argsort(src)
    ordered = jnp.take(all_blocks, order, axis=0)
    return _merge_leading(ordered, axis)


def ring_reduce_scatter(x: jax.Array, axis_name: str, *, axis: int = 0) -> jax.Array:
    """Reduce-scatter (sum) via an N-1 step ring.

    Equivalent to ``lax.psum_scatter(x, axis_name, scatter_dimension=axis,
    tiled=True)``."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    if x.shape[axis] % n:
        raise ValueError(f"dim {axis} ({x.shape[axis]}) not divisible by {n}")
    chunks = _split_dim(x, axis, n)  # [n, ...] leading chunk index

    # Ring reduce-scatter: at step s, rank i sends its running partial for
    # chunk (i - s - 1) mod n to rank i+1 (the partial created at rank i at
    # s=0 is destined for chunk (i-1), i.e. rank i-1, which it reaches after
    # the n-1 hops). Each hop adds the local contribution for the chunk the
    # partial is destined for; after the last hop rank i holds the full sum
    # of chunk i minus its own contribution, added at the end.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(acc, s):
        c = (idx - s - 1) % n
        acc = acc + jnp.take(chunks, c, axis=0)
        return lax.ppermute(acc, axis_name, perm), None

    acc = jnp.zeros_like(jnp.take(chunks, 0, axis=0))
    acc, _ = lax.scan(step, acc, jnp.arange(n - 1))
    return acc + jnp.take(chunks, idx, axis=0)


def overlapped_matmul_ag(
    x: jax.Array,
    w: jax.Array,
    axis_name: str,
    *,
    contract_sharded: bool = False,
) -> jax.Array:
    """y = all_gather(x) @ w, with the gather chunked and overlapped.

    x: [m_local, k] shard (gather along rows); w: [k, n] local weights.
    Each ring step matmuls the chunk that just arrived while the next chunk
    is in flight — XLA schedules the ppermute DMA concurrently with the dot.
    Unique-mode reference: ``lax.all_gather(x, axis, tiled=True) @ w``."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x @ w
    idx = lax.axis_index(axis_name)
    m_local = x.shape[0]
    out = jnp.zeros((n * m_local,) + (w.shape[-1],), _dot_dtype(x, w))
    out = lax.pvary(out, (axis_name,))  # mark carry as axis-varying for scan
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        block, out = carry
        src = (idx - s) % n  # rank whose shard we currently hold
        nxt = lax.ppermute(block, axis_name, perm)  # comm for step s+1 ...
        out = lax.dynamic_update_slice_in_dim(
            out, (block @ w).astype(out.dtype), src * m_local, axis=0
        )  # ... overlaps this dot
        return (nxt, out), None

    (_, out), _ = lax.scan(step, (x, out), jnp.arange(n))
    return out


def overlapped_matmul_rs(
    x: jax.Array,
    w: jax.Array,
    axis_name: str,
) -> jax.Array:
    """y = reduce_scatter(x @ w) with the scatter chunked and overlapped.

    x: [m, k_local]; w: [k_local, n]. Each rank computes its partial product
    in row-chunks; partials ride the ring accumulating, so the ppermute of
    chunk j overlaps the dot producing chunk j+1. Result: rows m/n per rank,
    summed over the axis. Unique-mode reference:
    ``lax.psum_scatter(x @ w, axis, scatter_dimension=0, tiled=True)``."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x @ w
    idx = lax.axis_index(axis_name)
    m = x.shape[0]
    if m % n:
        raise ValueError(f"rows {m} not divisible by axis size {n}")
    mc = m // n
    perm = [(i, (i + 1) % n) for i in range(n)]

    def chunk_dot(c):
        return lax.dynamic_slice_in_dim(x, c * mc, mc, axis=0) @ w

    # Same ring schedule as ring_reduce_scatter, but each rank *computes*
    # its chunk partial just-in-time: the dot producing the partial for
    # step s+1 overlaps the ppermute of step s on real hardware.
    def step(acc, s):
        c = (idx - s - 1) % n  # chunk this traveling partial is destined for
        acc = acc + chunk_dot(c).astype(acc.dtype)
        return lax.ppermute(acc, axis_name, perm), None

    acc = lax.pvary(jnp.zeros((mc, w.shape[-1]), _dot_dtype(x, w)), (axis_name,))
    acc, _ = lax.scan(step, acc, jnp.arange(n - 1))
    return (acc + chunk_dot(idx)).astype(_dot_dtype(x, w))


def _dot_dtype(a: jax.Array, b: jax.Array):
    return jnp.result_type(a.dtype, b.dtype)


def _split_dim(x: jax.Array, axis: int, n: int) -> jax.Array:
    shape = x.shape
    new = shape[:axis] + (n, shape[axis] // n) + shape[axis + 1 :]
    return jnp.moveaxis(x.reshape(new), axis, 0)


def _merge_leading(x: jax.Array, axis: int) -> jax.Array:
    # x: [n, ...]; concatenate leading dim into `axis` of the remainder.
    x = jnp.moveaxis(x, 0, axis)
    shape = x.shape
    return x.reshape(shape[:axis] + (shape[axis] * shape[axis + 1],) + shape[axis + 2 :])
