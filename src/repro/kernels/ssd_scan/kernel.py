"""SSD intra-chunk kernel (Mamba2 state-space duality).

Computes, for each (batch, head, chunk) grid cell, the quadratic
*intra-chunk* part of the SSD algorithm plus the chunk's boundary-state
contribution:

    y_diag[z]  = (C_z B_z^T * L_z) (x_z * dt_z)     [Q,P]
    states[z]  = sum_k decay_out[k] B_k (x_k dt_k)  [P,N]

The sequential inter-chunk recurrence (O(n_chunks) tiny updates) stays in
jnp — it is bandwidth-trivial. The chunk length Q is the BLOCKS knob: each
grid step's VMEM working set is Q*(P + 2*N) + a few Q*Q f32 tiles, and
Pallas double-buffers consecutive chunks (the paper's overlap, again).

Layout: the wrapper moves heads ahead of the sequence so every block ends
in two dimensions the TPU can tile — (Q, P), (Q, N), (Q, 1), (1, Q) — and
every in-kernel product is a plain 2-D matmul. The within-chunk cumulative
sum is a product with a lower-triangular ones matrix, computed once as a
column and once as a row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _ssd_chunk_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref,
                      st_ref, dec_ref, *, q: int):
    # blocks (leading grid dims are 1): x [Q,P]  dt as a column [Q,1] and a
    # row [1,Q]  a [1,1]  b,c [Q,N]
    x = x_ref[0, 0, 0]
    dt_col = dtc_ref[0, 0, 0]
    a = a_ref[0].astype(jnp.float32)
    da_col = dt_col.astype(jnp.float32) * a  # [Q,1]
    da_row = dtr_ref[0, 0, 0].astype(jnp.float32) * a  # [1,Q]
    b = b_ref[0, 0, 0]
    c = c_ref[0, 0, 0]

    qi = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = ki <= qi  # [Q,Q] lower triangle incl. diagonal
    ones_lo = tri.astype(jnp.float32)
    cs_col = jnp.dot(ones_lo, da_col, precision=_HI,
                     preferred_element_type=jnp.float32)  # cumsum, [Q,1]
    cs_row = jax.lax.dot_general(
        da_row, ones_lo, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)  # the same cumsum, [1,Q]
    cs_last = cs_row[:, q - 1:q]  # [1,1]

    # L decay matrix: exp(segsum) on the lower triangle
    l_mat = jnp.where(tri, jnp.exp(cs_col - cs_row), 0.0)  # [Q,Q]

    xdt = x * dt_col.astype(x.dtype)  # [Q,P]
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,Q]
    att = (cb * l_mat).astype(x.dtype)
    y_ref[0, 0, 0] = jnp.dot(att, xdt, preferred_element_type=jnp.float32
                             ).astype(y_ref.dtype)

    # chunk state: sum_k exp(cs[-1] - cs[k]) b_k (x_k dt_k), as [P,N]
    bd = b * jnp.exp(cs_last - cs_col).astype(b.dtype)  # [Q,N]
    st_ref[0, 0, 0] = jax.lax.dot_general(
        xdt, bd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(st_ref.dtype)
    dec_ref[0, 0, 0] = jnp.exp(cs_last).astype(dec_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_intra_chunk_call(x: jax.Array, dt: jax.Array, a: jax.Array,
                         b: jax.Array, c: jax.Array, *, chunk: int,
                         interpret: bool = False):
    """x: [B,S,H,P]; dt: [B,S,H]; a: [H]; b,c: [B,S,G,N].

    Returns (y_diag [B,S,H,P] f32-accurate, states [B,nc,H,P,N] f32,
    chunk_decay [B,nc,H] f32)."""
    bs, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert s % chunk == 0
    nc = s // chunk
    rep = h // g
    kernel = functools.partial(_ssd_chunk_kernel, q=chunk)
    # head-major: [B,H,nc,Q,...]; b/c stay per group (head -> h // rep)
    xh = x.reshape(bs, nc, chunk, h, p).transpose(0, 3, 1, 2, 4)
    dth = dt.reshape(bs, nc, chunk, h).transpose(0, 3, 1, 2)
    bh = b.reshape(bs, nc, chunk, g, n).transpose(0, 3, 1, 2, 4)
    ch = c.reshape(bs, nc, chunk, g, n).transpose(0, 3, 1, 2, 4)

    def per_head(i, j, z):
        return (i, j, z, 0, 0)

    def per_group(i, j, z):
        return (i, j // rep, z, 0, 0)

    y, st, dec = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bs, h, nc, chunk, p), jnp.float32),
            jax.ShapeDtypeStruct((bs, h, nc, p, n), jnp.float32),
            jax.ShapeDtypeStruct((bs, h, nc, 1, 1), jnp.float32),
        ),
        grid=(bs, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), per_head),
            pl.BlockSpec((1, 1, 1, chunk, 1), per_head),
            pl.BlockSpec((1, 1, 1, 1, chunk), per_head),
            pl.BlockSpec((1, 1, 1), lambda i, j, z: (j, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n), per_group),
            pl.BlockSpec((1, 1, 1, chunk, n), per_group),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, 1, chunk, p), per_head),
            pl.BlockSpec((1, 1, 1, p, n), per_head),
            pl.BlockSpec((1, 1, 1, 1, 1), per_head),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xh, dth[..., None], dth[..., None, :], a.reshape(h, 1, 1), bh, ch)
    return (y.transpose(0, 2, 3, 1, 4).reshape(bs, s, h, p),
            st.transpose(0, 2, 1, 3, 4),
            dec.reshape(bs, h, nc).transpose(0, 2, 1))
