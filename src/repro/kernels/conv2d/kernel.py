"""Row-streamed conv2d kernel — NullHop's MAC array, TPU-adapted.

NullHop streams feature-map rows: 'after a couple of rows are received, the
MACs start to operate'. The TPU analogue: the grid walks row-tiles of the
output; each step's BlockSpec DMAs a (tile_h + K - 1)-row input slab into
VMEM and issues K*K MXU dots of shape [(tile_h*W), Cin] x [Cin, Cout] — a
direct (im2col-free) convolution where the 3x3 taps become 9 shifted
matmuls, which is how a systolic MXU wants convs (vs the FPGA's spatial
MAC mesh; see DESIGN.md hardware-adaptation notes).

Overlapping row slabs can't be expressed as disjoint blocked windows, so
ops.py pre-pads and the index_map uses Element indexing on rows via an
input layout trick: the input is passed pre-sliced into overlapping slabs
[n_tiles, tile_h+K-1, W+2p, Cin] (built with one cheap gather in ops.py),
making every BlockSpec a plain disjoint block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _conv_kernel(x_ref, w_ref, b_ref, o_ref, *, kh: int, kw: int,
                 tile_h: int, out_w: int, relu: bool):
    # x: [1, 1, tile_h+kh-1, out_w+kw-1, Cin]; w: [kh, kw, Cin, Cout]
    x = x_ref[0, 0]
    cin = x.shape[-1]
    cout = o_ref.shape[-1]
    acc = jnp.zeros((tile_h * out_w, cout), jnp.float32)
    for dy in range(kh):
        for dx in range(kw):
            patch = x[dy:dy + tile_h, dx:dx + out_w, :].reshape(
                tile_h * out_w, cin)
            acc += jnp.dot(patch, w_ref[dy, dx],
                           preferred_element_type=jnp.float32)
    acc += b_ref[...][None, :]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[0, 0] = acc.reshape(tile_h, out_w, cout).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_h", "relu", "interpret"))
def conv2d_slabs(slabs: jax.Array, w: jax.Array, b: jax.Array, *,
                 tile_h: int, relu: bool = True,
                 interpret: bool = False) -> jax.Array:
    """slabs: [B, n_tiles, tile_h+kh-1, W+kw-1, Cin] (pre-overlapped);
    w: [kh, kw, Cin, Cout]. Returns [B, n_tiles, tile_h, W, Cout]."""
    bsz, nt, slab_h, slab_w, cin = slabs.shape
    kh, kw, _, cout = w.shape
    out_w = slab_w - (kw - 1)
    assert slab_h == tile_h + kh - 1
    kernel = functools.partial(_conv_kernel, kh=kh, kw=kw, tile_h=tile_h,
                               out_w=out_w, relu=relu)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bsz, nt, tile_h, out_w, cout),
                                       slabs.dtype),
        grid=(bsz, nt),
        in_specs=[
            pl.BlockSpec((1, 1, slab_h, slab_w, cin),
                         lambda i, t: (i, t, 0, 0, 0)),
            pl.BlockSpec((kh, kw, cin, cout), lambda i, t: (0, 0, 0, 0)),
            pl.BlockSpec((cout,), lambda i, t: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_h, out_w, cout),
                               lambda i, t: (i, t, 0, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(slabs.reshape(bsz, nt, slab_h, slab_w, cin), w, b)
