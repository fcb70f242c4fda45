"""Trip-count-aware HLO cost analysis vs XLA's own (on unrolled graphs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze, shape_bytes


def _scan_matmul(n, unroll=1):
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=n, unroll=unroll)
        return y
    return f


X = jax.ShapeDtypeStruct((256, 256), jnp.float32)
W = jax.ShapeDtypeStruct((256, 256), jnp.float32)


@pytest.mark.parametrize("n", [1, 7, 23])
def test_trip_count_multiplication(n):
    c = jax.jit(_scan_matmul(n)).lower(X, W).compile()
    cost = analyze(c.as_text())
    assert cost.flops == pytest.approx(n * 2 * 256**3, rel=1e-6)


def test_matches_xla_on_unrolled():
    c = jax.jit(_scan_matmul(6, unroll=6)).lower(X, W).compile()
    xla = c.cost_analysis()
    mine = analyze(c.as_text())
    assert mine.flops == pytest.approx(float(xla["flops"]), rel=1e-6)
    assert mine.bytes == pytest.approx(float(xla["bytes accessed"]), rel=0.05)


def test_nested_scans_multiply():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y
    c = jax.jit(f).lower(X, W).compile()
    cost = analyze(c.as_text())
    assert cost.flops == pytest.approx(15 * 2 * 256**3, rel=1e-6)


def test_grad_flops_roughly_3x_forward():
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)
    gf = jax.jit(jax.grad(loss))
    cf = gf.lower(W, X).compile()
    cost_bwd = analyze(cf.as_text())
    cost_fwd = analyze(jax.jit(loss).lower(W, X).compile().as_text())
    ratio = cost_bwd.flops / cost_fwd.flops
    assert 2.0 <= ratio <= 4.0


def test_shape_bytes_parsing():
    assert shape_bytes("f32[16,16]{1,0}") == 1024
    assert shape_bytes("bf16[8]") == 16
    assert shape_bytes("(s32[], f32[4,4]{1,0})") == 4 + 64
    assert shape_bytes("pred[10]") == 10


def test_collective_accounting_in_loops():
    """A psum inside a scan must count trip-count times."""
    from conftest import run_in_subprocess
    code = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.hlo_cost import analyze

mesh = jax.make_mesh((4,), ("m",), axis_types=(jax.sharding.AxisType.Auto,))

def f(x):
    def body(c, _):
        return jax.lax.pvary(jax.lax.psum(c, "m") * 0.25, ("m",)), None
    y, _ = jax.lax.scan(body, x, None, length=9)
    return y

g = shard_map(f, mesh=mesh, in_specs=P("m"), out_specs=P("m"))
c = jax.jit(g).lower(jax.ShapeDtypeStruct((64,), jnp.float32)).compile()
cost = analyze(c.as_text(), 4)
per = 2 * (16 * 4) * (3 / 4)  # all-reduce of 16 f32 per device, ring factor
expected = 9 * per
assert abs(cost.collective_bytes - expected) / expected < 0.05, (
    cost.collective_bytes, expected)
print("collective ok", cost.collective_bytes)
"""
    out = run_in_subprocess(code, n_devices=4)
    assert "collective ok" in out
