"""Three-way-overlap streaming executor: correctness and overlap behaviour."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.channels import ChannelGroup
from repro.core.streaming import HostStreamingExecutor
from repro.core.transfer import (
    Buffering,
    Management,
    Partitioning,
    TransferEngine,
    TransferPolicy,
)


def _layers(n, d, key):
    out = []

    def apply_fn(params, x):
        w, b = params
        return jnp.tanh(x @ w + b)

    jitted = jax.jit(apply_fn)
    for i in range(n):
        key, k = jax.random.split(key)
        w = np.asarray(jax.random.normal(k, (d, d)) * 0.1, np.float32)
        b = np.zeros(d, np.float32)
        out.append((f"l{i}", [w, b], jitted))
    return out


def _per_layer(layers, x):
    y, out = jnp.asarray(x), []
    for _, (w, b), fn in layers:
        y = fn([jnp.asarray(w), jnp.asarray(b)], y)
        out.append(np.asarray(y))
    return out


def _reference(layers, x):
    return _per_layer(layers, x)[-1]


@pytest.mark.parametrize("policy", [
    TransferPolicy.user_level_polling(),
    TransferPolicy.kernel_level(),
    TransferPolicy(Management.INTERRUPT, Buffering.DOUBLE, Partitioning.UNIQUE),
    TransferPolicy.kernel_level_ring(3),
    TransferPolicy.kernel_level_ring(5, block_bytes=1 << 14),
], ids=lambda p: p.tag)
def test_streamed_equals_reference(policy):
    layers = _layers(5, 64, jax.random.PRNGKey(0))
    x = np.random.rand(2, 64).astype(np.float32)
    eng = TransferEngine(policy)
    out, timing = HostStreamingExecutor(eng).run(layers, x)
    np.testing.assert_allclose(out, _reference(layers, x), rtol=1e-5, atol=1e-5)
    assert len(timing.layers) == 5
    assert all(l.rx_bytes > 0 for l in timing.layers)
    eng.close()


def test_second_frame_hits_layout_cache():
    layers = _layers(4, 32, jax.random.PRNGKey(1))
    x = np.random.rand(2, 32).astype(np.float32)
    eng = TransferEngine(TransferPolicy.kernel_level_ring(4))
    ex = HostStreamingExecutor(eng)
    out1, _ = ex.run(layers, x)
    assert eng.layouts.misses == 4 and eng.layouts.hits == 0
    out2, _ = ex.run(layers, x)
    assert eng.layouts.misses == 4 and eng.layouts.hits == 4  # no re-derive
    # the frame result must be a FRESH array each run (interior layers
    # reuse zero-copy RX buffers, the final layer never does)
    assert out1 is not out2
    np.testing.assert_allclose(out1, out2, rtol=1e-6, atol=1e-6)
    # steady state: the host params are the same objects -> zero pack copies
    for key in [(i, f"l{i}") for i in range(4)]:
        lay = eng.layouts._layouts[key]
        assert lay.pack_count == 2 and lay.copy_count == 1
    eng.close()


def test_overlapped_rx_returns_final_layer_output():
    """The async-RX pipeline must hand back the LAST layer's fmap, not a
    stale earlier ticket."""
    layers = _layers(6, 48, jax.random.PRNGKey(2))
    x = np.random.rand(3, 48).astype(np.float32)
    eng = TransferEngine(TransferPolicy.kernel_level_ring(4))
    out, timing = HostStreamingExecutor(eng).run(layers, x)
    np.testing.assert_allclose(out, _reference(layers, x), rtol=1e-5, atol=1e-5)
    eng.close()


def test_staged_false_matches_staged_true():
    """The legacy baseline path and the ring path are numerically identical."""
    layers = _layers(4, 32, jax.random.PRNGKey(3))
    x = np.random.rand(2, 32).astype(np.float32)
    outs = []
    for staged in (True, False):
        eng = TransferEngine(TransferPolicy(
            Management.INTERRUPT, Buffering.DOUBLE, Partitioning.UNIQUE))
        out, _ = HostStreamingExecutor(eng, staged=staged).run(layers, x)
        outs.append(out)
        eng.close()
    np.testing.assert_array_equal(outs[0], outs[1])


def test_single_layer_and_empty_edge_cases():
    eng = TransferEngine(TransferPolicy.kernel_level_ring(4))
    layers = _layers(1, 16, jax.random.PRNGKey(4))
    x = np.random.rand(1, 16).astype(np.float32)
    out, timing = HostStreamingExecutor(eng).run(layers, x)
    np.testing.assert_allclose(out, _reference(layers, x), rtol=1e-5,
                               atol=1e-5)
    assert len(timing.layers) == 1
    eng.close()


@pytest.mark.parametrize("staged", [True, False])
def test_empty_layer_list_returns_transferred_input(staged):
    """Zero layers must hand back the round-tripped input, not None (the
    overlapped path used to fall off the end with host_out=None)."""
    eng = TransferEngine(TransferPolicy.kernel_level_ring(4))
    x = np.random.rand(3, 8).astype(np.float32)
    out, timing = HostStreamingExecutor(eng, staged=staged).run([], x)
    assert out is not None
    np.testing.assert_array_equal(np.asarray(out).reshape(x.shape), x)
    assert timing.layers == []
    eng.close()


@pytest.mark.parametrize("zero_copy_rx", [True, False], ids=["zc", "copy"])
@pytest.mark.parametrize("make_engine,staged", [
    (lambda: TransferEngine(TransferPolicy.kernel_level_ring(4)), True),
    (lambda: TransferEngine(TransferPolicy.kernel_level_ring(4)), False),
    (lambda: TransferEngine(TransferPolicy.user_level_polling()), True),
    (lambda: ChannelGroup(TransferPolicy.kernel_level_ring(4), n_channels=2,
                          min_stripe_bytes=1 << 10), True),
], ids=["overlapped", "basic-prefetch", "basic-polling", "group"])
def test_last_outputs_are_each_layers_rx(make_engine, staged, zero_copy_rx):
    """One host fmap per layer, in layer order, each that layer's device
    output; the last is the array run() returned, and a second run's
    entries hold the second frame."""
    layers = _layers(4, 48, jax.random.PRNGKey(5))
    eng = make_engine()
    ex = HostStreamingExecutor(eng, staged=staged, zero_copy_rx=zero_copy_rx)
    assert ex.last_outputs == ()
    try:
        for seed in (0, 1):
            x = np.random.default_rng(seed).random((2, 48), np.float32)
            out, _ = ex.run(layers, x)
            got = ex.last_outputs
            assert len(got) == len(layers)
            assert got[-1] is out
            for g, want in zip(got, _per_layer(layers, x)):
                np.testing.assert_array_equal(np.asarray(g).reshape(
                    want.shape), want)
        ex.run([], x)
        assert ex.last_outputs == ()
    finally:
        eng.close()
