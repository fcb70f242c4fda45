"""End-to-end behaviour: training convergence, restart, serving, NullHop."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.accel.nullhop import NullHopExecutor
from repro.accel.roshambo import RoShamBoCNN, RoShamBoConfig
from repro.configs.registry import smoke_config
from repro.core.transfer import (
    Buffering,
    Management,
    Partitioning,
    TransferEngine,
    TransferPolicy,
)
from repro.data.pipeline import DataConfig, StagedPipeline, SyntheticLMSource
from repro.models.api import build_model
from repro.optim import AdamWConfig
from repro.serve.engine import ServeConfig, ServingEngine
from repro.train.loop import TrainConfig, Trainer
from repro.utils.timing import StepClock


def _train(cfg, steps, ckpt_dir="", policy=None, n_micro=1):
    model = build_model(cfg)
    tcfg = TrainConfig(steps=steps, n_microbatches=n_micro, warmup=2,
                       log_every=2, opt=AdamWConfig(lr=1e-3),
                       checkpoint_dir=ckpt_dir, checkpoint_every=4,
                       async_checkpoint=False)
    src = SyntheticLMSource(DataConfig(global_batch=4, seq_len=32), cfg)
    pipe = StagedPipeline(src, policy or TransferPolicy.kernel_level())
    tr = Trainer(model, tcfg)
    out = tr.run(pipe)
    pipe.close()
    return tr, out


def test_training_loss_decreases():
    cfg = smoke_config("qwen2.5-3b")
    tr, _ = _train(cfg, steps=12)
    assert tr.history[-1]["loss"] < tr.history[0]["loss"]


def test_microbatched_equals_unmicrobatched_loss():
    """Blocks-mode batch partitioning must not change the metrics."""
    cfg = smoke_config("granite-moe-1b-a400m").replace(
        dtype="float32", capacity_factor=32.0)
    tr1, _ = _train(cfg, steps=3, n_micro=1)
    tr2, _ = _train(cfg, steps=3, n_micro=2)
    assert tr1.history[0]["loss"] == pytest.approx(tr2.history[0]["loss"],
                                                   rel=2e-3)


def test_restart_resumes_from_checkpoint(tmp_path):
    cfg = smoke_config("h2o-danube-1.8b")
    d = str(tmp_path / "ckpt")
    _train(cfg, steps=8, ckpt_dir=d)
    tr2, out2 = _train(cfg, steps=12, ckpt_dir=d)
    assert out2["fault"].restarts == 1
    assert tr2.history[0]["step"] >= 8  # resumed, not from scratch


def test_serving_greedy_deterministic():
    cfg = smoke_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, ServeConfig(max_seq=64))
    prompts = np.ones((2, 8), np.int32)
    r1 = eng.generate(prompts, max_new_tokens=8)
    r2 = eng.generate(prompts, max_new_tokens=8)
    np.testing.assert_array_equal(r1[0].tokens, r2[0].tokens)
    assert r1[0].tokens.shape == (8,)


def test_serving_multichannel_matches_single():
    """Striped prompt TX / token RX (ChannelGroup) must generate the same
    tokens as the single-engine path."""
    from repro.core.channels import ChannelGroup

    cfg = smoke_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.ones((2, 8), np.int32)
    single = ServingEngine(model, params, ServeConfig(max_seq=64))
    multi = ServingEngine(model, params, ServeConfig(max_seq=64,
                                                     n_channels=2))
    assert isinstance(multi.engine, ChannelGroup)
    r1 = single.generate(prompts, max_new_tokens=6)
    r2 = multi.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(r1[0].tokens, r2[0].tokens)
    single.close(), multi.close()


def test_serving_online_adaptation_matches_single():
    """The online-adaptive engine (rolling refit + safe-point plan swaps)
    must serve byte-identical greedy tokens — adaptation may change HOW
    bytes move, never WHAT arrives."""
    from repro.core.adaptive import AdaptiveChannelGroup

    cfg = smoke_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.ones((2, 8), np.int32)
    single = ServingEngine(model, params, ServeConfig(max_seq=64))
    online = ServingEngine(model, params,
                           ServeConfig(max_seq=64, online_adaptation=True))
    assert isinstance(online.engine, AdaptiveChannelGroup)
    r1 = single.generate(prompts, max_new_tokens=6)
    r2 = online.generate(prompts, max_new_tokens=6)
    r3 = online.generate(prompts, max_new_tokens=6)  # across a safe point
    np.testing.assert_array_equal(r1[0].tokens, r2[0].tokens)
    np.testing.assert_array_equal(r2[0].tokens, r3[0].tokens)
    single.close(), online.close()


def test_straggler_detection():
    clock = StepClock(window=20, zscore_threshold=3.0)
    for _ in range(15):
        clock.record(0.10 + np.random.rand() * 0.001)
    assert clock.record(0.5)  # 5x step time -> straggler
    assert not clock.record(0.101)


# ---- NullHop / RoShamBo (the paper's workload) ----------------------------

def test_nullhop_streamed_equals_monolithic():
    cnn = RoShamBoCNN()
    params = cnn.init(jax.random.PRNGKey(1))
    frame = np.random.default_rng(1).standard_normal(
        (1, 64, 64, 1)).astype(np.float32)
    ref = np.asarray(cnn.apply(params, jnp.asarray(frame)))
    for policy in (TransferPolicy.user_level_polling(),
                   TransferPolicy(Management.INTERRUPT, Buffering.DOUBLE,
                                  Partitioning.BLOCKS, block_bytes=1 << 14)):
        res = NullHopExecutor(cnn, policy).run_frame(params, frame)
        np.testing.assert_allclose(res.logits, ref, rtol=1e-4, atol=1e-4)
        assert len(res.timing.layers) == 5
        assert res.timing.frame_s > 0
        assert all(0.0 <= s <= 1.0 for s in res.sparsity)


@pytest.mark.parametrize("policy,staged", [
    (TransferPolicy.kernel_level_ring(4), True),
    (TransferPolicy.user_level_polling(), True),
    (TransferPolicy(Management.INTERRUPT, Buffering.DOUBLE,
                    Partitioning.BLOCKS, block_bytes=1 << 12), True),
    (TransferPolicy.kernel_level_ring(4), False),
], ids=["ring4", "polling", "interrupt-blocks", "ring4-unstaged"])
def test_nullhop_sparsity_equals_eager_recompute(policy, staged):
    """The per-layer sparsity counted over the RX'd fmaps is the zero
    fraction an independent eager pass over the network reads."""
    cnn = RoShamBoCNN(RoShamBoConfig(input_hw=32))
    params = jax.tree.map(np.asarray, cnn.init(jax.random.PRNGKey(2)))
    frames = np.random.default_rng(2).standard_normal(
        (2, 1, 32, 32, 1)).astype(np.float32)
    ex = NullHopExecutor(cnn, policy, staged=staged)
    try:
        for frame in frames:
            res = ex.run_frame(params, frame)
            x, ref = jnp.asarray(frame), []
            for spec in cnn.cfg.layers:
                x = cnn.layer_apply(spec, params[spec.name], x)
                ref.append(float((x == 0).mean()))
            assert res.sparsity == ref
            assert all(type(v) is float for v in res.sparsity)
            assert any(v > 0 for v in res.sparsity)
    finally:
        ex.close()


def test_streaming_executor_streams_params_per_layer():
    cnn = RoShamBoCNN()
    params = cnn.init(jax.random.PRNGKey(1))
    frame = np.random.default_rng(1).standard_normal(
        (1, 64, 64, 1)).astype(np.float32)
    ex = NullHopExecutor(cnn, TransferPolicy(Management.INTERRUPT,
                                             Buffering.DOUBLE,
                                             Partitioning.UNIQUE))
    res = ex.run_frame(params, frame)
    tx_bytes = sum(l.tx_bytes for l in res.timing.layers)
    assert tx_bytes > frame.nbytes  # params streamed per layer


def test_layer_transfer_bytes_in_100kb_regime():
    """The paper: RoShamBo transfer lengths are ~100 KB."""
    cnn = RoShamBoCNN()
    params = cnn.init(jax.random.PRNGKey(0))
    sizes = cnn.layer_transfer_bytes(params)
    assert len(sizes) == 5
    mid = sorted(s["tx_bytes"] for s in sizes)[2]
    assert 3e4 < mid < 3e6
