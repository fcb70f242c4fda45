"""Continuous batching: heterogeneous prompts, slot refill, correctness vs
single-request generation."""

import jax
import numpy as np
import pytest

from repro.configs.registry import smoke_config
from repro.models.api import build_model
from repro.serve.continuous import ContinuousBatchingEngine, Request

pytestmark = pytest.mark.usefixtures("own_runtime")


def _setup():
    cfg = smoke_config("qwen2.5-3b").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_continuous_matches_single_request():
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (9, 14, 11)]
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_seq=64)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    done = eng.run_to_completion()
    assert len(done) == 3

    # oracle: each request generated alone
    from repro.serve.engine import ServeConfig, ServingEngine
    for req in done:
        solo = ServingEngine(model, params, ServeConfig(max_seq=64))
        res = solo.generate(req.prompt[None], max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(req.tokens),
                                      res[0].tokens)


def test_more_requests_than_slots_all_complete():
    cfg, model, params = _setup()
    rng = np.random.default_rng(1)
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_seq=48)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab, 8).astype(np.int32), max_new_tokens=4))
    done = eng.run_to_completion()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert all(len(r.tokens) == 4 for r in done)


def test_run_to_completion_respects_max_steps():
    """max_steps is exact: the old check ran max_steps + 1 decode steps."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(2)
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_seq=128)
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, 8).astype(
        np.int32), max_new_tokens=100))
    eng.run_to_completion(max_steps=3)
    assert eng.steps == 3
    eng.close()


def test_token_movement_rides_transfer_engine():
    """Prompt admission is a measured TX and each decode step a measured RX
    on the engine (the ROADMAP 'fold token movement onto the engine' item)."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(3)
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_seq=64)
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, 6).astype(
        np.int32), max_new_tokens=3))
    eng.run_to_completion()
    tx = [s for s in eng.transfer.stats if s.direction == "tx"]
    rx = [s for s in eng.transfer.stats if s.direction == "rx"]
    assert len(tx) == 1  # one admitted prompt
    # prefill yields token 1; the remaining max_new_tokens-1 decode steps
    # each RX one token batch
    assert len(rx) == 2
    eng.close()
