"""Moonlight-16B-A3B (latent attention, sigmoid-routed experts kept in
host memory) against its plain reference at smoke size on the CPU, and
the RMS-norm eps field every model reads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.core.streaming import HostStreamingExecutor
from repro.core.transfer import TransferEngine, TransferPolicy
from repro.models import moonlight as ml
from repro.models.api import build_model
from repro.models.layers import moe, norm
from repro.models.reference import moonlight as ref
from repro.serve.continuous import ContinuousBatchingEngine, Request
from repro.utils import spans

KEY = jax.random.PRNGKey(0)
S_MAX = 64


def published(cfg) -> dict:
    """The published config keys the reference reads, from a ModelConfig."""
    return {"num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "n_routed_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.top_k, "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True,
            "routed_scaling_factor": cfg.routed_scaling,
            "first_k_dense_replace": cfg.first_dense_layers}


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config("moonlight-16b-a3b").replace(dtype="float32")
    params, experts = ml.init_params(KEY, cfg)
    on_device = [{k: jnp.asarray(v) for k, v in e.items()} for e in experts]
    return cfg, params, experts, on_device


def _tokens(n, seed=1, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _serve(runner, params, toks, s):
    """Prefill toks[:s], then decode the rest one at a time; the logits
    at positions s-1 .. len-1."""
    lg, cache = runner.prefill(params, {"tokens": jnp.asarray(toks[None, :s])},
                               S_MAX)
    out = [np.asarray(lg[0, 0])]
    cache = cache._replace(length=cache.length[:, None])
    for i in range(s, len(toks)):
        lg, cache = runner.decode(params, jnp.asarray(toks[None, i:i + 1]),
                                  cache)
        out.append(np.asarray(lg[0, 0]))
    return np.stack(out)


def _reference(cfg, params, experts, toks):
    logits, own, _ = jax.jit(lambda p, e, t: ref.forward(
        published(cfg), p, e, t))(params, experts, jnp.asarray(toks[None]))
    return np.asarray(logits[0]), [np.asarray(o[0]) for o in own]


# -- the RMS-norm eps field ----------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2.5-3b", "zamba2-1.2b",
                                  "mamba2-780m"])
def test_rms_norm_eps_reaches_every_norm(arch, monkeypatch):
    cfg = smoke_config(arch).replace(dtype="float32", rms_norm_eps=3e-5)
    seen = []
    real = norm.rms_norm

    def recording(x, scale, eps=1e-6):
        seen.append(eps)
        return real(x, scale, eps)

    monkeypatch.setattr(norm, "rms_norm", recording)
    m = build_model(cfg)
    params = m.init(KEY)
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab)
    jax.jit(m.forward)(params, {"tokens": toks, "labels": toks})
    assert seen and set(seen) == {3e-5}


def test_rms_norm_eps_moves_the_output():
    cfg = smoke_config("qwen2.5-3b").replace(dtype="float32")
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab)
    params = build_model(cfg).init(KEY)
    out = [np.asarray(jax.jit(build_model(cfg.replace(rms_norm_eps=e)).forward)(
        params, {"tokens": toks})[0]) for e in (1e-6, 1e-6, 0.5)]
    assert np.array_equal(out[0], out[1])
    assert not np.allclose(out[0], out[2], atol=1e-3)


def test_registry_eps_is_the_published_one():
    assert get_config("h2o-danube-1.8b").rms_norm_eps == 1e-5
    assert smoke_config("h2o-danube-1.8b").rms_norm_eps == 1e-5
    assert get_config("moonlight-16b-a3b").rms_norm_eps == 1e-5
    for arch in ARCHS:
        if arch != "h2o-danube-1.8b":
            assert get_config(arch).rms_norm_eps == 1e-6, arch


def test_moonlight_config_is_served_not_built():
    cfg = get_config("moonlight-16b-a3b")
    assert "moonlight-16b-a3b" not in ARCHS
    assert 15.9e9 < cfg.param_count() < 16.0e9
    with pytest.raises(NotImplementedError):
        build_model(cfg)


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("s", [5, 13])
def test_mla_prefill_then_decode_matches_reference(model, s):
    cfg, params, _experts, on_device = model
    toks = _tokens(18)
    got = _serve(ml.ExpertOffload(cfg, on_device, prefill_pad=16), params,
                 toks, s)
    want, _ = _reference(cfg, params, on_device, toks)
    np.testing.assert_allclose(got, want[s - 1:], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t", [1, 5, 64, 257])
def test_router_is_the_published_one_and_drops_nothing(model, t):
    cfg, params, _experts, on_device = model
    lp = params["layers"][cfg.first_dense_layers]
    h = jax.random.normal(jax.random.fold_in(KEY, t), (1, t, cfg.d_model))
    bias = jax.random.normal(jax.random.fold_in(KEY, 99), (cfg.n_experts,))
    lp_moe = dict(lp["moe"], bias=0.1 * bias)
    ids, w = moe.sigmoid_route(lp_moe["router"], lp_moe["bias"], h,
                               top_k=cfg.top_k, scale=cfg.routed_scaling)
    r_ids, r_w, own, gap = ref.gate(published(cfg), lp_moe, h)
    ids, w, r_ids, r_w = map(np.asarray, (ids, w, r_ids, r_w))
    order, r_order = np.argsort(ids, -1), np.argsort(r_ids, -1)
    np.testing.assert_array_equal(np.take_along_axis(ids, order, -1),
                                  np.take_along_axis(r_ids, r_order, -1))
    np.testing.assert_allclose(np.take_along_axis(w, order, -1),
                               np.take_along_axis(r_w, r_order, -1),
                               rtol=1e-6)
    # every token keeps all top_k distinct experts, weights summing to the
    # routed scale: nothing is dropped at a capacity, whatever the batch
    assert all(len(set(row)) == cfg.top_k for row in ids.reshape(-1,
                                                                 cfg.top_k))
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scaling, rtol=1e-5)
    assert float(np.asarray(gap).max()) == 0.0
    # applied one expert at a time, the chosen experts give the dense
    # reference mixture
    ex = on_device[0]
    x = jnp.zeros((1, t, cfg.d_model))
    for e in np.unique(ids):
        x = moe.expert_add(x, h, jnp.asarray(ids), jnp.asarray(w), e,
                           ex["wi"][e], ex["wo"][e])
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(ref.routed(ex, h, jnp.asarray(ids),
                                             jnp.asarray(w))),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [1, 2, 3])
def test_expert_groups_cover_each_expert_once_in_near_equal_groups(window):
    rng = np.random.default_rng(window)
    for n in range(1, 65):
        sel = np.sort(rng.choice(64, n, replace=False))
        groups = ml.expert_groups(n, window)
        assert len(groups) == min(n, ml.GROUPS_PER_SLOT * window)
        # every expert exactly once, in ascending order
        assert np.array_equal(np.concatenate([sel[g] for g in groups]), sel)
        sizes = [g.stop - g.start for g in groups]
        assert min(sizes) >= 1 and max(sizes) - min(sizes) <= 1
        assert sizes == sorted(sizes)
    assert max(ml.group_sizes(64, window)) == -(-64 // (ml.GROUPS_PER_SLOT
                                                        * window))


@pytest.mark.parametrize("depth", [2, 3, 4])
def test_runner_groups_over_the_executors_window(model, depth):
    cfg, _params, experts, on_device = model
    with TransferEngine(TransferPolicy.kernel_level_ring(depth)) as te:
        runner = ml.ExpertOffload(cfg, experts, HostStreamingExecutor(te))
    assert runner.window == depth - 1
    assert ml.ExpertOffload(cfg, on_device).window == 3  # as at depth 4


@pytest.mark.parametrize("depth", [2, 3, 4])
def test_streamed_decode_equals_resident_and_reference(model, depth):
    cfg, params, experts, on_device = model
    toks = _tokens(16, seed=2)
    with TransferEngine(TransferPolicy.kernel_level_ring(depth)) as te:
        streamed = _serve(ml.ExpertOffload(
            cfg, experts, HostStreamingExecutor(te), prefill_pad=16),
            params, toks, 9)
    resident = _serve(ml.ExpertOffload(cfg, on_device, prefill_pad=16),
                      params, toks, 9)
    assert np.array_equal(streamed, resident)
    want, _ = _reference(cfg, params, on_device, toks)
    np.testing.assert_allclose(streamed, want[8:], rtol=1e-4, atol=1e-4)


def _decode_rows(cfg, rows):
    cache = ml.init_cache(cfg, rows, S_MAX)
    cache = cache._replace(length=jnp.zeros((cfg.n_layers, rows), jnp.int32))
    return jnp.arange(1, rows + 1)[:, None], cache


@pytest.mark.parametrize("depth", [2, 3, 4])
def test_bytes_moved_per_layer_are_the_routed_experts(model, depth):
    cfg, params, experts, _on_device = model
    per_expert = experts[0]["wi"][0].nbytes + experts[0]["wo"][0].nbytes
    submits = []
    with TransferEngine(TransferPolicy.kernel_level_ring(depth)) as te:
        tx_sg = te.tx_sg

        def counting(segments, *a, **kw):
            submits.append(len(segments) // 2)
            return tx_sg(segments, *a, **kw)

        te.tx_sg = counting
        runner = ml.ExpertOffload(cfg, experts, HostStreamingExecutor(te),
                                  prefill_pad=16)
        runner.calls = []
        token, cache = _decode_rows(cfg, 3)
        spans.clear()
        before = te.tx_bytes_total
        with spans.span("repro.test.step", new_frame=True) as root:
            runner.decode(params, token, cache)
        moved = te.tx_bytes_total - before
    routes = runner.calls[-1].routes
    distinct = [len(np.unique(r)) for r in routes]
    sizes = [[g.stop - g.start for g in ml.expert_groups(n, depth - 1)]
             for n in distinct]
    assert moved == sum(distinct) * per_expert
    # one tx_sg per group, of the group's experts
    assert submits == [k for layer in sizes for k in layer]
    recs = [r for r in spans.snapshot() if r.frame == root._seq]
    layers = [r for r in recs if r.name == "repro.moe.experts"]
    assert len(layers) == len(distinct)
    for lay, ks in zip(layers, sizes):
        tx = [r for r in recs if r.name == "repro.stream.expert_tx"
              and lay.t0 <= r.t0 and r.t1 <= lay.t1]
        assert [r.nbytes for r in tx] == [k * per_expert for k in ks]
    counted = [c for c in spans.counts() if c.frame == root._seq]
    assert [c.value for c in counted
            if c.name == ml.EXPERTS_MOVED] == distinct
    assert [c.value for c in counted
            if c.name == ml.EXPERT_GROUPS] == [len(k) for k in sizes]


@pytest.mark.parametrize("depth", [2, 3, 4])
def test_no_expert_program_compiles_after_warm_up(model, depth):
    """The first MoE layer of a shape compiles the expert program for every
    group size the rule can give there, whatever that layer routed; no
    later layer compiles it."""
    cfg, params, experts, _on_device = model
    rows = depth + 2  # a shape of its own per case
    lp = params["layers"][cfg.first_dense_layers]
    token, cache = _decode_rows(cfg, rows)
    x = ml.moonlight_embed(params["embed"], token)
    x, h, ids, w, _c, _kr = ml.moonlight_route(
        cfg, 0, lp, x, cache.c[1], cache.kr[1], cache.length[1])
    n_max = min(cfg.n_experts, rows * cfg.top_k)
    assert len(ml.group_sizes(n_max, depth - 1)) > 1
    compiles = []

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            compiles.append(event)

    with TransferEngine(TransferPolicy.kernel_level_ring(depth)) as te:
        runner = ml.ExpertOffload(cfg, experts, HostStreamingExecutor(te))
        runner._experts(0, lp, x, h, ids, w, np.asarray([[0]]))  # one expert
        jax.monitoring.register_event_listener(on_event)
        try:
            for n in range(1, n_max + 1):
                chosen = np.arange(cfg.n_experts - n, cfg.n_experts)[None]
                runner._experts(0, lp, x, h, ids, w, chosen)
        finally:
            jax.monitoring.unregister_event_listener(on_event)
    assert compiles == []


def test_served_through_the_engine_matches_reference(model):
    cfg, params, experts, on_device = model
    prompts = [_tokens(11, seed=4), _tokens(6, seed=5)]
    with TransferEngine(TransferPolicy.kernel_level_ring(4)) as te:
        runner = ml.ExpertOffload(cfg, experts, HostStreamingExecutor(te),
                                  prefill_pad=16)
        runner.calls = []
        eng = ContinuousBatchingEngine(runner.model(), params, n_slots=2,
                                       max_seq=S_MAX)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        spans.clear()
        eng.run_to_completion()
        eng.close()
    steps = [r for r in spans.snapshot() if r.name == "repro.serve.step"]
    assert len(steps) == eng.steps == 4
    pre = [c for c in runner.calls if c.kind == "prefill"]
    dec = [c for c in runner.calls if c.kind == "decode"]
    for row, (req, first) in enumerate(zip(reqs, pre)):
        seq = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
        want, own = _reference(cfg, params, on_device, seq)
        s = len(req.prompt)
        got = np.stack([np.asarray(first.logits[0, 0])]
                       + [np.asarray(c.logits[row, 0]) for c in dec])
        np.testing.assert_allclose(got, want[s - 1:], rtol=1e-4, atol=1e-4)
        assert req.tokens == [int(t) for t in got.argmax(-1)]
        # the served routes are the reference's own choice
        for m, o in enumerate(own):
            served = np.concatenate([first.routes[m][0]]
                                    + [c.routes[m][row] for c in dec])
            np.testing.assert_array_equal(np.sort(served, -1),
                                          np.sort(o, -1))
